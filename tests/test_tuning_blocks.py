"""`select_blocks("flash_decode")` coverage: VMEM-fit halving boundary,
pages-per-split floor, and non-power-of-two kv-head counts at the kernel
boundary (ISSUE 8 satellite)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.ref import flash_decode_ref
from repro.kernels.tuning import _VMEM_BUDGET, select_blocks


# ---------------------------------------------------------------------------
# VMEM-fit halving of the kv-head tile (block_n)
# ---------------------------------------------------------------------------

def test_vmem_halving_boundary():
    # f32 pool, page=512, head_dim=256: the double-buffered K+V tile is
    # 4*c*bh*hd*itemsize bytes — 16 MiB at bh=8, so the tile halves
    # 8 -> 4 -> 2 and stops exactly at the 4 MiB budget.
    blk = select_blocks("flash_decode", 2, 4, 512, 256, 4)
    assert blk.block_n == 2
    tile = 4 * 512 * blk.block_n * 256 * 4
    assert tile <= _VMEM_BUDGET < tile * 2


def test_exact_budget_is_not_halved():
    # equality is "fits": a tile exactly at the budget keeps all 8 heads
    c, hd = 256, 128
    assert 4 * c * 8 * hd * 4 == _VMEM_BUDGET
    assert select_blocks("flash_decode", 2, 4, c, hd, 4).block_n == 8


def test_head_tile_floor_is_one():
    # a single head over budget still yields a legal (degenerate) tile
    blk = select_blocks("flash_decode", 2, 4, 4096, 1024, 4)
    assert blk.block_n == 1
    assert 4 * 4096 * 1 * 1024 * 4 > _VMEM_BUDGET


def test_int8_pool_keeps_wide_tile():
    # the quantized-KV direction (ROADMAP item 2): 1-byte pool entries
    # fit the full 8-head tile where the f32 pool halved to 2
    assert select_blocks("flash_decode", 2, 4, 512, 256, 1).block_n == 8
    assert select_blocks("flash_decode", 2, 4, 512, 256, 4).block_n == 2


# ---------------------------------------------------------------------------
# pages-per-split (block_k)
# ---------------------------------------------------------------------------

def test_pages_per_split_floor_is_one():
    # zero allocated pages (fresh slot) must still give a runnable
    # 1-page split, in every batch regime
    for m in (1, 8, 64, 512):
        assert select_blocks("flash_decode", m, 0, 16, 64, 4).block_k == 1


def test_pages_per_split_caps_at_table_and_pages():
    assert select_blocks("flash_decode", 2, 3, 16, 64, 4).block_k == 3
    assert select_blocks("flash_decode", 2, 64, 16, 64, 4).block_k == 4
    assert select_blocks("flash_decode", 512, 64, 16, 64, 4).block_k == 8


def test_slot_tile_always_one():
    # one grid row per slot regardless of batch size
    for m in (1, 8, 128, 512):
        assert select_blocks("flash_decode", m, 4, 16, 64, 4).block_m == 1


# ---------------------------------------------------------------------------
# non-power-of-two kv-head counts through the kernel boundary
# ---------------------------------------------------------------------------

def _case(seed, slots, np_, ps, kvh, g, d, positions):
    key = jax.random.PRNGKey(seed)
    p1 = slots * np_ + 1
    ks = jax.random.split(key, 5)
    pool = (2, p1, kvh, ps, d)             # stacked head-major, 2 layers
    k_pages = jax.random.normal(ks[0], pool, jnp.float32)
    v_pages = jax.random.normal(ks[1], pool, jnp.float32)
    k_pages = k_pages.at[:, -1].set(41.0)  # loud trash page
    v_pages = v_pages.at[:, -1].set(-59.0)
    phys = np.full((slots, np_), p1 - 1, np.int64)
    nxt = 0
    for b, pos in enumerate(positions):
        n_alloc = min(-(-(int(pos) + 1) // ps), np_) if pos >= 0 else 0
        phys[b, :n_alloc] = np.arange(nxt, nxt + n_alloc)
        nxt += n_alloc
    q = jax.random.normal(ks[2], (slots, 1, kvh * g, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (slots, 1, kvh, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (slots, 1, kvh, d), jnp.float32)
    return (q, k_pages, v_pages, k_new, v_new,
            jnp.asarray(phys, jnp.int32), jnp.asarray(positions, jnp.int32))


@pytest.mark.parametrize("kvh,g,block_heads", [
    (6, 2, 4),    # no multiple of 8 divides 6: the tile is all 6 heads
    (7, 1, None), # prime head count: the full-kvh tile (7)
    (7, 1, 4),    # prime + a request below kvh: still the full tile
])
def test_non_pow2_head_count_parity(kvh, g, block_heads):
    args = _case(3, 2, 4, 8, kvh, g, 16, [5, 13])
    out = flash_decode_paged(*args, layer=1, impl="pallas", interpret=True,
                             block_heads=block_heads)
    oracle = flash_decode_ref(*args, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
