"""Compile the serving kernels for a described TPU v5e, with no chip.

The TPU compiler installed with JAX compiles for a topology that is
described, not attached. It refuses what interpret mode accepts: block
shapes off the (8, 128) tiling, vector ops Mosaic cannot lower, more VMEM
than a kernel may use. Each test compiles one kernel at the Qwen1.5-4B
widths (d_model 2560, d_ff 6912, 20 kv heads of 128) and checks that the
program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.assign import vq_assign_pallas
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.fused_amm import vq_amm_pallas
from repro.kernels.lut_gemm import lut_gemm_pallas

V, C = 8, 16                                  # serving default (v, c)
# (K, N) of every projection: q/k/v/o, MLP gate/up, MLP down
PROJ = [(2560, 2560), (2560, 6912), (6912, 2560)]
ROWS = [8, 32, 256]                           # decode, prefill chunk, mid+
KVH, HD, PAGE, SLOTS, PAGES_PER_SLOT = 20, 128, 16, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # no TPU compiler here fails every test of this file, never skips it
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k", sorted({k for k, _ in PROJ}))
def test_vq_assign_compiles(one_chip, m, k):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda x, z: vq_assign_pallas(x, z),
                   s((m, nc, V), jnp.bfloat16), s((nc, C, V), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", PROJ)
def test_lut_gemm_compiles(one_chip, m, k, n):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda i, t, sc: lut_gemm_pallas(i, t, sc),
                   s((m, nc), jnp.int32), s((nc, C, n), jnp.int8),
                   s((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", PROJ)
def test_vq_amm_compiles(one_chip, m, k, n):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda x, z, t, sc: vq_amm_pallas(x, z, t, sc),
                   s((m, nc, V), jnp.bfloat16), s((nc, C, V), jnp.bfloat16),
                   s((nc, C, n), jnp.int8), s((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pool", ["fp", "vq"])
def test_flash_decode_compiles(one_chip, pool):
    s = _spec(one_chip)
    p1 = SLOTS * PAGES_PER_SLOT + 1
    tok = s((SLOTS, 1, KVH, HD), jnp.bfloat16)
    if pool == "vq":                   # kv_v=4, kv_c=16 codes over head_dim
        nc, kc, kv = HD // 4, 16, 4
        pages = s((p1, PAGE, KVH, nc), jnp.uint8)
        cb = {"zk": s((nc, kc, kv), jnp.float32),
              "zv": s((nc, kc, kv), jnp.float32),
              "sk": s((KVH,), jnp.float32), "sv": s((KVH,), jnp.float32)}
    else:
        pages = s((p1, PAGE, KVH, HD), jnp.bfloat16)
        cb = None

    def step(q, kp, vp, kn, vn, phys, pos, cb):
        return flash_decode_paged(q, kp, vp, kn, vn, phys, pos,
                                  impl="pallas", codebook=cb)

    hlo = _compile(step, tok, pages, pages, tok, tok,
                   s((SLOTS, PAGES_PER_SLOT), jnp.int32),
                   s((SLOTS,), jnp.int32), cb)
    assert "tpu_custom_call" in hlo
