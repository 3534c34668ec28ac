"""Paged decode attention over a latent page pool (MLA, absorbed form).

A latent pool holds one row per cached position and layer: the
``kv_lora_rank`` latent (already RMS-normed) followed by the
``qk_rope_head_dim`` RoPE key, in pages of ``page`` rows, ``(L, P+1,
page, W)`` over all layers (the last page is the trash page). The row is
stored zero-padded to whole 128-lane tiles: at Moonlight's widths 512 +
64 = 576 columns in a row of W = 640. The TPU's tiled layout pads a
576-wide minor dimension to 640 in HBM anyway, so the padding costs no
memory, and it keeps every page a whole number of tiles, which the
kernel's page DMAs need (Mosaic refuses a 576-wide slice of a
640-padded array). Two arrays of 512 and 64 would pad the narrow one to
128, a quarter more bytes a row.

Decode runs the absorbed form straight off the pool. The queries and
the probabilities stay float32 against the bf16 rows: each of the
kernel's two contractions is two bf16 MXU passes, a float32 operand's
bf16 part and remainder (``_dot_f32``). The caller folds
``W_kb`` into each head's no-RoPE query (``q_lat = q_nope W_kb^T``, R
wide) and concatenates the RoPE query, so every head scores a cached
position by one W-wide dot with its row, and the value is the row's
first R columns: multi-query attention with one shared K/V "head", a key
width of W and a value width of R. The caller applies ``W_vb`` to the
output. Reading the latent row costs ``W`` elements a position, against
``H * (D_nope + D_rope + D_v)`` for per-head K/V expanded from it.

Two implementations share the masks and the online softmax:

  ``pallas`` — the kernel (``mla_decode`` in the trace): one grid step per
               slot; it DMAs the slot's live pages only, ``CHUNK_PAGES``
               at a time into two VMEM chunk buffers that alternate over
               the whole grid, so the next chunk, of this slot or the
               next, is in flight while one is scored (pages of a chunk
               past the slot's length are the slot's own or the trash
               page, so they are finite and masked), and folds the fresh
               token's self term before it writes the output.
  ``xla``    — loops over blocks of pages of every slot at once, up to
               the longest slot's length, gathering each block (the CPU
               path).

The fresh token's row is not in the pool yet (the caller writes it after
the layer loop); its self term is exact, from the row as computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
MLA_NAME = "mla_decode"
CHUNK_PAGES = 8


def _fold_self(m, l, acc, q, new, r):
    """Fold the fresh row into the cached triple (m, l: (.., H, 1); acc:
    (.., H, R)): the softmax-weighted latent (.., H, R). q (.., H, W) and
    ``new`` (.., 1, W), float32."""
    s_new = jnp.sum(q * new, axis=-1, keepdims=True)
    m_f = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_f)
    p_new = jnp.exp(s_new - m_f)
    return (acc * alpha + p_new * new[..., :r]) / (l * alpha + p_new)


def _dot_f32(a, b, dims):
    """A float32 ``a`` against bf16 ``b`` to float32 accuracy in two bf16
    MXU passes: ``a``'s bf16 part and the bf16 part of its remainder, each
    times ``b`` (exact products, float32 sums)."""
    hi = a.astype(b.dtype)
    lo = (a - hi.astype(a.dtype)).astype(b.dtype)
    dot = lambda x: jax.lax.dot_general(x, b, dims,
                                        preferred_element_type=jnp.float32)
    return dot(hi) + dot(lo)


def _kernel(layer_ref, phys_ref, pos_ref, q_ref, new_ref, pool_hbm, o_ref,
            buf, sem, ctr, *, ps, cp, r, nb):
    """One slot per grid step, its live chunks in a loop. The two VMEM
    chunk buffers alternate over the whole grid (``ctr`` counts the chunks
    read so far): while a slot's last chunk is scored, the next slot's
    first one is already on its way, so no slot waits for its first DMA
    but the grid's first."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    span = cp * ps
    chunks = lambda i: (jnp.maximum(pos_ref[i], 0) + span - 1) // span
    n = chunks(b)
    after = jnp.minimum(b + 1, nb - 1)
    n_after = jnp.where(b + 1 < nb, chunks(after), 0)

    def copies(slot, c, bi):
        return [pltpu.make_async_copy(
            pool_hbm.at[layer, phys_ref[slot, c * cp + j]],
            buf.at[bi, pl.ds(j * ps, ps)], sem.at[bi])
            for j in range(cp)]

    def start(slot, c, bi):
        for cpy in copies(slot, c, bi):
            cpy.start()

    @pl.when(b == 0)
    def _grid_start():
        ctr[0] = 0

        @pl.when(n > 0)
        def _first():
            start(0, 0, 0)

    base = ctr[0]
    f32 = jnp.float32
    q = q_ref[0].astype(f32)                             # (H, W)
    pos = pos_ref[b]
    h = q.shape[0]

    def body(c, carry):
        m, l, acc = carry
        bi, nxt = (base + c) % 2, (base + c + 1) % 2

        @pl.when(c + 1 < n)
        def _next():
            start(b, c + 1, nxt)

        @pl.when((c + 1 == n) & (n_after > 0))
        def _next_slot():
            start(after, 0, nxt)

        for cpy in copies(b, c, bi):
            cpy.wait()
        k = buf[bi]                                      # (span, W)
        sc = _dot_f32(q, k, (((1,), (1,)), ((), ())))   # (H, span)
        kj = c * span + jax.lax.broadcasted_iota(jnp.int32, (h, span), 1)
        mask = kj < pos
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = _dot_f32(p, k[:, :r], (((1,), (0,)), ((), ())))
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    m0 = jnp.full((h, 1), NEG_INF, f32)
    l0 = jnp.zeros((h, 1), f32)
    acc0 = jnp.zeros((h, r), f32)
    m, l, acc = jax.lax.fori_loop(0, n, body, (m0, l0, acc0))

    @pl.when((n == 0) & (n_after > 0))
    def _pass_on():                     # an empty slot starts the next one
        start(after, 0, base % 2)

    ctr[0] = base + n
    o_ref[0] = _fold_self(m, l, acc, q, new_ref[0].astype(f32), r)


def _mla_pallas(q, pool, layer, new, phys, pos, r: int,
                interpret: bool = False):
    b, h, w = q.shape
    ps = pool.shape[2]
    cp = min(CHUNK_PAGES, phys.shape[1])
    pad = (-phys.shape[1]) % cp
    if pad:                            # trash-pad: past every slot's length
        phys = jnp.pad(phys, ((0, 0), (0, pad)),
                       constant_values=pool.shape[1] - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda ib, *_: (ib, 0, 0)),
            pl.BlockSpec((1, 1, w), lambda ib, *_: (ib, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda ib, *_: (ib, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, cp * ps, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kern = functools.partial(_kernel, ps=ps, cp=cp, r=r, nb=b)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=MLA_NAME)(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), phys, pos, q,
        new[:, None, :], pool)


def mla_decode_paged(q: jax.Array, pool: jax.Array, new: jax.Array,
                     phys: jax.Array, positions, rank: int, *, layer=0,
                     impl: str = "xla", interpret: bool = False) -> jax.Array:
    """Single-token absorbed latent attention over layer ``layer`` of the
    stacked latent pool, read where it lies (no per-layer slice of the
    pool is made).

    q (B, H, W): each head's absorbed query ``[q_nope W_kb^T, q_rope]``,
    already scaled by the softmax scale, float32; pool
    (L, P+1, page, W); new (B, W) the fresh token's row (not in the pool
    yet);
    phys (B, NP) physical page ids, trash-redirected; positions (B,)
    cached length of each slot (-1: inactive lane, which attends only its
    own row); rank R, the latent width. impl "pallas" | "xla".
    Returns the softmax-weighted latent (B, H, R) float32."""
    pos = jnp.asarray(positions, jnp.int32)
    if impl == "pallas":
        return _mla_pallas(q, pool, layer, new, phys, pos, rank,
                           interpret=interpret)
    if impl != "xla":
        raise ValueError(f"unknown MLA decode impl {impl!r} (pallas | xla)")
    return _mla_xla(q, pool, layer, new, phys, pos, rank)


def _mla_xla(q, pool, layer, new, phys, pos, r, block: int = 4):
    """The same contract in XLA: a loop over blocks of ``block`` pages of
    every slot at once, up to the longest slot's last live page, each
    block's pages gathered and folded into an online softmax."""
    b, h, w = q.shape
    ps = pool.shape[2]
    span = block * ps
    pad = (-phys.shape[1]) % block
    if pad:
        phys = jnp.pad(phys, ((0, 0), (0, pad)),
                       constant_values=pool.shape[1] - 1)
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    q = q.astype(f32)

    def body(c, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(phys, c * block, block, axis=1)
        rows = pool[layer, pages].reshape(b, span, w).astype(f32)
        sc = jnp.einsum("bhw,btw->bht", q, rows, precision=hi)
        kj = c * span + jnp.arange(span)
        mask = kj[None, None, :] < pos[:, None, None]
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bht,btr->bhr", p, rows[..., :r], precision=hi)
        return (m_new, l * alpha + jnp.sum(p, axis=-1, keepdims=True),
                acc * alpha + pv)

    n = (jnp.max(pos) + span - 1) // span
    init = (jnp.full((b, h, 1), NEG_INF, f32), jnp.zeros((b, h, 1), f32),
            jnp.zeros((b, h, r), f32))
    m, l, acc = jax.lax.fori_loop(0, jnp.maximum(n, 0), body, init)
    return _fold_self(m, l, acc, q, new[:, None, :].astype(f32), r)
