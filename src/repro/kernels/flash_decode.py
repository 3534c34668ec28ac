"""Paged flash-decode: attention over the paged KV pool, no gather.

``decode_paged`` historically materialised a dense per-slot KV view with
``model._paged_view`` (an HBM gather of every allocated page) before
running plain SDPA. This module reads the pool *in place* through the
page table instead — the LUT-DLA operand-residency discipline (CCM→IMM)
applied to attention:

  * **split-KV**: each slot's logical KV length is cut into fixed-size
    splits of ``split_pages`` pages (splits align to page boundaries by
    construction). Every split reduces to a triple ``(m, l, acc)`` —
    running max, sum of exponentials at that max, and the partial
    numerator ``sum_j exp(s_j - m) v_j``.
  * **LSE reduction**: triples form a commutative monoid under
    :func:`combine_splits` with identity ``(NEG_INF, 0, 0)``; a second
    tiny pass (:func:`reduce_splits`) folds the per-split triples and
    the new token's self term into the exact softmax output. All-masked
    splits (unallocated / out-of-window pages) emit the identity, never
    NaN: probabilities are forced to zero *under the mask*, not by
    relying on ``exp(-inf)``.
  * **GQA in-tile**: queries arrive grouped ``(B, KVH, G, D)`` so the
    ``G`` query heads sharing one kv head hit the same K/V tile; the
    Pallas kernel carries a ``(bh, G)`` running state per block of
    ``bh`` kv heads.
  * **trash-page redirection**: ``phys`` already maps unallocated pages
    to the trash page; their keys all sit at ``kj >= pos`` and are
    masked, so whatever the trash page holds is never attended.

Three implementations share the same masks and split algebra:

  ``pallas``  — the real kernel. Scalar-prefetched page table drives the
                BlockSpec index map, so each (slot, split, page) grid
                step DMAs exactly one physical page into VMEM.
  ``ref``     — XLA-native. Scores are computed against the *whole*
                pool and gathered per slot (scores are ~8x smaller than
                KV rows, so this moves far less HBM traffic than
                gathering K/V), then probabilities scatter back to pool
                space for the value contraction.
  (callers may also pick ``gather`` upstream — the legacy
  ``_paged_view`` + ``_sdpa_decode_combine`` path, see
  ``model.decode_paged``.)

The pure split-triple functions double as the property-test surface:
``tests/test_flash_decode.py`` checks split-count/order invariance and
identity behaviour against the full-softmax oracle
:func:`repro.kernels.ref.flash_decode_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .ops import require_local, tp_size
from .tuning import flash_head_tile, select_blocks

# Finite stand-in for -inf. exp(NEG_INF - NEG_INF) == 1 (not NaN), which
# is exactly what makes the identity triple compose safely.
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# split-triple algebra (pure, tiny — the property-test surface)
# ---------------------------------------------------------------------------

def combine_splits(a: Tuple[jax.Array, jax.Array, jax.Array],
                   b: Tuple[jax.Array, jax.Array, jax.Array]):
    """Merge two split triples ``(m, l, acc)`` into one.

    Associative and commutative; ``(NEG_INF, 0, 0)`` is the identity.
    ``m`` is the running max, ``l`` the sum of ``exp(s - m)``, and
    ``acc`` the matching partial numerator (trailing value axis).
    """
    m_a, l_a, o_a = a
    m_b, l_b, o_b = b
    m = jnp.maximum(m_a, m_b)
    wa = jnp.exp(m_a - m)
    wb = jnp.exp(m_b - m)
    return (m, l_a * wa + l_b * wb,
            o_a * wa[..., None] + o_b * wb[..., None])


def reduce_splits(m: jax.Array, l: jax.Array, acc: jax.Array):
    """Fold per-split triples over the leading split axis in one pass.

    m, l: (NS, ...); acc: (NS, ..., D). Returns the combined triple
    (same as left-folding :func:`combine_splits`, but vectorised).
    """
    m_t = jnp.max(m, axis=0)
    w = jnp.exp(m - m_t[None])
    return m_t, jnp.sum(l * w, axis=0), jnp.sum(acc * w[..., None], axis=0)


def _split_masks(pos, win, ks, kj):
    """Shared causal/window/kv_start mask. kj broadcasts against pos."""
    mask = (kj < pos) & (kj >= ks)
    # boolean or, not a select of booleans: Mosaic cannot lower the latter
    return mask & ((win <= 0) | (kj > pos - win))


def flash_decode_splits(qg: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, phys: jax.Array,
                        pos: jax.Array, win: jax.Array, ks: jax.Array,
                        split_pages: int):
    """Per-split triples in plain JAX — the mid-level oracle.

    qg: (B, KVH, G, D) float32 queries, already scaled by D**-0.5.
    k_pages/v_pages: (P+1, page, KVH, D) pool (last page = trash).
    phys: (B, NS*split_pages) physical page ids (trash-padded).
    pos/ks: (B,) int32; win: scalar int32 (0 = no window).
    Returns (m, l, acc) shaped (NS, B, KVH, G[, D]) float32.
    """
    b, kvh, g, d = qg.shape
    ps = k_pages.shape[1]
    nsp = phys.shape[1]
    ns = nsp // split_pages
    sl = split_pages * ps                                  # tokens / split
    kg = k_pages[phys].reshape(b, ns, sl, kvh, d)
    vg = v_pages[phys].reshape(b, ns, sl, kvh, d)
    kj = jnp.arange(ns * sl, dtype=jnp.int32).reshape(ns, sl)
    mask = _split_masks(pos[:, None, None], win, ks[:, None, None],
                        kj[None])                          # (B, NS, SL)
    sc = jnp.einsum("bkgd,bstkd->bskgt", qg, kg,
                    preferred_element_type=jnp.float32)
    sc = jnp.where(mask[:, :, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)                               # (B, NS, KVH, G)
    p = jnp.where(mask[:, :, None, None, :],
                  jnp.exp(sc - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bskgt,bstkd->bskgd", p,
                     vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    to_split = lambda t: jnp.moveaxis(t, 1, 0)             # (NS, B, ...)
    return to_split(m), to_split(l), to_split(acc)


# ---------------------------------------------------------------------------
# Pallas kernel (phase 1: per-split triples, pages DMAed in place)
# ---------------------------------------------------------------------------

def _flash_kernel(phys_ref, pos_ref, win_ref, ks_ref,    # scalar prefetch
                  q_ref, k_ref, v_ref,                   # inputs
                  m_ref, l_ref, acc_ref, *, ps, sp):
    """One (slot, kv-head tile, split, page) grid step.

    The page dimension is innermost, so (m, l, acc) output blocks stay
    VMEM-resident across a split: init at page 0, rescale-and-accumulate
    in place afterwards (the in-kernel LSE carry).
    """
    ib = pl.program_id(0)
    is_ = pl.program_id(2)
    ip = pl.program_id(3)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lp = is_ * sp + ip                                   # LOGICAL page id
    kj = lp * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    mask = _split_masks(pos_ref[ib], win_ref[0], ks_ref[ib], kj)

    q = q_ref[0].astype(jnp.float32)                     # (bh, G, D)
    k = jnp.transpose(k_ref[0].astype(jnp.float32), (1, 0, 2))  # (bh,ps,D)
    sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    sc = jnp.where(mask, sc, NEG_INF)                    # (bh, G, ps)
    m_prev = m_ref[0, 0]                                 # (bh, G)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    v = jnp.transpose(v_ref[0].astype(jnp.float32), (1, 0, 2))  # (bh,ps,D)
    pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[0, 0] = acc_ref[0, 0] * alpha[..., None] + pv


def _splits_pallas(qg, k_pages, v_pages, phys, pos, win, ks,
                   split_pages: int, block_heads: int,
                   interpret: bool = False):
    """Phase-1 triples via ``pallas_call``. Same contract as
    :func:`flash_decode_splits`; the page table is a scalar-prefetch
    operand whose values drive the K/V BlockSpec index maps — each grid
    step DMAs one physical page, nothing is ever gathered in HBM."""
    b, kvh, g, d = qg.shape
    ps = k_pages.shape[1]
    sp = split_pages
    ns = phys.shape[1] // sp
    bh = block_heads
    grid = (b, kvh // bh, ns, sp)

    def page_map(ib, ih, is_, ip, phys_ref, *_):
        return (phys_ref[ib, is_ * sp + ip], 0, ih, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, g, d),
                         lambda ib, ih, is_, ip, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, ps, bh, d), page_map),
            pl.BlockSpec((1, ps, bh, d), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bh, g),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0)),
            pl.BlockSpec((1, 1, bh, g),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0)),
            pl.BlockSpec((1, 1, bh, g, d),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0, 0)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((ns, b, kvh, g), jnp.float32),
        jax.ShapeDtypeStruct((ns, b, kvh, g), jnp.float32),
        jax.ShapeDtypeStruct((ns, b, kvh, g, d), jnp.float32),
    ]
    kern = functools.partial(_flash_kernel, ps=ps, sp=sp)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        phys, pos, win, ks, qg, k_pages, v_pages)


# ---------------------------------------------------------------------------
# quantized-pool variants: pages hold uint8 codebook indices
# (core/kv_codebook.py); fp K/V never exists in HBM — the kernel
# dequantizes (or LUT-accumulates) in VMEM / registers.
# ---------------------------------------------------------------------------

def _deq_tile(codes, rep, table, s):
    """In-kernel dequant of one code tile as two 2-D MXU matmuls.

    codes (ps, bh, nc) int32; rep (nc, nc*c) spreads code ``k`` over
    columns ``k*c .. k*c+c-1``; table (nc*c, D) is the block-diagonal
    codebook (row ``k*c+j`` holds centroid ``j`` of subspace ``k`` at
    lanes ``k*v .. k*v+v-1``); s (bh,) f32 per-head scales. The one-hot
    of each code row times ``table`` is the dequantized row, so the MXU
    does the lookup. Rows are head-major, so the (bh, ps) -> rows merge
    is tile-aligned when ``ps`` is a multiple of 8.
    Returns fp K or V rows (bh, ps, D) — never round-tripped to HBM.
    """
    ps_, bh, nc = codes.shape
    ncc = rep.shape[1]
    c = ncc // nc
    ct = jnp.transpose(codes, (1, 0, 2)).reshape(bh * ps_, nc)
    spread = jnp.dot(ct.astype(jnp.float32), rep,
                     preferred_element_type=jnp.float32)   # exact: ints < c
    j = jax.lax.broadcasted_iota(jnp.int32, (bh * ps_, ncc), 1) % c
    oh = (spread.astype(jnp.int32) == j).astype(jnp.float32)
    rows = jnp.dot(oh, table, preferred_element_type=jnp.float32)
    return rows.reshape(bh, ps_, -1) * s[:, None, None]


def _deq_operands(z):
    """(nc, c, v) centroids -> (rep, table) operands of :func:`_deq_tile`."""
    nc, c, v = z.shape
    rep = (jnp.arange(nc * c)[None, :] // c
           == jnp.arange(nc)[:, None]).astype(jnp.float32)
    table = (z.astype(jnp.float32)[:, :, None, :]
             * jnp.eye(nc, dtype=jnp.float32)[:, None, :, None])
    return rep, table.reshape(nc * c, nc * v)


def _flash_kernel_kvq(phys_ref, pos_ref, win_ref, ks_ref,   # scalar prefetch
                      q_ref, kc_ref, vc_ref, rep_ref, tk_ref, tv_ref,
                      sk_ref, sv_ref,                       # inputs
                      m_ref, l_ref, acc_ref, *, ps, sp):
    """Quantized-pool twin of :func:`_flash_kernel`: the DMAed page block
    is a uint8 code tile (``nc`` bytes/token/head instead of ``4*D``);
    K/V are dequantized in VMEM right before the score / value dots."""
    ib = pl.program_id(0)
    is_ = pl.program_id(2)
    ip = pl.program_id(3)

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lp = is_ * sp + ip                                   # LOGICAL page id
    kj = lp * ps + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    mask = _split_masks(pos_ref[ib], win_ref[0], ks_ref[ib], kj)

    q = q_ref[0].astype(jnp.float32)                     # (bh, G, D)
    k = _deq_tile(kc_ref[0].astype(jnp.int32), rep_ref[...], tk_ref[...],
                  sk_ref[:, 0])                          # (bh, ps, D)
    sc = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    sc = jnp.where(mask, sc, NEG_INF)                    # (bh, G, ps)
    m_prev = m_ref[0, 0]                                 # (bh, G)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
    p = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    v = _deq_tile(vc_ref[0].astype(jnp.int32), rep_ref[...], tv_ref[...],
                  sv_ref[:, 0])                          # (bh, ps, D)
    pv = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    m_ref[0, 0] = m_new
    l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[0, 0] = acc_ref[0, 0] * alpha[..., None] + pv


def _splits_pallas_kvq(qg, kc_pages, vc_pages, zk, zv, sk, sv,
                       phys, pos, win, ks,
                       split_pages: int, block_heads: int,
                       interpret: bool = False):
    """Phase-1 triples off a QUANTIZED pool. Same grid/page-map contract
    as :func:`_splits_pallas`; the codebook tables (in the
    :func:`_deq_operands` form) and per-head scales ride along as small
    VMEM-resident operands (tables whole, sk/sv tiled with the kv-head
    grid axis)."""
    b, kvh, g, d = qg.shape
    ps = kc_pages.shape[1]
    nc, c, v = zk.shape
    sp = split_pages
    ns = phys.shape[1] // sp
    bh = block_heads
    grid = (b, kvh // bh, ns, sp)

    def page_map(ib, ih, is_, ip, phys_ref, *_):
        return (phys_ref[ib, is_ * sp + ip], 0, ih, 0)

    def table_map(ib, ih, is_, ip, *_):
        return (0, 0)

    def scale_map(ib, ih, is_, ip, *_):
        return (ih, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, g, d),
                         lambda ib, ih, is_, ip, *_: (ib, ih, 0, 0)),
            pl.BlockSpec((1, ps, bh, nc), page_map),
            pl.BlockSpec((1, ps, bh, nc), page_map),
            pl.BlockSpec((nc, nc * c), table_map),
            pl.BlockSpec((nc * c, d), table_map),
            pl.BlockSpec((nc * c, d), table_map),
            pl.BlockSpec((bh, 1), scale_map),
            pl.BlockSpec((bh, 1), scale_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bh, g),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0)),
            pl.BlockSpec((1, 1, bh, g),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0)),
            pl.BlockSpec((1, 1, bh, g, d),
                         lambda ib, ih, is_, ip, *_: (is_, ib, ih, 0, 0)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((ns, b, kvh, g), jnp.float32),
        jax.ShapeDtypeStruct((ns, b, kvh, g), jnp.float32),
        jax.ShapeDtypeStruct((ns, b, kvh, g, d), jnp.float32),
    ]
    rep, table_k = _deq_operands(zk)
    table_v = _deq_operands(zv)[1]
    kern = functools.partial(_flash_kernel_kvq, ps=ps, sp=sp)
    return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        phys, pos, win, ks, qg, kc_pages, vc_pages,
        rep, table_k, table_v,
        sk.reshape(kvh, 1).astype(jnp.float32),
        sv.reshape(kvh, 1).astype(jnp.float32))


def _flash_xla_kvq(qg, kc_pages, vc_pages, zk, zv, sk, sv,
                   phys, pos, win, ks):
    """XLA-native quantized-pool decode: LUT-accumulate, never dequantize.

    The paper's CCM→IMM split applied to attention scores: per (kv head,
    query head, subspace) build the tiny LUT ``q_sub · z`` — scores are
    then a one-hot contraction of the gathered CODE pages (uint8, ``nc``
    bytes/token/head of HBM traffic instead of ``4*D``). The value side
    pools probability mass per (subspace, centroid) first and applies
    each centroid vector once — fp K/V rows are never materialised, not
    even transiently. Returns the cache triple (m, l, acc)."""
    b, kvh, g, d = qg.shape
    ps = kc_pages.shape[1]
    nc, c, v = zk.shape
    np_ = phys.shape[1]
    t = np_ * ps
    kc = kc_pages[phys].reshape(b, t, kvh, nc)           # uint8 gathers —
    vc = vc_pages[phys].reshape(b, t, kvh, nc)           # 4-16x less HBM
    # score LUT: fold the per-head K scale into the query
    qs = (qg * sk[None, :, None, None]).reshape(b, kvh, g, nc, v)
    lut_k = jnp.einsum("bkgsv,scv->bkgsc", qs, zk.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    oh_k = jax.nn.one_hot(kc.astype(jnp.int32), c, dtype=jnp.float32)
    sc = jnp.einsum("btksc,bkgsc->bkgt", oh_k, lut_k,
                    preferred_element_type=jnp.float32)  # (B, KVH, G, T)
    kj = jnp.arange(t, dtype=jnp.int32)
    mask = _split_masks(pos[:, None], win, ks[:, None], kj[None])  # (B, T)
    sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)                             # (B, KVH, G)
    p = jnp.where(mask[:, None, None, :],
                  jnp.exp(sc - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    # value LUT-accumulate: probability mass per (head, subspace, centroid)
    oh_v = jax.nn.one_hot(vc.astype(jnp.int32), c, dtype=jnp.float32)
    w = jnp.einsum("bkgt,btksc->bkgsc", p, oh_v,
                   preferred_element_type=jnp.float32)
    acc = jnp.einsum("bkgsc,scv->bkgsv", w, zv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    acc = acc.reshape(b, kvh, g, d) * sv[None, :, None, None]
    return m, l, acc


# ---------------------------------------------------------------------------
# XLA-native impl ("ref"): page-table decode without gathering K/V rows
# ---------------------------------------------------------------------------

def _flash_xla(qg, k_pages, v_pages, phys, pos, win, ks):
    """Whole-softmax paged decode moving only score-sized HBM traffic.

    Per key token a score is KVH*G floats but a K row is KVH*D — scoring
    against the whole pool and *gathering scores* (then scattering
    probabilities for the V contraction) reads each pool page once and
    never materialises a dense KV view. Returns the combined cache
    triple (m, l, acc) shaped (B, KVH, G[, D]) — the caller folds the
    self term.
    """
    b, kvh, g, d = qg.shape
    p1, ps = k_pages.shape[0], k_pages.shape[1]
    np_ = phys.shape[1]
    sc_all = jnp.einsum("bkgd,ptkd->bptkg", qg, k_pages,
                        preferred_element_type=jnp.float32)
    sc = jnp.take_along_axis(
        sc_all, phys[:, :, None, None, None], axis=1)    # (B,NP,ps,KVH,G)
    kj = jnp.arange(np_ * ps, dtype=jnp.int32).reshape(np_, ps)
    mask = _split_masks(pos[:, None, None], win, ks[:, None, None],
                        kj[None])                        # (B, NP, ps)
    sc = jnp.where(mask[..., None, None], sc, NEG_INF)
    m = jnp.max(sc, axis=(1, 2))                         # (B, KVH, G)
    p = jnp.where(mask[..., None, None],
                  jnp.exp(sc - m[:, None, None]), 0.0)
    l = jnp.sum(p, axis=(1, 2))
    # scatter probabilities to pool space. A scatter-ADD keeps duplicate
    # targets exact: unallocated pages of one slot all redirect to the
    # trash page (their masked rows contribute zeros), and CoW-shared
    # pages live in distinct batch rows so they never collide.
    p_all = jnp.zeros((b, p1, ps, kvh, g), jnp.float32)
    p_all = p_all.at[jnp.arange(b)[:, None], phys].add(p)
    acc = jnp.einsum("bptkg,ptkd->bkgd", p_all,
                     v_pages.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return m, l, acc


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       phys: jax.Array, positions, *,
                       window=0, kv_start=0, impl: str = "ref",
                       codebook: Optional[dict] = None,
                       split_pages: Optional[int] = None,
                       block_heads: Optional[int] = None,
                       interpret: bool = False) -> jax.Array:
    """Single-token paged decode, numerically matching
    ``layers._sdpa_decode_combine`` over the gathered view.

    q (B,1,H,D); k_pages/v_pages (P+1, page, KVH, D) — one layer's slice
    of the pool, last page = trash; k_new/v_new (B,1,KVH,D) the fresh
    token (NOT yet in the pool — the caller scatters it afterwards, and
    its self term is always computed from the fp values, so the newest
    token is exact even on a quantized pool).
    phys (B, NP) physical page ids, already trash-redirected.
    positions (B,) int32 per-slot lengths (-1 = inactive lane: output is
    the garbage ``v_new`` row, discarded by the caller — same contract
    as ``_sdpa_decode_combine``). window/kv_start: scalar or (B,).
    codebook: one layer's slice of the KV codebook pytree (``{"zk":
    (nc,c,v), "zv": ..., "sk": (KVH,), "sv": ...}``, see
    core/kv_codebook.py) — when given, k_pages/v_pages are uint8 CODE
    pools ``(P+1, page, KVH, nc)`` and the impl dequantizes in VMEM
    (pallas) or LUT-accumulates (ref) without materialising fp K/V.
    impl: "pallas" | "ref". Returns (B, 1, H*D) in q.dtype.
    """
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"flash decode is single-token (got S={s})")
    ps, kvh = k_pages.shape[1], k_pages.shape[2]
    msize = tp_size()
    if impl == "pallas" and msize > 1:
        if kvh % msize:
            require_local("flash_decode_paged")
        # a Mosaic kernel is opaque to GSPMD: run it on each chip's
        # kv heads (the pool is head-sharded, parallel/sharding.py)
        return _flash_decode_tp(
            q, k_pages, v_pages, k_new, v_new, phys, positions, window,
            kv_start, codebook, split_pages, block_heads, interpret)
    g = h // kvh
    np_ = phys.shape[1]
    scale = d ** -0.5
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32) * scale
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (b,))
    ks = jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32), (b,))
    win = jnp.asarray(window, jnp.int32).reshape(-1)[:1]   # (1,) scalar

    if impl == "pallas":
        deq = 0 if codebook is None else 4
        blk = select_blocks("flash_decode", b, np_, ps, d,
                            k_pages.dtype.itemsize, deq_itemsize=deq)
        sp = min(split_pages or blk.block_k, np_)
        bh = flash_head_tile(kvh, block_heads or blk.block_n)
        pad = (-np_) % sp
        if pad:                       # trash-pad: kj >= NP*page >= pos
            phys = jnp.pad(phys, ((0, 0), (0, pad)),
                           constant_values=k_pages.shape[0] - 1)
        if codebook is None:
            m, l, acc = _splits_pallas(qg, k_pages, v_pages, phys, pos,
                                       win, ks, sp, bh, interpret=interpret)
        else:
            m, l, acc = _splits_pallas_kvq(
                qg, k_pages, v_pages, codebook["zk"], codebook["zv"],
                codebook["sk"], codebook["sv"], phys, pos, win, ks,
                sp, bh, interpret=interpret)
        m, l, acc = reduce_splits(m, l, acc)
    elif impl == "ref":
        if codebook is None:
            m, l, acc = _flash_xla(qg, k_pages, v_pages, phys, pos,
                                   win[0], ks)
        else:
            m, l, acc = _flash_xla_kvq(
                qg, k_pages, v_pages, codebook["zk"], codebook["zv"],
                codebook["sk"], codebook["sv"], phys, pos, win[0], ks)
    else:
        raise ValueError(f"unknown flash impl {impl!r} (pallas | ref)")

    # fold the self term (qg is pre-scaled). The new token is always
    # live, so the denominator is >= exp(0) — never zero, even for
    # fully-masked (pos=-1) lanes.
    s_new = jnp.einsum("bkgd,bkd->bkg", qg,
                       k_new[:, 0].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    m_f = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_f)
    p_new = jnp.exp(s_new - m_f)
    denom = l * alpha + p_new
    out = (acc * alpha[..., None]
           + p_new[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32))
    out = out / denom[..., None]
    return out.reshape(b, 1, h * d).astype(q.dtype)


def _flash_decode_tp(q, k_pages, v_pages, k_new, v_new, phys, positions,
                     window, kv_start, codebook, split_pages, block_heads,
                     interpret):
    """:func:`flash_decode_paged` (pallas) per ``model`` shard of heads."""
    heads = P(None, None, "model", None)
    cb_spec = (None if codebook is None else
               {k: P("model") if k in ("sk", "sv") else P()
                for k in codebook})

    def local(q, kp, vp, kn, vn, phys, pos, win, ks, cb):
        return flash_decode_paged(
            q, kp, vp, kn, vn, phys, pos, window=win, kv_start=ks,
            impl="pallas", codebook=cb, split_pages=split_pages,
            block_heads=block_heads, interpret=interpret)
    return jax.shard_map(
        local, in_specs=(heads,) * 5 + (P(),) * 4 + (cb_spec,),
        out_specs=P(None, None, "model"), check_vma=False)(
        q, k_pages, v_pages, k_new, v_new, phys,
        jnp.asarray(positions, jnp.int32), jnp.asarray(window, jnp.int32),
        jnp.asarray(kv_start, jnp.int32), codebook)


def resolve_flash_impl(flash: str, on_tpu: Optional[bool] = None) -> str:
    """Map ``QuantConfig.flash`` to a concrete decode impl.

    "auto" picks the Pallas kernel on TPU and the legacy gather path on
    CPU hosts: interpret-mode Pallas is orders of magnitude slower than
    XLA, and "gather" keeps CPU decode bit-identical to earlier
    releases. Opt into "ref" explicitly for the XLA no-gather path.
    """
    if flash == "auto":
        if on_tpu is None:
            on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu else "gather"
    if flash not in ("pallas", "ref", "gather"):
        raise ValueError(
            f"unknown flash mode {flash!r} (auto | pallas | ref | gather)")
    return flash
