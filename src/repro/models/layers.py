"""Shared model building blocks (RMSNorm, RoPE, attention, SwiGLU MLP).

Every projection routes through :func:`proj` — a LutLinear — so the paper's
VQ-AMM technique is a first-class switch for all architectures. Functions
return ``(out, recon)`` where ``recon`` is the accumulated reconstruction
loss (non-zero only in ``lut_train`` mode).

Attention masks are *parametric* (q_offset / window / prefix_len scalars),
never materialised as (S, T) tensors outside the score computation — this is
what lets the 32k/500k shapes lower with bounded memory (the chunked
online-softmax path builds only (S, chunk) mask tiles per scan step).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lut import QuantConfig, lut_linear_apply, lut_linear_init
from repro.kernels.flash_decode import flash_decode_paged

Params = Dict


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x (B, S, H, D), positions (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs   # (B, S, half)
    cos = jnp.cos(angles)[..., None, :]                          # (B, S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def proj(p: Params, x: jax.Array, qc: QuantConfig,
         tp: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """One LutLinear projection (out, recon); ``tp`` is its
    tensor-parallel role, "col" or "row" (parallel/sharding.py)."""
    return lut_linear_apply(p, x, qc, tp)


def init_proj(key, k, n, qc: QuantConfig, bias=False, dtype=jnp.float32):
    return lut_linear_init(key, k, n, qc, bias=bias, dtype=dtype)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def mask_tile(qi: jax.Array, kj: jax.Array, window, prefix_len) -> jax.Array:
    """(s, t) boolean attention mask from absolute positions.

    qi (s,), kj (t,): query/key absolute positions. window: 0 = global,
    >0 = sliding window. prefix_len: positions < prefix_len attend
    bidirectionally within the prefix (prefix-LM / VLM image tokens).
    """
    m = kj[None, :] <= qi[:, None]
    win = jnp.asarray(window)
    m = m & jnp.where(win > 0, kj[None, :] > qi[:, None] - win, True)
    pl = jnp.asarray(prefix_len)
    m = m | ((qi[:, None] < pl) & (kj[None, :] < pl))
    return m


def _trivial_start(kv_start) -> bool:
    """True when ``kv_start`` is the static no-op value (int 0).

    ``kv_start`` masks out key positions ``< kv_start`` — the left-pad
    convention for batch-to-completion serving (prompts right-aligned, pad
    ids occupying cache rows ``[0, pad_len)``). Keeping the zero case a
    *Python* check preserves the exact HLO (and bit-identical outputs) of
    every pre-existing call site.
    """
    return isinstance(kv_start, int) and kv_start == 0


def _start_mask(kv_start, kj: jax.Array, b: int) -> jax.Array:
    """(B, t) boolean mask keeping keys at positions >= kv_start.

    kv_start: scalar or (B,) first *valid* key position per row.
    """
    ks = jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32), (b,))
    return kj[None, :] >= ks[:, None]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg, qc: QuantConfig, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": init_proj(ks[0], d, h * hd, qc, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_proj(ks[1], d, kvh * hd, qc, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_proj(ks[2], d, kvh * hd, qc, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_proj(ks[3], h * hd, d, qc, dtype=dtype),
        "norm": jnp.zeros((d,), dtype),
    }


def _sdpa(q, k, v, q_offset, window, prefix_len, impl="naive", chunk=1024,
          ulysses=None, kv_start=0):
    """Grouped-query SDPA. q (B,S,H,D), k/v (B,T,KVH,D).

    kv_start: scalar or (B,) — key positions < kv_start are masked out
    (left-padded batched prompts; see :func:`_trivial_start`).
    """
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    scale = d ** -0.5
    if impl == "chunked" and t > chunk:
        out = _sdpa_chunked(qg, k, v, scale, chunk, q_offset, window,
                            prefix_len, ulysses, kv_start=kv_start)
        return out.reshape(b, s, h, d)
    qi = jnp.arange(s) + q_offset
    kj = jnp.arange(t)
    mask = mask_tile(qi, kj, window, prefix_len)                 # (s, t)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if _trivial_start(kv_start):
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    else:                                        # (B, s, t) per-row mask
        mb = mask[None] & _start_mask(kv_start, kj, b)[:, None, :]
        scores = jnp.where(mb[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def _sdpa_chunked(qg, k, v, scale, chunk, q_offset, window, prefix_len,
                  ulysses=None, kv_start=0):
    """Online-softmax attention scanning KV chunks (flash-style memory)."""
    b, s, kvh, g, d = qg.shape
    t = k.shape[1]
    pad = (-t) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nchunks = k.shape[1] // chunk
    kc = jnp.moveaxis(k.reshape(b, nchunks, chunk, kvh, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nchunks, chunk, kvh, d), 1, 0)
    qi = jnp.arange(s) + q_offset

    def _c(x, spec):
        return jax.lax.with_sharding_constraint(x, spec) \
            if ulysses is not None else x

    def step(carry, inp):
        m_run, l_run, acc = carry
        ci, kb, vb = inp
        kj = ci * chunk + jnp.arange(chunk)
        mk = mask_tile(qi, kj, window, prefix_len)               # (s, chunk)
        sc = jnp.einsum("bskgd,btkd->bkgst", qg, kb,
                        preferred_element_type=jnp.float32) * scale
        if _trivial_start(kv_start):
            sc = jnp.where(mk[None, None, None], sc, -1e30)
        else:
            mb = mk[None] & _start_mask(kv_start, kj, b)[:, None, :]
            sc = jnp.where(mb[:, None, None], sc, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        # keep the V stream in its storage dtype (bf16): casting vb to f32
        # here hoists a whole-cache f32 convert out of the scan (2× cache
        # HBM traffic + f32 collectives). The MXU accumulates in f32 via
        # preferred_element_type; only the (small) p tile is cast.
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kvh, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, s, d), jnp.float32)
    if ulysses is not None:
        # pin the online-softmax carries to the query's seq-sharding, or
        # GSPMD replicates the carry and all-gathers the probs per chunk
        b_ax = ulysses["q"][0]
        m0 = _c(m0, jax.sharding.PartitionSpec(b_ax, None, None, "model"))
        l0 = _c(l0, jax.sharding.PartitionSpec(b_ax, None, None, "model"))
        acc0 = _c(acc0, jax.sharding.PartitionSpec(
            b_ax, None, None, "model", None))
    (_, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(nchunks), kc, vc))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).astype(qg.dtype)              # (b,s,kvh,g,d)


def rope_interleaved_hd(x: jax.Array, positions: jax.Array,
                        theta: float) -> jax.Array:
    """Interleaved (GPT-J pairing) RoPE for hd-major layout.

    x (B, S, D, H): pairs are (2i, 2i+1) along D, so the rotation is local
    to any even-sized shard of D — no cross-shard halves like the classic
    rotate-half form."""
    b, s, d, h = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs        # (B?,S,half)
    cos = jnp.cos(ang)[..., None]                                 # (B?,S,half,1)
    sin = jnp.sin(ang)[..., None]
    xr = x.astype(jnp.float32).reshape(b, s, half, 2, h)
    x1, x2 = xr[..., 0, :], xr[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-2)
    return out.reshape(b, s, d, h).astype(x.dtype)


def _sdpa_hd(q, k, v, q_offset, window, prefix_len, impl="naive",
             chunk=1024):
    """GQA SDPA in hd-major layout. q (B,S,D,H), k/v (B,T,D,KVH)."""
    b, s, d, h = q.shape
    t, kvh = k.shape[1], k.shape[3]
    g = h // kvh
    qg = q.reshape(b, s, d, kvh, g)
    scale = d ** -0.5
    if impl == "chunked" and t > chunk:
        out = _sdpa_hd_chunked(qg, k, v, scale, chunk, q_offset, window,
                               prefix_len)                        # (b,s,k,g,d)
    else:
        qi = jnp.arange(s) + q_offset
        kj = jnp.arange(t)
        mask = mask_tile(qi, kj, window, prefix_len)
        scores = jnp.einsum("bsdkg,btdk->bkgst", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btdk->bkgsd", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = jnp.moveaxis(out, 3, 1)                             # (b,s,k,g,d)
    # back to hd-major flat (B, S, D·H)
    return jnp.transpose(out, (0, 1, 4, 2, 3)).reshape(b, s, d * h) \
        .astype(q.dtype)


def _sdpa_hd_chunked(qg, k, v, scale, chunk, q_offset, window, prefix_len):
    """Online-softmax over KV chunks, hd-major layout. Returns
    (b, s, kvh, g, d) fp32."""
    b, s, d, kvh, g = qg.shape
    t = k.shape[1]
    pad = (-t) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nchunks = k.shape[1] // chunk
    kc = jnp.moveaxis(k.reshape(b, nchunks, chunk, d, kvh), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nchunks, chunk, d, kvh), 1, 0)
    qi = jnp.arange(s) + q_offset

    def step(carry, inp):
        m_run, l_run, acc = carry
        ci, kb, vb = inp
        kj = ci * chunk + jnp.arange(chunk)
        mk = mask_tile(qi, kj, window, prefix_len)
        sc = jnp.einsum("bsdkg,btdk->bkgst", qg, kb,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(mk[None, None, None], sc, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btdk->bkgsd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kvh, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, s, d), jnp.float32)
    (_, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(nchunks), kc, vc))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1)                                # (b,s,k,g,d)


def _sdpa_local(q, k, v, window: int):
    """Block-local sliding-window attention (q_offset=0, S % window == 0).

    Each query block of W positions attends only to its own and the
    previous key block — S×2W work instead of S×T. For gemma3's 5:1
    local:global pattern this removes ~16× of the attention compute and
    score traffic on 5/6 of the layers at 32k context. [§Perf I8]
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    w = window
    nb = s // w
    scale = d ** -0.5
    qb = q.reshape(b, nb, w, kvh, g, d)
    kb = k.reshape(b, nb, w, kvh, d)
    vb = v.reshape(b, nb, w, kvh, d)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kcat = jnp.concatenate([kprev, kb], axis=2)          # (b, nb, 2w, kvh, d)
    vcat = jnp.concatenate([vprev, vb], axis=2)
    scores = jnp.einsum("bnwkgd,bntkd->bnkgwt", qb, kcat,
                        preferred_element_type=jnp.float32) * scale
    # relative mask: query abs = n·w + i, key abs = n·w + (t − w)
    qi = jnp.arange(w)[:, None]
    kt = jnp.arange(2 * w)[None, :] - w
    rel = qi - kt
    mask = (rel >= 0) & (rel < w)                         # causal ∧ window
    first = (jnp.arange(nb) == 0)[:, None, None]          # block −1 invalid
    mask = mask[None] & ~(first & (kt < 0)[None])         # (nb, w, 2w)
    scores = jnp.where(mask[None, :, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnkgwt,bntkd->bnwkgd", probs.astype(v.dtype), vcat,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def _sdpa_verify(q, k_cache, v_cache, k_new, v_new, pos, window):
    """Multi-token verify over a read-only cache + the proposed tokens.

    The speculative-decoding verify step scores S proposed tokens per row
    in one call: query t of row b sits at absolute position pos[b] + t and
    attends (a) committed cache rows < pos[b] and (b) the proposed tokens
    0..t themselves, whose K/V arrive fresh — they are NOT in the cache
    yet (the caller scatters the slab afterwards, exactly like decode).
    Cache rows >= pos[b] are masked: they hold draft-phase or stale KV.

    q (B,S,H,D); k_cache/v_cache (B,T,KVH,D); k_new/v_new (B,S,KVH,D).
    pos: (B,) per-row committed lengths (-1 = inactive lane: every cache
    row is masked and the row attends only its own fresh tokens — the
    output is discarded by the caller). Returns (B, S, H·D).
    """
    b, s, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    scale = d ** -0.5
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    win = jnp.asarray(window)
    qi = jnp.arange(s)                                   # in-round index
    # cache part: kj < pos[b], windowed against absolute query positions
    kj = jnp.arange(t)
    mc = kj[None, None, :] < pos_b[:, None, None]        # (B, 1, T)
    q_abs = pos_b[:, None] + qi[None, :]                 # (B, S)
    mc = mc & jnp.where(win > 0,
                        kj[None, None, :] > q_abs[:, :, None] - win, True)
    # self part: fresh token j visible to query t iff j <= t (causal)
    ms = qi[None, :] <= qi[:, None]                      # (S, S)
    ms = ms & jnp.where(win > 0, qi[None, :] > qi[:, None] - win, True)
    k_all = jnp.concatenate([k_cache, k_new], axis=1)    # (B, T+S, KVH, D)
    v_all = jnp.concatenate([v_cache, v_new], axis=1)
    mask = jnp.concatenate(
        [jnp.broadcast_to(mc, (b, s, t)),
         jnp.broadcast_to(ms[None], (b, s, s))], axis=-1)  # (B, S, T+S)
    sc = jnp.einsum("bskgd,btkd->bkgst", qg, k_all,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    probs = jax.nn.softmax(sc, axis=-1).astype(v_all.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_all)
    return out.reshape(b, s, h * d).astype(q.dtype)


def _sdpa_decode_combine(q, k_cache, v_cache, k_new, v_new, pos, window,
                         kv_start=0):
    """Single-token decode over an *unmodified* cache + the new token.

    Two-part online softmax: the cache part (positions < pos) and the self
    term (the new token), combined without ever materialising an updated
    cache — the caller writes the (tiny) new-token slab back once per step
    outside the layer loop. [§Perf I5]

    q (B,1,H,D); k_cache/v_cache (B,T,KVH,D); k_new/v_new (B,1,KVH,D).
    pos: scalar, or (B,) per-row sequence lengths (continuous batching —
    each slot decodes at its own position). kv_start: scalar or (B,) first
    valid cache row (left-padded batch-to-completion prompts).
    """
    b, _, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    scale = d ** -0.5
    kj = jnp.arange(t)
    pos_a = jnp.asarray(pos)
    per_row = pos_a.ndim > 0 or not _trivial_start(kv_start)
    sc = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale
    if per_row:
        pos_b = jnp.broadcast_to(pos_a, (b,))
        mask = kj[None, :] < pos_b[:, None]                      # (B, T)
        win = jnp.asarray(window)
        mask = mask & jnp.where(
            win > 0, kj[None, :] > pos_b[:, None] - win, True)
        if not _trivial_start(kv_start):
            mask = mask & _start_mask(kv_start, kj, b)
        sc = jnp.where(mask[:, None, None, :], sc, -1e30)
    else:
        mask = (kj < pos)
        win = jnp.asarray(window)
        mask = mask & jnp.where(win > 0, kj > pos - win, True)   # (T,)
        sc = jnp.where(mask[None, None, None], sc, -1e30)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                       preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(jnp.max(sc, axis=-1), s_new)                 # (b,k,g)
    p_old = jnp.exp(sc - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = jnp.sum(p_old, axis=-1) + p_new
    out = (jnp.einsum("bkgt,btkd->bkgd", p_old.astype(v_cache.dtype),
                      v_cache, preferred_element_type=jnp.float32)
           + p_new[..., None] * v_new[:, 0, :, None, :])
    out = out / denom[..., None]
    return out.reshape(b, 1, h * d).astype(q.dtype)


def _ulysses_specs(q, k):
    """Sequence-parallel (DeepSpeed-Ulysses) resharding decision.

    When the kv heads don't divide the model axis, head/hd sharding of the
    S×T score contraction makes GSPMD all-reduce full score tensors
    (hundreds of GB at 32k). Instead, reshard Q/K/V to *sequence*-sharded
    over the model axis (an all-to-all), attend locally with full heads,
    and reshard back. Returns (spec, out_spec) or (None, None) when not
    applicable / no ambient mesh. [§Perf I6]
    """
    try:
        am = jax.sharding.get_abstract_mesh()
        names = getattr(am, "axis_names", ())
        if "model" not in names:
            return None, None
        msize = am.shape["model"]
        if msize <= 1:
            return None, None
        kvh = k.shape[2]
        b, s, t = q.shape[0], q.shape[1], k.shape[1]
        if kvh % msize == 0:
            return None, None                  # heads shard fine: no need
        if s % msize or s <= msize or t % msize:
            return None, None
        from jax.sharding import PartitionSpec as _P
        b_ax = "data" if ("data" in names and b % am.shape["data"] == 0
                          and b >= am.shape["data"]) else None
        return {
            "q": _P(b_ax, "model", None, None),      # queries: seq-sharded
            "kv": _P(b_ax, None, None, None),        # keys/values: gathered
            "out": _P(b_ax, None, None, "model"),    # back to hd-sharded
        }, True
    except Exception:
        return None, None


def attention(p: Params, x: jax.Array, cfg, qc: QuantConfig,
              q_offset=0, window=0, prefix_len=0,
              cache: Optional[Params] = None,
              decode_slab: bool = False,
              kv_start=0,
              paged_phys: Optional[jax.Array] = None,
              flash_impl: str = "ref",
              ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Pre-norm GQA attention block. Returns (out, recon, new_cache).

    Args:
      p: layer params {"wq","wk","wv","wo","norm"} (LutLinear pytrees).
      x: (B, S, D) residual-stream input.
      q_offset: absolute position of the first query — a scalar, or a
        (B,) array of per-row positions (continuous-batching decode,
        where every slot sits at a different sequence length). Per-row
        offsets are only supported on the ``decode_slab`` path.
      window: 0 = global attention, >0 = sliding window of that width.
      prefix_len: positions < prefix_len attend bidirectionally (VLM).
      cache: KV cache per cfg.head_layout —
        "heads": {"k": (B, T, KVH, HD), ...}; "hd": {"k": (B, T, HD, KVH)}.
        New K/V are written at q_offset (scalar offsets only).
      decode_slab: single-token decode — the cache is consumed strictly
        read-only and new_cache is just the new-token
        {"k": (B, 1, KVH, HD), "v": ...} slab (the caller owns the write,
        e.g. a paged-cache scatter at per-slot positions).
      kv_start: scalar or (B,) — cache rows < kv_start are masked out
        (the left-pad convention: batch-to-completion engines right-align
        prompts, so rows [0, pad_len) hold pad garbage that must never be
        attended; see docs/serving.md).
      paged_phys: (B, NP) trash-redirected physical page ids. When set
        (single-token ``decode_slab`` only), ``cache`` is one layer's
        slice of the paged POOL ``{"k": (P+1, page, KVH, HD), ...}`` and
        decode runs the flash kernel straight off the pages — no dense
        per-slot view exists (see kernels/flash_decode.py).
      flash_impl: "pallas" | "ref" — concrete flash impl (the "auto" /
        "gather" resolution happens in ``model.decode_paged``).

    Returns: (out (B, S, D), recon scalar, new_cache or slab or None).
    """
    b, s, _ = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    q, r1 = proj(p["wq"], xn, qc, "col")
    k, r2 = proj(p["wk"], xn, qc, "col")
    v, r3 = proj(p["wv"], xn, qc, "col")
    qo = jnp.asarray(q_offset)
    if qo.ndim == 0:
        positions = (jnp.arange(s) + q_offset)[None, :]          # (1, S)
    else:                                                        # (B, S)
        positions = qo[:, None] + jnp.arange(s)[None, :]
    if cfg.head_layout == "hd":
        # hd-major: projection columns are (hd, head) ordered; the reshape
        # is shard-aligned with the column-parallel weight sharding.
        q = rope_interleaved_hd(q.reshape(b, s, hd, h), positions,
                                cfg.rope_theta)
        k = rope_interleaved_hd(k.reshape(b, s, hd, kvh), positions,
                                cfg.rope_theta)
        v = v.reshape(b, s, hd, kvh)
    else:
        q = rope(q.reshape(b, s, h, hd), positions, cfg.rope_theta)
        k = rope(k.reshape(b, s, kvh, hd), positions, cfg.rope_theta)
        v = v.reshape(b, s, kvh, hd)
    if decode_slab and cache is not None and cfg.head_layout != "hd":
        # vector-quantized pool: cache["k"/"v"] are uint8 codes and the
        # per-layer codebook slice rides along; the fresh slab stays fp
        # (model._encode_rows quantizes at the scatter site) and the
        # kernel dequantizes / LUT-accumulates the pool in place.
        cb = cache.get("codebook")
        if s == 1:
            if paged_phys is not None:
                # paged flash decode: cache is the raw page pool slice;
                # the kernel walks it through the page table in place.
                out = flash_decode_paged(
                    q, cache["k"], cache["v"], k, v, paged_phys,
                    q_offset, window=window, kv_start=kv_start,
                    impl=flash_impl, codebook=cb,
                    interpret=jax.default_backend() != "tpu")
            else:
                out = _sdpa_decode_combine(
                    q, cache["k"].astype(x.dtype),
                    cache["v"].astype(x.dtype),
                    k.astype(x.dtype), v.astype(x.dtype),
                    q_offset, window, kv_start=kv_start)
        else:
            # multi-token verify (speculative decoding): the cache stays
            # read-only; the S proposed tokens attend committed rows
            # < q_offset[b] plus each other causally (kv_start is the
            # paged engine's static 0 here).
            out = _sdpa_verify(q, cache["k"].astype(x.dtype),
                               cache["v"].astype(x.dtype),
                               k.astype(x.dtype), v.astype(x.dtype),
                               q_offset, window)
        out, r4 = proj(p["wo"], out, qc, "row")
        if cb is not None:      # quantized pool: slab must stay fp
            slab = {"k": k, "v": v}
        else:
            slab = {"k": k.astype(cache["k"].dtype),
                    "v": v.astype(cache["v"].dtype)}
        return out, r1 + r2 + r3 + r4, slab

    k_fresh, v_fresh = k, v
    new_cache = None
    if cache is not None:
        kc = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), q_offset, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), q_offset, axis=1)
        new_cache = {"k": kc, "v": vc}
        k, v = kc.astype(x.dtype), vc.astype(x.dtype)
    # block-local fast path: static window + q_offset==0 (train/prefill).
    # All keys a window can see are inside the current sequence, so the
    # fresh (pre-cache) K/V suffice. [§Perf I8]
    if (isinstance(window, int) and window > 0 and s > 1
            and isinstance(q_offset, int) and q_offset == 0
            and s % window == 0 and isinstance(prefix_len, int)
            and prefix_len == 0 and cfg.head_layout != "hd"
            and _trivial_start(kv_start)):
        out = _sdpa_local(q, k_fresh, v_fresh, window).reshape(b, s, h * hd)
        out, r4 = proj(p["wo"], out, qc, "row")
        return out, r1 + r2 + r3 + r4, new_cache

    # decode (s==1): the full score row is tiny — use the naive path. The
    # chunked path would reshape the (possibly seq-sharded) T dim, forcing
    # GSPMD to all-gather the whole cache; the naive einsum instead reduces
    # over the sharded T (flash-decoding semantics for free). [§Perf I4]
    impl = "naive" if s == 1 else cfg.attn_impl
    if cfg.head_layout == "hd":
        if not _trivial_start(kv_start):
            raise NotImplementedError(
                "kv_start masking is not supported for head_layout='hd'")
        out = _sdpa_hd(q, k, v, q_offset, window, prefix_len,
                       impl, cfg.attn_chunk)
    else:
        specs, apply_u = (None, False)
        if s > 1:                              # prefill / train
            specs, apply_u = _ulysses_specs(q, k)
        if apply_u:
            q = jax.lax.with_sharding_constraint(q, specs["q"])
            k = jax.lax.with_sharding_constraint(k, specs["kv"])
            v = jax.lax.with_sharding_constraint(v, specs["kv"])
        out = _sdpa(q, k, v, q_offset, window, prefix_len,
                    impl, cfg.attn_chunk,
                    ulysses=specs if apply_u else None, kv_start=kv_start)
        if apply_u:                            # all-to-all back to hd-shard
            out = jax.lax.with_sharding_constraint(out, specs["out"])
        out = out.reshape(b, s, h * hd)
    out, r4 = proj(p["wo"], out, qc, "row")
    return out, r1 + r2 + r3 + r4, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, f: int, cfg, qc: QuantConfig, dtype):
    ks = jax.random.split(key, 3)
    return {
        "wg": init_proj(ks[0], d, f, qc, dtype=dtype),
        "wu": init_proj(ks[1], d, f, qc, dtype=dtype),
        "wd": init_proj(ks[2], f, d, qc, dtype=dtype),
        "norm": jnp.zeros((d,), dtype),
    }


def mlp(p: Params, x: jax.Array, cfg, qc: QuantConfig):
    """Pre-norm SwiGLU MLP. Returns (out, recon)."""
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    g, r1 = proj(p["wg"], xn, qc, "col")
    u, r2 = proj(p["wu"], xn, qc, "col")
    d_, r3 = proj(p["wd"], jax.nn.silu(g) * u, qc, "row")
    return d_, r1 + r2 + r3
