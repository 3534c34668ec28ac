"""Multi-head latent attention (MLA, DeepSeek-V2/V3) over a latent pool.

Per position the layer caches one row: the ``kv_lora_rank`` latent
``c = RMSNorm(x W_kv_a[:, :R])`` and the RoPE key ``k_r = RoPE(x W_kv_a[:,
R:])``, shared by all heads (``cfg.latent_row`` wide, zero-padded to whole
128-lane tiles). Head h's key and value would be ``[c W_kb[h], k_r]`` and
``c W_vb[h]``, with ``W_kv_b = [W_kb | W_vb]`` per head. Every path here
runs the absorbed form: the query's no-RoPE part is folded through W_kb
(``q_lat = q_nope W_kb[h]^T``, R wide), each head scores a position by one
dot of ``[q_lat, q_rope]`` with the cached row, the softmax weights sum
the rows' latents, and W_vb maps that latent sum to the head's output
before ``W_o``. No per-head K/V is ever formed from the cache. What a
deployment holds is in the activations' dtype (the cached rows, the
projections' outputs, the heads' outputs); the absorbed queries, scores,
probabilities and latent sums, which stand for per-head keys and values
no deployment holds, stay in float32.

* prefill and the training forward attend over a dense view of rows (the
  chunk's own rows written into the slot's view at their positions);
* decode reads the paged pool through ``kernels/mla_decode.py``: the
  Pallas kernel on a TPU, a gathered view elsewhere.

Parameters: ``wq`` (D, H (Dn + Dr)), ``wkv_a`` (D, R + Dr), ``kv_norm``
(R,), ``wkv_b`` (R, H (Dn + Dv)), ``wo`` (H Dv, D), ``norm`` (D,). The
softmax scale is ``(Dn + Dr) ** -0.5``. RoPE uses the repository's
half-rotation layout over the Dr dims.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lut import QuantConfig
from repro.kernels.mla_decode import NEG_INF, mla_decode_paged
from .layers import init_proj, proj, rms_norm, rope

Params = dict


def init_mla(key, cfg, qc: QuantConfig, dtype):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": init_proj(ks[0], d, h * (dn + dr), qc, dtype=dtype),
        "wkv_a": init_proj(ks[1], d, r + dr, qc, dtype=dtype),
        "wkv_b": init_proj(ks[2], r, h * (dn + dv), qc, dtype=dtype),
        "wo": init_proj(ks[3], h * dv, d, qc, dtype=dtype),
        "norm": jnp.zeros((d,), dtype),
        "kv_norm": jnp.zeros((r,), dtype),
    }


def _kv_b(p: Params, cfg):
    """(W_kb (R, H, Dn), W_vb (R, H, Dv)) from the stored ``wkv_b``."""
    if "w" not in p["wkv_b"]:
        raise NotImplementedError(
            "latent attention absorbs W_kv_b into the query and output; "
            "a LUT-only projection has no weight to absorb")
    r, h = cfg.kv_lora_rank, cfg.num_heads
    w = p["wkv_b"]["w"].reshape(r, h, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _pad_row(parts, width: int):
    """Concatenate the last axes of ``parts`` and zero-pad to ``width``."""
    a = jnp.concatenate(parts, axis=-1)
    pad = width - a.shape[-1]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def latent_rows(p: Params, xn, cfg, qc: QuantConfig, positions):
    """(B, S, latent_row) cache rows of the normed inputs, and recon."""
    r = cfg.kv_lora_rank
    kv, rec = proj(p["wkv_a"], xn, qc)
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_r = rope(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return _pad_row([c, k_r], cfg.latent_row), rec


def absorbed_queries(p: Params, xn, cfg, qc: QuantConfig, positions):
    """(B, S, H, latent_row) float32 absorbed queries, pre-scaled, and
    recon. ``q_lat`` is kept in float32: it stands for the per-head keys
    ``c W_kb``, which no deployment holds, so it is not rounded."""
    b, s, _ = xn.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q, rec = proj(p["wq"], xn, qc)
    q = q.reshape(b, s, h, dn + dr)
    q_r = rope(q[..., dn:], positions, cfg.rope_theta)
    w_kb, _ = _kv_b(p, cfg)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :dn], w_kb,
                       preferred_element_type=jnp.float32)
    qf = _pad_row([q_lat, q_r.astype(jnp.float32)], cfg.latent_row)
    return qf * (dn + dr) ** -0.5, rec


def latent_out(p: Params, o_lat, cfg, qc: QuantConfig, dtype):
    """W_vb then W_o of the float32 softmax-weighted latents (B, S, H, R):
    the heads' outputs are rounded to ``dtype`` once, before ``W_o``."""
    b, s = o_lat.shape[:2]
    _, w_vb = _kv_b(p, cfg)
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w_vb.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST).astype(dtype)
    return proj(p["wo"], o.reshape(b, s, -1), qc, "row")


def attend_rows(q, rows, q_offset, r: int):
    """Causal absorbed attention of float32 queries q (B, S, H, W) at
    positions q_offset + [0, S) over rows (B, T, W) at positions [0, T),
    in float32: (B, S, H, R)."""
    s, t = q.shape[1], rows.shape[1]
    hi = jax.lax.Precision.HIGHEST
    rows = rows.astype(jnp.float32)
    sc = jnp.einsum("bshw,btw->bhst", q, rows, precision=hi)
    qi = jnp.arange(s) + q_offset
    mask = jnp.arange(t)[None, :] <= qi[:, None]
    sc = jnp.where(mask[None, None], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhst,btr->bshr", probs, rows[..., :r], precision=hi)


def mla_attention(p: Params, x: jax.Array, cfg, qc: QuantConfig,
                  q_offset=0, window=0, prefix_len=0,
                  cache: Optional[Params] = None, decode_slab: bool = False,
                  kv_start=0, paged_phys: Optional[jax.Array] = None,
                  flash_impl: str = "ref"):
    """Pre-norm latent attention block, with the contract of
    ``layers.attention`` over a latent cache ``{"latent": ...}``.

    * ``decode_slab`` (single token, per-row ``q_offset``): ``cache`` is
      ``{"latent": the stacked pool (L, P+1, page, W), "layer": this
      layer's index}``, read in place through ``paged_phys``
      (``flash_impl`` "pallas" runs the kernel, anything else the
      gathered view); returns the fresh rows ``{"latent": (B, 1, W)}``.
    * otherwise ``cache`` is a dense row view (B, T, W) or None; the
      block's rows are written into it at ``q_offset`` and the updated
      view is returned.

    Windows, prefix-LM spans and ``kv_start`` masks do not apply to the
    latent-attention models (all full causal). Returns (out, recon,
    new cache or slab)."""
    del window, prefix_len, kv_start
    b, s, _ = x.shape
    r = cfg.kv_lora_rank
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    qo = jnp.asarray(q_offset)
    positions = (qo[:, None] if qo.ndim else qo) + jnp.arange(s)[None, :]
    rows, r1 = latent_rows(p, xn, cfg, qc, positions)
    q, r2 = absorbed_queries(p, xn, cfg, qc, positions)
    if decode_slab:
        pool = cache["latent"]
        impl = "pallas" if flash_impl == "pallas" else "xla"
        o_lat = mla_decode_paged(
            q[:, 0], pool, rows[:, 0], paged_phys, q_offset, r,
            layer=cache["layer"], impl=impl,
            interpret=jax.default_backend() != "tpu")
        out, r3 = latent_out(p, o_lat[:, None], cfg, qc, x.dtype)
        return out, r1 + r2 + r3, {"latent": rows.astype(pool.dtype)}
    new_cache = None
    if cache is not None:
        view = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], rows.astype(cache["latent"].dtype), q_offset,
            axis=1)
        new_cache = {"latent": view}
        rows = view.astype(x.dtype)
    o_lat = attend_rows(q, rows, q_offset, r)
    out, r3 = latent_out(p, o_lat, cfg, qc, x.dtype)
    return out, r1 + r2 + r3, new_cache
