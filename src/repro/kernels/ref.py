"""Pure-jnp oracles for the LUT-DLA kernels.

These are the ground truth for kernel tests AND the XLA-native path used by
full-model lowering (the one-hot-matmul formulation has identical MXU cost to
the Pallas kernel, so roofline numbers derived from it are faithful).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.similarity import Metric, pairwise_distance


def assign_ref(x: jax.Array, z: jax.Array, metric: Metric = "l2") -> jax.Array:
    """Nearest-centroid assignment per subspace.

    x : (M, nc, v)   input sub-vectors
    z : (nc, c, v)   centroids
    -> (M, nc) int32 indices

    Distances are computed in fp32 whatever the input dtype, as the
    kernels compute them (at full MXU precision on TPU, so f32 inputs
    are not rounded to bf16 here).
    """
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    if metric == "l2":
        # batched MXU form: ||x||^2 - 2<x,z> + ||z||^2
        x2 = jnp.sum(x * x, axis=-1)[..., None]                # (M, nc, 1)
        z2 = jnp.sum(z * z, axis=-1)[None]                     # (1, nc, c)
        xz = jnp.einsum("mkv,kcv->mkc", x, z,
                        precision=jax.lax.Precision.HIGHEST)   # (M, nc, c)
        d = x2 - 2.0 * xz + z2
    else:
        diff = jnp.abs(x[:, :, None, :] - z[None])             # (M, nc, c, v)
        d = jnp.sum(diff, -1) if metric == "l1" else jnp.max(diff, -1)
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def lut_gemm_ref(idx: jax.Array, lut: jax.Array,
                 scale: jax.Array | None = None) -> jax.Array:
    """LUT gather-accumulate (gather formulation — the literal oracle).

    idx  : (M, nc) int32
    lut  : (nc, c, N)   (float or int8)
    scale: optional (N,) dequant scale when lut is int8
    -> (M, N) float32
    """
    # per-subspace row gather: lut[k][idx[:, k]] -> (nc, M, N), then sum_k.
    gathered = jax.vmap(lambda l, i: l[i], in_axes=(0, 1))(
        lut.astype(jnp.float32), idx)
    out = jnp.sum(gathered, axis=0)
    if scale is not None:
        out = out * scale[None, :].astype(jnp.float32)
    return out


def vq_amm_ref(x: jax.Array, z: jax.Array, lut: jax.Array,
               scale: jax.Array | None = None,
               metric: Metric = "l2",
               out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the fused assign+lookup kernel (two-pass composition).

    x   : (M, nc, v)    input sub-vectors
    z   : (nc, c, v)    centroids
    lut : (nc, c, N)    precomputed table (float or int8)
    -> (M, N)

    Exactly ``lut_gemm_onehot(assign_ref(x, z), lut)`` — the fused Pallas
    kernel must match this bit-for-bit on indices and to fp32-accumulation
    tolerance on values.
    """
    idx = assign_ref(x, z, metric)
    return lut_gemm_onehot(idx, lut, scale, out_dtype=out_dtype)


def lut_gemm_onehot(idx: jax.Array, lut: jax.Array,
                    scale: jax.Array | None = None,
                    out_dtype=jnp.float32) -> jax.Array:
    """One-hot-matmul formulation (TPU-native; identical math to the kernel).

    out[m, n] = sum_k onehot(idx[m,k]) @ lut[k]    — MXU friendly.
    """
    nc, c, n = lut.shape
    onehot = jax.nn.one_hot(idx, c, dtype=out_dtype)           # (M, nc, c)
    out = jnp.einsum("mkc,kcn->mn", onehot,
                     lut.astype(out_dtype),
                     preferred_element_type=out_dtype)
    if scale is not None:
        out = out * scale[None, :].astype(out_dtype)
    return out


def flash_decode_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     k_new: jax.Array, v_new: jax.Array,
                     phys: jax.Array, positions, window=0,
                     kv_start=0) -> jax.Array:
    """Oracle for paged flash decode: gather the view, one full softmax.

    No online/split reductions at all — the (trusted) dense formulation
    the split-KV kernel must reproduce to fp32 tolerance.

    q (B,1,H,D); k_pages/v_pages (P+1, page, KVH, D) one-layer pool
    slice; k_new/v_new (B,1,KVH,D) the fresh token; phys (B, NP)
    trash-redirected page ids; positions (B,) per-slot lengths (-1 =
    inactive). Returns (B, 1, H*D) in q.dtype.
    """
    b, _, h, d = q.shape
    ps, kvh = k_pages.shape[1], k_pages.shape[2]
    g = h // kvh
    np_ = phys.shape[1]
    t = np_ * ps
    qg = q.reshape(b, kvh, g, d).astype(jnp.float32)
    kg = k_pages[phys].reshape(b, t, kvh, d).astype(jnp.float32)
    vg = v_pages[phys].reshape(b, t, kvh, d).astype(jnp.float32)
    scale = d ** -0.5
    kj = jnp.arange(t, dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(positions, jnp.int32), (b,))
    ks = jnp.broadcast_to(jnp.asarray(kv_start, jnp.int32), (b,))
    win = jnp.asarray(window, jnp.int32)
    mask = (kj[None] < pos[:, None]) & (kj[None] >= ks[:, None])
    mask = mask & jnp.where(win > 0, kj[None] > pos[:, None] - win, True)
    sc = jnp.einsum("bkgd,btkd->bkgt", qg, kg,
                    preferred_element_type=jnp.float32) * scale
    s_new = jnp.einsum("bkgd,bkd->bkg", qg,
                       k_new[:, 0].astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
    sc_all = jnp.concatenate([sc, s_new[..., None]], axis=-1)
    mask_all = jnp.concatenate(
        [mask, jnp.ones((b, 1), bool)], axis=-1)       # self: always live
    sc_all = jnp.where(mask_all[:, None, None, :], sc_all, -1e30)
    probs = jax.nn.softmax(sc_all, axis=-1)
    v_all = jnp.concatenate([vg, v_new[:, :1].astype(jnp.float32)], axis=1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h * d).astype(q.dtype)


def flash_decode_kvq_ref(q: jax.Array, kc_pages: jax.Array,
                         vc_pages: jax.Array, cb: dict,
                         k_new: jax.Array, v_new: jax.Array,
                         phys: jax.Array, positions, window=0,
                         kv_start=0) -> jax.Array:
    """Oracle for the vector-quantized pool: dequantize-then-reference.

    kc_pages/vc_pages (P+1, page, KVH, nc) uint8 code pools; cb is one
    layer's codebook slice {"zk": (nc,c,v), "zv": ..., "sk": (KVH,),
    "sv": ...}. Decodes the whole pool with plain advanced indexing (no
    one-hot tricks, no LUT factoring) and delegates to the dense oracle
    — the trusted semantics both the LUT-accumulate ref impl and the
    in-kernel-dequant pallas impl must reproduce.
    """
    def deq(codes, z, s):
        nc = z.shape[0]
        sub = z.astype(jnp.float32)[jnp.arange(nc), codes.astype(jnp.int32)]
        rows = sub.reshape(*codes.shape[:-1], -1)
        return rows * s.astype(jnp.float32)[:, None]
    k_pages = deq(kc_pages, cb["zk"], cb["sk"])
    v_pages = deq(vc_pages, cb["zv"], cb["sv"])
    return flash_decode_ref(q, k_pages, v_pages, k_new, v_new, phys,
                            positions, window=window, kv_start=kv_start)
