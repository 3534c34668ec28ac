"""LUT construction and the ``LutLinear`` layer — the paper's technique as a
first-class, drop-in replacement for every projection in the framework.

Three operating modes (``QuantConfig.mode``):

  * ``dense``      — plain ``x @ w + b`` (the paper's comparison baseline).
  * ``lut_train``  — LUTBoost training path: STE quantisation of activations,
                     forward value ``Â·W`` with backward ``A·W`` (paper §V-2),
                     plus the two-sided stop-gradient reconstruction loss.
  * ``lut_infer``  — deployment path: precomputed LUT (optionally int8),
                     assignment + gather-accumulate kernels. No dense weight
                     needed at runtime.

Parameters of one LutLinear (a plain pytree dict):
  w  (K, N)            dense weight  (absent after `strip_for_inference`)
  b  (N,)              optional bias
  z  (nc, c, v)        centroids (trainable in LUTBoost stages 2/3)
  lut (nc, c, N)       precomputed table      (inference only)
  lut_scale (N,)       dequant scale          (int8 inference only)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from .codebook import CodebookSpec, init_centroids
from .similarity import (Metric, assign_subspaces, ste_quantize_subspaces)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Global VQ-AMM operating point (threaded through every model)."""
    mode: str = "dense"            # dense | lut_train | lut_infer
    v: int = 8                     # sub-vector length
    c: int = 16                    # centroids per subspace
    metric: Metric = "l2"          # l2 | l1 | chebyshev
    lut_dtype: str = "float32"     # float32 | bfloat16 | int8
    recon_weight: float = 0.05     # paper's penalty ratio
    task_grad_to_centroids: bool = False   # LUT-NN-style alternative path
    impl: str = "auto"             # kernel impl: auto | fused | pallas | ref
    fuse: bool = True              # lut_infer: one fused assign+LUT kernel
    #                                (indices stay in VMEM) vs two-pass
    flash: str = "auto"            # paged decode attention: auto | pallas |
    #                                ref | gather (auto = pallas on TPU,
    #                                gather elsewhere; see kernels/
    #                                flash_decode.py)
    kv_quant: str = "none"         # paged KV pool: none (fp rows) | vq
    #                                (pages store uint8 codebook indices;
    #                                see core/kv_codebook.py + docs/
    #                                serving.md §KV-cache quantization)
    kv_v: int = 4                  # KV sub-vector length over head_dim
    kv_c: int = 16                 # KV centroids per subspace (<= 256)

    def __post_init__(self):
        if self.kv_quant not in ("none", "vq"):
            raise ValueError(
                f"kv_quant must be 'none' or 'vq', got {self.kv_quant!r}")
        if self.kv_quant == "vq" and self.kv_c > 256:
            raise ValueError(
                f"kv_c={self.kv_c} does not fit uint8 page codes")

    @property
    def spec(self) -> CodebookSpec:
        return CodebookSpec(v=self.v, c=self.c, metric=self.metric)

    @property
    def is_lut(self) -> bool:
        return self.mode in ("lut_train", "lut_infer")

    def replace(self, **kw) -> "QuantConfig":
        return dataclasses.replace(self, **kw)


DENSE = QuantConfig(mode="dense")


def lut_linear_init(key: jax.Array, k: int, n: int, qc: QuantConfig,
                    bias: bool = False, dtype=jnp.float32,
                    w_scale: Optional[float] = None) -> Params:
    """Initialise a (K, N) projection, with centroids when LUT mode is on."""
    kw, kz = jax.random.split(key)
    scale = w_scale if w_scale is not None else (1.0 / (k ** 0.5))
    p: Params = {"w": (scale * jax.random.normal(kw, (k, n))).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((n,), dtype)
    if qc.is_lut:
        p["z"] = init_centroids(kz, k, qc.spec, dtype=dtype)
    return p


def build_lut(w: jax.Array, z: jax.Array) -> jax.Array:
    """Precompute LUT[k, j, n] = z[k, j, :] . w[k*v:(k+1)*v, n] (paper step-2).

    w (K, N), z (nc, c, v) -> (nc, c, N)
    """
    nc, c, v = z.shape
    k, n = w.shape
    assert nc * v == k, (w.shape, z.shape)
    wr = w.reshape(nc, v, n)
    return jnp.einsum("kcv,kvn->kcn", z.astype(jnp.float32),
                      wr.astype(jnp.float32))


def quantize_lut_int8(lut: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-output-column int8 quantisation of the LUT.

    The scale is shared across subspaces so the int accumulation
    ``sum_k lut8[k, idx, n]`` dequantises with one multiply per column.
    """
    amax = jnp.max(jnp.abs(lut), axis=(0, 1))                  # (N,)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    lut8 = jnp.clip(jnp.round(lut / scale[None, None, :]), -127, 127)
    return lut8.astype(jnp.int8), scale.astype(jnp.float32)


def _inference_tables(w: jax.Array, z: jax.Array, lut_dtype: str):
    """One projection's serving tables: ``(lut,)`` or ``(lut8, scale)``."""
    lut = build_lut(w, z)
    if lut_dtype == "int8":
        return quantize_lut_int8(lut)
    if lut_dtype == "bfloat16":
        return (lut.astype(jnp.bfloat16),)
    return (lut,)


def precompute_layer(p: Params, qc: QuantConfig) -> Params:
    """Turn a trained LutLinear into its inference form (adds lut/scale).

    Handles leading batch dims on (w, z) — stacked scan layers (L, ...) and
    per-expert weights (L, E, ...). The stacked layer axis is walked with
    ``lax.map``, so only one layer's fp32 table exists at a time (a
    full-width MLP table is ~140 MB per layer, ~5.7 GB for all 40).
    """
    if "z" not in p:
        return p
    tables = functools.partial(_inference_tables, lut_dtype=qc.lut_dtype)
    lead = p["z"].ndim - 3
    for _ in range(max(lead - 1, 0)):         # inner (expert) dims
        tables = jax.vmap(tables)
    if lead:
        per_layer = tables
        tables = lambda w, z: jax.lax.map(lambda wz: per_layer(*wz), (w, z))
    out = dict(p)
    built = jax.jit(tables)(p["w"], p["z"])
    out["lut"] = built[0]
    if qc.lut_dtype == "int8":
        out["lut_scale"] = built[1]
    return out


def missing_luts(params) -> list:
    """Key paths of the LutLinear layers (those carrying centroids ``z``)
    in ``params`` that have no precomputed ``lut``."""
    found = []

    def walk(tree, path):
        if isinstance(tree, dict):
            if "z" in tree:
                if "lut" not in tree:
                    found.append(path or "/")
                return
            for k, v in tree.items():
                walk(v, f"{path}/{k}")
        elif isinstance(tree, (list, tuple)):
            for i, v in enumerate(tree):
                walk(v, f"{path}/{i}")
    walk(params, "")
    return found


def strip_for_inference(p: Params) -> Params:
    """Drop the dense weight once the LUT exists (deployment footprint)."""
    return {k: v for k, v in p.items() if k != "w" or "lut" not in p}


def lut_linear_apply(p: Params, x: jax.Array, qc: QuantConfig,
                     tp: Optional[str] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Apply the projection. Returns (out, recon_loss_scalar).

    x: (..., K) -> (..., N). recon_loss is 0.0 except in lut_train mode.
    tp: "col" | "row" tensor-parallel role, passed to the fused kernel
    dispatch (see :func:`repro.kernels.ops.vq_amm`).
    """
    from . import lutboost  # circular-safe: only used for capture hook
    lutboost.record_activation(p, x)

    zero = jnp.zeros((), jnp.float32)
    if qc.mode == "dense" or "z" not in p:
        out = x @ p["w"]
        if "b" in p:
            out = out + p["b"]
        return out, zero

    k = p["z"].shape[0] * p["z"].shape[2]
    lead = x.shape[:-1]
    xs = x.reshape(*lead, k // qc.v, qc.v)

    if qc.mode == "lut_train":
        x_hat = ste_quantize_subspaces(xs, p["z"], qc.metric)
        a_hat = x_hat.reshape(*lead, k).astype(x.dtype)
        out_q = a_hat @ p["w"]                          # Â·W
        if qc.task_grad_to_centroids:
            # LUT-NN-style: task gradient reaches centroids through the STE.
            out = out_q
            out_d = jax.lax.stop_gradient(x) @ p["w"]
        else:
            # Paper-faithful: forward value Â·W, backward path A·W; centroids
            # learn only from the reconstruction loss.
            out_d = x @ p["w"]                          # A·W
            out = out_d + jax.lax.stop_gradient(out_q - out_d)
        sg = jax.lax.stop_gradient
        recon = (jnp.mean((sg(out_q) - out_d) ** 2)
                 + jnp.mean((out_q - sg(out_d)) ** 2))
        if "b" in p:
            out = out + p["b"]
        return out, recon.astype(jnp.float32)

    if qc.mode == "lut_infer":
        x2d = xs.reshape(-1, k // qc.v, qc.v)
        lut = p.get("lut")
        if lut is None:
            # on-the-fly build for direct calls only: serving engines
            # refuse params without tables (core.lut.missing_luts)
            lut = build_lut(p["w"], p["z"])
        if qc.fuse:
            # CCM pipelined into IMM: no (M, nc) index tensor in HBM.
            out = kops.vq_amm(x2d, p["z"], lut, p.get("lut_scale"),
                              qc.metric, impl=qc.impl, tp=tp)
        else:
            idx = kops.vq_assign(x2d, p["z"], qc.metric, impl=qc.impl)
            out = kops.lut_matmul(idx, lut, p.get("lut_scale"), impl=qc.impl)
        out = out.reshape(*lead, -1).astype(x.dtype)
        if "b" in p:
            out = out + p["b"]
        return out, zero

    raise ValueError(f"unknown quant mode: {qc.mode}")


def assignment_only(p: Params, x: jax.Array, qc: QuantConfig) -> jax.Array:
    """Expose raw indices (used by tests/benchmarks). x (..., K)."""
    k = p["z"].shape[0] * p["z"].shape[2]
    xs = x.reshape(-1, k // qc.v, qc.v)
    return assign_subspaces(xs, p["z"], qc.metric)
