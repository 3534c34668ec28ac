"""Public kernel entry points with impl dispatch.

``impl``:
  * "fused"   — single Pallas kernel doing assign + LUT accumulation with
                indices confined to VMEM (no (M, nc) HBM round-trip).
                Only meaningful for :func:`vq_amm`; the single-stage entry
                points treat it as "auto".
  * "pallas"  — the Pallas kernels (interpret=True automatically on CPU).
                For :func:`vq_amm` this is the two-pass assign→lut_gemm
                composition (the fused kernel's baseline).
  * "ref"     — XLA-native one-hot/einsum formulation. Used for full-model
                lowering in the multi-pod dry-run: the HLO cost is identical
                to the kernel's MXU work, and XLA can shard/fuse it.
  * "auto"    — fused on TPU for vq_amm, pallas on TPU otherwise,
                ref off-TPU (default).

Block sizes default to the shared decode/prefill heuristic in
:mod:`repro.kernels.tuning`; pass ``block_m``/``block_n``/``block_k``
through ``**kw`` to override.

Tensor parallelism: a Mosaic kernel is one opaque custom call that GSPMD
cannot split, so under a mesh it would gather every operand (every LUT)
onto every chip. When an ambient mesh (``jax.set_mesh``) has a ``model``
axis, :func:`vq_amm` given ``tp="col"`` or ``tp="row"`` runs the kernel
per shard under ``shard_map`` instead: column-parallel projections keep
their N-sharded outputs, row-parallel ones sum partial outputs with one
``psum``. A Pallas impl under such a mesh with no role, or with a split
the axis does not divide, raises (:func:`require_local`) rather than
gather; the XLA oracle (``impl="ref"``) is partitioned by GSPMD as usual.
"""
from __future__ import annotations

from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.similarity import Metric
from . import ref as _ref
from .assign import vq_assign_pallas
from .fused_amm import vq_amm_pallas
from .lut_gemm import lut_gemm_pallas

Impl = Literal["auto", "fused", "pallas", "ref"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tp_size() -> int:
    """Size of the ambient mesh's ``model`` axis: 1 with no mesh, and
    inside a ``shard_map`` over that axis (each shard is already local)."""
    mesh = jax.sharding.get_abstract_mesh()
    if (mesh.empty or "model" not in mesh.axis_names
            or "model" in mesh.manual_axes):
        return 1
    return mesh.shape["model"]


def require_local(kernel: str) -> None:
    """Refuse a Pallas kernel that would run whole under a ``model``
    mesh axis: GSPMD cannot split it, so it would gather every operand
    (every table, the whole KV pool) onto every chip."""
    msize = tp_size()
    if msize > 1:
        raise NotImplementedError(
            f"{kernel}: a Pallas kernel under a 'model' mesh axis of size "
            f"{msize} needs a tensor-parallel split it divides (vq_amm "
            f"tp='col'|'row'; flash decode kv heads); use impl='ref' "
            f"here")


def vq_assign(x: jax.Array, z: jax.Array, metric: Metric = "l2",
              impl: Impl = "auto", **kw) -> jax.Array:
    """CCM stage: nearest-centroid assignment per subspace.

    Args:
      x: (M, nc, v) inputs split into ``nc`` sub-vectors of length ``v``.
      z: (nc, c, v) codebook centroids (``c`` per subspace).
      metric: "l2" | "l1" | "chebyshev" distance.
      impl: dispatch (see module docstring); "fused" degrades to "auto"
        here — there is no single-stage fusion to do.
      **kw: block-size overrides (``block_m`` / ``block_k``) forwarded to
        the Pallas kernel; defaults come from :mod:`repro.kernels.tuning`.

    Returns: (M, nc) int32 centroid indices.
    """
    if impl in ("auto", "fused"):        # no single-stage fusion to do
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.assign_ref(x, z, metric)
    require_local("vq_assign")
    return vq_assign_pallas(x, z, metric, interpret=not _on_tpu(), **kw)


def lut_matmul(idx: jax.Array, lut: jax.Array, scale=None,
               impl: Impl = "auto", out_dtype=jnp.float32, **kw) -> jax.Array:
    """IMM stage: accumulate precomputed partial products out of the LUT.

    Args:
      idx: (M, nc) int32 centroid indices from :func:`vq_assign`.
      lut: (nc, c, N) table — ``lut[k, j] = z[k, j] · W[k·v:(k+1)·v]``.
      scale: optional (N,) per-output-column dequant scale (int8 LUTs).
      impl: dispatch; "fused" degrades to "auto" (single stage).
      out_dtype: accumulator/output dtype (fp32 default).
      **kw: block-size overrides (``block_m``/``block_n``/``block_k``).

    Returns: (M, N) output, ``sum_k lut[k, idx[m, k], :]`` (× scale).
    """
    if impl in ("auto", "fused"):
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.lut_gemm_onehot(idx, lut, scale, out_dtype=out_dtype)
    require_local("lut_matmul")
    return lut_gemm_pallas(idx, lut, scale, interpret=not _on_tpu(),
                           out_dtype=out_dtype, **kw)


def vq_amm(x: jax.Array, z: jax.Array, lut: jax.Array, scale=None,
           metric: Metric = "l2", impl: Impl = "auto",
           out_dtype=jnp.float32, tp: Optional[str] = None,
           **kw) -> jax.Array:
    """Fused approximate matmul: CCM assignment + IMM accumulation in one.

    Args:
      x: (M, nc, v) inputs; z: (nc, c, v) centroids;
      lut: (nc, c, N) precomputed table; scale: optional (N,) dequant.
      metric: "l2" | "l1" | "chebyshev".
      impl: "auto" prefers the fused Pallas kernel on TPU (indices never
        reach HBM) and the XLA-native oracle elsewhere; "pallas" runs the
        unfused two-pass pipeline — kept as the fused kernel's measurable
        baseline; "ref" forces the oracle.
      out_dtype: accumulator/output dtype.
      tp: tensor-parallel role of the projection under a ``model`` mesh
        axis — "col" (LUT sharded on N) or "row" (LUT, centroids and x
        sharded on subspaces). A Pallas impl under that axis needs one.
      **kw: block-size overrides (``block_m``/``block_n``/``block_k``).

    Returns: (M, N) ≈ ``x.reshape(M, K) @ W`` for the W the LUT encodes.
    """
    if impl == "auto":
        impl = "fused" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.vq_amm_ref(x, z, lut, scale, metric, out_dtype=out_dtype)
    msize = tp_size()
    split = {"col": lut.shape[2], "row": lut.shape[0]}.get(tp)
    if msize > 1 and split and split % msize == 0:
        return _vq_amm_tp(x, z, lut, scale, metric, impl, out_dtype, tp, kw)
    require_local("vq_amm")
    if impl == "pallas":                 # two-pass baseline
        akw = {k: v for k, v in kw.items() if k in ("block_m", "block_k")}
        idx = vq_assign_pallas(x, z, metric, interpret=not _on_tpu(), **akw)
        return lut_gemm_pallas(idx, lut, scale, interpret=not _on_tpu(),
                               out_dtype=out_dtype, **kw)
    return vq_amm_pallas(x, z, lut, scale, metric,
                         interpret=not _on_tpu(), out_dtype=out_dtype, **kw)


def _vq_amm_tp(x, z, lut, scale, metric, impl, out_dtype, tp, kw):
    """:func:`vq_amm` with the kernel run per ``model`` shard.

    Row-parallel shards psum their accumulators before the int8 scale is
    applied: integer-valued partial sums add exactly in f32, so the
    result is bit-identical to the unsharded kernel."""
    def local(x, z, lut, *s):
        return vq_amm(x, z, lut, s[0] if s else None, metric, impl,
                      out_dtype, **kw)
    if tp == "col":
        args = (x, z, lut) + (() if scale is None else (scale,))
        specs = (P(), P(), P(None, None, "model"), P("model"))
        return jax.shard_map(local, in_specs=specs[:len(args)],
                             out_specs=P(None, "model"),
                             check_vma=False)(*args)
    out = jax.shard_map(
        lambda *a: jax.lax.psum(local(*a), "model"),
        in_specs=(P(None, "model", None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(), check_vma=False)(x, z, lut)
    if scale is not None:
        out = out * scale[None, :].astype(out_dtype)
    return out
