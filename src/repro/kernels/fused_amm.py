"""Pallas TPU kernel: fused VQ-assign + LUT-GEMM (CCM pipelined into IMM).

The paper's accelerator never writes centroid indices to DRAM: the CCM's
comparison chain emits each index straight into the IMM's address port
through an on-chip buffer (§IV, Fig 5). The unfused TPU port lost that
property — ``vq_assign_pallas`` materialised the full (M, nc) int32 index
tensor in HBM and ``lut_gemm_pallas`` read it back, one round-trip per
projection per decode step. This kernel restores the fusion:

  per (m, n, k) grid tile —
    1. CCM: distances of the (bm, bk, v) activation block against the
       (bk, c, v) centroid block. L2 goes through the MXU cross-term
       (batched (bm×v)×(v×c) matmul); L1/Chebyshev are VPU reductions.
    2. argmin -> one-hot (bm, bk, c) entirely in registers/VMEM.
    3. IMM: (bm, bk*c) × (bk*c, bn) contraction against the resident LUT
       tile with fp32 accumulation in VMEM scratch (the LS scratchpad).

Indices never exist outside VMEM. Both the centroid block and the LUT block
are M-stationary (their index maps ignore the m grid coordinate), exactly
like the unfused kernels — so for decode shapes every LUT tile is still
fetched from HBM exactly once.

Cost of fusion: the assignment for an (i, k) tile is recomputed for each of
the N/bn output tiles. For decode (M <= 8) the distance work is O(bm·bk·c·v)
against O(bk·c·bn) LUT bytes streamed — noise. For prefill the block
heuristic keeps bn wide so the recompute factor stays small.

dtypes: activations/centroids may be bf16 (distances are computed in fp32);
the LUT may be int8 (paper's +INT8 point) with the per-column fp32 scale
applied once after the k-accumulation.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.similarity import Metric
from .tuning import select_blocks


def _fused_kernel(x_ref, z_ref, lut_ref, o_ref, acc_ref, *,
                  n_k: int, metric: str):
    kg = pl.program_id(2)

    @pl.when(kg == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- CCM: distances + argmin, all in VMEM -----------------------------
    x = x_ref[...].astype(jnp.float32)                      # (bm, bk, v)
    z = z_ref[...].astype(jnp.float32)                      # (bk, c, v)
    if metric == "l2":
        x2 = jnp.sum(x * x, axis=-1)[..., None]             # (bm, bk, 1)
        z2 = jnp.sum(z * z, axis=-1)[None]                  # (1, bk, c)
        xz = jax.lax.dot_general(                           # (bk, bm, c) MXU
            x, z,
            dimension_numbers=(((2,), (2,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)
        d = x2 - 2.0 * jnp.transpose(xz, (1, 0, 2)) + z2    # (bm, bk, c)
    else:
        diff = jnp.abs(x[:, :, None, :] - z[None])          # (bm, bk, c, v)
        d = jnp.sum(diff, -1) if metric == "l1" else jnp.max(diff, -1)
    idx = jnp.argmin(d, axis=-1)                            # (bm, bk) int32

    # --- index -> one-hot, straight into the IMM contraction --------------
    bm, bk, c = d.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bm, bk, c), 2)
    onehot = (iota == idx[:, :, None]).astype(jnp.float32)
    lut = lut_ref[...].astype(jnp.float32)                  # (bk, c, bn)
    acc_ref[...] += jax.lax.dot_general(
        onehot.reshape(bm, bk * c), lut.reshape(bk * c, -1),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kg == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "metric", "block_m", "block_n", "block_k", "interpret", "out_dtype"))
def vq_amm_pallas(x: jax.Array, z: jax.Array, lut: jax.Array,
                  scale: Optional[jax.Array] = None,
                  metric: Metric = "l2",
                  block_m: Optional[int] = None,
                  block_n: Optional[int] = None,
                  block_k: Optional[int] = None,
                  interpret: bool = False,
                  out_dtype=jnp.float32) -> jax.Array:
    """Fused approximate matmul: x (M, nc, v), z (nc, c, v), lut (nc, c, N)
    -> out (M, N) with out = lut_gemm(assign(x, z), lut) and no (M, nc)
    index tensor ever touching HBM.

    scale: optional (N,) fp32 dequantisation scale for int8 LUTs.
    Block sizes default to the shared decode/prefill heuristic table.
    """
    m, nc, v = x.shape
    nc_z, c, v_z = z.shape
    nc_l, c_l, n = lut.shape
    assert (nc, v) == (nc_z, v_z), (x.shape, z.shape)
    assert (nc, c) == (nc_l, c_l), (z.shape, lut.shape)

    auto = select_blocks("fused", m, nc, c, n, lut.dtype.itemsize)
    bm = min(block_m or auto.block_m, m)
    bn = min(block_n or auto.block_n, n)
    bk = min(block_k or auto.block_k, nc)

    if m % bm or n % bn or nc % bk:
        pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-nc) % bk
        # Padded rows/subspaces see all-zero x AND all-zero centroids: every
        # distance ties at 0, argmin picks centroid 0 of an all-zero LUT
        # column block — contributes exactly 0 to the accumulation.
        xp = jnp.pad(x, ((0, pad_m), (0, pad_k), (0, 0)))
        zp = jnp.pad(z, ((0, pad_k), (0, 0), (0, 0)))
        lp = jnp.pad(lut, ((0, pad_k), (0, 0), (0, pad_n)))
        out = vq_amm_pallas(xp, zp, lp, None, metric, bm, bn, bk,
                            interpret, out_dtype)
        out = out[:m, :n]
    else:
        grid = (m // bm, n // bn, nc // bk)   # k innermost: LS accumulation
        out = pl.pallas_call(
            functools.partial(_fused_kernel, n_k=grid[2], metric=metric),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk, v), lambda i, j, k: (i, k, 0)),
                pl.BlockSpec((bk, c, v), lambda i, j, k: (k, 0, 0)),
                pl.BlockSpec((bk, c, bn), lambda i, j, k: (k, 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            name="vq_amm_pallas",
        )(x, z, lut)
    if scale is not None:
        out = out * scale[None, :].astype(out_dtype)
    return out
