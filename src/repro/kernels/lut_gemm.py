"""Pallas TPU kernel: LUT gather-accumulate GEMM (the IMM, paper §IV-B).

Implements the paper's LUT-Stationary (LS) dataflow, adapted to the TPU
memory hierarchy:

  * ASIC "PSum LUT SRAM"      -> LUT tile (bk, c, bn) resident in VMEM
  * ASIC "scratchpad"         -> output tile (bm, bn) accumulated in VMEM
  * ASIC "ping-pong buffer"   -> Pallas's automatic HBM->VMEM double-buffered
                                 pipeline prefetching the next (n, k) LUT tile
  * index-addressed SRAM read -> one-hot(idx) @ LUT-tile matmul on the MXU
                                 (the idiomatic TPU "table lookup")

Grid order is ``(m, n, k)`` with k innermost: the output tile (m, n) is
revisited consecutively over k, accumulating partial sums in VMEM exactly
like the LS scratchpad; the LUT block's index map ignores ``m``, so when
``M <= bm`` (decode / modest batch) each LUT tile is fetched from HBM exactly
once — the LS property "never load the same LUT twice".

dtypes: the LUT may be int8 (paper's +INT8 operating point) with a per-column
fp32 scale applied once after the k-accumulation; accumulation is fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import select_blocks


def _lut_gemm_kernel(idx_ref, lut_ref, o_ref, acc_ref, *, n_k: int, c: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[...]                                     # (bm, bk) int32
    lut = lut_ref[...].astype(jnp.float32)                 # (bk, c, bn)
    bm, bk = idx.shape
    # one-hot over centroids: (bm, bk, c); the matmul below is the "lookup".
    iota = jax.lax.broadcasted_iota(jnp.int32, (bm, bk, c), 2)
    onehot = (iota == idx[:, :, None]).astype(jnp.float32)
    # (bm, [bk*c]) x ([bk*c], bn) contraction on the MXU.
    acc_ref[...] += jax.lax.dot_general(
        onehot.reshape(bm, bk * c), lut.reshape(bk * c, -1),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret", "out_dtype"))
def lut_gemm_pallas(idx: jax.Array, lut: jax.Array,
                    scale: jax.Array | None = None,
                    block_m: Optional[int] = None,
                    block_n: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False,
                    out_dtype=jnp.float32) -> jax.Array:
    """idx (M, nc) int32, lut (nc, c, N) -> out (M, N).

    scale: optional (N,) fp32 dequantisation scale for int8 LUTs.
    Block sizes default to the shared decode/prefill heuristic table.
    """
    m, nc = idx.shape
    nc_l, c, n = lut.shape
    assert nc == nc_l, (idx.shape, lut.shape)
    auto = select_blocks("lut_gemm", m, nc, c, n, lut.dtype.itemsize)
    bm = min(block_m or auto.block_m, m)
    bn = min(block_n or auto.block_n, n)
    bk = min(block_k or auto.block_k, nc)
    if m % bm or n % bn or nc % bk:
        pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-nc) % bk
        idx_p = jnp.pad(idx, ((0, pad_m), (0, pad_k)))
        lut_p = jnp.pad(lut, ((0, pad_k), (0, 0), (0, pad_n)))
        # padded subspaces point at centroid 0 of an all-zero LUT: harmless.
        out = lut_gemm_pallas(idx_p, lut_p, None, bm, bn, bk, interpret,
                              out_dtype)
        out = out[:m, :n]
    else:
        grid = (m // bm, n // bn, nc // bk)   # k innermost: LS accumulation
        out = pl.pallas_call(
            functools.partial(_lut_gemm_kernel, n_k=grid[2], c=c),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, c, bn), lambda i, j, k: (k, 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            name="lut_gemm_pallas",
        )(idx, lut)
    if scale is not None:
        out = out * scale[None, :].astype(out_dtype)
    return out
