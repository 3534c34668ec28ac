"""Pallas TPU kernel: VQ centroid assignment (the CCM, paper §IV-A).

The paper's CCM pipelines one input vector through a chain of dPEs, each
holding one centroid. On TPU there is no systolic comparison chain; the
native formulation computes all ``c`` distances for a tile of ``bm`` rows ×
``bk`` subspaces at once:

  * L2:        ||x||^2 - 2 x·z^T + ||z||^2   — the cross term is a batched
               (bm×v)×(v×c) matmul -> MXU.
  * L1 / Chebyshev: |x - z| reductions        -> VPU.

Grid: ``(M/bm, nc/bk)``. Block shapes:
  x   (bm, bk, v)   — input sub-vectors for this tile
  z   (bk, c, v)    — centroids, stationary across the M grid dimension
  out (bm, bk)      — int32 indices

The centroid block's index map ignores the m grid coordinate, so Pallas
keeps it resident in VMEM while streaming M tiles — the CCM's
"centroid buffer".
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.similarity import Metric
from .tuning import select_blocks


def _assign_kernel(x_ref, z_ref, o_ref, *, metric: str):
    x = x_ref[...].astype(jnp.float32)          # (bm, bk, v)
    z = z_ref[...].astype(jnp.float32)          # (bk, c, v)
    if metric == "l2":
        x2 = jnp.sum(x * x, axis=-1)[..., None]                 # (bm, bk, 1)
        z2 = jnp.sum(z * z, axis=-1)[None]                      # (1, bk, c)
        # batched matmul over the subspace dim -> MXU
        xz = jax.lax.dot_general(
            x, z,
            dimension_numbers=(((2,), (2,)), ((1,), (0,))),     # (bk, bm, c)
            preferred_element_type=jnp.float32)
        d = x2 - 2.0 * jnp.transpose(xz, (1, 0, 2)) + z2        # (bm, bk, c)
    else:
        diff = jnp.abs(x[:, :, None, :] - z[None])              # (bm, bk, c, v)
        d = jnp.sum(diff, -1) if metric == "l1" else jnp.max(diff, -1)
    o_ref[...] = jnp.argmin(d, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("metric", "block_m", "block_k",
                                             "interpret"))
def vq_assign_pallas(x: jax.Array, z: jax.Array, metric: Metric = "l2",
                     block_m: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """x (M, nc, v), z (nc, c, v) -> idx (M, nc) int32.

    Block sizes default to the shared decode/prefill heuristic table.
    """
    m, nc, v = x.shape
    nc_z, c, v_z = z.shape
    assert (nc, v) == (nc_z, v_z), (x.shape, z.shape)
    auto = select_blocks("assign", m, nc, c)
    bm = min(block_m or auto.block_m, m)
    bk = min(block_k or auto.block_k, nc)
    if m % bm or nc % bk:
        # pad M and nc up to multiples (indices in padding are discarded)
        pad_m = (-m) % bm
        pad_k = (-nc) % bk
        xp = jnp.pad(x, ((0, pad_m), (0, pad_k), (0, 0)))
        zp = jnp.pad(z, ((0, pad_k), (0, 0), (0, 0)))
        out = vq_assign_pallas(xp, zp, metric, bm, bk, interpret)
        return out[:m, :nc]

    grid = (m // bm, nc // bk)
    return pl.pallas_call(
        functools.partial(_assign_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk, v), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bk, c, v), lambda i, j: (j, 0, 0)),   # M-stationary
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nc), jnp.int32),
        interpret=interpret,
        name="vq_assign_pallas",
    )(x, z)
