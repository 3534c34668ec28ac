"""Block-size selection shared by every LUT-DLA Pallas kernel.

A tiny autotune table instead of per-call magic numbers. Two workload
regimes dominate serving:

  * decode   (M <= 8)   — one token per active sequence. The LUT stream
                          dominates; M-tiles are as small as the batch and
                          the N-tile is kept wide so each (bk, c, bn) LUT
                          block is fetched from HBM exactly once
                          (LS property: "never load the same LUT twice").
  * prefill  (M >= 256) — batched prompt processing. MXU-shaped M-tiles
                          amortise the LUT fetch across many rows.

Anything in between ("mid") gets a compromise tile. Entries are
(block_m, block_n, block_k); the wrappers clamp each to the actual dim, and
``fit_vmem`` shrinks block_n until the resident LUT tile fits the VMEM
budget for large-``c`` codebooks.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

# per-kernel VMEM budget for the M-stationary LUT/centroid block (bytes).
# Conservative: real VMEM is ~16 MB/core but the pipeline double-buffers
# input blocks and holds the fp32 accumulator too.
_VMEM_BUDGET = 4 * 1024 * 1024

# lane width of a TPU vreg: the minor dim of a block must be a multiple
# of it or the whole array dim
_INDEX_LANES = 128
# rows x subspaces of one assign / lut_gemm tile
_ROWS_X_SUBSPACES = 8192


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    block_m: int
    block_n: int
    block_k: int          # subspace (nc) tile


#: regime -> kernel kind -> (bm, bn, bk).  ``bn`` is unused by "assign".
#: "flash_decode" reinterprets the axes (see select_blocks): bm is the
#: per-step slot tile (always 1 — one grid row per slot), bn the kv-head
#: tile and bk the pages per split.
_TABLE = {
    "decode": {
        "assign":   BlockConfig(8, 0, 16),
        "lut_gemm": BlockConfig(8, 512, 16),
        "fused":    BlockConfig(8, 512, 16),
        "flash_decode": BlockConfig(1, 8, 4),
    },
    "mid": {
        "assign":   BlockConfig(128, 0, 8),
        "lut_gemm": BlockConfig(128, 256, 16),
        "fused":    BlockConfig(128, 256, 8),
        "flash_decode": BlockConfig(1, 8, 8),
    },
    "prefill": {
        "assign":   BlockConfig(256, 0, 8),
        "lut_gemm": BlockConfig(256, 512, 16),
        "fused":    BlockConfig(256, 512, 8),
        "flash_decode": BlockConfig(1, 8, 8),
    },
}


def regime(m: int) -> str:
    """Workload regime from the row count (decode | mid | prefill)."""
    if m <= 8:
        return "decode"
    if m >= 256:
        return "prefill"
    return "mid"


def fit_vmem(block_n: int, block_k: int, c: int,
             bytes_per_entry: int = 4) -> tuple[int, int]:
    """Shrink (block_n, then block_k) until the (bk, c, bn) LUT tile fits
    the VMEM budget. Returns (block_n, block_k)."""
    bn, bk = block_n, block_k
    while bn > 128 and bk * c * bn * bytes_per_entry > _VMEM_BUDGET:
        bn //= 2
    while bk > 1 and bk * c * bn * bytes_per_entry > _VMEM_BUDGET:
        bk //= 2
    return bn, bk


def flash_head_tile(kvh: int, cap: int) -> int:
    """kv-head tile of the paged flash-decode kernel for ``kvh`` heads.

    The tile is the second-minor dim of the K/V page block ``(page,
    heads, head_dim)``, which the TPU compiler accepts only as a multiple
    of 8 or as all ``kvh`` heads. Returns the largest such divisor of
    ``kvh`` that is <= ``cap`` (the VMEM bound from :func:`select_blocks`),
    else the smallest legal tile.
    """
    legal = [d for d in range(8, kvh, 8) if kvh % d == 0] + [kvh]
    fit = [d for d in legal if d <= cap]
    return max(fit) if fit else min(legal)


def select_blocks(kind: str, m: int, nc: int, c: int,
                  n: Optional[int] = None,
                  itemsize: int = 4,
                  deq_itemsize: int = 0) -> BlockConfig:
    """Pick (block_m, block_n, block_k) for kernel ``kind`` on this shape.

    kind: "assign" | "lut_gemm" | "fused" | "flash_decode".  All values
    are upper bounds — callers clamp to the actual dims (and pad
    non-multiples).
    itemsize: bytes per LUT entry (1 for int8 LUTs — they fit 4x bigger
    tiles in the same VMEM budget).
    deq_itemsize: flash_decode only — a vector-quantized KV pool DMAs
    uint8 code tiles (itemsize=1) but dequantizes them to fp INSIDE the
    kernel, so the dequantized copies stay VMEM-resident too; this is
    their element size (0 for fp pools). Counting the code tile at the
    full head_dim width overstates it by ``v``x — conservative on
    purpose.

    For "flash_decode" the axes are reinterpreted for the paged
    attention kernel: m = batch slots, nc = pages per slot, c = page
    size (tokens), n = head_dim, itemsize = KV pool bytes/elt. The
    returned block_n is the kv-head tile bound (halved until the double-
    buffered K+V page tile fits VMEM; :func:`flash_head_tile` turns it
    into a tile the compiler accepts) and block_k the pages per split.
    """
    cfg = _TABLE[regime(m)][kind]
    if kind == "flash_decode":
        bh = cfg.block_n
        hd = n or 128
        # resident per grid step: K and V page tiles (double-buffered),
        # plus the in-kernel dequantized fp tiles for quantized pools
        per_elt = itemsize + deq_itemsize
        while bh > 1 and 4 * c * bh * hd * per_elt > _VMEM_BUDGET:
            bh //= 2
        sp = min(cfg.block_k, max(nc, 1))
        return BlockConfig(cfg.block_m, bh, sp)
    bm = min(cfg.block_m, max(m, 1))
    bk = min(cfg.block_k, max(nc, 1))
    if kind in ("assign", "lut_gemm"):
        # the (bm, bk) index block carries bk in its minor (lane) dim,
        # which the TPU compiler takes only as a multiple of 128 or as
        # all nc subspaces (the wrappers pad nc up to a multiple of bk)
        bk = min(max(nc, 1), _INDEX_LANES)
        # the (bm, bk, c) distance / one-hot intermediate pads c up to
        # 128 lanes in VMEM: bound bm * bk to keep it near 4 MiB
        bm = min(bm, max(8, _ROWS_X_SUBSPACES // bk))
    if kind == "assign":
        return BlockConfig(bm, 0, bk)
    bn, bk = fit_vmem(cfg.block_n, bk, c, itemsize)
    if n is not None:
        bn = min(bn, max(n, 1))
    return BlockConfig(bm, bn, bk)
