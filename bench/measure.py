"""Arithmetic shared by the metric readers of ``bench/metrics``.

Every function takes the harness's ``Run`` and returns a number, or None
where the run holds nothing to read (a reader then reports nothing)."""
from __future__ import annotations

from typing import Optional

import numpy as np

import work
from work import flash_decode, model


def pct(values, q: float) -> Optional[float]:
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, q)) if values.size else None


def ttft_ms(run):
    """Scheduled send to first token, per counted request (ms)."""
    return [1e3 * (r.token_t[0] - r.spec.due) for r in run.counted()
            if r.token_t]


def itl_ms(run):
    """Every gap between consecutive output tokens of counted requests."""
    out = []
    for r in run.counted():
        out += list(1e3 * np.diff(r.token_t))
    return out


def queue_wait_ms(run):
    """Scheduled send to the step that gave the request a slot."""
    return [1e3 * (r.admitted - r.spec.due) for r in run.counted()
            if r.admitted is not None]


def out_tok_s(run) -> Optional[float]:
    n = sum(sum(1 for t in r.token_t if 0.0 <= t <= run.window[1])
            for r in run.recs)
    return n / run.window_s if run.window_s > 0 and n else None


def idle_share(run) -> Optional[float]:
    if run.traced is None:
        return None
    return 100.0 * run.traced.idle_share


def _sizes(run):
    s = run.sizes
    return dict(layers=s.layers, d=s.d, heads=s.heads, kv_heads=s.kv_heads,
                head_dim=s.head_dim, ff=s.ff, vocab=s.vocab)


def mfu(run) -> Optional[float]:
    """Dense-equivalent model FLOPs of the tokens the traced steps
    processed, over the traced window and the chip's bf16 peak (%)."""
    if run.traced is None or not run.traced_steps or run.peaks is None:
        return None
    sz = _sizes(run)
    flops = 0
    for s in run.traced_steps:
        for start, count in s.prefill:
            for p in range(start, start + count):
                flops += model.flops_per_token(context=p + 1, **sz)
        for ctx in s.decode:
            flops += model.flops_per_token(context=ctx, **sz)
    if not flops:
        return None
    return 100.0 * flops / run.traced.window_s / run.peaks["bf16_flops"]


def flash_decode_roofline(run) -> Optional[float]:
    """Least time of every paged flash-decode call of the traced steps
    (live positions only) over the kernel's summed device time (%)."""
    if run.traced is None or run.peaks is None:
        return None
    kernel_s = run.traced.kernel_s.get("flash_decode", 0.0)
    if kernel_s <= 0:
        return None
    s = run.sizes
    least = 0.0
    for st in run.traced_steps:
        if not st.decode:
            continue
        ops, nb = flash_decode.work([c - 1 for c in st.decode], s.heads,
                                    s.kv_heads, s.head_dim)
        least += work.least_time(ops, nb, run.peaks)[0] * s.layers
    return 100.0 * least / kernel_s if least else None
