"""Readings from the names the program gives its own work in a traced
run's profile: the step programs on the device's ``XLA Modules`` line
(``jit_decode_step``, ``jit_prefill_chunk``, ...) and the engine's
``engine.*`` host spans (``Obs(jax_annotations=True)``) with the counts
they carry as arguments (``engine.step``: ``step``, ``decode_slots``,
``prefill_tokens``, ``live_pages``).

The trace is the one the traced run leaves under the harness's
``TRACE_DIR``, parsed once per run; the window is the ``bench.window``
span. Device idle time is time in the window with no op on the first
used device's ``XLA Ops`` line, as in ``bench/reduce.py``, charged here to
the innermost covering span among those a reader names by prefix. A
trace without these names (a program that does not record them) reads as
nothing: every reading is then None, never an error.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import reduce

ENGINE = "engine."
BENCH = "bench."
NO_SPAN = "(no span)"


@dataclasses.dataclass
class Spans:
    """What one trace holds inside its window."""
    window_s: float
    modules: Dict[str, List[float]]    # program -> device durations (s)
    steps: List[dict]                  # arguments of each engine.step
    gaps: Optional[List[Tuple[int, int]]]  # device idle intervals (ns);
    #                                        None: no device op in the window
    host: List[Tuple[int, int, str]]   # host spans (ns), window excluded

    @property
    def idle_s(self) -> Optional[float]:
        if self.gaps is None:
            return None
        return sum(e - s for s, e in self.gaps) * 1e-9

    def idle_by(self, prefixes: Sequence[str]) -> Dict[str, float]:
        """Device idle seconds by the innermost host span covering them,
        among the spans whose names start with one of ``prefixes``;
        idle time under none of them is charged to ``NO_SPAN``."""
        if self.gaps is None:
            return {}
        spans = [x for x in self.host if x[2].startswith(tuple(prefixes))]
        segs = reduce._leaf_segments(spans)
        starts = [s for s, _, _ in segs]
        out: Dict[str, float] = defaultdict(float)
        for gs, ge in self.gaps:
            covered = 0
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            while i < len(segs) and segs[i][0] < ge:
                s, e, name = segs[i]
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    out[name] += ov * 1e-9
                    covered += ov
                i += 1
            if ge - gs > covered:
                out[NO_SPAN] += (ge - gs - covered) * 1e-9
        return dict(out)

    def decode_steps(self) -> int:
        """``engine.step`` spans in the window that decoded a slot."""
        return sum(1 for a in self.steps if a.get("decode_slots", 0) > 0)


def parse(path: str) -> Spans:
    """Read one ``.xplane.pb`` (raises ValueError without a window)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    win, host = None, []
    ops, modules = [], defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(line.events)
                hit = [e for e in evs if e.name == reduce.WINDOW]
                if hit:
                    lo = int(hit[0].start_ns)
                    win = (lo, lo + int(hit[0].duration_ns))
                    host = [e for e in evs if e.name != reduce.WINDOW
                            and e.name.startswith((ENGINE, BENCH))]
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name) for e in line.events]
                if line.name == "XLA Ops":
                    ops.append(evs)
                elif line.name == "XLA Modules":
                    for s, e, name in evs:
                        modules[name.partition("(")[0]].append((s, e))
    if win is None:
        raise ValueError(f"no host span {reduce.WINDOW!r} in {path}")
    lo, hi = win
    inside = lambda s, e: s >= lo and e <= hi
    host = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name,
             e.stats) for e in host]
    steps = [dict(stats) for s, e, name, stats in host
             if name == ENGINE + "step" and inside(s, e)]
    busy = []
    for evs in ops:
        busy = reduce._union(reduce._clip([(s, e) for s, e, _ in evs],
                                          lo, hi))
        if busy:
            break
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return Spans(
        window_s=(hi - lo) * 1e-9,
        modules={k: [(e - s) * 1e-9 for s, e in v if inside(s, e)]
                 for k, v in modules.items()},
        steps=steps, gaps=gaps if busy else None,
        host=[(max(s, lo), min(e, hi), n) for s, e, n, _ in host
              if e > lo and s < hi])


@functools.lru_cache(maxsize=1)
def _parse_once(path: str, mtime_ns: int) -> Spans:
    return parse(path)


def of_run(run) -> Optional[Spans]:
    """The spans of a traced run's trace; None for an untraced run."""
    if run.traced is None:
        return None
    import harness
    path = reduce.find_trace(str(harness.TRACE_DIR))
    if path is None:
        return None
    return _parse_once(path, os.stat(path).st_mtime_ns)


# ---------------------------------------------------------------------------
# the readings (bench/metrics/*.py)
# ---------------------------------------------------------------------------

def program_ms(sp: Optional[Spans], program: str) -> Optional[float]:
    """Median device duration of one step program's runs (ms)."""
    runs = sp.modules.get(program) if sp is not None else None
    return 1e3 * statistics.median(runs) if runs else None


def prefill_step_share(sp: Optional[Spans]) -> Optional[float]:
    """Share of the window's engine steps that ran a prefill chunk (%)."""
    if sp is None or not sp.steps:
        return None
    n = sum(1 for a in sp.steps if a.get("prefill_tokens", 0) > 0)
    return 100.0 * n / len(sp.steps)


def idle_ms_per_decode_step(sp: Optional[Spans],
                            span: str) -> Optional[float]:
    """Device idle time charged to one ``engine.*`` span, per engine step
    that decoded (ms)."""
    if sp is None or sp.gaps is None or not sp.decode_steps():
        return None
    return 1e3 * sp.idle_by((ENGINE,)).get(span, 0.0) / sp.decode_steps()
