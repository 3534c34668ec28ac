"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

What it reads, all on the trace's one clock:

* the window: the host span named ``bench.window`` that the harness opens
  around the traced steps;
* device busy time: the union of the intervals of every event on the
  ``XLA Ops`` line of each TPU device plane, clipped to the window and
  averaged over the devices that ran anything in it;
* per-kernel device time: the summed durations of the ops that a
  kernel's ``matches(op)`` (``bench/work``) claims; an op's event name is
  its HLO text;
* the breakdown: the ten ops with the most device self time (a while
  loop's time less its body's), labelled by HLO name and result shape,
  and the device's idle time charged to what the host was doing meanwhile:
  the innermost host span (the engine's phase names from
  ``Obs(jax_annotations=True)``, the harness's ``bench.*`` spans, JAX's
  own dispatch annotations; Python frames are not recorded).

Nothing here knows a model: a later kernel is found by its own names.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_SUFFIX = re.compile(r"(\.\d+)+$")
_SHAPE = re.compile(r"\w+\[[\d,]*\]")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    kernel_s: Dict[str, float]
    kernel_events: Dict[str, int]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_trace(logdir: str) -> Optional[str]:
    """Newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e) for e in line.events]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def label(op: str) -> str:
    """Short name of a device op from its HLO text: the instruction name
    without its number, and its (first) result shape."""
    name, _, rest = op.partition(" = ")
    name = _SUFFIX.sub("", name.lstrip("%"))
    shape = _SHAPE.search(rest)
    return f"{name} {shape.group(0)}" if shape else name


def _self_times(evs):
    """Each event's duration less the time of the events nested in it."""
    out = []
    stack: List[list] = []
    for s, e, ev in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            out.append(stack.pop())
        item = [s, e, ev, e - s]
        if stack and e <= stack[-1][1]:
            stack[-1][3] -= e - s
        stack.append(item)
    out += stack
    return [(s, e, ev, self_) for s, e, ev, self_ in out]


def _leaf_segments(spans: Sequence[Tuple[float, float, str]]):
    """Partition time into segments labelled by the innermost of properly
    nested host spans: sorted (start, end, label) triples."""
    marks = []
    for i, (s, e, name) in enumerate(spans):
        marks.append((s, 1, -e, i))
        marks.append((e, 0, 0, i))
    marks.sort()
    stack: List[int] = []
    out = []
    last = None
    for t, kind, _, i in marks:
        if last is not None and stack and t > last:
            out.append((last, t, spans[stack[-1]][2]))
        if kind == 1:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
        last = t
    return out


def reduce_trace(path: str, kernels: Dict[str, Callable[[str], bool]],
                 top: int = 10) -> Reduced:
    """Reduce one trace file. ``kernels`` maps a kernel's name to the
    predicate that tells its ops by their HLO text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_lines, dev_ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_lines += list(plane.lines)
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops.append(_events(line))
    win = None
    annot = []
    for line in host_lines:
        evs = _events(line)
        hits = [x for x in evs if x[2].name == WINDOW]
        if hits:
            win = (hits[0][0], hits[0][1])
            annot = [(s, e, ev.name) for s, e, ev in evs
                     if ev.name != WINDOW and not ev.name.startswith("$")]
    if win is None:
        raise ValueError(f"no host span {WINDOW!r} in {path}")
    lo, hi = win
    busy, per_dev, used = 0.0, [], 0
    kernel_ns = defaultdict(float)
    kernel_n = defaultdict(int)
    op_ns = defaultdict(float)
    for evs in dev_ops:
        inside = [(s, e, ev) for s, e, ev in evs if e > lo and s < hi]
        if not inside:
            continue
        used += 1
        u = _union(_clip([(s, e) for s, e, _ in inside], lo, hi))
        per_dev.append(u)
        busy += sum(e - s for s, e in u)
        for s, e, ev, self_ in _self_times(inside):
            name = label(ev.name)
            for kname, match in kernels.items():
                if match(ev.name):
                    kernel_ns[kname] += min(e, hi) - max(s, lo)
                    kernel_n[kname] += 1
                    name = f"{kname}: {name}"
                    break
            op_ns[name] += self_
    if not used:
        raise ValueError(f"no device op inside the window in {path}")
    # idle gaps of the first device used, charged to the host's phase
    gaps = []
    cur = lo
    for s, e in per_dev[0]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    segs = _leaf_segments([x for x in annot if x[1] > lo and x[0] < hi])
    starts = [s for s, _, _ in segs]
    idle = defaultdict(float)
    for gs, ge in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, gs) - 1, 0)
        while i < len(segs) and segs[i][0] < ge:
            s, e, name = segs[i]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                idle[name] += ov
                covered += ov
            i += 1
        idle["(no host span)"] += (ge - gs) - covered
    ns = 1e-9
    rank = lambda d: [(k, v * ns) for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]
    return Reduced(window_s=(hi - lo) * ns, busy_s=busy / used * ns,
                   devices=used,
                   kernel_s={k: kernel_ns[k] * ns for k in kernels},
                   kernel_events={k: kernel_n[k] for k in kernels},
                   top_ops=rank(op_ns), idle_gaps=rank(idle))
