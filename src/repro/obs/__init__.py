"""Serving observability: metrics registry, tracing, snapshots, perf gate.

:class:`Obs` is the per-engine bundle the serving stack records into —
one :class:`~repro.obs.metrics.Registry` per engine (so per-replica
counters stay attributable) plus a :class:`~repro.obs.tracing.Tracer`
that *may be shared* across replicas to export one merged Perfetto
timeline. ``Obs.pid`` is the replica index (stamped by
:class:`~repro.serve.router.ReplicaRouter`) and keys the trace track.

Hot-path contract (enforced by ``analysis/astlint.py``'s
``SYNC_FREE_PATHS`` knob and ``tests/test_obs.py``): recording never
touches device values — counters are host ints, timestamps are
``perf_counter`` at points the engine already runs host code, and with
``Obs.disabled()`` the timing layer collapses to a shared no-op context
manager (counters stay live: they double as engine state that tests and
schedulers read).
"""
from __future__ import annotations

import time
from typing import Optional

from .metrics import Counter, Gauge, Histogram, Registry, safe_ratio
from .tracing import (NULL_CTX, REQUEST_PID, Tracer, jax_annotation,
                      validate_trace)
from .snapshot import (infer_direction, load_snapshot, make_row,
                       merge_snapshot, normalize_row, write_snapshot)
from .perfgate import compare, gate

__all__ = [
    "Obs", "Registry", "Counter", "Gauge", "Histogram", "Tracer",
    "safe_ratio", "jax_annotation", "validate_trace", "REQUEST_PID",
    "NULL_CTX", "make_row", "normalize_row", "write_snapshot",
    "merge_snapshot", "load_snapshot", "infer_direction", "compare",
    "gate",
]


class _Phase:
    """Times one engine step phase: feeds a histogram and, when the
    tracer is live, appends one ``X`` trace event."""

    __slots__ = ("obs", "name", "_t0")

    def __init__(self, obs: "Obs", name: str):
        self.obs, self.name = obs, name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        obs = self.obs
        if obs.timing:
            obs._phase_hist(self.name).observe(t1 - self._t0)
        tr = obs.tracer
        if tr.enabled:
            tr._events.append(
                ("X", self.name, "phase", (self._t0 - tr._t0) * 1e6,
                 (t1 - self._t0) * 1e6, obs.pid, 0, None))
        return False


class Obs:
    """Per-engine observability bundle: registry + tracer + switches.

    * ``metrics`` — always-live :class:`Registry` (engine counters are
      backed by it even when "disabled").
    * ``tracer`` — ring-buffer :class:`Tracer`; pass a shared instance
      to merge replicas into one exported timeline.
    * ``timing`` — when False, :meth:`phase` returns a shared no-op
      context manager and no histograms are touched (the < 5% overhead
      micro-benchmark in ``serve_bench`` measures this path).
    * ``jax_annotations`` — additionally wrap phases in
      ``jax.profiler.TraceAnnotation`` named ``engine.<phase>``, so
      they sit on the device profile's clock beside ``XLA Modules`` (off
      by default; purely additive). :meth:`span` opens a profiler-only
      span, and the engine computes span arguments (``set_metadata``)
      only when this is on.
    """

    def __init__(self, metrics: Optional[Registry] = None,
                 tracer: Optional[Tracer] = None, timing: bool = True,
                 jax_annotations: bool = False):
        self.metrics = metrics if metrics is not None else Registry()
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.timing = timing
        self.jax_annotations = jax_annotations
        self.pid = 0
        self._phase_hists = {}

    @classmethod
    def disabled(cls) -> "Obs":
        """Recording compiled out: no timing, no tracing (counters stay
        live — they are engine state, and an ``inc`` costs what the old
        ad-hoc ``+=`` did)."""
        return cls(timing=False)

    @property
    def active(self) -> bool:
        return self.timing or self.tracer.enabled

    def _phase_hist(self, name: str) -> Histogram:
        h = self._phase_hists.get(name)
        if h is None:
            h = self.metrics.histogram(f"engine.phase.{name}_s", unit="s",
                                       desc=f"host time in step phase "
                                            f"'{name}'")
            self._phase_hists[name] = h
        return h

    def phase(self, name: str):
        """Context manager timing one step phase (no-op when inactive)."""
        if self.jax_annotations:
            return _AnnotatedPhase(self, name)
        if not (self.timing or self.tracer.enabled):
            return NULL_CTX
        return _Phase(self, name)

    def span(self, name: str):
        """Profiler-only span ``engine.<name>`` (no histogram, no trace
        event of this bundle's tracer): a ``TraceAnnotation`` when
        ``jax_annotations`` is on, else the shared null context."""
        if self.jax_annotations:
            return jax_annotation("engine." + name)
        return NULL_CTX

    def annotate(self, name: str, **args) -> None:
        """Instant annotation event on this replica's trace track
        (degradation flip, preemption, CoW fork, fault, health change)."""
        tr = self.tracer
        if tr.enabled:
            tr.instant(name, cat="annot", pid=self.pid,
                       args=args or None)

    def track(self, name: str, value: float) -> None:
        """Counter time-series sample on this replica's track."""
        tr = self.tracer
        if tr.enabled:
            tr.counter(name, value, pid=self.pid)


class _AnnotatedPhase(_Phase):
    """_Phase that also enters a ``jax.profiler.TraceAnnotation`` named
    ``engine.<phase>``, which may carry arguments."""

    __slots__ = ("_ann",)

    def __enter__(self):
        self._ann = jax_annotation("engine." + self.name)
        self._ann.__enter__()
        return super().__enter__()

    def set_metadata(self, **args) -> None:
        """Attach host ints to the profiler span (read back as the
        event's stats), as ``TraceAnnotation.set_metadata`` does."""
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._ann.__exit__(*exc)
        return False
