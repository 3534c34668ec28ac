"""Tests for the serving observability stack (ISSUE 10).

Covers the metrics registry (streaming-histogram accuracy bounds,
snapshot round-trip, guarded ratios), the tracer (Perfetto JSON
validity, ring-buffer bound, disabled no-op), the snapshot schema
(write / merge / legacy normalization), the perf gate (direction and
tolerance rules, the CLI's exit-1 on a seeded regression), the astlint
``SYNC_FREE_PATHS`` knob, and the instrumented engine's hot-path
contract — one device read per step and steady-state recompile-freedom
with obs fully on, plus fault/degradation annotations in a chaos trace.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis import run_ast_lint
from repro.configs import get_smoke_config
from repro.core.lut import DENSE
from repro.models.model import Model
from repro.obs import (Obs, Registry, Tracer, compare, gate, make_row,
                       load_snapshot, merge_snapshot, normalize_row,
                       safe_ratio, validate_trace, write_snapshot, NULL_CTX)
from repro.serve import (Engine, FaultInjector, FaultSchedule, ReplicaRouter,
                         Request, SlotScheduler)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    return m, m.init(KEY, DENSE)


def _mk_engine(m, params, slots=2, **kw):
    kw.setdefault("max_seq", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return Engine(m, params, DENSE, batch_size=slots, **kw)


# ---------------------------------------------------------------------------
# metrics: histogram accuracy, registry round-trip, guarded ratios
# ---------------------------------------------------------------------------

def test_histogram_percentile_error_bound():
    """Quantile estimates stay within the documented growth-1 relative
    error of the exact sample quantiles, across a wide dynamic range."""
    r = Registry()
    h = r.histogram("lat", growth=1.25)
    samples = np.exp(
        np.random.default_rng(0).normal(loc=-5.0, scale=2.0, size=4000))
    for v in samples:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        est = h.percentile(q)
        assert abs(est - exact) / exact <= 0.25 + 1e-9, (q, est, exact)
        # the documented per-value bound is growth-1; quantile rank
        # discretisation adds at most one bucket, hence the 2x slack
        assert abs(est - exact) / exact <= 2 * (h.growth - 1.0)
    assert h.count == len(samples)
    assert np.isclose(h.total, samples.sum())
    assert np.isclose(h.mean, samples.mean())


def test_histogram_under_overflow_and_empty():
    h = Registry().histogram("x", lo=1e-3, hi=1e3)
    assert h.percentile(0.5) == 0.0          # empty: defined, not NaN
    h.observe(1e-9)                          # underflow -> exact min
    h.observe(5e8)                           # overflow  -> exact max
    assert h.percentile(0.0) == 1e-9
    assert h.percentile(1.0) == 5e8
    assert h.count == 2 and h.min == 1e-9 and h.max == 5e8


def test_registry_snapshot_round_trip():
    r = Registry()
    r.counter("a.b", unit="tokens").inc(7)
    r.gauge("g", unit="B").set(3.5)
    h = r.histogram("h", unit="s")
    for v in (1e-4, 2e-2, 5.0, 1e-8, 1e9):
        h.observe(v)
    r2 = Registry.from_snapshot(r.snapshot())
    assert r2.snapshot() == r.snapshot()
    assert r2.get_histogram("h").percentile(0.5) == h.percentile(0.5)
    prom = r.prometheus()
    assert "# TYPE a_b counter" in prom and "a_b 7" in prom
    assert '{quantile="0.99"}' in prom and "h_count 5" in prom


def test_ratios_guard_empty_denominators(qwen):
    assert safe_ratio(3.0, 0.0) == 0.0
    assert safe_ratio(3.0, 0.0, default=1.0) == 1.0
    r = Registry()
    assert r.ratio("nope", "nothing") == 0.0
    r.counter("num").inc(4)
    assert r.ratio("num", "nothing") == 0.0   # zero denominator, no raise
    # engine/scheduler rates are well-defined before any work
    m, params = qwen
    eng = _mk_engine(m, params)
    assert eng.prefix_hit_rate == 0.0
    assert eng.acceptance_rate == 0.0
    assert eng.tokens_per_verify == 0.0
    sched = SlotScheduler(2)
    assert (sched.shed_count, sched.expired_count, sched.preemptions) \
        == (0, 0, 0)


# ---------------------------------------------------------------------------
# tracer: JSON validity, ring bound, disabled no-op
# ---------------------------------------------------------------------------

def test_tracer_export_is_valid_and_nested(tmp_path):
    tr = Tracer(enabled=True)
    tr.name_process(0, "replica 0")
    with tr.span("step", pid=0):
        with tr.span("decode", pid=0):
            tr.instant("degradation", pid=0, args={"to": "no_spec"})
    tr.request_begin(1, "req 1", {"prompt": 3})
    tr.request_instant(1, "req 1", "requeued")
    tr.request_end(1, "req 1", {"reason": "COMPLETED"})
    tr.counter("pressure", 0.5, pid=0)
    path = tmp_path / "t.json"
    doc = tr.export(str(path))
    assert validate_trace(doc) == []
    on_disk = json.loads(path.read_text())
    assert validate_trace(on_disk) == []
    names = {e["name"] for e in on_disk["traceEvents"]}
    assert {"step", "decode", "degradation", "req 1", "pressure",
            "process_name"} <= names
    labels = {e["args"]["name"] for e in on_disk["traceEvents"]
              if e.get("ph") == "M"}
    assert {"replica 0", "requests"} <= labels


def test_validate_trace_catches_breakage():
    assert validate_trace({}) == ["missing traceEvents"]
    # async end with no begin
    bad = {"traceEvents": [{"ph": "e", "cat": "request", "id": 9,
                            "name": "r", "ts": 1.0, "pid": 999, "tid": 0}]}
    assert any("without begin" in p for p in validate_trace(bad))
    # sibling span overlapping its parent's end
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "ts": 0.0, "dur": 10.0, "pid": 0, "tid": 0},
        {"ph": "X", "name": "b", "ts": 5.0, "dur": 10.0, "pid": 0, "tid": 0},
    ]}
    assert any("overlaps" in p for p in validate_trace(bad))


def test_tracer_ring_buffer_is_bounded():
    tr = Tracer(enabled=True, capacity=16)
    for i in range(500):
        tr.instant(f"e{i}")
    assert len(tr) == 16


def test_disabled_obs_is_noop():
    tr = Tracer(enabled=False)
    assert tr.span("x") is NULL_CTX
    tr.instant("x")
    tr.request_begin(1, "r")
    assert len(tr) == 0
    obs = Obs.disabled()
    assert obs.phase("decode") is NULL_CTX        # no allocation, no timing
    assert not obs.active
    obs.annotate("degradation", to="no_spec")
    obs.track("pressure", 1.0)
    assert len(obs.tracer) == 0
    assert obs.metrics.snapshot()["histograms"] == {}
    # counters stay live even when "disabled" — they are engine state
    obs.metrics.counter("c").inc()
    assert obs.metrics.counters()["c"] == 1


# ---------------------------------------------------------------------------
# snapshot schema: write / merge / legacy normalization
# ---------------------------------------------------------------------------

def test_snapshot_write_merge_load(tmp_path):
    path = str(tmp_path / "BENCH.json")
    write_snapshot(path, [make_row("serve.a.us_per_tok", 10.0),
                          make_row("kvacc.x", 1.0, unit="", direction="up")],
                   bench="serve")
    doc = load_snapshot(path)
    assert doc["bench"] == "serve" and doc["schema"] == 2
    assert doc["host"]                        # fingerprint present
    merge_snapshot(path, [make_row("kvacc.y", 2.0, unit="",
                                   direction="up", tol=0.5)],
                   prefix="kvacc.")
    doc = load_snapshot(path)
    names = [r["name"] for r in doc["rows"]]
    assert names == ["serve.a.us_per_tok", "kvacc.y"]   # kvacc.x replaced
    assert doc["rows"][1]["tol"] == 0.5
    assert doc["bench"] == "serve"            # non-prefix meta preserved


def test_legacy_rows_are_normalized():
    legacy = normalize_row({"name": "serve.chaos.goodput_pct",
                            "value": "93.0"})
    assert legacy["direction"] == "up" and legacy["unit"] == "%"
    assert legacy["value"] == 93.0
    timer = normalize_row({"name": "micro/fused_amm_512", "value": 12.5,
                           "derived": ""})
    assert timer["direction"] == "down"
    # legacy kernels_micro rows carry no unit hint in the name — the
    # micro/ prefix marks them as us timers so the gate applies the
    # ±25% timer tolerance, not the exact-ratio rule
    assert timer["unit"] == "us"


# ---------------------------------------------------------------------------
# perf gate: direction/tolerance rules + CLI exit codes
# ---------------------------------------------------------------------------

def _doc(rows, host="h1"):
    return {"host": host, "rows": [normalize_row(r) for r in rows]}


def test_perfgate_timer_tolerance_and_direction():
    base = _doc([make_row("t.us_per_x", 100.0),
                 make_row("good.rate", 0.9, unit="", direction="up")])
    # +20% timer is inside ±25%; rate identical: passes
    regs, _ = compare(base, _doc([make_row("t.us_per_x", 120.0),
                                  make_row("good.rate", 0.9, unit="",
                                           direction="up")]))
    assert regs == []
    # +30% timer regresses; a *faster* timer never does
    regs, _ = compare(base, _doc([make_row("t.us_per_x", 130.0),
                                  make_row("good.rate", 0.9, unit="",
                                           direction="up")]))
    assert [d.name for d in regs] == ["t.us_per_x"]
    # up-direction row moving down regresses exactly (no timer slack)
    regs, _ = compare(base, _doc([make_row("t.us_per_x", 100.0),
                                  make_row("good.rate", 0.89, unit="",
                                           direction="up")]))
    assert [d.name for d in regs] == ["good.rate"]


def test_perfgate_cross_host_timers_and_one_sided_rows():
    base = _doc([make_row("t.us_per_x", 100.0)], host="h1")
    fresh = _doc([make_row("t.us_per_x", 900.0),
                  make_row("brand.new", 1.0)], host="h2")
    regs, deltas = compare(base, fresh, gate_timers="auto")
    assert regs == []                         # cross-host timer not gated
    assert any("cross-host" in d.note for d in deltas)
    assert any(d.base is None for d in deltas)   # new row reported
    regs, _ = compare(base, fresh, gate_timers="always")
    assert [d.name for d in regs] == ["t.us_per_x"]
    # per-row tol override beats the timer default
    base = _doc([make_row("t.us_per_x", 100.0, tol=0.01)])
    regs, _ = compare(base, _doc([make_row("t.us_per_x", 110.0, tol=0.01)]))
    assert [d.name for d in regs] == ["t.us_per_x"]
    code, lines = gate([(base, base, "self")])
    assert code == 0 and lines[-1].startswith("perf gate: OK")


def test_perf_gate_cli_exits_1_on_seeded_regression(tmp_path):
    """The CI entry point must demonstrably fail on a regression."""
    base = str(tmp_path / "base.json")
    fresh = str(tmp_path / "fresh.json")
    write_snapshot(base, [make_row("serve.x.us_per_tok", 100.0),
                          make_row("serve.goodput_pct", 95.0, unit="%",
                                   direction="up")])
    write_snapshot(fresh, [make_row("serve.x.us_per_tok", 101.0),
                           make_row("serve.goodput_pct", 95.0, unit="%",
                                    direction="up")])
    cli = os.path.join(ROOT, "scripts", "perf_gate.py")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    ok = subprocess.run([sys.executable, cli, "--baseline", base,
                         "--fresh", fresh], env=env,
                        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # seed a goodput regression
    write_snapshot(fresh, [make_row("serve.x.us_per_tok", 101.0),
                           make_row("serve.goodput_pct", 80.0, unit="%",
                                    direction="up")])
    bad = subprocess.run([sys.executable, cli, "--baseline", base,
                          "--fresh", fresh], env=env,
                         capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "REGRESSED" in bad.stdout and "serve.goodput_pct" in bad.stdout


def test_perfgate_reads_committed_bench_snapshots():
    """The committed BENCH_*.json baselines parse and self-compare clean
    (whatever schema vintage they are)."""
    pairs = []
    for rel in ("BENCH_serve.json", "BENCH_kernels.json"):
        p = os.path.join(ROOT, rel)
        if os.path.exists(p):
            doc = load_snapshot(p)
            pairs.append((doc, doc, rel))
    assert pairs, "no committed BENCH snapshots found"
    code, _ = gate(pairs)
    assert code == 0


# ---------------------------------------------------------------------------
# astlint: the SYNC_FREE_PATHS knob
# ---------------------------------------------------------------------------

def test_astlint_sync_free_paths_knob(tmp_path):
    """A step-loop-reachable sync read inside ``src/repro/obs`` is
    downgraded to info (the obs layer is declared sync-free); the same
    code anywhere else still warns."""
    from repro.analysis import astlint
    src_root = tmp_path / "src" / "repro"
    for sub in ("", "obs", "serve"):
        d = src_root / sub if sub else src_root
        d.mkdir(parents=True, exist_ok=True)
        (d / "__init__.py").write_text("")
    body = textwrap.dedent("""
        import numpy as np

        def record(x):
            return np.asarray(x)
    """)
    (src_root / "obs" / "rec.py").write_text(body)
    (src_root / "serve" / "rec2.py").write_text(body)
    (src_root / "serve" / "engine.py").write_text(textwrap.dedent("""
        from repro.obs.rec import record
        from repro.serve.rec2 import record as record2

        class Engine:
            def step(self):
                record(1)
                record2(1)
    """))
    findings, _ = run_ast_lint(str(tmp_path / "src"))
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f.path)
    assert any("rec2.py" in p for p in by_rule.get("step-sync", [])), \
        "sync read outside SYNC_FREE_PATHS must still warn"
    assert all("obs" not in p for p in by_rule.get("step-sync", [])), \
        "obs-layer sync read must not trip the step-sync rule"
    assert any("rec.py" in p for p in by_rule.get("sync-site", []))
    assert "src/repro/obs" in astlint.SYNC_FREE_PATHS


# ---------------------------------------------------------------------------
# instrumented engine: hot-path contract + request lifecycle
# ---------------------------------------------------------------------------

def test_engine_obs_on_one_device_read_per_step(qwen, tmp_path):
    """Full instrumentation (timers + tracer) must not add device reads:
    still exactly one ``_device_read`` per work step, and the request's
    latency families + finish tally land in the registry."""
    m, params = qwen
    obs = Obs(tracer=Tracer(enabled=True))
    eng = _mk_engine(m, params, obs=obs)
    req = Request(tokens=[3, 4, 5], max_new_tokens=6)
    eng.run([req])
    assert eng.device_reads == 6             # one fetch per step, obs on
    met = obs.metrics
    cs = met.counters()
    assert cs["engine.device_reads"] == 6
    assert cs["engine.emitted_tokens"] == 6
    assert cs["req.finish.completed"] == 1
    for fam in ("req.ttft_steps", "req.latency_steps", "req.ttft_s",
                "req.latency_s", "req.tpot_s"):
        h = met.get_histogram(fam)
        assert h is not None and h.count == 1, fam
    assert met.get_histogram("req.ttft_s").min > 0.0
    # phase spans recorded and balanced in the export
    for ph in ("admit", "prefill_chunk", "decode", "sample", "device_read"):
        h = met.get_histogram(f"engine.phase.{ph}_s")
        assert h is not None and h.count > 0, ph
    path = tmp_path / "eng.json"
    doc = obs.tracer.export(str(path))
    assert validate_trace(doc) == []
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert phs.count("b") == 1 and phs.count("e") == 1   # one request span


def test_engine_obs_disabled_same_tokens_and_counters(qwen):
    """Obs off vs on is behaviourally invisible: identical greedy tokens,
    identical counters; disabled timing records no phase histograms."""
    m, params = qwen
    outs = {}
    for tag, obs in (("off", Obs.disabled()), ("on", Obs())):
        req = Request(tokens=[5, 6, 7], max_new_tokens=5)
        eng = _mk_engine(m, params, obs=obs)
        eng.run([req])
        outs[tag] = req.out_tokens
        assert eng.device_reads == 5
    assert outs["on"] == outs["off"]
    off = Obs.disabled()
    eng = _mk_engine(m, params, obs=off)
    eng.run([Request(tokens=[5, 6, 7], max_new_tokens=5)])
    snap = off.metrics.snapshot()
    assert not any(n.startswith("engine.phase.")
                   for n in snap["histograms"])
    assert snap["counters"]["engine.emitted_tokens"]["value"] == 5


def test_chaos_trace_has_spans_and_annotations(qwen, tmp_path):
    """A faulted 2-replica run exports one merged, valid timeline:
    request spans survive cross-replica migration, and the injected
    faults + degradation/health flips appear as annotations."""
    m, params = qwen
    tracer = Tracer(enabled=True)
    router = ReplicaRouter(
        [_mk_engine(m, params, obs=Obs(tracer=tracer), num_pages=8)
         for _ in range(2)])
    FaultInjector(FaultSchedule.canned(replicas=2)).attach(router)
    reqs = [Request(tokens=[2 + i, 3 + i], max_new_tokens=6 + 4 * (i % 2))
            for i in range(6)]
    for r in reqs:
        router.submit(r)
    router.run_until_idle()
    assert all(r.done for r in reqs)
    doc = tracer.export(str(tmp_path / "chaos.json"))
    assert validate_trace(doc) == []
    evs = doc["traceEvents"]
    annot = {e["name"] for e in evs if e.get("ph") == "i"}
    assert any(a.startswith("fault.") for a in annot), annot
    assert "health" in annot                  # replica death flip
    begins = [e for e in evs if e.get("ph") == "b"]
    ends = [e for e in evs if e.get("ph") == "e"]
    assert {e["id"] for e in begins} == {e["id"] for e in ends}
    assert len(begins) == len(reqs)           # migration keeps ONE span
    pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert pids == {0, 1}                     # both replica tracks present
    assert router.retried_requests > 0
    assert router.obs.metrics.counters()["router.health.to_dead"] == 1


def test_scheduler_preemption_annotates(qwen, tmp_path):
    """Decode-growth preemption under pool pressure lands a ``preempt``
    instant on the replica track."""
    m, params = qwen
    obs = Obs(tracer=Tracer(enabled=True))
    eng = _mk_engine(m, params, num_pages=5, obs=obs)   # tight pool
    # slot A decodes across a page boundary with zero free pages while
    # slot B holds 3 pages mid-prefill -> decode growth preempts B
    reqs = [Request(tokens=[2, 3, 4, 5, 6, 7], max_new_tokens=20),
            Request(tokens=list(range(2, 26)), max_new_tokens=4)]
    eng.run(reqs)
    assert all(r.done for r in reqs)
    assert eng.scheduler.preemptions > 0
    names = [ev[1] for ev in obs.tracer._events if ev[0] == "i"]
    assert "preempt" in names


@pytest.mark.slow
def test_recompile_guard_with_obs_on(qwen):
    """Steady-state decode stays recompile-free with full instrumentation
    — phase timers and tracer recording add zero trace-time effects."""
    from repro.analysis import run_recompile_guard
    m, params = qwen
    obs = Obs(tracer=Tracer(enabled=True))
    eng = _mk_engine(m, params, obs=obs)

    def _mixed(seed):
        # one temperature request: greedy + sampled batches are two
        # pytree classes of the sample jit (see test_recompile_guard.py)
        return [Request(tokens=[seed, seed + 1], max_new_tokens=3),
                Request(tokens=[seed + 2] * 3, max_new_tokens=4),
                Request(tokens=[seed + 4, seed + 5], max_new_tokens=2,
                        temperature=0.7)]

    report = run_recompile_guard(
        eng, _mixed(3), _mixed(11),
        expected_counts={"prefill": 1, "decode": 1, "verify": 0,
                         "sample": 2})
    assert report.ok, "\n".join(f.render() for f in report.findings)
    assert report.steady_events == []
    assert len(obs.tracer) > 0               # tracing really was on


# ---------------------------------------------------------------------------
# engine spans on the profiler's clock (Obs(jax_annotations=True))
# ---------------------------------------------------------------------------

def _profiled_run(eng, reqs, logdir):
    """Step ``eng`` through ``reqs`` under ``jax.profiler.trace``; returns
    the host events of the trace and, per step, the engine's own tallies:
    (prefilled tokens, emitted tokens, requests given their first token)."""
    import glob
    from jax.profiler import ProfileData
    for r in reqs:
        eng.submit(r)
    done = []
    with jax.profiler.trace(str(logdir)):
        while eng.scheduler.has_work:
            pre, emi = eng.prefilled_tokens, eng.emitted_tokens
            fresh = sum(1 for r in reqs if not r.out_tokens)
            eng.step()
            done.append((eng.prefilled_tokens - pre,
                         eng.emitted_tokens - emi,
                         fresh - sum(1 for r in reqs if not r.out_tokens)))
    path, = glob.glob(str(logdir / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    return events, done


def test_engine_step_spans_carry_what_the_step_did(qwen, tmp_path):
    """Each ``engine.step`` span's counts equal the engine's own tallies
    of that step: ``prefill_tokens`` the prefilled tokens, and
    ``decode_slots`` the emitted tokens less the first tokens that
    prefill gave; one device read per step still, annotations on."""
    m, params = qwen
    eng = _mk_engine(m, params, obs=Obs(jax_annotations=True))
    reqs = [Request(tokens=list(range(3, 3 + n)), max_new_tokens=k)
            for n, k in ((9, 5), (3, 7), (6, 3))]
    events, done = _profiled_run(eng, reqs, tmp_path)
    steps = sorted((ev for ev in events if ev.name == "engine.step"),
                   key=lambda ev: ev.start_ns)
    assert len(steps) == len(done) == eng.step_count
    for i, (ev, (prefilled, emitted, first)) in enumerate(zip(steps, done)):
        args = dict(ev.stats)
        assert args["step"] == i
        assert args["prefill_tokens"] == prefilled
        assert args["decode_slots"] == emitted - first
        assert args["live_pages"] >= 0
    assert sum(p for p, _, _ in done) == eng.prefilled_tokens
    assert sum(dict(ev.stats)["decode_slots"] for ev in steps) + len(reqs) \
        == eng.emitted_tokens
    # one read per decode step and one per finished prompt, as unannotated
    decode_steps = sum(1 for ev in steps if dict(ev.stats)["decode_slots"])
    assert eng.device_reads == decode_steps + len(reqs)
    # the phases carry their own counts, under the engine's prefix
    chunks = [dict(ev.stats) for ev in events
              if ev.name == "engine.prefill_chunk"]
    assert sum(c["tokens"] for c in chunks) == eng.prefilled_tokens
    assert {c["req"] for c in chunks} == {r._seq for r in reqs}
    decodes = [dict(ev.stats) for ev in events if ev.name == "engine.decode"]
    assert sum(d["slots"] for d in decodes) == sum(
        dict(ev.stats)["decode_slots"] for ev in steps)
    assert all(d["positions"] >= d["slots"] for d in decodes)
    names = {ev.name for ev in events}
    assert {"engine.admit", "engine.upload", "engine.sample",
            "engine.device_read"} <= names
    assert not any(n in names for n in ("step", "decode", "upload"))
    hists = eng.obs.metrics.snapshot()["histograms"]
    assert "engine.phase.decode_s" in hists
    assert not any(n in hists for n in ("engine.phase.step_s",
                                        "engine.phase.upload_s"))


def test_step_programs_are_named_in_the_profile(qwen, tmp_path):
    """The host dispatches read ``PjitFunction(<program>)`` under the
    programs' own names; none of the engine's programs is a lambda. One
    slot: one device read per emitted token, annotations on."""
    m, params = qwen
    eng = _mk_engine(m, params, obs=Obs(jax_annotations=True))
    events, _ = _profiled_run(
        eng, [Request(tokens=[3, 4, 5], max_new_tokens=6)], tmp_path)
    assert eng.device_reads == eng.emitted_tokens == 6
    names = {ev.name for ev in events}
    for prog in ("prefill_chunk", "decode_step", "sample_tokens"):
        assert f"PjitFunction({prog})" in names, prog
    assert "PjitFunction(<lambda>)" not in names
    progs = {name: fn.__wrapped__.__name__
             for name, fn in eng.jit_entry_points().items()}
    assert progs == {"prefill": "prefill_chunk", "decode": "decode_step",
                     "verify": "verify_step", "sample": "sample_tokens"}


def test_annotations_off_phase_is_the_shared_null_context():
    """Without annotations an inactive bundle hands back the shared null
    context from ``phase`` (no allocation), and ``span`` does whatever
    the timing; with them both are profiler spans that take arguments."""
    assert Obs(timing=False).phase("decode") is NULL_CTX
    assert Obs().span("step") is NULL_CTX
    assert Obs.disabled().span("upload") is NULL_CTX
    on = Obs(timing=False, jax_annotations=True)
    for span in (on.phase("decode"), on.span("step")):
        assert span is not NULL_CTX
        with span:
            span.set_metadata(slots=2, positions=9)
    assert on.metrics.snapshot()["histograms"] == {}   # timing stays off
