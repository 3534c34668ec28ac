"""The benchmark's cell loop: set-up, measured window, metrics, check.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json``: the cell (``workloads``), its configuration and
  traffic names, and which metrics it reports;
* ``bench/configs/<config>.json``: the model as run, its ``reference``
  module (``bench/reference/<name>.py``) and the limit of the check;
* ``bench/traffic/<mix>.json``: the mix's parameters, read by the general
  generator ``bench/traffic.py``, or, where ``bench/traffic/<mix>.py``
  exists, by that file's own ``Traffic`` class (a new kind of arrivals or
  prompts is a new file);
* ``bench/cells/<cell>.json``: what the cell adds to the mix (the rate);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or None.

The window drives the program's serving engine through ``submit`` and
``step`` on one device and stamps every request's tokens after each step
on the host clock. After the window the program's state is freed and the
plain reference re-runs a seeded sample of the served sequences; the
mean, over the sample's served tokens, of the gap by which a served
token's reference logit lies below the reference's best decides
``correct`` (PERF.md gives why the mean and not the widest gap).
"""
from __future__ import annotations

import dataclasses
import gc
from contextlib import nullcontext
import importlib.util
import json
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import reduce  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import work  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_CAP_S = 60.0       # an answer may come this late past the close
REF_BATCH = 8            # sequences per reference call
REF_SEQS = 8             # served sequences the check samples


def log(what: str, **kv) -> None:
    """One progress line on standard error."""
    print(f"[bench] {what} " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    """The module of one file, loaded once per path."""
    path = Path(path).resolve()
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", str(path.with_suffix(""))), path)
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: str
    root: Path
    config: dict
    mix: dict
    extra: dict
    metrics: Dict[str, dict]       # this cell's metrics, by trace flag


def load_cell(name: str, trace: bool, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    extra_path = root / "bench" / "cells" / f"{name}.json"
    metrics = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if name in m.get("workloads", [name]):
            metrics[m["name"]] = m
    return Cell(name=name, chips=w["chips"], traffic=w["traffic"],
                root=Path(root),
                config=load_json(root / conf["file"]),
                mix=load_json(root / "bench" / "traffic" /
                              f"{w['traffic']}.json"),
                extra=load_json(extra_path) if extra_path.exists() else {},
                metrics=metrics)


def make_traffic(cell: Cell, seed: int, vocab: int, slots: int):
    """The cell's traffic source: ``Traffic`` of the mix's own
    ``bench/traffic/<mix>.py`` where there is one, else the general
    generator's."""
    own = cell.root / "bench" / "traffic" / f"{cell.traffic}.py"
    kind = load_module(own).Traffic if own.exists() else traffic_mod.Traffic
    return kind(cell.mix, cell.extra, seed, vocab, slots)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Compiles:
    """Counts jit traces and backend compiles while ``on`` (the window
    should hold none)."""
    on = False
    traces = 0
    compiles = 0
    _registered = False

    @classmethod
    def listen(cls) -> None:
        if cls._registered:
            return
        import jax.monitoring

        def hear(name, _secs, **_kw):
            if cls.on:
                if name.endswith("jaxpr_trace_duration"):
                    cls.traces += 1
                elif name.endswith("backend_compile_duration"):
                    cls.compiles += 1
        jax.monitoring.register_event_duration_secs_listener(hear)
        cls._registered = True


def enable_cache() -> None:
    """JAX's persistent compilation cache at one fixed directory of the
    checkout, every program cached."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def reference_module(cfg: dict):
    return load_module(BENCH / "reference" / f"{cfg['reference']}.py")


def model_config(cfg: dict):
    """The program's model configuration: its preset, with every size of
    the configuration file as run."""
    from repro.configs import get_config
    base = get_config(cfg["program_preset"])
    return base.replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"]
                         // cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg.get("qkv_bias", False), dtype=cfg["torch_dtype"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"])


def quant_config(cfg: dict):
    from repro.core.lut import DENSE, QuantConfig
    p = cfg["projection"]
    if p["mode"] == "dense":
        return DENSE
    return QuantConfig(mode=p["mode"], v=p["v"], c=p["c"],
                       metric=p["metric"], lut_dtype=p["lut_dtype"])


def build_engine(cfg: dict, seed: int, trace: bool):
    """Weights from the seed (one jitted call, on the device), the
    program's own conversion for a LUT configuration, and the engine."""
    import jax
    from repro.core import precompute_model, strip_model
    from repro.models.model import Model
    from repro.obs import Obs
    from repro.serve import Engine
    ref = reference_module(cfg)
    sizes = ref.sizes_from_config(cfg)
    qc = quant_config(cfg)
    model = Model(model_config(cfg))
    params = jax.jit(lambda k: ref.init_params(sizes, k))(ref.seed_key(seed))
    if qc.mode == "lut_infer":
        params = strip_model(precompute_model(params, qc))
    dep = cfg["deployment"]
    eng = Engine(model, params, qc, batch_size=dep["slots"],
                 max_seq=dep["max_seq"], page_size=dep["page_size"],
                 obs=Obs(jax_annotations=trace))
    return eng


def warm_up(eng) -> None:
    """Compile the shapes the window uses: a full and a partial prefill
    chunk, the prefill's own sampling, the decode and sample programs."""
    from repro.serve import Request
    eng.run([Request(tokens=[1] * (eng.prefill_chunk + 1),
                     max_new_tokens=3)])


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the benchmark sees it."""
    spec: traffic_mod.Spec
    req: object
    admitted: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    ready: int = 0                     # prompt tokens made ready

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)

    @property
    def done(self) -> bool:
        return self.req.done


@dataclasses.dataclass
class StepWork:
    """What one engine step computed, as seen from the host."""
    t1: float                          # when the step returned
    prefill: List[tuple]               # (first position, tokens)
    decode: List[int]                  # context of each decoded token


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    setup_s: float
    recs: List[Rec]
    window: tuple                      # (start, end) on the host clock
    steps: List[StepWork]
    traced: object = None              # reduce.Reduced
    traced_steps: List[StepWork] = dataclasses.field(default_factory=list)
    sizes: object = None
    peaks: dict = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def counted(self) -> List[Rec]:
        return [r for r in self.recs if r.spec.in_window]


class Feeder:
    """Feeds one engine from one traffic source and watches it."""

    def __init__(self, eng, source: traffic_mod.Traffic, seconds: float,
                 trace: bool, annotate):
        self.eng, self.src, self.seconds = eng, source, seconds
        self.trace = trace
        self.ann = annotate
        self.recs: List[Rec] = []
        self.live: List[Rec] = []
        self.steps: List[StepWork] = []
        self.traced_steps: List[StepWork] = []
        self.window = (0.0, 0.0)
        self.backlog = None
        self.drain_cap = DRAIN_CAP_S
        self.t0 = time.perf_counter()

    def submit(self, spec) -> None:
        from repro.serve import Request
        req = Request(tokens=list(spec.prompt), max_new_tokens=spec.max_new)
        self.eng.submit(req)
        rec = Rec(spec=spec, req=req)
        self.recs.append(rec)
        self.live.append(rec)

    def step(self) -> StepWork:
        sched = self.eng.scheduler
        t0 = time.perf_counter()
        with self.ann("bench.step"):
            self.eng.step()
        t1 = time.perf_counter()
        with self.ann("bench.observe"):
            in_slot = {id(s.req): s for s in sched.slots if s.req is not None}
            work_ = StepWork(t1 - self.t0, [], [])
            keep = []
            for r in self.live:
                slot = in_slot.get(id(r.req))
                if r.admitted is None and (slot is not None or r.done):
                    r.admitted = t0 - self.t0
                n_before = len(r.token_t)
                n = len(r.req.out_tokens)
                if n > n_before:
                    r.token_t += [t1 - self.t0] * (n - n_before)
                    first = 1 if n_before == 0 else 0
                    work_.decode += [r.prompt_len + j
                                     for j in range(n_before + first, n)]
                if r.done or n:
                    ready = r.prompt_len
                elif slot is not None:
                    ready = min(slot.pos, r.prompt_len)
                else:
                    ready = r.ready
                if ready > r.ready:
                    work_.prefill.append((r.ready, ready - r.ready))
                    r.ready = ready
                if not r.done:
                    keep.append(r)
            self.live = keep
        return work_

    def prime(self) -> None:
        """Closed loop: fill every slot and finish their prefill (the
        backlog's state at the start of a steady window)."""
        for _ in range(self.eng.num_slots):
            self.submit(next(self.backlog))
        while any(not r.token_t for r in self.live):
            self.step()

    def run(self) -> None:
        eng, seconds = self.eng, self.seconds
        open_loop = self.src.loop == "open"
        pending = (self.src.open_schedule(seconds, max(self.drain_cap, 1.0))
                   if open_loop else None)
        nxt = 0
        tr_lo, tr_hi = trace_span(seconds)
        span, traced = None, False     # the open trace window; done
        t0 = time.perf_counter()
        shift = t0 - self.t0           # events before the window go < 0
        for r in self.recs:
            r.admitted = None if r.admitted is None else r.admitted - shift
            r.token_t = [t - shift for t in r.token_t]
        self.t0 = t0
        while True:
            now = time.perf_counter() - self.t0
            if self.trace and not traced and span is None and now >= tr_lo:
                span = start_trace(self.ann)
            elif span is not None and now >= tr_hi:
                span.__exit__(None, None, None)
                span, traced = None, True
            with self.ann("bench.submit"):
                if open_loop:
                    while nxt < len(pending) and pending[nxt].due <= now:
                        self.submit(pending[nxt])
                        nxt += 1
                else:
                    backlog = self.src.mix.get("backlog", 0)
                    while (len(eng.scheduler.waiting) < backlog
                           and now < seconds):
                        self.submit(next(self.backlog))
            if open_loop:
                if now >= seconds and all(
                        r.done for r in self.recs if r.spec.in_window):
                    break
                if now >= seconds + self.drain_cap:
                    break
            elif now >= seconds:
                break
            if not eng.scheduler.has_work:
                if open_loop and nxt < len(pending):
                    with self.ann("bench.wait"):
                        time.sleep(max(0.0, pending[nxt].due - now))
                continue
            w = self.step()
            self.steps.append(w)
            if span is not None:
                self.traced_steps.append(w)
        if span is not None:
            span.__exit__(None, None, None)
        if self.trace and (traced or span is not None):
            # stopping writes the trace out (seconds): only after the loop
            stop_trace()
        end = self.steps[-1].t1 if self.steps else 0.0
        self.window = (0.0, end if not open_loop else min(end, seconds))


def trace_span(seconds: float):
    """The part of the window that a traced run records: from a quarter
    of the way in, at most 8 seconds."""
    lo = 0.25 * seconds
    return lo, lo + min(8.0, 0.5 * seconds)


def start_trace(annotate):
    """Start the profiler (replacing the last trace) and open the window
    span that ``bench/reduce.py`` looks for; returns the span, which the
    caller closes. The profiler runs on until :func:`stop_trace`."""
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # annotations only, no frames
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    span = annotate(reduce.WINDOW)
    span.__enter__()
    return span


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_for_check(recs: List[Rec], seed: int, n: int = REF_SEQS):
    """Up to ``n`` served requests drawn from the seed, the one with the
    most served tokens always among them."""
    served = [r for r in recs if r.req.out_tokens]
    if not served:
        return []
    longest = max(served, key=lambda r: len(r.req.out_tokens))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(int(seed) % (1 << 63) ^ 0x5EED)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def check(cfg: dict, seed: int, recs: List[Rec], longest: int,
          control: bool = False) -> dict:
    """Run the reference over a seeded sample of served sequences, padded
    to a multiple of 128 at least ``longest`` (the mix's longest sequence).

    Returns statistics of the served tokens' gaps below the reference's
    best (``gaps``; with ``control`` also ``control_gaps``, of the
    control's own first choices at the same positions) and the counts."""
    ref_mod = reference_module(cfg)
    sizes = ref_mod.sizes_from_config(cfg)
    sample = sample_for_check(recs, seed)
    seqs = [list(r.spec.prompt) + list(r.req.out_tokens) for r in sample]
    first = [r.prompt_len for r in sample]
    max_seq = cfg["deployment"]["max_seq"]
    length = min(max_seq, -(-longest // 128) * 128)
    ref = ref_mod.Reference(sizes, seed)
    g, gc_ = ref_mod.served_gaps(ref, seqs, first, length, REF_BATCH,
                                 control=control)
    out = {"gaps": gap_stats(g), "served_tokens_checked": int(g.size),
           "sequences_checked": len(seqs),
           "distinct_served_tokens": len({t for r in sample
                                          for t in r.req.out_tokens})}
    if control:
        out["control_gaps"] = gap_stats(gc_)
    return out


def gap_stats(g) -> dict:
    """Summary of per-token gaps, for the readings' log."""
    if not g.size:
        return {}
    return {"mean": float(g.mean()), "median": float(np.median(g)),
            "p90": float(np.percentile(g, 90)), "max": float(g.max()),
            "zero_share": float((g == 0).mean())}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def read_metrics(run: Run) -> Dict[str, dict]:
    out = {}
    for name, m in run.cell.metrics.items():
        reader = load_module(BENCH / "metrics" / f"{name}.py")
        value = reader.read(run)
        if value is not None:
            out[name] = {"value": value, "unit": m["unit"]}
    return out


def reduce_traced(run: Run) -> None:
    path = reduce.find_trace(str(TRACE_DIR))
    if path is None:
        raise RuntimeError("the traced run wrote no trace")
    kernels = {}
    for f in sorted((BENCH / "work").glob("*.py")):
        mod = load_module(f)
        if hasattr(mod, "matches"):
            kernels[f.stem] = mod.matches
    run.traced = reduce.reduce_trace(path, kernels)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, chips_required: bool = True,
             root: Path = ROOT, control: bool = False,
             cache: bool = True) -> dict:
    """One run of one cell. Returns the result object (the JSON line)."""
    cell = load_cell(workload, trace, root)
    if chips_required:
        require_chips(cell.chips)
    import jax
    if cache:
        enable_cache()
    from repro.obs.tracing import jax_annotation
    ann = jax_annotation if trace else (lambda name: nullcontext())
    cfg = cell.config
    d0 = jax.devices()
    log("start", cell=workload, seed=seed, seconds=seconds, trace=int(trace),
        platform=d0[0].platform, device_kind=repr(d0[0].device_kind),
        device_count=len(d0),
        since_start_s=round(time.perf_counter() - t_start, 3))
    eng = build_engine(cfg, seed, trace)
    jax.block_until_ready(eng.params)
    log("engine", since_start_s=round(time.perf_counter() - t_start, 3))
    warm_up(eng)
    log("warm", since_start_s=round(time.perf_counter() - t_start, 3))
    src = make_traffic(cell, seed, cfg["vocab_size"], eng.num_slots)
    drv = Feeder(eng, src, seconds, trace, ann)
    if src.loop == "closed":
        drv.backlog = src.backlog()
        if cell.mix.get("prefill_before_window"):
            drv.prime()
    jax.block_until_ready(eng.kv.data)
    setup_s = time.perf_counter() - t_start
    log("setup", setup_s=round(setup_s, 3))
    Compiles.listen()
    Compiles.on, Compiles.traces, Compiles.compiles = True, 0, 0
    drv.run()
    Compiles.on = False
    dev = device_info()
    log("window", steps=len(drv.steps), requests=len(drv.recs),
        window_s=round(drv.window[1] - drv.window[0], 3),
        after_close_s=round(time.perf_counter() - drv.t0 - seconds, 3),
        traces_in_window=Compiles.traces,
        compiles_in_window=Compiles.compiles,
        memory_peak_bytes=dev["memory_peak_bytes"])
    ref_mod = reference_module(cfg)
    run = Run(cell=cell, setup_s=setup_s, recs=drv.recs, window=drv.window,
              steps=drv.steps, traced_steps=drv.traced_steps,
              sizes=ref_mod.sizes_from_config(cfg),
              peaks=work.peaks(dev["kind"]) if dev["platform"] == "tpu"
              else None)
    counted = [r for r in drv.recs if r.spec.in_window]
    open_loop = src.loop == "open"
    failed = sum(
        1 for r in counted
        if (open_loop and not r.done) or (r.done and r.req.finish_reason
                                          .name != "COMPLETED"))
    del eng, drv
    gc.collect()
    if trace:
        reduce_traced(run)
        dev["busy_s"] = run.traced.busy_s
        dev["window_s"] = run.traced.window_s
    metrics = read_metrics(run)
    t_chk = time.perf_counter()
    chk = check(cfg, seed, counted, src.max_len(), control=control)
    log("check", seconds=round(time.perf_counter() - t_chk, 3), **chk)
    limit = dict(cfg["check"], **cell.extra.get("check", {}))[
        "mean_logit_gap"]
    gap = chk["gaps"].get("mean")
    checks = {
        "mean_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "served_tokens_checked": {"value": chk["served_tokens_checked"],
                                  "limit": 1},
    }
    correct = (gap is not None and limit is not None and gap <= limit
               and failed == 0 and chk["served_tokens_checked"] >= 1)
    result = {"correct": bool(correct), "attempted": len(counted),
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.traced.top_ops],
            "idle_gaps": [[k, v] for k, v in run.traced.idle_gaps]}
    if control:
        result["gaps"] = chk["gaps"]
        result["control_gaps"] = chk["control_gaps"]
    result["checks"] = checks
    return result
