"""Serving A/B: continuous batching (paged KV) vs batch-to-completion.

Replays the same mixed-length workload through both engines and reports
wall-clock generation throughput. The workload interleaves one long
request with several short ones per batch-of-`slots` group — the
batch-to-completion engine head-of-line blocks on the long member of
every group, while the continuous engine refills freed slots mid-decode.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--smoke]

``--smoke`` uses the CPU smoke config, asserts the continuous engine wins
by >= 1.3x tokens/s (the acceptance floor; typical margin is ~2x), and is
wired into CI so the serving A/B cannot bit-rot.

``--sharded`` adds a tensor-parallel row: the same workload through
``Engine(mesh=...)`` over all visible devices, token-checked against the
single-device continuous run. On a CPU host pass ``--devices N`` to
run on N host devices (``jax_num_cpu_devices``) — the row then measures dispatch overhead, not real TP speedup (host "devices" share
the same cores; see docs/serving.md §Sharded serving).

The prefix-reuse row replays a shared-system-prompt workload (one warming
request, then N requests sharing its page-aligned prefix) through a cold
engine (``prefix_cache=False``) and a warm one, asserts the two produce
token-identical greedy output, and reports the prefill-token reduction
(``--smoke`` asserts >= 30%; typical is ~2x that, since only the private
user suffix of each warm request is prefilled).

``--spec`` adds the speculative-decoding A/B (docs/speculative.md). The
smoke model is briefly TRAINED first (the +1-mod-V synthetic stream with
a small vocab), because speculation's win depends on the drafter
predicting the target — on random weights no cheap drafter agrees with
the target and every row would honestly lose. The trained model emits
periodic streams and the mixed-length workload's prompts contain one
full period, so the zero-model-cost n-gram drafter proposes the true
continuation from the first generated token: that row is asserted
token-identical to the non-speculative engine and (under ``--smoke``)
>1.0x tokens/s, with the acceptance rate reported. An early-exit model
drafter row (``draft_layers=1``) is reported unasserted: on this
compute-bound CPU host its draft passes cost real FLOPs, so it hovers
near 1.0x — the row exists to exercise the model-drafter path
end-to-end and to report its acceptance.

``--chaos`` adds the fault-tolerance rows: a 2-replica router replays the
mixed workload under ``FaultSchedule.canned`` (pool squeeze + injected
decode failure + replica crash mid-decode; docs/robustness.md), asserting
zero lost requests and token-identical completed output, and reporting
goodput (``--smoke`` asserts >= 90%).

``--longctx`` adds the long-context decode A/B (docs/kernels.md §Paged
flash decode): paged state is built directly at an 8k-token context and
one greedy decode chain runs through ``decode_paged`` twice — once on the
legacy gather path (``QuantConfig.flash="gather"``: pool -> dense KV view
every step) and once on the flash page-table path (``"pallas"`` on TPU,
the XLA ``"ref"`` formulation on CPU hosts). The chains are asserted
token-identical; ``--smoke`` additionally asserts the flash row wins
tokens/s (>= 1.0x floor; typical CPU margin is ~1.3x — the dense view
re-materialises ~25MB/step that the flash path never touches).

``--kvq`` adds the vector-quantized KV-page rows (docs/serving.md
§KV-cache quantization): an fp-pool engine and a ``kv_quant="vq"`` engine
(uint8 codebook indices in the pages, codebook fit from a calibration
prefill) are compared on measured HBM bytes per cached token and on the
number of full-length sequences resident in ONE pool byte budget — and
the quantized capacity is demonstrated by decoding that many requests
concurrently to completion (``--smoke`` asserts >= 4x bytes/token and
>= 2x resident sequences; typical at v=4/c=16 on the fp32 pool is 16x).

``--obs`` adds the observability-overhead A/B (docs/observability.md):
the mixed workload through a fully instrumented engine (phase timers on,
tracer recording) and through ``Obs.disabled()``, interleaved best-of-3;
``--smoke`` asserts the instrumented engine keeps >= 95% of the bare
tokens/s (the < 5% overhead ceiling), and a micro-row prices one step's
worth of recording in microseconds.

``--trace PATH`` (requires ``--chaos``) attaches one shared
:class:`~repro.obs.Tracer` to both chaos replicas and exports the
faulted run as Chrome/Perfetto ``trace_event`` JSON — request lifecycle
spans, per-replica step-phase spans, and fault/degradation/preemption
annotations — validated structurally before the bench exits (load it at
``ui.perfetto.dev``).

``--snapshot PATH`` (or ``auto``) writes every emitted row plus run
metadata to a ``BENCH_serve.json`` perf snapshot — the on-disk trajectory
for ROADMAP item 5 — which ``scripts/perf_gate.py`` diffs against the
committed copy in CI.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.core.lut import DENSE
from repro.models.model import Model
from repro.obs import Obs, Tracer, validate_trace
from repro.serve import (BatchToCompletionEngine, Engine, FaultInjector,
                         FaultSchedule, FinishReason, ReplicaHealth,
                         ReplicaRouter, Request, SpecConfig)

try:                                   # `python -m benchmarks.serve_bench`
    from .common import emit, snapshot, time_jax_pair
except ImportError:                    # `python benchmarks/serve_bench.py`
    from common import emit, snapshot, time_jax_pair


def mixed_workload(n_requests: int, slots: int, prompt_len: int = 4,
                   long_new: int = 56, short_new: int = 2):
    """One long + (slots-1) short requests per group, fixed prompt length.

    Fixed prompts keep the batch engine on a single compiled prefill shape
    — the A/B then measures scheduling, not recompilation."""
    reqs = []
    for i in range(n_requests):
        long = (i % slots) == 0
        n_new = long_new if long else short_new + (i % 3)
        reqs.append(Request(tokens=[(7 * i + j) % 50 + 2
                                    for j in range(prompt_len)],
                            max_new_tokens=n_new))
    return reqs


def _run_timed(engine, reqs):
    t0 = time.perf_counter()
    engine.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    return toks, dt


def shared_prefix_workload(n_requests: int, system_len: int = 24,
                           user_len: int = 4, n_new: int = 4):
    """N requests sharing one deterministic system prompt + private suffix."""
    system = [(5 * j) % 60 + 2 for j in range(system_len)]
    return [Request(tokens=system + [(11 * i + j) % 60 + 2
                                     for j in range(user_len)],
                    max_new_tokens=n_new)
            for i in range(n_requests)]


def prefix_bench(mk_engine, n_requests: int, smoke: bool) -> float:
    """Cold/warm A/B over the shared-system-prompt workload.

    The first request is served to completion before the rest are
    submitted (it warms the prefix index the way long-lived production
    traffic would); the cold engine replays the identical arrival
    sequence with ``prefix_cache=False``.
    Returns the warm engine's prefill-token reduction in [0, 1).
    """
    streams, engines = {}, {}
    for tag, warm in (("cold", False), ("warm", True)):
        eng = mk_engine(prefix_cache=warm)
        reqs = shared_prefix_workload(n_requests)
        eng.run([reqs[0]])
        for r in reqs[1:]:
            eng.submit(r)
        eng.run_until_idle()
        assert all(r.done and len(r.out_tokens) == r.max_new_tokens
                   for r in reqs), f"prefix {tag}: incomplete requests"
        streams[tag] = [r.out_tokens for r in reqs]
        engines[tag] = eng
    assert streams["warm"] == streams["cold"], \
        "prefix reuse changed greedy output vs the cold path"
    warm = engines["warm"]
    reduction = 1.0 - warm.prefilled_tokens / max(warm.prompt_tokens, 1)
    emit("serve.prefix_reuse.prefill_reduction", reduction * 100.0,
         f"prefilled {warm.prefilled_tokens}/{warm.prompt_tokens} prompt "
         f"tokens, hit_rate={warm.prefix_hit_rate:.2f}, "
         f"cow_forks={warm.kv.cow_forks}", unit="%", direction="up")
    print(f"prefix reuse: tokens identical to cold path; prefill tokens "
          f"reduced {reduction * 100:.0f}% "
          f"({engines['cold'].prefilled_tokens} -> {warm.prefilled_tokens})")
    if smoke:
        assert reduction >= 0.30, (
            f"shared-system-prompt workload must cut prefill tokens by "
            f">=30%, got {reduction * 100:.0f}%")
        print("prefix smoke check OK (>= 30% prefill reduction)")
    return reduction


def spec_bench(slots: int, n_requests: int, smoke: bool) -> float:
    """Speculative-decoding A/B: n-gram-drafted vs plain continuous.

    Trains the smoke model briefly on the +1-mod-V synthetic stream with
    a small vocab (see the module docstring for why trained weights are
    a precondition, not a convenience), then replays a mixed-length
    workload whose prompts hold one full output period. Returns the
    asserted row's tokens/s ratio over the non-speculative engine.
    """
    from repro.data import SyntheticDataset
    from repro.train import TrainConfig, Trainer
    vocab = 24
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive",
                                                 vocab_size=vocab)
    model = Model(cfg)
    ds = SyntheticDataset(cfg, global_batch=16, seq_len=64)
    params = model.init(jax.random.PRNGKey(0), DENSE)
    params, _, _ = Trainer(model, ds, DENSE, TrainConfig(
        total_steps=150, lr=3e-3, warmup=10, log_every=1000)).run(params)
    max_seq = 96

    def cycle_workload(n):
        """mixed_workload's long/short mix, prompts = one full +1 cycle."""
        reqs = mixed_workload(n, slots, prompt_len=28)
        for i, r in enumerate(reqs):
            r.tokens = [(3 * i + j) % vocab for j in range(28)]
        return reqs

    def mk(spec=None):
        return Engine(model, params, DENSE, batch_size=slots,
                      max_seq=max_seq, page_size=16, prefill_chunk=8,
                      spec_decode=spec)

    rows = [("continuous", None),
            ("spec_ngram", SpecConfig(k=8, drafter="ngram")),
            ("spec_exit1", SpecConfig(k=6, draft_layers=1))]
    rates, streams = {}, {}
    for tag, spec in rows:
        eng = mk(spec)
        eng.run(cycle_workload(slots))          # warmup (per-engine jits)
        reqs = cycle_workload(n_requests)
        toks, dt = _run_timed(eng, reqs)
        assert all(r.done and len(r.out_tokens) == r.max_new_tokens
                   for r in reqs), f"{tag}: incomplete requests"
        rates[tag] = toks / dt
        streams[tag] = [r.out_tokens for r in reqs]
        extra = ""
        if spec is not None:
            assert streams[tag] == streams["continuous"], \
                f"{tag}: speculative greedy output diverges from the " \
                f"non-speculative engine"
            extra = (f" acceptance={eng.acceptance_rate:.2f}"
                     f" tok/verify={eng.tokens_per_verify:.2f}")
        emit(f"serve.{tag}.us_per_tok", dt / max(toks, 1) * 1e6,
             f"tok/s={toks / dt:.1f}{extra}")
        print(f"{tag}: {toks / dt:.1f} tok/s{extra}")
    ratio = rates["spec_ngram"] / rates["continuous"]
    print(f"speculative (ngram drafter): {ratio:.2f}x tokens/s vs "
          f"continuous, token-identical output "
          f"(exit1 model drafter: "
          f"{rates['spec_exit1'] / rates['continuous']:.2f}x, "
          f"compute-bound on CPU — see docs/speculative.md)")
    if smoke:
        assert ratio > 1.0, (
            f"n-gram-drafted speculative decoding must beat the plain "
            f"continuous engine on the periodic smoke workload, got "
            f"{ratio:.2f}x")
        print("spec smoke check OK (> 1.0x, token-identical)")
    return ratio


def chaos_bench(slots: int, n_requests: int, max_seq: int,
                smoke: bool, trace_path: str = "") -> float:
    """Fault-tolerant serving under the canned chaos schedule.

    A 2-replica router replays the mixed workload while
    ``FaultSchedule.canned`` squeezes replica 0's page pool dry, injects
    a one-shot decode failure, then stalls and hard-crashes replica 1
    mid-decode (docs/robustness.md). Asserted invariants: ZERO lost
    requests (every request finishes with a reason — crash recovery
    requeues the dead replica's in-flight work), and completed requests
    are token-identical to a fault-free run. Returns goodput — the
    fraction of requests that finished ``COMPLETED`` (not shed, not
    deadline-expired); ``--smoke`` asserts >= 90%.
    """
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), DENSE)

    def mk_router(tracer=None):
        # one SHARED tracer across both replicas -> one merged timeline;
        # the router stamps each engine's pid with its replica index
        return ReplicaRouter([Engine(model, params, DENSE, batch_size=slots,
                                     max_seq=max_seq, page_size=16,
                                     prefill_chunk=8,
                                     obs=Obs(tracer=tracer)
                                     if tracer is not None else None)
                              for _ in range(2)])

    def workload():
        # longs first: least-loaded dispatch then spreads them across both
        # replicas, so the mid-decode crash of the last replica actually
        # has in-flight work to recover (mixed_workload puts every long
        # request at an even index, which round-robins them all onto
        # replica 0 otherwise)
        reqs = mixed_workload(n_requests, slots)
        return sorted(reqs, key=lambda r: -r.max_new_tokens)

    ref_reqs = workload()
    mk_router().run(ref_reqs)               # fault-free reference output

    tracer = Tracer(enabled=True) if trace_path else None
    router = mk_router(tracer)
    router.run(mixed_workload(2 * slots, slots, long_new=3, short_new=2))
    if tracer is not None:
        tracer.clear()                  # drop warmup; trace the chaos run
    FaultInjector(FaultSchedule.canned(replicas=2)).attach(router)
    reqs = workload()
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    router.run_until_idle()
    dt = time.perf_counter() - t0

    lost = [r for r in reqs if not r.done]
    assert not lost, f"chaos: {len(lost)} request(s) lost"
    completed = [r for r in reqs
                 if r.finish_reason is FinishReason.COMPLETED]
    for got, want in zip(reqs, ref_reqs):
        if got.finish_reason is FinishReason.COMPLETED:
            assert got.out_tokens == want.out_tokens, \
                "chaos: completed request diverged from fault-free run"
    goodput = len(completed) / len(reqs)
    toks = sum(len(r.out_tokens) for r in reqs)
    dead = sum(st.health is ReplicaHealth.DEAD for st in router.status)
    emit("serve.chaos.goodput_pct", goodput * 100.0,
         f"completed={len(completed)}/{len(reqs)} "
         f"retried={router.retried_requests} "
         f"shed={sum(r.shed for r in reqs)} dead_replicas={dead}",
         unit="%", direction="up")
    emit("serve.chaos.us_per_tok", dt / max(toks, 1) * 1e6,
         f"tok/s={toks / dt:.1f} under faults")
    print(f"chaos: {goodput * 100:.0f}% goodput, zero lost, completed "
          f"output token-identical to fault-free run "
          f"({router.retried_requests} recovery retries, {dead} replica(s) "
          f"died)")
    if smoke:
        assert goodput >= 0.90, (
            f"chaos goodput must stay >= 90% under the canned fault "
            f"schedule, got {goodput * 100:.0f}%")
        print("chaos smoke check OK (>= 90% goodput, zero lost)")
    if tracer is not None:
        doc = tracer.export(trace_path)
        problems = validate_trace(doc)
        assert not problems, f"chaos trace invalid: {problems[:5]}"
        n_req_spans = sum(1 for e in doc["traceEvents"]
                          if e.get("ph") == "b")
        n_annot = sum(1 for e in doc["traceEvents"]
                      if e.get("ph") == "i" and e.get("cat") == "annot")
        assert n_req_spans and n_annot, (
            "chaos trace exported but carries no request spans or no "
            "fault/degradation annotations")
        print(f"chaos trace: {len(doc['traceEvents'])} events "
              f"({n_req_spans} request spans, {n_annot} annotations) -> "
              f"{trace_path} (valid; open at ui.perfetto.dev)")
    return goodput


def longctx_bench(smoke: bool, ctx: int = 8192, slots: int = 2,
                  steps: int = 8) -> float:
    """Long-context decode A/B: flash page-table decode vs the gather path.

    The paged state is synthesised directly — pool pages filled with
    unit-normal pseudo prompt KV, a fully-allocated page table, per-slot
    positions [ctx-1, ctx//2] — because the row measures the *decode*
    path and an 8k real prefill would dominate the wall clock without
    touching it. head_dim is widened to 64 (the smoke config's 16 keeps
    the whole model tiny; at 8k the interesting regime is KV-traffic-
    bound, which is head_dim-proportional). A greedy chain of ``steps``
    tokens runs under ``QuantConfig.flash="gather"`` and under the flash
    impl for this host ("pallas" on TPU, "ref" on CPU); the chains must
    be token-identical, then one steady-state step is timed interleaved.
    Returns the flash/gather tokens/s ratio (``--smoke`` asserts >= 1.0).
    """
    ps = 16
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive",
                                                 head_dim=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), DENSE)
    pages_per_slot = (ctx + steps + ps - 1) // ps
    num_pages = slots * pages_per_slot
    kv = model.init_paged_cache(slots, ctx + steps, ps, num_pages)
    key = jax.random.PRNGKey(1)
    kv = {k: jax.random.normal(jax.random.fold_in(key, i), v.shape,
                               v.dtype) * 0.3
          for i, (k, v) in enumerate(sorted(kv.items()))}
    page_table = jnp.arange(num_pages, dtype=jnp.int32).reshape(
        slots, pages_per_slot)
    pos0 = jnp.array(([ctx - 1] + [ctx // 2] * (slots - 1))[:slots],
                     jnp.int32)
    tok0 = jnp.full((slots, 1), 3, jnp.int32)
    flash = "pallas" if jax.default_backend() == "tpu" else "ref"

    def mk_step(impl):
        qc = DENSE.replace(flash=impl)

        def step(tok, kv, positions):
            logits, kv = model.decode_paged(params, tok, kv, page_table,
                                            positions, qc)
            return jnp.argmax(logits, -1).astype(jnp.int32), kv
        return jax.jit(step)

    def run_chain(step_fn):
        toks, kv_r, posn, tok = [], kv, pos0, tok0
        for _ in range(steps):
            nxt, kv_r = step_fn(tok, kv_r, posn)
            toks.append([int(t) for t in nxt])
            tok, posn = nxt[:, None], posn + 1
        return toks

    gather_j, flash_j = mk_step("gather"), mk_step(flash)
    streams = {"gather": run_chain(gather_j), flash: run_chain(flash_j)}
    assert streams[flash] == streams["gather"], (
        f"longctx: flash ({flash}) greedy chain diverged from the gather "
        f"path")
    t_g, t_f = time_jax_pair(gather_j, flash_j, tok0, kv, pos0,
                             warmup=1, iters=5)
    view_mb = (2 * slots * pages_per_slot * ps * cfg.num_kv_heads
               * cfg.head_dim * cfg.num_layers * 4 / 1e6)
    ratio = t_g / t_f
    emit(f"serve.longctx{ctx}.gather.us_per_tok", t_g / slots,
         f"dense KV view {view_mb:.1f}MB/step")
    emit(f"serve.longctx{ctx}.flash_{flash}.us_per_tok", t_f / slots,
         f"{ratio:.2f}x vs gather; tokens identical over {steps} "
         f"greedy steps x {slots} slots")
    print(f"longctx {ctx}: flash ({flash}) {ratio:.2f}x tokens/s vs "
          f"gather, token-identical greedy chains")
    if smoke:
        assert ratio >= 1.0, (
            f"flash decode must not lose to the gather path at {ctx}-token "
            f"context, got {ratio:.2f}x")
        print("longctx smoke check OK (>= 1.0x, token-identical)")
    return ratio


def kvq_bench(slots: int, smoke: bool) -> float:
    """Vector-quantized KV pages A/B (docs/serving.md §KV-cache quantization).

    Two engines on the same smoke model: an fp pool and a ``kv_quant="vq"``
    pool whose pages hold uint8 codebook indices (the engine fits the
    codebook from a calibration prefill at construction). The row reports
    the measured HBM bytes one cached token pins (from the actual pool
    arrays, so dtype/layout changes show up) and the resident-sequence
    capacity both pools reach under ONE byte budget — the fp engine's pool.
    The quantized capacity is then *demonstrated*, not just computed: a
    batch of full-length requests equal to the fp pool's capacity times
    >=2 runs concurrently to completion inside that same budget, with the
    peak decode concurrency checked against the batch size.

    Returns the capacity ratio (``--smoke`` asserts bytes/token >= 4x and
    capacity >= 2x; typical at v=4/c=16 on the fp32 smoke pool is 16x).
    """
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), DENSE)
    KVQ = DENSE.replace(kv_quant="vq")
    ps, max_seq, chunk = 8, 32, 4
    prompt_len, n_new = 4, 20
    need = prompt_len + n_new                  # tokens a request pins
    pages_per_req = -(-need // ps)

    # fp engine: pool sized so exactly two full requests fit resident
    # (plus one spare page so allocation is not knife-edge) — the byte
    # budget every other number in this row is measured against.
    fp = Engine(model, params, DENSE, batch_size=slots, max_seq=max_seq,
                page_size=ps, prefill_chunk=chunk, prefix_cache=False,
                num_pages=2 * pages_per_req + 1)
    budget = fp.kv.pool_bytes
    cap_fp = budget // (pages_per_req * fp.kv.page_bytes)

    # quantized engine under the SAME byte budget: every fp page's bytes
    # buy `bytes_per_token` ratio more code pages.
    probe = Engine(model, params, KVQ, batch_size=1, max_seq=max_seq,
                   page_size=ps, prefill_chunk=chunk, prefix_cache=False)
    bpt_fp, bpt_q = fp.kv.bytes_per_token, probe.kv.bytes_per_token
    nq = budget // probe.kv.page_bytes
    cap_q = budget // (pages_per_req * probe.kv.page_bytes)
    cap_used = min(cap_q, 4 * slots)
    kvq = Engine(model, params, KVQ, batch_size=cap_used, max_seq=max_seq,
                 page_size=ps, prefill_chunk=chunk, prefix_cache=False,
                 num_pages=nq, kv_codebook=probe.kv_codebook)
    assert kvq.kv.pool_bytes <= budget, (
        f"kvq pool {kvq.kv.pool_bytes}B exceeds the fp byte budget "
        f"{budget}B")

    # demonstrate the capacity: cap_used identical full-length requests,
    # all resident at once, to completion.
    reqs = [Request(tokens=[(7 * i + j) % 50 + 2
                            for j in range(prompt_len)],
                    max_new_tokens=n_new) for i in range(cap_used)]
    peak, t0 = 0, time.perf_counter()
    for r in reqs:
        kvq.submit(r)
    while kvq.scheduler.has_work:
        kvq.step()
        peak = max(peak, len(kvq.scheduler.decode_slots()))
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in reqs)
    assert all(r.done and len(r.out_tokens) == r.max_new_tokens
               for r in reqs), "kvq: incomplete requests"
    assert peak == cap_used, (
        f"kvq: only {peak}/{cap_used} sequences decoded concurrently — "
        f"the capacity claim did not hold on the pool")

    bytes_ratio = bpt_fp / bpt_q
    cap_ratio = cap_q / max(cap_fp, 1)
    cb = kvq.kv_codebook
    emit("serve.kvq.bytes_per_tok", bpt_q,
         f"fp {bpt_fp}B -> vq {bpt_q}B ({bytes_ratio:.1f}x smaller; "
         f"v={cb.v} c={cb.c}, {cb.equivalent_bits:.1f} eq-bits)",
         unit="B", direction="down")
    emit("serve.kvq.resident_seqs_per_pool", cap_q,
         f"{cap_q} vs fp {cap_fp} full {need}-token seqs in the same "
         f"{budget}B pool ({cap_ratio:.1f}x); {peak} demonstrated live",
         unit="seqs", direction="up")
    emit("serve.kvq.us_per_tok", dt / max(toks, 1) * 1e6,
         f"tok/s={toks / dt:.1f} at {peak} concurrent quantized slots")
    print(f"kvq: {bpt_fp}B -> {bpt_q}B per cached token "
          f"({bytes_ratio:.1f}x), {cap_q} vs {cap_fp} resident seqs in a "
          f"{budget}B pool ({cap_ratio:.1f}x), {peak} run live")
    if smoke:
        assert bytes_ratio >= 4.0, (
            f"vq KV pages must cut bytes/token >= 4x, got "
            f"{bytes_ratio:.2f}x")
        assert cap_ratio >= 2.0, (
            f"vq KV pages must hold >= 2x the concurrent sequences at a "
            f"fixed pool byte budget, got {cap_ratio:.2f}x")
        print(f"kvq smoke check OK (>= 4x bytes/token, >= 2x resident "
              f"sequences, {peak} decoded concurrently)")
    return cap_ratio


def obs_bench(model, params, slots: int, n_requests: int, max_seq: int,
              smoke: bool) -> float:
    """Observability-overhead A/B: fully instrumented vs ``Obs.disabled()``.

    Same mixed workload, two engines differing only in the obs bundle —
    phase timers + an enabled tracer vs the disabled no-op path
    (counters stay live in both; they are engine state). Interleaved
    best-of-3 tokens/s absorbs host scheduler noise the same way
    ``time_jax_pair`` does. Returns the relative overhead in [0, 1);
    ``--smoke`` asserts < 5% (the ISSUE ceiling). A micro-row prices the
    raw recording primitive so the per-step cost is visible even when
    the end-to-end delta drowns in noise.
    """
    def mk(obs):
        return Engine(model, params, DENSE, batch_size=slots,
                      max_seq=max_seq, page_size=16, prefill_chunk=8,
                      prefix_cache=False, obs=obs)

    eng_off = mk(Obs.disabled())
    eng_on = mk(Obs(tracer=Tracer(enabled=True)))
    for e in (eng_off, eng_on):       # per-instance jit warmup
        e.run(mixed_workload(slots, slots, long_new=3, short_new=2))
    best = {"off": 0.0, "on": 0.0}
    for _ in range(3):
        for tag, e in (("off", eng_off), ("on", eng_on)):
            toks, dt = _run_timed(e, mixed_workload(n_requests, slots))
            best[tag] = max(best[tag], toks / dt)
    overhead = 1.0 - best["on"] / best["off"]

    # micro: one phase record (timer observe + trace event append), x7
    # for a step's worth of phases (admit, prefill, decode, sample,
    # draft, verify, device_read)
    obs = eng_on.obs
    n_iter = 20000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        with obs.phase("decode"):
            pass
    per_phase_us = (time.perf_counter() - t0) / n_iter * 1e6
    # floor at 1.0: the perf-gate tolerance is *relative* to the
    # baseline, so committing a 0.0 row (obs measured faster than bare,
    # i.e. pure noise) would gate every future positive reading
    emit("serve.obs.overhead_pct", max(overhead * 100.0, 1.0),
         f"obs-on {best['on']:.1f} vs obs-off {best['off']:.1f} tok/s, "
         f"best-of-3 interleaved", unit="%", direction="down", tol=4.0)
    emit("serve.obs.record_us_per_step", per_phase_us * 7,
         f"{per_phase_us:.3f}us per phase record (hist observe + trace "
         f"append) x 7 phases/step", tol=1.0)
    print(f"obs overhead: {overhead * 100:+.1f}% tokens/s "
          f"({best['on']:.1f} instrumented vs {best['off']:.1f} bare), "
          f"{per_phase_us:.3f}us per phase record")
    if smoke:
        assert overhead < 0.05, (
            f"instrumented engine lost {overhead * 100:.1f}% tokens/s — "
            f"the < 5% observability-overhead ceiling is blown")
        print("obs smoke check OK (< 5% overhead, obs fully on)")
    return overhead


def bench(slots: int, n_requests: int, max_seq: int, smoke: bool,
          sharded: bool = False, devices: int = 0, spec: bool = False,
          chaos: bool = False, longctx: bool = False, kvq: bool = False,
          obs: bool = False, trace: str = ""):
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0), DENSE)

    def batch_engine():
        return BatchToCompletionEngine(model, params, DENSE,
                                       batch_size=slots, max_seq=max_seq)

    def cont_engine(mesh=None, prefix_cache=True):
        return Engine(model, params, DENSE, batch_size=slots,
                      max_seq=max_seq, page_size=16, prefill_chunk=8,
                      mesh=mesh, prefix_cache=prefix_cache)

    makers = [("batch_to_completion", batch_engine),
              ("continuous_paged", cont_engine)]
    if sharded:
        # honour an explicit --devices N even when MORE devices are visible
        # (the mesh takes the first N); default to everything
        n_dev = min(devices, jax.device_count()) if devices \
            else jax.device_count()
        if n_dev < 2:
            print("[serve_bench] --sharded skipped: 1 device visible "
                  "(use --devices N to force host devices)")
        else:
            from repro.launch.mesh import make_test_mesh
            mesh = make_test_mesh((1, n_dev), ("data", "model"))
            makers.append((f"continuous_paged_tp{n_dev}",
                           lambda: cont_engine(mesh=mesh)))

    results = {}
    token_streams = {}
    for tag, mk in makers:
        eng = mk()
        # warmup on the engine instance itself: jitted prefill/decode are
        # per-instance, so a throwaway engine would put compilation back
        # into the timed region
        eng.run(mixed_workload(slots, slots, long_new=3, short_new=2))
        reqs = mixed_workload(n_requests, slots)
        toks, dt = _run_timed(eng, reqs)
        assert all(r.done and len(r.out_tokens) == r.max_new_tokens
                   for r in reqs), f"{tag}: incomplete requests"
        results[tag] = toks / dt
        token_streams[tag] = [r.out_tokens for r in reqs]
        emit(f"serve.{tag}.us_per_tok", dt / max(toks, 1) * 1e6,
             f"tok/s={toks / dt:.1f} toks={toks}")

    for tag in results:
        if tag.startswith("continuous_paged_tp"):
            assert token_streams[tag] == token_streams["continuous_paged"], \
                f"{tag}: sharded tokens diverge from single-device engine"
            print(f"{tag}: tokens identical to single-device continuous "
                  f"engine ({results[tag]:.1f} vs "
                  f"{results['continuous_paged']:.1f} tok/s)")

    ratio = results["continuous_paged"] / results["batch_to_completion"]
    print(f"\ncontinuous vs batch-to-completion: {ratio:.2f}x tokens/s "
          f"({results['continuous_paged']:.1f} vs "
          f"{results['batch_to_completion']:.1f})")
    if smoke:
        assert ratio >= 1.3, (
            f"continuous batching must beat batch-to-completion by >=1.3x "
            f"on the mixed-length smoke workload, got {ratio:.2f}x")
        print("smoke check OK (>= 1.3x)")

    # shared-system-prompt row: cold/warm parity + prefill-token reduction
    prefix_bench(cont_engine, n_requests, smoke)
    # speculative-decoding rows (trains its own small-vocab model)
    if spec:
        spec_bench(slots, n_requests, smoke)
    # fault-injected rows (2-replica router under the canned schedule)
    if chaos:
        chaos_bench(slots, n_requests, max_seq, smoke, trace_path=trace)
    # 8k-context decode A/B (flash page-table decode vs gather)
    if longctx:
        longctx_bench(smoke)
    # vector-quantized KV pages: bytes/token + fixed-pool capacity rows
    if kvq:
        kvq_bench(slots, smoke)
    # observability overhead A/B (< 5% ceiling under --smoke)
    if obs:
        obs_bench(model, params, slots, n_requests, max_seq, smoke)
    return ratio


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU smoke config + 1.3x assertion (CI)")
    ap.add_argument("--sharded", action="store_true",
                    help="add a tensor-parallel engine row over all "
                         "visible devices (token-checked vs single-device)")
    ap.add_argument("--devices", type=int, default=0,
                    help="run on N devices; on a CPU host, N host devices "
                         "(for --sharded on CPU)")
    ap.add_argument("--spec", action="store_true",
                    help="add the speculative-decoding A/B rows (trains a "
                         "small-vocab smoke model first; with --smoke, "
                         "asserts >1.0x + token-identical output)")
    ap.add_argument("--chaos", action="store_true",
                    help="add fault-injected rows: a 2-replica router under "
                         "the canned chaos schedule (with --smoke, asserts "
                         "zero lost requests and >= 90%% goodput)")
    ap.add_argument("--longctx", action="store_true",
                    help="add the 8k-context decode A/B: flash page-table "
                         "decode vs the gather path (with --smoke, asserts "
                         "token-identical chains and >= 1.0x tokens/s)")
    ap.add_argument("--kvq", action="store_true",
                    help="add the vector-quantized KV-page rows: measured "
                         "bytes/token and resident-sequence capacity at a "
                         "fixed pool byte budget (with --smoke, asserts "
                         ">= 4x bytes/token and >= 2x capacity)")
    ap.add_argument("--obs", action="store_true",
                    help="add the observability-overhead A/B row (with "
                         "--smoke, asserts < 5%% tokens/s overhead with "
                         "phase timers and the tracer fully on)")
    ap.add_argument("--trace", default="",
                    help="with --chaos: export the faulted run as "
                         "Chrome/Perfetto trace_event JSON to this path "
                         "(validated; open at ui.perfetto.dev)")
    ap.add_argument("--snapshot", default="",
                    help="write a BENCH_serve.json perf snapshot to this "
                         "path ('auto' = repo root)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    args = ap.parse_args()
    if args.trace and not args.chaos:
        ap.error("--trace requires --chaos (it exports the faulted run)")
    if args.devices:
        # Set before any backend starts: the CPU backend then holds N host
        # devices (an accelerator backend is unaffected). Everything runs
        # in this one process, which keeps every chip it takes.
        jax.config.update("jax_num_cpu_devices", args.devices)
        if jax.device_count() < args.devices:
            raise SystemExit(
                f"--devices {args.devices}: only {jax.device_count()} "
                f"{jax.default_backend()} device(s) visible")
    bench(args.slots, args.requests, args.max_seq, args.smoke, args.sharded,
          args.devices, args.spec, args.chaos, args.longctx, args.kvq,
          args.obs, args.trace)
    if args.snapshot:
        path = args.snapshot
        if path == "auto":
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "BENCH_serve.json")
        snapshot(os.path.normpath(path), bench="serve",
                 smoke=args.smoke, slots=args.slots,
                 requests=args.requests, max_seq=args.max_seq,
                 sharded=bool(args.sharded), spec=bool(args.spec),
                 chaos=bool(args.chaos), longctx=bool(args.longctx),
                 kvq=bool(args.kvq), obs=bool(args.obs))


if __name__ == "__main__":
    main()
