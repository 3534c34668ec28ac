"""Continuous-batching engine: scheduler, paged cache, parity, sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import precompute_model
from repro.core.lut import DENSE, QuantConfig
from repro.models.model import Model
from repro.serve import (Engine, PageAllocator, PagePoolExhausted,
                         PagedKVCache, PageTable, ReplicaRouter, Request,
                         SlotScheduler)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# host-side units: allocator + page table (no model, no device compute)
# ---------------------------------------------------------------------------

def test_page_allocator_exhaustion_is_clean():
    a = PageAllocator(3)
    got = a.alloc(2)
    assert len(got) == 2 and a.available == 1
    with pytest.raises(PagePoolExhausted) as ei:
        a.alloc(2)
    assert "2 page(s)" in str(ei.value) and "1 of 3" in str(ei.value)
    assert a.available == 1          # failed alloc took nothing
    a.free(got)
    assert a.available == 3


def test_page_table_grow_release_reuse():
    pt = PageTable(num_slots=2, max_seq=32, page_size=8)   # 4 pages/slot
    pt.ensure(0, 9)                  # 2 pages
    pt.ensure(1, 1)                  # 1 page
    assert pt.live_pages == 3
    assert (pt.table[0, :2] >= 0).all() and pt.table[0, 2] == -1
    dev = np.asarray(pt.device())
    assert dev.shape == (2, 4)
    pt.ensure(0, 9)                  # idempotent
    assert pt.live_pages == 3
    pt.release(0)
    assert pt.live_pages == 1 and (pt.table[0] == -1).all()
    pt.ensure(0, 32)                 # freed pages are reusable
    assert pt.live_pages == 5
    with pytest.raises(PagePoolExhausted):
        pt.ensure(1, 33)             # beyond max_seq


def test_scheduler_admission_is_fifo_and_page_aware():
    cfg = get_smoke_config("qwen1.5-4b")
    m = Model(cfg)
    kv = PagedKVCache(m, num_slots=2, max_seq=32, page_size=8, num_pages=3)
    sched = SlotScheduler(2)
    sched.submit(Request(tokens=list(range(16))))   # 2 pages
    sched.submit(Request(tokens=list(range(8))))    # 1 page
    sched.submit(Request(tokens=list(range(8))))    # must wait
    admitted = sched.admit(kv)
    assert [s.idx for s in admitted] == [0, 1]
    assert kv.live_pages == 3 and len(sched.waiting) == 1
    assert sched.admit(kv) == []                    # pool full -> deferred
    sched.evict(admitted[1], kv)                    # slot frees mid-flight
    again = sched.admit(kv)                         # admitted immediately
    assert [s.idx for s in again] == [1]
    assert len(sched.waiting) == 0


# ---------------------------------------------------------------------------
# engine-level behaviour (smoke model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    return m, m.init(KEY, DENSE)


def _mk_engine(m, params, qc=DENSE, slots=2, **kw):
    kw.setdefault("max_seq", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return Engine(m, params, qc, batch_size=slots, **kw)


def test_admission_mid_decode_and_isolation(qwen):
    """5 requests through 2 slots with mixed budgets: late requests are
    admitted as earlier ones finish mid-decode, and every request's greedy
    output matches its solo run."""
    m, params = qwen
    budgets = [2, 9, 3, 2, 4]
    reqs = [Request(tokens=[i + 2, i + 3], max_new_tokens=n)
            for i, n in enumerate(budgets)]
    _mk_engine(m, params).run(reqs)
    assert all(r.done and len(r.out_tokens) == r.max_new_tokens
               for r in reqs)
    for i, n in enumerate(budgets):
        solo = Request(tokens=[i + 2, i + 3], max_new_tokens=n)
        _mk_engine(m, params, slots=1).run([solo])
        assert reqs[i].out_tokens == solo.out_tokens


def test_eviction_on_eos_frees_slot(qwen):
    m, params = qwen
    probe = Request(tokens=[5, 6, 7], max_new_tokens=8)
    _mk_engine(m, params).run([probe])
    eos = probe.out_tokens[2]
    req = Request(tokens=[5, 6, 7], max_new_tokens=8)
    eng = _mk_engine(m, params, eos_id=eos)
    eng.run([req])
    stop = probe.out_tokens.index(eos)
    assert req.out_tokens == probe.out_tokens[:stop + 1]
    assert req.done
    assert eng.kv.live_pages == 0            # pages returned on eviction
    assert all(s.free for s in eng.scheduler.slots)


def test_impossible_request_raises_cleanly(qwen):
    m, params = qwen
    eng = _mk_engine(m, params)
    with pytest.raises(PagePoolExhausted) as ei:
        eng.run([Request(tokens=list(range(40)), max_new_tokens=2)])
    assert "max_seq" in str(ei.value)


def test_oversized_request_rejected_at_submit_not_mid_run(qwen):
    """An unservable request is refused at submit() — it must not abort a
    run with valid requests already queued."""
    m, params = qwen
    eng = _mk_engine(m, params)
    good = Request(tokens=[2, 3], max_new_tokens=3)
    eng.submit(good)
    with pytest.raises(PagePoolExhausted):
        eng.submit(Request(tokens=list(range(40)), max_new_tokens=2))
    eng.run_until_idle()
    assert good.done and len(good.out_tokens) == 3


def test_oversubscribed_pool_defers_then_completes(qwen):
    """Pool holds ~1.5 sequences for 2 slots: the engine preempts/defers
    but still completes everything, identical to solo runs."""
    m, params = qwen
    reqs = [Request(tokens=[3, 4, 5], max_new_tokens=20),
            Request(tokens=[6, 7, 8], max_new_tokens=20)]
    _mk_engine(m, params, num_pages=5).run(reqs)
    assert all(r.done and len(r.out_tokens) == 20 for r in reqs)
    for r in reqs:
        solo = Request(tokens=list(r.tokens), max_new_tokens=20)
        _mk_engine(m, params, slots=1).run([solo])
        assert r.out_tokens == solo.out_tokens


def test_decode_can_preempt_prefilling_neighbour(qwen):
    """Exhaustion while the only other occupied slot is still PREFILLING
    must preempt it (not crash): slot A decodes across a page boundary
    with zero free pages while slot B holds 3 pages mid-prefill."""
    m, params = qwen
    a = Request(tokens=[2, 3, 4, 5, 6, 7], max_new_tokens=20)
    b = Request(tokens=list(range(2, 26)), max_new_tokens=4)   # 24-tok prompt
    eng = _mk_engine(m, params, num_pages=5)
    eng.run([a, b])
    assert a.done and len(a.out_tokens) == 20
    assert b.done and len(b.out_tokens) == 4
    for r in (a, b):
        solo = Request(tokens=list(r.tokens),
                       max_new_tokens=r.max_new_tokens)
        _mk_engine(m, params, slots=1).run([solo])
        assert r.out_tokens == solo.out_tokens


def test_mamba2_long_prefill_next_to_decode_is_isolated():
    """ssm: decode steps must not clobber the recurrent state of a slot
    that is mid-prefill (states of non-decoding lanes are kept)."""
    cfg = get_smoke_config("mamba2-2.7b").replace(attn_impl="naive")
    m = Model(cfg)
    params = m.init(KEY, DENSE)
    a = Request(tokens=[2, 3, 4], max_new_tokens=12)
    b = Request(tokens=list(range(2, 22)), max_new_tokens=4)  # 5 chunks
    _mk_engine(m, params).run([a, b])
    for r in (a, b):
        solo = Request(tokens=list(r.tokens),
                       max_new_tokens=r.max_new_tokens)
        _mk_engine(m, params, slots=1).run([solo])
        assert r.out_tokens == solo.out_tokens


def test_hybrid_long_prefill_next_to_decode_is_isolated():
    """zamba2 (hybrid): a multi-chunk prefill running next to a decoding
    slot must not be corrupted by the decode steps (non-decoding lanes
    write to the trash row of the slot-dense shared-attn cache)."""
    cfg = get_smoke_config("zamba2-1.2b").replace(attn_impl="naive")
    m = Model(cfg)
    params = m.init(KEY, DENSE)
    a = Request(tokens=[2, 3, 4], max_new_tokens=12)
    b = Request(tokens=list(range(2, 22)), max_new_tokens=4)  # 5 chunks
    _mk_engine(m, params).run([a, b])
    for r in (a, b):
        solo = Request(tokens=list(r.tokens),
                       max_new_tokens=r.max_new_tokens)
        _mk_engine(m, params, slots=1).run([solo])
        assert r.out_tokens == solo.out_tokens


def test_full_length_prompt_truncates_instead_of_crashing(qwen):
    """A prompt of exactly max_seq is servable: one token is generated and
    the request is evicted as truncated (the re-admission path after a
    preemption can legitimately present this boundary)."""
    m, params = qwen
    req = Request(tokens=list(range(2, 34)), max_new_tokens=8)   # 32 == max_seq
    eng = _mk_engine(m, params)
    eng.run([req])
    assert req.done and len(req.out_tokens) == 1
    assert eng.kv.live_pages == 0


def test_pool_outgrowth_truncates_without_aborting_run(qwen):
    """A request whose generation outgrows an undersized pool (2 pages =
    16 tokens, prompt 16, no preemptable neighbour) finishes as truncated
    — it must not abort the run or lose the other request."""
    m, params = qwen
    a = Request(tokens=list(range(2, 18)), max_new_tokens=12)  # 2 full pages
    b = Request(tokens=[3, 4], max_new_tokens=3)
    eng = _mk_engine(m, params, num_pages=2)
    eng.run([a, b])
    assert a.done and 1 <= len(a.out_tokens) < 12   # truncated at capacity
    assert b.done and len(b.out_tokens) == 3
    assert eng.kv.live_pages == 0


def test_batch_engine_truncates_at_max_seq(qwen):
    """BatchToCompletionEngine must stop decoding when the cache is full
    instead of letting clamped writes corrupt the last row: the tokens it
    does emit match a run with ample cache."""
    from repro.serve import BatchToCompletionEngine
    m, params = qwen
    big = Request(tokens=list(range(2, 14)), max_new_tokens=12)
    BatchToCompletionEngine(m, params, DENSE, batch_size=1,
                            max_seq=64).run([big])
    small = Request(tokens=list(range(2, 14)), max_new_tokens=12)
    BatchToCompletionEngine(m, params, DENSE, batch_size=1,
                            max_seq=16).run([small])
    n = len(small.out_tokens)
    assert 0 < n < 12                      # truncated
    assert small.out_tokens == big.out_tokens[:n]


def test_identical_hot_requests_diverge(qwen):
    """Per-slot PRNG keys: two identical temperature>0 requests sharing a
    decode batch must not sample identical sequences."""
    m, params = qwen
    a = Request(tokens=[4, 5, 6], max_new_tokens=12, temperature=1.5)
    b = Request(tokens=[4, 5, 6], max_new_tokens=12, temperature=1.5)
    _mk_engine(m, params).run([a, b])
    assert len(a.out_tokens) == len(b.out_tokens) == 12
    assert a.out_tokens != b.out_tokens


def test_no_per_step_temperature_upload(qwen):
    """The decode loop must NOT rebuild and re-upload the per-slot temps
    array every step: the device buffer is refreshed only on admission /
    eviction (regression for the host->device churn the batch engine
    already avoided)."""
    m, params = qwen
    reqs = [Request(tokens=[4, 5, 6], max_new_tokens=12, temperature=1.2),
            Request(tokens=[5, 6, 7], max_new_tokens=12, temperature=0.8)]
    eng = _mk_engine(m, params)
    eng.run(reqs)
    assert all(len(r.out_tokens) == 12 for r in reqs)
    decode_steps = max(len(r.out_tokens) for r in reqs)
    # one upload after the admissions; evictions only zero the buffer
    assert eng.temps_uploads <= 2 < decode_steps
    # and after eviction the buffer is all-greedy again (no stale temps
    # forcing the PRNG path for the next occupant)
    assert not (eng._temps_h > 0).any()


def test_batch_engine_stamps_latency_fields(qwen):
    """BatchToCompletionEngine must stamp first_token_step / finish_step so
    A/B latency comparisons against the continuous engine don't crash on
    None (the fields Request documents)."""
    from repro.serve import BatchToCompletionEngine
    m, params = qwen
    reqs = [Request(tokens=[3, 4, 5], max_new_tokens=2, arrival=0),
            Request(tokens=[6, 7], max_new_tokens=6, arrival=0)]
    eng = BatchToCompletionEngine(m, params, DENSE, batch_size=2, max_seq=32)
    eng.run(reqs)
    for r in reqs:
        assert r.first_token_step is not None and r.finish_step is not None
        # TTFT/latency arithmetic like serve_demo's report() must work
        assert r.finish_step - r.arrival >= r.first_token_step - r.arrival > 0
    # head-of-line blocking is visible in the stamps: the short request
    # finishes earlier than the long one, both monotone in the step clock
    assert reqs[0].finish_step <= reqs[1].finish_step
    # truncation path stamps too
    trunc = Request(tokens=list(range(2, 14)), max_new_tokens=30)
    BatchToCompletionEngine(m, params, DENSE, batch_size=1,
                            max_seq=16).run([trunc])
    assert trunc.done and trunc.finish_step is not None


def test_replica_router_least_loaded_dispatch_and_parity(qwen):
    """Host-level DP: two single-device replicas serve interleaved requests
    with per-request outputs identical to solo runs, and the oversized /
    oversubscription behaviour matches a single engine per replica."""
    m, params = qwen
    router = ReplicaRouter([_mk_engine(m, params, slots=1),
                            _mk_engine(m, params, slots=1)])
    reqs = [Request(tokens=[i + 2, i + 3], max_new_tokens=4)
            for i in range(4)]
    used = {id(router.submit(r)) for r in reqs}
    assert len(used) == 2                      # least-loaded spreads work
    router.run_until_idle()
    for r in reqs:
        solo = Request(tokens=list(r.tokens), max_new_tokens=4)
        _mk_engine(m, params, slots=1).run([solo])
        assert r.out_tokens == solo.out_tokens
    with pytest.raises(PagePoolExhausted):     # per-replica admissibility
        router.submit(Request(tokens=list(range(40)), max_new_tokens=2))


def test_greedy_unaffected_by_hot_neighbour(qwen):
    m, params = qwen
    solo = Request(tokens=[7, 8, 9], max_new_tokens=6)
    _mk_engine(m, params, slots=1).run([solo])
    hot = Request(tokens=[1, 2, 3], max_new_tokens=6, temperature=2.0)
    greedy = Request(tokens=[7, 8, 9], max_new_tokens=6)
    _mk_engine(m, params).run([hot, greedy])
    assert greedy.out_tokens == solo.out_tokens


# ---------------------------------------------------------------------------
# decode == forward parity through the paged cache
# ---------------------------------------------------------------------------

def _paged_parity(name, qc, params_fn):
    """Chunked paged prefill + per-slot paged decode must reproduce the
    full-sequence forward logits."""
    cfg = get_smoke_config(name).replace(attn_impl="naive")
    m = Model(cfg)
    params = params_fn(m)
    B, S, PRE = 1, 12, 7
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    logits_full, _ = m.forward(params, {"tokens": toks}, qc)

    eng = Engine(m, params, qc, batch_size=2, max_seq=32, page_size=8,
                 prefill_chunk=4)
    eng.kv.ensure(0, PRE)
    pt = eng.kv.table_device()
    kv = eng.kv.data
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    for lo in range(0, PRE, 4):
        hi = min(lo + 4, PRE)
        t = np.zeros((1, 4), np.int32)
        t[0, :hi - lo] = np.asarray(toks)[0, lo:hi]
        lg, kv = m.prefill_paged(params, jnp.asarray(t), kv, pt,
                                 i32(0), i32(lo), i32(hi - lo), qc)
    np.testing.assert_allclose(np.asarray(lg)[0],
                               np.asarray(logits_full)[0, PRE - 1],
                               rtol=5e-3, atol=5e-3)
    for t_i in range(PRE, S):
        eng.kv.ensure(0, t_i + 1)
        pt = eng.kv.table_device()
        tk = np.zeros((2, 1), np.int32)
        tk[0, 0] = int(np.asarray(toks)[0, t_i])
        pos = np.zeros((2,), np.int32)
        pos[0] = t_i
        lg, kv = m.decode_paged(params, jnp.asarray(tk), kv, pt,
                                jnp.asarray(pos), qc)
        np.testing.assert_allclose(np.asarray(lg)[0],
                                   np.asarray(logits_full)[0, t_i],
                                   rtol=5e-3, atol=5e-3)


def test_paged_parity_dense_attention():
    _paged_parity("qwen1.5-4b", DENSE, lambda m: m.init(KEY, DENSE))


@pytest.mark.slow
def test_paged_parity_mamba2():
    _paged_parity("mamba2-2.7b", DENSE, lambda m: m.init(KEY, DENSE))


@pytest.mark.slow
def test_paged_parity_lut_infer():
    qc_t = QuantConfig(mode="lut_train", v=4, c=8)
    qc_i = QuantConfig(mode="lut_infer", v=4, c=8, impl="ref")

    def mk(m):
        return precompute_model(m.init(KEY, qc_t), qc_i)
    _paged_parity("qwen1.5-4b", qc_i, mk)


def test_engine_refuses_lut_infer_without_tables():
    """Serving never falls back to rebuilding each table per call: params
    without precomputed LUTs are refused until precompute_model runs."""
    cfg = get_smoke_config("qwen1.5-4b")
    m = Model(cfg)
    qc_i = QuantConfig(mode="lut_infer", v=4, c=8, impl="ref")
    params = m.init(KEY, QuantConfig(mode="lut_train", v=4, c=8))
    with pytest.raises(ValueError, match="precompute_model"):
        Engine(m, params, qc_i, batch_size=2, max_seq=32)
    Engine(m, precompute_model(params, qc_i), qc_i, batch_size=2,
           max_seq=32)


def test_lut_infer_engine_matches_dense_cache_engine():
    """End-to-end: continuous engine (paged) == batch engine (dense cache)
    for greedy decoding on the lut_infer path."""
    from repro.serve import BatchToCompletionEngine
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    qc_t = QuantConfig(mode="lut_train", v=4, c=8)
    qc_i = QuantConfig(mode="lut_infer", v=4, c=8, impl="ref")
    params = precompute_model(m.init(KEY, qc_t), qc_i)
    a = Request(tokens=[3, 4, 5, 6], max_new_tokens=6)
    b = Request(tokens=[3, 4, 5, 6], max_new_tokens=6)
    Engine(m, params, qc_i, batch_size=2, max_seq=32,
           prefill_chunk=4, page_size=8).run([a])
    BatchToCompletionEngine(m, params, qc_i, batch_size=2,
                            max_seq=32).run([b])
    assert a.out_tokens == b.out_tokens
