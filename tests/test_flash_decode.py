"""Paged flash-decode kernel: split-KV properties, oracle parity, serving.

Layers of evidence, innermost out:
  1. the split-triple algebra (combine/reduce) is invariant to split
     count and order, matches full softmax, and treats all-masked splits
     as the identity (property tests via hypothesis when available);
  2. the kernel (both the Pallas grid and the XLA "ref" impl), reading
     one layer of a stacked head-major pool at its index, matches the
     full-softmax oracle ``kernels.ref.flash_decode_ref`` AND the legacy
     gather path over that layer's view across
     GQA/window/kv_start/page-boundary/layer grids;
  3. model-level decode chains (dense, lut_infer, gemma-style
     GQA+sliding-window) are token-identical across impls;
  4. the serving engine under pool exhaustion + preemption produces
     token-identical output on the flash path (pages are never read
     after reclaim).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.core.lut import DENSE, QuantConfig
from repro.kernels.flash_decode import (NEG_INF, combine_splits,
                                        flash_decode_paged,
                                        flash_decode_splits, reduce_splits,
                                        resolve_flash_impl)
from repro.kernels.ref import flash_decode_ref
from repro.models.layers import _sdpa_decode_combine
from repro.models.model import Model
from repro.serve import Engine, PageTable, Request
from repro.serve.faults import Fault, FaultInjector, FaultSchedule

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# split-triple algebra (hypothesis property tests)
# ---------------------------------------------------------------------------

def _chunk_triples(s, v, mask, bounds):
    """Per-split (m, l, acc) triples for a 1-D masked softmax problem."""
    triples = []
    for lo, hi in bounds:
        sc, mc, vc = s[lo:hi], mask[lo:hi], v[lo:hi]
        m = np.max(np.where(mc, sc, NEG_INF)) if hi > lo else NEG_INF
        p = np.where(mc, np.exp(sc - m), 0.0)
        triples.append((np.float32(m), np.float32(p.sum()),
                        (p[:, None] * vc).sum(0).astype(np.float32)))
    m, l, acc = (np.stack([t[i] for t in triples]) for i in range(3))
    return jnp.asarray(m), jnp.asarray(l), jnp.asarray(acc)


def _partition(n, pieces, rng):
    cuts = np.sort(rng.choice(np.arange(1, n), size=min(pieces - 1, n - 1),
                              replace=False)) if n > 1 and pieces > 1 else []
    bounds, lo = [], 0
    for c in list(cuts) + [n]:
        bounds.append((lo, int(c)))
        lo = int(c)
    return bounds


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 20), n=st.integers(1, 40),
       pa=st.integers(1, 7), pb=st.integers(1, 7))
def test_split_reduction_count_and_order_invariant(seed, n, pa, pb):
    """Reducing per-split triples gives the same answer for any split
    count and any split order, and matches the oracle softmax."""
    rng = np.random.RandomState(seed)
    s = (rng.randn(n) * 3).astype(np.float32)
    v = rng.randn(n, 4).astype(np.float32)
    mask = rng.rand(n) < 0.7                       # some all-masked splits
    outs = []
    for pieces in (pa, pb):
        m, l, acc = _chunk_triples(s, v, mask, _partition(n, pieces, rng))
        perm = rng.permutation(m.shape[0])         # order invariance
        outs.append(reduce_splits(m[perm], l[perm], acc[perm]))
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    m_t, l_t, acc_t = outs[0]
    assert np.isfinite(np.asarray(l_t)) and np.all(
        np.isfinite(np.asarray(acc_t)))            # never NaN
    if mask.any():
        sm = np.where(mask, s, -np.inf)
        p = np.exp(sm - sm.max())
        oracle = (p[:, None] * v).sum(0) / p.sum()
        np.testing.assert_allclose(np.asarray(acc_t) / np.asarray(l_t),
                                   oracle, rtol=1e-4, atol=1e-5)
    else:                                          # identity, not NaN
        assert float(l_t) == 0.0 and float(m_t) == np.float32(NEG_INF)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 20), n=st.integers(1, 24),
       where=st.integers(0, 8))
def test_all_masked_split_is_identity(seed, n, where):
    """Splicing an all-masked (empty page) split anywhere is a no-op."""
    rng = np.random.RandomState(seed)
    s = (rng.randn(n) * 2).astype(np.float32)
    v = rng.randn(n, 3).astype(np.float32)
    mask = np.ones(n, bool)
    m, l, acc = _chunk_triples(s, v, mask, _partition(n, 3, rng))
    ident = (jnp.full((1,), NEG_INF), jnp.zeros((1,)), jnp.zeros((1, 3)))
    i = where % (m.shape[0] + 1)
    m2 = jnp.concatenate([m[:i], ident[0], m[i:]])
    l2 = jnp.concatenate([l[:i], ident[1], l[i:]])
    acc2 = jnp.concatenate([acc[:i], ident[2], acc[i:]])
    for a, b in zip(reduce_splits(m, l, acc), reduce_splits(m2, l2, acc2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
        assert np.all(np.isfinite(np.asarray(b)))


def test_combine_splits_identity_and_fold():
    """(NEG_INF, 0, 0) is a two-sided identity and pairwise folding
    equals the vectorised reduction."""
    rng = np.random.RandomState(7)
    s = (rng.randn(20) * 3).astype(np.float32)
    v = rng.randn(20, 5).astype(np.float32)
    mask = rng.rand(20) < 0.6
    m, l, acc = _chunk_triples(s, v, mask, _partition(20, 5, rng))
    ident = (jnp.asarray(NEG_INF, jnp.float32), jnp.asarray(0.0),
             jnp.zeros((5,)))
    folded = ident
    for i in range(m.shape[0]):
        folded = combine_splits(folded, (m[i], l[i], acc[i]))
    folded = combine_splits(folded, ident)         # right identity too
    for a, b in zip(folded, reduce_splits(m, l, acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel-level parity (oracle + gather path)
# ---------------------------------------------------------------------------

# layers of the synthetic stacked pool; tests read its first and last
LAYERS = 3


def _mk_case(seed, slots, np_, ps, kvh, g, d, positions):
    """Synthetic stacked head-major pool ``(LAYERS, P+1, KVH, page, D)``
    + page tables honouring the engine invariant (pages covering pos+1
    tokens allocated, rest trash). Every layer holds different rows.
    Physical ids are a permutation — pages are deliberately NOT laid out
    in logical order — and the trash page holds violent garbage so any
    unmasked read is loud."""
    key = jax.random.PRNGKey(seed)
    p1 = slots * np_ + 1
    ks = jax.random.split(key, 5)
    pool = (LAYERS, p1, kvh, ps, d)
    k_pages = jax.random.normal(ks[0], pool, jnp.float32)
    v_pages = jax.random.normal(ks[1], pool, jnp.float32)
    k_pages = k_pages.at[:, -1].set(37.0)
    v_pages = v_pages.at[:, -1].set(-53.0)
    perm = np.random.RandomState(seed).permutation(p1 - 1)
    phys = np.full((slots, np_), p1 - 1, np.int64)
    for b, pos in enumerate(positions):
        n_alloc = min(-(-(int(pos) + 1) // ps), np_) if pos >= 0 else 0
        phys[b, :n_alloc] = perm[b * np_: b * np_ + n_alloc]
    q = jax.random.normal(ks[2], (slots, 1, kvh * g, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (slots, 1, kvh, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (slots, 1, kvh, d), jnp.float32)
    return (q, k_pages, v_pages, k_new, v_new,
            jnp.asarray(phys, jnp.int32), jnp.asarray(positions, jnp.int32))


def _gather_out(q, k_pages, v_pages, k_new, v_new, phys, pos, window,
                kv_start, layer):
    """The legacy path: layer ``layer``'s gathered (B, T, KVH, D) view
    through ``layers._sdpa_decode_combine``."""
    slots, np_ = phys.shape
    kvh, ps, d = k_pages.shape[2:]

    def view(pool):
        return jnp.swapaxes(pool[layer][phys], 2, 3).reshape(
            slots, np_ * ps, kvh, d)
    return _sdpa_decode_combine(q, view(k_pages), view(v_pages), k_new,
                                v_new, pos, window, kv_start=kv_start)


# positions: exactly on a page boundary (16), one past it (17), mid-page
# (9), and an inactive lane (-1).
_POSITIONS = [16, 17, 9, -1]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("kvh,g", [(2, 1), (2, 3)])         # MHA and GQA
@pytest.mark.parametrize("window,kv_start", [(0, 0), (11, 0), (0, 5),
                                             (11, 5)])
def test_flash_matches_oracle_and_gather(impl, kvh, g, window, kv_start,
                                         layer):
    q, kp, vp, kn, vn, phys, pos = _mk_case(
        seed=3, slots=4, np_=4, ps=8, kvh=kvh, g=g, d=16,
        positions=_POSITIONS)
    out = flash_decode_paged(q, kp, vp, kn, vn, phys, pos, layer=layer,
                             window=window, kv_start=kv_start, impl=impl,
                             interpret=True)
    oracle = flash_decode_ref(q, kp, vp, kn, vn, phys, pos, window,
                              kv_start, layer=layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)
    gather = _gather_out(q, kp, vp, kn, vn, phys, pos, window, kv_start,
                         layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gather),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_flash_single_slot_batch(impl):
    """B=1 decode (the other batch-shape extreme of the grid)."""
    q, kp, vp, kn, vn, phys, pos = _mk_case(
        seed=11, slots=1, np_=4, ps=8, kvh=2, g=2, d=16, positions=[24])
    out = flash_decode_paged(q, kp, vp, kn, vn, phys, pos, layer=1,
                             impl=impl, interpret=True)
    oracle = flash_decode_ref(q, kp, vp, kn, vn, phys, pos, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sp", [1, 2, 4])
def test_paged_split_count_invariance(sp):
    """flash_decode_splits reduces to the same triple for every
    page-aligned split size (trash-padding included)."""
    q, kp, vp, kn, vn, phys, pos = _mk_case(
        seed=5, slots=3, np_=4, ps=8, kvh=2, g=2, d=16,
        positions=[16, 31, -1])
    b, _, h, d = q.shape
    qg = q.reshape(b, 2, 2, d) * d ** -0.5
    win = jnp.asarray(0, jnp.int32)
    ks = jnp.zeros((b,), jnp.int32)
    pad = (-phys.shape[1]) % sp
    phys_p = jnp.pad(phys, ((0, 0), (0, pad)),
                     constant_values=kp.shape[1] - 1)
    kp, vp = kp[0], vp[0]
    got = reduce_splits(*flash_decode_splits(qg, kp, vp, phys_p, pos, win,
                                             ks, sp))
    want = reduce_splits(*flash_decode_splits(qg, kp, vp, phys, pos, win,
                                              ks, phys.shape[1]))
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


def test_trash_page_contents_never_attended():
    """Changing what the trash page holds must not change any output —
    the redirection proof for unallocated pages."""
    q, kp, vp, kn, vn, phys, pos = _mk_case(
        seed=9, slots=3, np_=4, ps=8, kvh=2, g=2, d=16,
        positions=[9, 16, -1])
    for impl in ("ref", "pallas"):
        a = flash_decode_paged(q, kp, vp, kn, vn, phys, pos, impl=impl,
                               interpret=True)
        b = flash_decode_paged(q, kp.at[:, -1].set(-1e4),
                               vp.at[:, -1].set(1e4), kn, vn, phys, pos,
                               impl=impl, interpret=True)
        live = np.asarray(pos) >= 0
        np.testing.assert_array_equal(np.asarray(a)[live],
                                      np.asarray(b)[live])


def test_resolve_flash_impl():
    assert resolve_flash_impl("auto", on_tpu=True) == "pallas"
    assert resolve_flash_impl("auto", on_tpu=False) == "gather"
    assert resolve_flash_impl("ref") == "ref"
    with pytest.raises(ValueError):
        resolve_flash_impl("nope")


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_flash_8k_context_parity(impl):
    """8k-token heavy: long-context parity at a realistic page count."""
    ps, np_ = 16, 512                                 # 8192 tokens / slot
    q, kp, vp, kn, vn, phys, pos = _mk_case(
        seed=17, slots=2, np_=np_, ps=ps, kvh=2, g=2, d=32,
        positions=[8191, 5000])
    out = flash_decode_paged(q, kp, vp, kn, vn, phys, pos, window=0,
                             impl=impl, interpret=True)
    oracle = flash_decode_ref(q, kp, vp, kn, vn, phys, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# model-level decode chains
# ---------------------------------------------------------------------------

def _chain_parity(cfg, qc_base, params=None, steps=3, lens=(9, 16)):
    """Greedy decode chains must be token-identical across flash impls
    (logits fp32-close); returns the max logits delta seen."""
    m = Model(cfg)
    if params is None:
        params = m.init(KEY, qc_base)
    slots, max_seq, ps = len(lens), 32, 8
    pt = PageTable(num_slots=slots, max_seq=max_seq, page_size=ps)
    kv = m.init_paged_cache(slots, max_seq, ps, pt.allocator.num_pages)
    for slot, n in enumerate(lens):
        pt.ensure(slot, n + steps + 1)
        toks = jnp.asarray(np.arange(2, 2 + n)[None] % cfg.vocab_size,
                           jnp.int32)
        toks = jnp.pad(toks, ((0, 0), (0, 16 - n)), constant_values=1)
        _, kv = m.prefill_paged(params, toks, kv, pt.device(), slot, 0, n,
                                qc_base)
    impls = ("gather", "ref", "pallas")
    kvs = {i: jax.tree_util.tree_map(lambda t: t, kv) for i in impls}
    qcs = {i: qc_base.replace(flash=i) for i in impls}
    tok = jnp.asarray([[5]] * slots, jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    worst = 0.0
    for step in range(steps):
        logits = {}
        for i in impls:
            logits[i], kvs[i] = m.decode_paged(
                params, tok, kvs[i], pt.device(), pos + step, qcs[i])
        for i in impls[1:]:
            assert bool(jnp.all(logits["gather"].argmax(-1)
                                == logits[i].argmax(-1))), (i, step)
            worst = max(worst, float(jnp.max(jnp.abs(
                logits["gather"] - logits[i]))))
        tok = jnp.asarray(logits["gather"].argmax(-1)[:, None], jnp.int32)
    return worst


def test_chain_parity_dense():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    assert _chain_parity(cfg, DENSE) < 1e-4


def test_chain_parity_lut_infer():
    from repro.core import precompute_model
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    qc_t = QuantConfig(mode="lut_train")
    m = Model(cfg)
    params = precompute_model(m.init(KEY, qc_t), qc_t.replace(
        mode="lut_infer"))
    assert _chain_parity(cfg, qc_t.replace(mode="lut_infer"),
                         params=params) < 1e-4


def test_chain_parity_gqa_sliding_window():
    """gemma-style config: q-heads > kv-heads AND per-layer sliding
    windows — the GQA tile mapping and window masks together."""
    cfg = get_smoke_config("gemma3-27b").replace(attn_impl="naive")
    assert cfg.num_heads > cfg.num_kv_heads and cfg.sliding_window > 0
    assert _chain_parity(cfg, DENSE) < 1e-4


# ---------------------------------------------------------------------------
# serving engine: exhaustion + preemption (satellite: recovery parity)
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


def test_flash_engine_matches_gather_engine(qwen):
    """Plain mixed-length run: flash-ref engine output == gather engine
    output, request for request."""
    m, params = qwen
    outs = {}
    for flash in ("gather", "ref"):
        reqs = [Request(tokens=[3, 4, 5], max_new_tokens=8),
                Request(tokens=list(range(2, 13)), max_new_tokens=6),
                Request(tokens=[7, 8], max_new_tokens=10)]
        _mk_engine(m, params, qc=DENSE.replace(flash=flash)).run(reqs)
        assert all(r.done for r in reqs)
        outs[flash] = [r.out_tokens for r in reqs]
    assert outs["gather"] == outs["ref"]


def test_flash_engine_pallas_smoke(qwen):
    """The Pallas kernel (interpret mode on CPU) inside the live engine."""
    m, params = qwen
    outs = {}
    for flash in ("gather", "pallas"):
        reqs = [Request(tokens=[3, 4, 5], max_new_tokens=3),
                Request(tokens=[6, 7], max_new_tokens=3)]
        _mk_engine(m, params, qc=DENSE.replace(flash=flash)).run(reqs)
        outs[flash] = [r.out_tokens for r in reqs]
    assert outs["gather"] == outs["pallas"]


def test_flash_engine_exhaustion_preemption_recovery(qwen):
    """PagePoolExhausted + preemption mid-decode on the flash path: an
    undersized pool (preemption pressure) plus an injected pool squeeze
    must still produce token-identical output to the gather path — the
    kernel never reads a reclaimed page."""
    m, params = qwen
    outs = {}
    for flash in ("gather", "ref"):
        reqs = [Request(tokens=[3, 4, 5], max_new_tokens=20),
                Request(tokens=[6, 7, 8], max_new_tokens=20)]
        eng = _mk_engine(m, params, qc=DENSE.replace(flash=flash),
                         num_pages=5)
        inj = FaultInjector(FaultSchedule(
            [Fault(step=4, kind="pool_exhaust", replica=0,
                   duration=3)])).attach(eng)
        eng.run(reqs)
        assert all(r.done and len(r.out_tokens) == 20 for r in reqs)
        assert inj.report()["by_kind"].get("pool_exhaust", 0) >= 1
        outs[flash] = [r.out_tokens for r in reqs]
    assert outs["gather"] == outs["ref"]
