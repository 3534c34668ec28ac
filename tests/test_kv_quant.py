"""Vector-quantized KV-cache pages: codebook laws, kernel parity, serving.

Layers of evidence, innermost out:
  1. codebook algebra — encode/decode round-trip error IS the nearest-
     centroid distance (property tests), codes are in-range uint8 with
     ``nc * v == head_dim``, and a row that sits on a centroid round-
     trips bit-identical (``from_rows`` builds exactly that situation
     for a whole run's row set);
  2. the quantized kernels — the LUT-accumulate "ref" impl and the
     dequant-in-VMEM Pallas grid — match the dequantize-then-reference
     oracle ``kernels.ref.flash_decode_kvq_ref`` across GQA / window /
     kv_start / page-boundary / inactive-lane / layer grids, each
     reading one layer of a stacked head-major code pool at its index;
  3. model-level decode chains over a quantized pool are token-identical
     across the gather / ref / pallas read paths, for dense and
     ``lut_infer`` weights (both lossy paths stacked) and for gemma-style
     GQA + sliding window;
  4. the serving engine with ``kv_quant="vq"``: prefix-cache warm==cold
     parity (the cache identifies CODES, salted by the codebook
     fingerprint), CoW forks preserve codes, speculative rollback keeps
     refcount == mapped rows after every step, the chaos schedule loses
     zero requests, and admission accounting reports real bytes.
"""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.core import precompute_model
from repro.core.kv_codebook import (CODEBOOK_KEY, KVCodebook,
                                    codebook_from_tree, kv_decode, kv_encode)
from repro.core.lut import DENSE, QuantConfig
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.ref import flash_decode_kvq_ref
from repro.models.model import Model
from repro.serve import (Engine, FaultInjector, FaultSchedule, FinishReason,
                         PageTable, ReplicaRouter, Request, SpecConfig)
from repro.serve.kv_cache import _chunk_keys

KEY = jax.random.PRNGKey(0)
KVQ = DENSE.replace(kv_quant="vq")


# ---------------------------------------------------------------------------
# codebook algebra (hypothesis property tests)
# ---------------------------------------------------------------------------

def _rand_layer_codebook(rng, nc, c, v, kvh):
    z = jnp.asarray(rng.randn(nc, c, v), jnp.float32)
    s = jnp.asarray(np.abs(rng.randn(kvh)) + 0.5, jnp.float32)
    return z, s


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 20), kvh=st.integers(1, 3),
       nc=st.integers(1, 4), c=st.integers(2, 12), v=st.integers(1, 4),
       t=st.integers(1, 6))
def test_roundtrip_error_is_nearest_centroid_distance(seed, kvh, nc, c, v, t):
    """decode(encode(x)) lands on the nearest centroid — per subspace the
    reconstruction error equals min-over-centroids distance (in the
    scale-normalised space the assignment runs in), so the round-trip
    error is bounded by the codebook covering radius by construction."""
    rng = np.random.RandomState(seed)
    z, s = _rand_layer_codebook(rng, nc, c, v, kvh)
    rows = jnp.asarray(rng.randn(t, kvh, nc * v) * 2, jnp.float32)
    codes = kv_encode(rows, z, s)
    rec = kv_decode(codes, z, s)
    x = np.asarray(rows / s[:, None]).reshape(t, kvh, nc, v)
    r = np.asarray(rec / s[:, None]).reshape(t, kvh, nc, v)
    # distance of every subvector to every centroid, then the min
    d = np.linalg.norm(x[..., None, :] - np.asarray(z)[None, None], axis=-1)
    got = np.linalg.norm(x - r, axis=-1)
    np.testing.assert_allclose(got, d.min(-1), rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 20), kvh=st.integers(1, 3),
       nc=st.integers(1, 4), c=st.integers(2, 16), v=st.integers(1, 4))
def test_codes_uint8_in_range_and_shape_algebra(seed, kvh, nc, c, v):
    rng = np.random.RandomState(seed)
    z, s = _rand_layer_codebook(rng, nc, c, v, kvh)
    rows = jnp.asarray(rng.randn(5, kvh, nc * v), jnp.float32)
    codes = kv_encode(rows, z, s)
    assert codes.dtype == jnp.uint8
    assert codes.shape == (5, kvh, nc)
    assert int(codes.max()) < c
    rec = kv_decode(codes, z, s)
    assert rec.shape == rows.shape and rec.dtype == jnp.float32


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 20), nc=st.integers(1, 3),
       c=st.integers(2, 8), v=st.integers(1, 4))
def test_centroid_rows_roundtrip_bit_identical(seed, nc, c, v):
    """A row assembled FROM centroids (unit scale) must encode to those
    centroids' indices and decode back bit-identical — quantize-of-
    centroid is exact, the germ of the from_rows identity tests."""
    rng = np.random.RandomState(seed)
    z = jnp.asarray(rng.randn(nc, c, v), jnp.float32)
    s = jnp.ones((1,), jnp.float32)
    idx = rng.randint(0, c, size=(4, 1, nc))
    rows = np.asarray(z)[np.arange(nc), idx].reshape(4, 1, nc * v)
    codes = kv_encode(jnp.asarray(rows), z, s)
    np.testing.assert_array_equal(np.asarray(codes), idx.astype(np.uint8))
    rec = kv_decode(codes, z, s)
    np.testing.assert_array_equal(np.asarray(rec), rows)


def test_from_rows_exact_cover_roundtrip_and_bounds():
    rng = np.random.RandomState(3)
    l, t, kvh, hd = 2, 5, 3, 16
    rows_k = jnp.asarray(rng.randn(l, t, kvh, hd), jnp.float32)
    rows_v = jnp.asarray(rng.randn(l, t, kvh, hd), jnp.float32)
    cb = KVCodebook.from_rows(rows_k, rows_v)
    assert (cb.nc, cb.c, cb.v) == (1, t * kvh, hd)
    for which, rows in (("k", rows_k), ("v", rows_v)):
        rec = cb.decode(cb.encode(rows, which), which)
        np.testing.assert_array_equal(np.asarray(rec), np.asarray(rows))
    with pytest.raises(ValueError, match="exact-cover"):
        KVCodebook.from_rows(jnp.zeros((1, 130, 2, 8)),
                             jnp.zeros((1, 130, 2, 8)))


def test_codebook_validation_and_fingerprint():
    z = jnp.zeros((2, 4, 300, 4))
    with pytest.raises(ValueError, match="uint8"):
        KVCodebook(zk=z, zv=z, sk=jnp.ones((2, 2)), sv=jnp.ones((2, 2)))
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(2, 6, 2, 8), jnp.float32)
    cb = KVCodebook.fit(rows, rows + 0.5, v=4, c=4, iters=2, key=KEY)
    assert cb.head_dim == 8 and cb.equivalent_bits == pytest.approx(0.5)
    assert cb.fingerprint() == codebook_from_tree(cb.tree()).fingerprint()
    cb2 = KVCodebook.fit(rows + 1.0, rows, v=4, c=4, iters=2, key=KEY)
    assert cb.fingerprint() != cb2.fingerprint()


# ---------------------------------------------------------------------------
# kernel-level parity vs the dequantize-then-reference oracle
# ---------------------------------------------------------------------------

# layers of the synthetic stacked code pool; tests read its first and last
LAYERS = 3


def _mk_kvq_case(seed, slots, np_, ps, kvh, g, d, positions, nc=4, c=16):
    """Synthetic stacked head-major quantized pool ``(LAYERS, P+1, KVH,
    page, nc)`` mirroring test_flash_decode._mk_case: permuted physical
    pages, in-range random codes, a random stacked codebook (one per
    layer) with non-trivial per-head scales, fp q/k_new/v_new."""
    key = jax.random.PRNGKey(seed)
    rng = np.random.RandomState(seed)
    p1 = slots * np_ + 1
    v = d // nc
    ks = jax.random.split(key, 5)
    pool = (LAYERS, p1, kvh, ps, nc)
    kc = jnp.asarray(rng.randint(0, c, pool), jnp.uint8)
    vc = jnp.asarray(rng.randint(0, c, pool), jnp.uint8)
    scale = lambda: jnp.asarray(np.abs(rng.randn(LAYERS, kvh)) + 0.5,
                                jnp.float32)
    cb = {"zk": jax.random.normal(ks[0], (LAYERS, nc, c, v), jnp.float32),
          "zv": jax.random.normal(ks[1], (LAYERS, nc, c, v), jnp.float32),
          "sk": scale(), "sv": scale()}
    perm = rng.permutation(p1 - 1)
    phys = np.full((slots, np_), p1 - 1, np.int64)
    for b, pos in enumerate(positions):
        n_alloc = min(-(-(int(pos) + 1) // ps), np_) if pos >= 0 else 0
        phys[b, :n_alloc] = perm[b * np_: b * np_ + n_alloc]
    q = jax.random.normal(ks[2], (slots, 1, kvh * g, d), jnp.float32)
    k_new = jax.random.normal(ks[3], (slots, 1, kvh, d), jnp.float32)
    v_new = jax.random.normal(ks[4], (slots, 1, kvh, d), jnp.float32)
    return (q, kc, vc, cb, k_new, v_new,
            jnp.asarray(phys, jnp.int32), jnp.asarray(positions, jnp.int32))


# page boundary (16), one past (17), mid-page (9), inactive lane (-1)
_POSITIONS = [16, 17, 9, -1]


@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("kvh,g", [(2, 1), (2, 3)])          # MHA and GQA
@pytest.mark.parametrize("window,kv_start", [(0, 0), (11, 0), (0, 5),
                                             (11, 5)])
def test_kvq_flash_matches_dequant_oracle(impl, kvh, g, window, kv_start,
                                          layer):
    q, kc, vc, cb, kn, vn, phys, pos = _mk_kvq_case(
        seed=3, slots=4, np_=4, ps=8, kvh=kvh, g=g, d=16,
        positions=_POSITIONS)
    out = flash_decode_paged(q, kc, vc, kn, vn, phys, pos, layer=layer,
                             window=window, kv_start=kv_start, impl=impl,
                             codebook=cb, interpret=True)
    oracle = flash_decode_kvq_ref(q, kc, vc, cb, kn, vn, phys, pos,
                                  window=window, kv_start=kv_start,
                                  layer=layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_kvq_flash_single_slot_batch(impl):
    q, kc, vc, cb, kn, vn, phys, pos = _mk_kvq_case(
        seed=11, slots=1, np_=4, ps=8, kvh=2, g=2, d=16, positions=[24])
    out = flash_decode_paged(q, kc, vc, kn, vn, phys, pos, layer=1,
                             impl=impl, codebook=cb, interpret=True)
    oracle = flash_decode_kvq_ref(q, kc, vc, cb, kn, vn, phys, pos,
                                  layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


def test_kvq_trash_page_codes_never_attended():
    """Rewriting the trash page's CODES must not change any live output."""
    q, kc, vc, cb, kn, vn, phys, pos = _mk_kvq_case(
        seed=9, slots=3, np_=4, ps=8, kvh=2, g=2, d=16,
        positions=[9, 16, -1])
    for impl in ("ref", "pallas"):
        a = flash_decode_paged(q, kc, vc, kn, vn, phys, pos, impl=impl,
                               codebook=cb, interpret=True)
        b = flash_decode_paged(q, kc.at[:, -1].set(15),
                               vc.at[:, -1].set(0), kn, vn, phys, pos,
                               impl=impl, codebook=cb, interpret=True)
        live = np.asarray(pos) >= 0
        np.testing.assert_array_equal(np.asarray(a)[live],
                                      np.asarray(b)[live])


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_kvq_flash_8k_context_parity(impl):
    """8k-token heavy: quantized long-context parity at a realistic page
    count — the regime the 4x-bytes claim is about."""
    ps, np_ = 16, 512                                  # 8192 tokens / slot
    q, kc, vc, cb, kn, vn, phys, pos = _mk_kvq_case(
        seed=17, slots=2, np_=np_, ps=ps, kvh=2, g=2, d=32,
        positions=[8191, 5000])
    out = flash_decode_paged(q, kc, vc, kn, vn, phys, pos, impl=impl,
                             codebook=cb, interpret=True)
    oracle = flash_decode_kvq_ref(q, kc, vc, cb, kn, vn, phys, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# model-level decode chains over a quantized pool
# ---------------------------------------------------------------------------

def _fit_for(cfg, v=4, c=16):
    rng = np.random.RandomState(1)
    rows = jnp.asarray(rng.randn(cfg.num_layers, 24, cfg.num_kv_heads,
                                 cfg.head_dim), jnp.float32)
    return KVCodebook.fit(rows, rows + 0.3, v=v, c=c, iters=2, key=KEY)


def _kvq_chain_parity(cfg, qc_base, params=None, steps=3, lens=(9, 16)):
    """Greedy chains over ONE quantized pool must be token-identical
    across the gather / ref / pallas read paths (they all read the same
    codes; only the float summation order differs)."""
    m = Model(cfg)
    if params is None:
        params = m.init(KEY, qc_base)
    cb = _fit_for(cfg)
    slots, max_seq, ps = len(lens), 32, 8
    pt = PageTable(num_slots=slots, max_seq=max_seq, page_size=ps)
    kv = m.init_paged_cache(slots, max_seq, ps, pt.allocator.num_pages,
                            codebook=cb)
    assert kv["k"].dtype == jnp.uint8 and CODEBOOK_KEY in kv
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


def test_kvq_chain_parity_dense():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    assert _kvq_chain_parity(cfg, KVQ) < 1e-4


def test_kvq_chain_parity_lut_infer():
    """Both lossy paths stacked: lut_infer weights + vq KV pool."""
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    qc_t = QuantConfig(mode="lut_train")
    m = Model(cfg)
    qc_i = qc_t.replace(mode="lut_infer", kv_quant="vq")
    params = precompute_model(m.init(KEY, qc_t), qc_i)
    assert _kvq_chain_parity(cfg, qc_i, params=params) < 1e-4


def test_kvq_chain_parity_gqa_sliding_window():
    cfg = get_smoke_config("gemma3-27b").replace(attn_impl="naive")
    assert cfg.num_heads > cfg.num_kv_heads and cfg.sliding_window > 0
    assert _kvq_chain_parity(cfg, KVQ) < 1e-4


def test_init_paged_cache_rejects_mismatched_codebook():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    bad = KVCodebook.fit(jnp.ones((cfg.num_layers, 8, 2, 8)),
                         jnp.ones((cfg.num_layers, 8, 2, 8)),
                         v=4, c=4, iters=1)
    with pytest.raises(ValueError):
        m.init_paged_cache(1, 32, 8, 4, codebook=bad)   # head_dim mismatch


# ---------------------------------------------------------------------------
# serving engine with kv_quant="vq"
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    return m, m.init(KEY, DENSE)


@pytest.fixture(scope="module")
def qwen_cb(qwen):
    """One calibration-fit codebook shared by every engine test (the fit
    is deterministic, but sharing skips re-running it per test)."""
    m, params = qwen
    probe = Engine(m, params, KVQ, batch_size=1, max_seq=32, page_size=8,
                   prefill_chunk=4, prefix_cache=False)
    return probe.kv_codebook


def _mk_engine(m, params, qc=DENSE, slots=2, cb=None, **kw):
    kw.setdefault("max_seq", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return Engine(m, params, qc, batch_size=slots, kv_codebook=cb, **kw)


def test_kvq_engine_read_paths_token_identical(qwen, qwen_cb):
    """One quantized engine per flash impl, identical greedy streams."""
    m, params = qwen
    outs = {}
    for flash in ("gather", "ref", "pallas"):
        reqs = [Request(tokens=[3, 4, 5], max_new_tokens=6),
                Request(tokens=[7, 8], max_new_tokens=4)]
        _mk_engine(m, params, qc=KVQ.replace(flash=flash),
                   cb=qwen_cb).run(reqs)
        assert all(r.done and len(r.out_tokens) == r.max_new_tokens
                   for r in reqs)
        outs[flash] = [r.out_tokens for r in reqs]
    assert outs["gather"] == outs["ref"] == outs["pallas"]


def test_kvq_engine_validation(qwen, qwen_cb):
    m, params = qwen
    with pytest.raises(ValueError, match="kv_quant"):
        _mk_engine(m, params, qc=DENSE, cb=qwen_cb)    # codebook w/o vq


def test_kvq_exact_cover_engine_token_identity(qwen):
    """End-to-end greedy identity under a from_rows exact-cover codebook:
    the fp engine's run is harvested row for row, those rows become the
    centroids, and the QUANTIZED engine must reproduce the fp tokens
    bit-identically (encode-on-write + decode-on-read both active)."""
    m, params = qwen
    prompt, n_new = [2, 3, 5, 7, 11], 8
    qc = DENSE.replace(flash="gather")

    def run(e_qc, cb=None):
        eng = _mk_engine(m, params, qc=e_qc, slots=1, cb=cb,
                         prefix_cache=False)
        req = Request(tokens=list(prompt), max_new_tokens=n_new)
        eng.run([req])
        assert req.done and len(req.out_tokens) == n_new
        return req.out_tokens

    fp_out = run(qc)
    # manual chain on a static table: same tokens, harvestable pool
    p = len(prompt)
    kv = m.init_paged_cache(1, 32, 8, 4)
    table = jnp.arange(4, dtype=jnp.int32).reshape(1, 4)
    logits, kv = m.prefill_paged(params, jnp.asarray([prompt], jnp.int32),
                                 kv, table, 0, 0, p, qc)
    toks = []
    for step in range(n_new):
        nxt = int(jnp.argmax(logits.reshape(-1)))
        toks.append(nxt)
        logits, kv = m.decode_paged(params, jnp.asarray([[nxt]], jnp.int32),
                                    kv, table,
                                    jnp.asarray([p + step], jnp.int32), qc)
    assert toks == fp_out, "manual chain diverged from the engine"
    t_rows = p + n_new - 1                 # every row the run READS
    cfg = m.cfg
    rows = {key: jnp.swapaxes(kv[key][:, np.arange(4)], 2, 3).reshape(
        cfg.num_layers, 32, cfg.num_kv_heads, cfg.head_dim)[:, :t_rows]
        for key in ("k", "v")}
    cb = KVCodebook.from_rows(rows["k"], rows["v"])
    assert run(KVQ.replace(flash="gather"), cb) == fp_out


def test_kvq_prefix_warm_cold_parity(qwen, qwen_cb):
    """Warm (prefix-cached) quantized engine == cold quantized engine,
    token for token, on page-aligned SUFFIX matches — the reused codes
    are bitwise the codes the cold run writes for itself, so parity is
    exact even though the pool is lossy. (Full-prompt CoW matches are
    the one warm case that re-runs a prompt token under different
    prefill chunking, where a lossy pool may legitimately drift within
    quantization error — covered by the codes-preservation test below
    and docs/serving.md.)"""
    m, params = qwen
    system = [(3 * j) % 40 + 2 for j in range(16)]      # 2 full pages
    streams, engines = {}, {}
    for tag, warm in (("cold", False), ("warm", True)):
        eng = _mk_engine(m, params, qc=KVQ, cb=qwen_cb, prefix_cache=warm)
        reqs = [Request(tokens=system + [50 + i], max_new_tokens=4)
                for i in range(3)]
        eng.run([reqs[0]])
        for r in reqs[1:]:
            eng.submit(r)
        eng.run_until_idle()
        assert all(r.done and len(r.out_tokens) == r.max_new_tokens
                   for r in reqs)
        streams[tag] = [r.out_tokens for r in reqs]
        engines[tag] = eng
    assert streams["warm"] == streams["cold"]
    assert engines["warm"].prefilled_tokens < engines["cold"].prefilled_tokens
    # the prefix index chains from the codebook fingerprint: the same
    # token chunks hash differently under a different (or no) codebook
    salt = engines["warm"].kv.table.content_salt
    assert salt == qwen_cb.fingerprint() != 0
    assert _chunk_keys(system, 8, salt) != _chunk_keys(system, 8, 0)


def test_kvq_cow_fork_preserves_codes(qwen, qwen_cb):
    """A full-prompt match CoW-forks a CODE page: the fork must leave the
    shared page's codes bitwise untouched, copy them into the private
    page, and later suffix-match requests must still reuse the original
    codes and stay token-identical to before the fork."""
    m, params = qwen
    system = [(3 * j) % 40 + 2 for j in range(16)]      # 2 full pages
    eng = _mk_engine(m, params, qc=KVQ, cb=qwen_cb)
    warm = Request(tokens=system + [50], max_new_tokens=4)
    eng.run([warm])
    salt = eng.kv.table.content_salt
    shared = [eng.kv.table.prefix.lookup(key)
              for key in _chunk_keys(system, 8, salt)]
    assert all(p is not None for p in shared)
    before = {key: np.asarray(eng.kv.data[key][:, shared])
              for key in ("k", "v")}

    fork = Request(tokens=list(system), max_new_tokens=4)
    eng.run([fork])
    assert eng.kv.cow_forks >= 1
    assert fork.done and len(fork.out_tokens) == 4
    for key in ("k", "v"):                 # shared codes bitwise intact
        np.testing.assert_array_equal(
            np.asarray(eng.kv.data[key][:, shared]), before[key])

    again = Request(tokens=system + [50], max_new_tokens=4)
    eng.run([again])                       # suffix reuse still exact
    assert again.out_tokens == warm.out_tokens


def test_kvq_spec_rollback_refcounts_match_mapped_rows(qwen, qwen_cb):
    """Speculative verify/rollback on a quantized pool: after EVERY step
    each physical page's refcount equals the slot rows mapping it, and
    the run completes token-identical to the non-speculative engine."""
    m, params = qwen

    def reqs():
        return [Request(tokens=[3, 4, 5, 6], max_new_tokens=10),
                Request(tokens=[9, 8, 7], max_new_tokens=8)]

    plain = reqs()
    _mk_engine(m, params, qc=KVQ, cb=qwen_cb, max_seq=64,
               prefill_chunk=8).run(plain)
    spec = reqs()
    eng = _mk_engine(m, params, qc=KVQ, cb=qwen_cb, max_seq=64,
                     prefill_chunk=8,
                     spec_decode=SpecConfig(k=3, drafter="ngram"))
    for r in spec:
        eng.submit(r)
    pt = eng.kv.table
    while eng.scheduler.has_work:
        eng.step()
        mapped = Counter(p for row in pt._slot_pages for p in row)
        for pg in range(pt.allocator.num_pages):
            assert pt.allocator.refcount(pg) == mapped.get(pg, 0), \
                f"page {pg}: refcount != mapped rows after rollback"
    assert [r.out_tokens for r in spec] == [r.out_tokens for r in plain]


def test_kvq_chaos_zero_lost(qwen, qwen_cb):
    """The canned chaos schedule over 2 quantized replicas: ZERO lost
    requests, every request COMPLETED with its full token budget.

    (No token-identity clause: crash recovery re-prefills prompt +
    already-emitted tokens on the surviving replica, and a re-prefill
    chunks attention differently than the original decode — exact on an
    fp pool, drift-within-quantization-error on a lossy one; see
    docs/serving.md. The robustness invariant — nothing lost, nothing
    truncated — is what kv_quant must preserve.)"""
    m, params = qwen
    prompts = [[i + 2, i + 3, i + 4] for i in range(6)]
    reqs = [Request(tokens=list(p), max_new_tokens=8) for p in prompts]
    router = ReplicaRouter([_mk_engine(m, params, qc=KVQ, cb=qwen_cb)
                            for _ in range(2)])
    inj = FaultInjector(FaultSchedule.canned(replicas=2)).attach(router)
    for r in reqs:
        router.submit(r)
    router.run_until_idle()
    assert all(r.done for r in reqs)                   # zero lost
    for r in reqs:
        assert r.finish_reason is FinishReason.COMPLETED
        assert len(r.out_tokens) == 8                  # full budget, no dupes
    fired = inj.report()["by_kind"]
    assert fired.get("crash", 0) >= 1 and fired.get("pool_exhaust", 0) >= 1


def test_kvq_admission_accounting_reports_bytes(qwen, qwen_cb):
    """occupancy()/byte properties reflect the uint8 pool: bytes/token
    shrinks >= 4x vs fp, live_bytes tracks live pages, and the MiB
    figures surface in the occupancy string."""
    m, params = qwen
    fp = _mk_engine(m, params, qc=DENSE)
    kvq = _mk_engine(m, params, qc=KVQ, cb=qwen_cb)
    assert fp.kv.bytes_per_token >= 4 * kvq.kv.bytes_per_token
    assert kvq.kv.page_bytes == kvq.kv.bytes_per_token * 8
    assert kvq.kv.pool_bytes == \
        kvq.kv.page_bytes * kvq.kv.table.allocator.num_pages
    assert kvq.kv.live_bytes == 0
    req = Request(tokens=[3, 4, 5], max_new_tokens=4)
    kvq.run([req])
    assert "MiB" in kvq.kv.occupancy()
    assert kvq.kv.table.page_bytes == kvq.kv.page_bytes
