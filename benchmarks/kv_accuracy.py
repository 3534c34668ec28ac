"""Lossy-path accuracy harness for vector-quantized KV-cache pages.

The ``kv_quant="vq"`` pool is the repo's first *lossy* serving artefact:
codes round-trip through a per-layer codebook instead of fp rows, so
"the tests pass" is not enough — the question is HOW MUCH the output
distribution moves. This harness answers it on the trained smoke model
(the +1-mod-V synthetic stream, the same recipe ``serve_bench --spec``
uses — accuracy deltas on random weights are meaningless because every
logit is noise):

* **teacher-forced logit MSE** — one on-distribution stream replayed
  through two paged decode chains that differ ONLY in the pool encoding
  (fp rows vs uint8 codes under a calibration-fit codebook); per-step
  next-token logits are compared elementwise.
* **perplexity delta** — the same two chains scored against the true
  continuation; ``--smoke`` asserts the quantized perplexity is within
  0.1 of fp (the ISSUE ceiling; typical at v=4/c=16 is ~100x tighter).
* **greedy argmax agreement** — the fraction of steps where the
  quantized chain's greedy choice matches fp (reported; asserted 1.0
  only in the exact-cover test below, where it is a theorem).
* **exact-cover token identity** — an end-to-end :class:`Engine` run
  under a :meth:`KVCodebook.from_rows` codebook (centroids = the exact
  row set, unit scales) must reproduce the fp engine's greedy tokens
  BIT-IDENTICALLY: encode lands every row on an exact copy of itself,
  so the lossy machinery — encode on write, in-kernel decode on read —
  is exercised while the answer stays provably lossless.

Run:  PYTHONPATH=src python benchmarks/kv_accuracy.py [--smoke]
      [--snapshot auto]

``--snapshot`` MERGES the ``kvacc.*`` rows into ``BENCH_serve.json``
(replacing stale ``kvacc.*`` rows, preserving everything else) — the
accuracy trajectory rides the serving snapshot rather than forking a
second on-disk history.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.core.kv_codebook import KVCodebook
from repro.core.lut import DENSE
from repro.data import SyntheticDataset
from repro.models.model import Model
from repro.serve import Engine, Request
from repro.obs.snapshot import merge_snapshot
from repro.train import TrainConfig, Trainer

try:                                   # `python -m benchmarks.kv_accuracy`
    from .common import ROWS, emit
except ImportError:                    # `python benchmarks/kv_accuracy.py`
    from common import ROWS, emit

VOCAB = 24
PAGE = 8


def _trained_smoke():
    """The spec_bench training recipe: smoke config, small vocab, +1-mod-V
    synthetic stream. 150 steps is enough for the model to put ~all its
    mass on the true successor, which is what makes perplexity deltas
    interpretable."""
    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive",
                                                 vocab_size=VOCAB)
    model = Model(cfg)
    ds = SyntheticDataset(cfg, global_batch=16, seq_len=64)
    params = model.init(jax.random.PRNGKey(0), DENSE)
    params, _, _ = Trainer(model, ds, DENSE, TrainConfig(
        total_steps=150, lr=3e-3, warmup=10, log_every=1000)).run(params)
    return model, params


def _teacher_chain(model, params, qc, stream, codebook=None):
    """Teacher-forced paged decode over ``stream``; returns per-step
    next-token logits ``(T - p0, V)`` for targets ``stream[p0:]``.

    The chain is the engine's own paged path (prefill_paged one chunk,
    then decode_paged token by token on a static full page table) — not
    a dense-cache stand-in — so encode-on-write and decode-in-kernel are
    both in the loop being measured.
    """
    t_total = len(stream)
    max_seq = -(-t_total // PAGE) * PAGE
    npages = max_seq // PAGE
    kv = model.init_paged_cache(1, max_seq, PAGE, npages, codebook=codebook)
    table = jnp.arange(npages, dtype=jnp.int32).reshape(1, npages)
    p0 = 4
    chunk = jnp.asarray([stream[:p0]], jnp.int32)
    logits, kv = model.prefill_paged(params, chunk, kv, table, 0, 0, p0, qc)
    step = jax.jit(lambda tok, kv, pos: model.decode_paged(
        params, tok, kv, table, pos, qc))
    outs = [logits.reshape(-1)]
    for t in range(p0, t_total - 1):
        tok = jnp.asarray([[stream[t]]], jnp.int32)
        logits, kv = step(tok, kv, jnp.asarray([t], jnp.int32))
        outs.append(logits.reshape(-1))
    return jnp.stack(outs)


def teacher_forced_bench(model, params, smoke: bool):
    """Logit MSE / perplexity delta / greedy agreement, fp vs quantized."""
    kvq_qc = DENSE.replace(kv_quant="vq")
    # the engine's own calibration fit (deterministic token ramp,
    # PRNGKey(0)) — the codebook a production engine would serve with
    probe = Engine(model, params, kvq_qc, batch_size=1, max_seq=64,
                   page_size=PAGE, prefill_chunk=4, prefix_cache=False)
    cb = probe.kv_codebook
    stream = [(3 + j) % VOCAB for j in range(48)]
    lf = _teacher_chain(model, params, DENSE, stream)
    lq = _teacher_chain(model, params, kvq_qc, stream, codebook=cb)
    targets = jnp.asarray(stream[4:], jnp.int32)

    def ppl(lg):
        lp = jax.nn.log_softmax(lg, -1)
        nll = -jnp.take_along_axis(lp, targets[:, None], -1)
        return float(jnp.exp(jnp.mean(nll)))

    mse = float(jnp.mean((lf - lq) ** 2))
    agree = float(jnp.mean(
        (jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).astype(jnp.float32)))
    ppl_fp, ppl_q = ppl(lf), ppl(lq)
    delta = abs(ppl_q - ppl_fp)
    emit("kvacc.logit_mse", mse,
         f"teacher-forced over {lf.shape[0]} steps, v={cb.v} c={cb.c}",
         unit="", direction="down", tol=1.0)
    emit("kvacc.ppl_delta", delta,
         f"fp ppl {ppl_fp:.4f} -> vq ppl {ppl_q:.4f}",
         unit="", direction="down", tol=1.0)
    emit("kvacc.greedy_agreement", agree * 100.0,
         f"{agree * 100:.1f}% of greedy choices identical to fp",
         unit="%", direction="up", tol=0.05)
    print(f"teacher-forced: logit MSE {mse:.3e}, ppl {ppl_fp:.4f} -> "
          f"{ppl_q:.4f} (delta {delta:.4f}), greedy agreement "
          f"{agree * 100:.1f}%")
    if smoke:
        assert delta <= 0.1, (
            f"quantized-cache perplexity drifted {delta:.4f} from fp — "
            f"the <= 0.1 acceptance ceiling is blown")
        assert mse <= 0.05, (
            f"teacher-forced logit MSE {mse:.3e} above the 0.05 ceiling")
        print("accuracy smoke check OK (ppl delta <= 0.1, MSE <= 0.05)")
    return delta


def exact_cover_bench(model, params) -> None:
    """Greedy token identity under an exact-cover codebook, end to end.

    fp engine -> greedy tokens; a manual fp paged chain (verified
    token-identical to the engine) harvests every cache row the run
    wrote; ``KVCodebook.from_rows`` makes those rows the centroids; the
    quantized ENGINE under that codebook must reproduce the fp tokens
    exactly. Always asserted — this is a theorem about the machinery,
    not a tolerance."""
    prompt, n_new = [2, 3, 5, 7, 11], 8
    qc = DENSE.replace(flash="gather")

    def run_engine(e_qc, cb=None):
        eng = Engine(model, params, e_qc, batch_size=1, max_seq=32,
                     page_size=PAGE, prefill_chunk=4, prefix_cache=False,
                     kv_codebook=cb)
        req = Request(tokens=list(prompt), max_new_tokens=n_new)
        eng.run([req])
        assert req.done and len(req.out_tokens) == n_new
        return req.out_tokens

    fp_out = run_engine(qc)

    # manual chain on a static table: same tokens, harvestable pool
    p = len(prompt)
    kv = model.init_paged_cache(1, 32, PAGE, 4)
    table = jnp.arange(4, dtype=jnp.int32).reshape(1, 4)
    logits, kv = model.prefill_paged(
        params, jnp.asarray([prompt], jnp.int32), kv, table, 0, 0, p, qc)
    toks, pos = [], p
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits.reshape(-1)))
        toks.append(nxt)
        logits, kv = model.decode_paged(
            params, jnp.asarray([[nxt]], jnp.int32), kv, table,
            jnp.asarray([pos], jnp.int32), qc)
        pos += 1
    assert toks == fp_out, (
        f"manual paged chain {toks} diverged from the fp engine {fp_out} "
        f"— the harvested rows would not describe the engine's run")

    # every row the run READS: positions [0, p + n_new - 1)
    t_rows = p + n_new - 1
    rows = {key: kv[key][:, np.arange(4)].reshape(
        model.cfg.num_layers, 32, model.cfg.num_kv_heads,
        model.cfg.head_dim)[:, :t_rows] for key in ("k", "v")}
    cb = KVCodebook.from_rows(rows["k"], rows["v"])
    vq_out = run_engine(DENSE.replace(kv_quant="vq", flash="gather"), cb)
    assert vq_out == fp_out, (
        f"exact-cover quantized engine {vq_out} != fp {fp_out}: "
        f"encode/decode is not lossless on its own centroid set")
    emit("kvacc.exact_cover_identity", 1.0,
         f"{n_new} greedy tokens bit-identical through the quantized "
         f"engine under a from_rows codebook",
         unit="", direction="up")
    print(f"exact-cover: quantized engine reproduced {fp_out} exactly")


def _merge_snapshot(path: str) -> None:
    """Fold this run's ``kvacc.*`` rows into an existing serve snapshot
    (or start one), replacing stale kvacc rows and nothing else."""
    merge_snapshot(path, ROWS, prefix="kvacc.", kv_accuracy=True)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="assert the acceptance ceilings (ppl delta <= "
                         "0.1, logit MSE <= 0.05)")
    ap.add_argument("--snapshot", default="",
                    help="merge kvacc.* rows into this BENCH_serve.json "
                         "('auto' = repo root)")
    args = ap.parse_args()
    model, params = _trained_smoke()
    teacher_forced_bench(model, params, args.smoke)
    exact_cover_bench(model, params)
    if args.snapshot:
        path = args.snapshot
        if path == "auto":
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "BENCH_serve.json")
        _merge_snapshot(os.path.normpath(path))


if __name__ == "__main__":
    main()
