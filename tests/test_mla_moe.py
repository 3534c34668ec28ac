"""Latent attention and the expert share against the plain reference
(``bench/reference/deepseek_v3.py``), on the CPU at a small
Moonlight-shaped size: one dense and two MoE layers, 8 experts of which
each token picks 2, 2 shared experts, a latent of 32, RoPE keys of 16,
no-RoPE and value heads of 32, in float32.

Tolerances: in float32 the program and the reference differ only in the
order of their sums (the absorbed against the expanded form of attention,
the blocked online softmax against one softmax, the experts' sum), a few
ulps of values of order 1, so 2e-4 absolute on logits of order 1 and 1e-5
on one layer's output hold with room; the served tokens' gaps below the
reference's best must be 0, since at this precision no near tie flips.
"""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.lut import DENSE
from repro.kernels.mla_decode import mla_decode_paged
from repro.kernels.moe_experts import moe_experts_pallas, moe_experts_ref
from repro.models.model import Model
from repro.models.moe import moe_ffn
from repro.obs import Obs
from repro.serve import Engine, Request

ROOT = Path(__file__).resolve().parents[1]
SEED = 2**33 + 7


def _reference():
    name = "_reference_deepseek_v3"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "bench" / "reference" / "deepseek_v3.py")
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


REF = _reference()


def cfg_file(c) -> dict:
    """A configuration file of ``bench/configs`` for a program preset."""
    return {
        "num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
        "num_attention_heads": c.num_heads, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "intermediate_size": c.d_ff, "moe_intermediate_size": c.moe_d_ff,
        "published": {"n_routed_experts": c.num_experts},
        "n_routed_experts": c.experts_held,
        "deployment": {"first_held_expert": c.first_held},
        "num_experts_per_tok": c.top_k,
        "n_shared_experts": c.num_shared_experts,
        "first_k_dense_replace": c.first_k_dense, "vocab_size": c.vocab_size,
        "rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
        "routed_scaling_factor": c.routed_scale, "norm_topk_prob": True,
        "scoring_func": c.router_scoring, "q_lora_rank": None,
        "tie_word_embeddings": c.tie_embeddings, "torch_dtype": c.dtype,
        "projection": {"mode": "dense"}}


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("moonlight-16b-a3b")
    sizes = REF.sizes_from_config(cfg_file(cfg))
    params = jax.jit(lambda k: REF.init_params(sizes, k))(REF.seed_key(SEED))
    return cfg, sizes, params, REF.Reference(sizes, SEED)


def _requests():
    rng = np.random.RandomState(0)
    return [Request(tokens=[int(t) for t in rng.randint(0, 256, n)],
                    max_new_tokens=k) for n, k in [(5, 9), (19, 4), (11, 7)]]


@pytest.mark.parametrize("flash", ["auto", "pallas"])
def test_engine_serves_the_reference_tokens(setup, flash):
    """Prefill then paged decode through ``Engine``, two slots for three
    requests (the third admitted mid-run into a freed slot): every served
    token is the reference's best at its position, on the CPU path and on
    the kernel (interpret mode)."""
    cfg, sizes, params, ref = setup
    eng = Engine(Model(cfg), params, DENSE.replace(flash=flash),
                 batch_size=2, max_seq=64, page_size=8, prefill_chunk=8)
    reqs = _requests()
    eng.run(reqs)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    gaps, _ = REF.served_gaps(ref, [r.tokens + r.out_tokens for r in reqs],
                              [len(r.tokens) for r in reqs], 64, 4)
    assert gaps.size == sum(r.max_new_tokens for r in reqs)
    assert gaps.max() == 0.0


@pytest.mark.parametrize("flash", ["auto", "pallas"])
def test_absorbed_decode_equals_expanded_attention(setup, flash):
    """``prefill_paged`` chunks and then ``decode_paged`` steps over the
    latent pool give the logits of the reference's expanded attention at
    every position, for two slots at different lengths, with a lane that
    is not decoding (-1) left out of the pool."""
    cfg, sizes, params, ref = setup
    m, qc = Model(cfg), DENSE.replace(flash=flash)
    ps, chunk, max_seq = 8, 8, 32
    kv = m.init_paged_cache(3, max_seq, ps, 3 * (max_seq // ps))
    table = jnp.arange(3 * (max_seq // ps), dtype=jnp.int32).reshape(3, -1)
    table = table[::-1]                         # pages out of order
    rng = np.random.RandomState(1)
    seqs = [list(rng.randint(0, 256, n)) for n in (11, 5)]
    prefill = jax.jit(lambda p, t, kv, s, pos, v: m.prefill_paged(
        p, t, kv, table, s, pos, v, qc))
    decode = jax.jit(lambda p, t, kv, pos: m.decode_paged(
        p, t, kv, table, pos, qc, with_load=True))
    for slot, sq in enumerate(seqs):
        for lo in range(0, len(sq) - 1, chunk):
            part = sq[lo:len(sq) - 1][:chunk]
            toks = jnp.zeros((1, chunk), jnp.int32).at[0, :len(part)].set(
                jnp.asarray(part))
            _, kv = prefill(params, toks, kv, slot, lo, len(part))
    got = [[], []]
    for step in range(4):
        toks = jnp.asarray([[sq[-1]] for sq in seqs] + [[0]], jnp.int32)
        pos = jnp.asarray([len(sq) - 1 for sq in seqs] + [-1], jnp.int32)
        logits, kv, load = decode(params, toks, kv, pos)
        assert load.shape == (cfg.num_layers - cfg.first_k_dense,
                              cfg.experts_held)
        assert int(load.sum()) == 2 * cfg.top_k * load.shape[0]
        for i, sq in enumerate(seqs):
            got[i].append(np.asarray(logits[i]))
            sq.append(int(jnp.argmax(logits[i])))
    width = max(len(sq) for sq in seqs)
    toks = np.zeros((2, width), np.int32)
    for i, sq in enumerate(seqs):
        toks[i, :len(sq)] = sq
    want = np.asarray(jnp.einsum("btd,dv->btv", ref.hidden(toks),
                                 ref.head().astype(jnp.float32)))
    for i, sq in enumerate(seqs):
        n = len(sq) - 4
        np.testing.assert_allclose(np.stack(got[i]),
                                   want[i, n - 1:n + 3], atol=2e-4)


def _layer_params(params):
    return jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])


def test_moe_output_does_not_depend_on_the_chunk_width(setup):
    """Drop-free dispatch: with the router's bias sending every token to
    expert 0 (the overflow a capacity buffer drops), each token's output
    is the same whether it comes in a chunk of 32, of 5 or alone."""
    cfg, _, params, _ = setup
    p = _layer_params(params)
    p = dict(p, router_bias=p["router_bias"].at[0].set(100.0))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, cfg.d_model))
    ffn = jax.jit(lambda x: moe_ffn(p, x, cfg, DENSE))
    whole, _, _, load = ffn(x)
    assert int(load[0]) == 32
    fives = jnp.concatenate([ffn(x[:, i:i + 5])[0] for i in range(0, 32, 5)],
                            axis=1)
    ones = jnp.concatenate([ffn(x[:, i:i + 1])[0] for i in range(32)], 1)
    np.testing.assert_allclose(fives, whole, atol=1e-5)
    np.testing.assert_allclose(ones, whole, atol=1e-5)


def test_expert_shares_sum_to_the_uncut_layer(setup):
    """The 4 shares of 2 of the 8 experts, each routing over all 8 and
    computing its own experts' part, with the shared experts counted
    once, add up to the uncut layer, and to the reference's."""
    cfg, sizes, params, _ = setup
    p = _layer_params(params)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    whole, _, _, load = moe_ffn(p, x, cfg, DENSE)
    total = jnp.zeros_like(whole)
    loads = []
    for i, first in enumerate(range(0, 8, 2)):
        share = cfg.replace(held_experts=2, first_held=first)
        sp = {k: v for k, v in p.items()
              if i == 0 or not k.startswith("shared_")}
        for k in ("wg", "wu", "wd"):
            sp[k] = {"w": p[k]["w"][first:first + 2]}
        out, _, _, ld = moe_ffn(sp, x, share, DENSE)
        total = total + out
        loads.append(ld)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    assert jnp.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 2 * 9 * cfg.top_k
    xn = REF._rms(x, p["norm"], sizes.eps)
    np.testing.assert_allclose(whole, REF._moe(sizes, xn, p, "ref"),
                               atol=1e-5)


def test_engine_step_span_carries_the_routing_counts(setup):
    """Each decode's (token, choice) pairs come back with the sampled
    tokens (one read-back a step) and go on the ``engine.step`` span."""
    cfg, _, params, _ = setup
    eng = Engine(Model(cfg), params, DENSE, batch_size=2, max_seq=64,
                 page_size=8, prefill_chunk=8, obs=Obs(jax_annotations=True))
    reqs = [Request(tokens=[3, 4, 5], max_new_tokens=5),
            Request(tokens=[7] * 9, max_new_tokens=5)]
    for r in reqs:
        eng.submit(r)
    reads = eng.device_reads
    while not any(s.req is not None and s.req.out_tokens and
                  len(eng.scheduler.decode_slots()) == 2
                  for s in eng.scheduler.slots):
        eng.step()
    reads, before = eng.device_reads, reads
    eng.step()
    assert eng.device_reads == reads + 1
    moe_layers = cfg.num_layers - cfg.first_k_dense
    assert eng._moe_stats["moe_pairs"] == 2 * cfg.top_k * moe_layers
    assert 1 <= eng._moe_stats["moe_max_load"] <= 2
    assert 1 <= eng._moe_stats["moe_experts_hit"] <= 8 * moe_layers


def test_moe_kernel_matches_its_xla_sum():
    """The held experts' Mosaic kernel (interpret mode) against the same
    sum in XLA, F tiled in 128-lane steps."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    t, d, f, e = 16, 128, 256, 3
    x = jax.random.normal(ks[0], (t, d)).astype(jnp.bfloat16)
    gates = jax.nn.relu(jax.random.normal(ks[1], (t, e)))
    wg = (0.1 * jax.random.normal(ks[2], (e, d, f))).astype(jnp.bfloat16)
    wu = (0.1 * jax.random.normal(ks[3], (e, d, f))).astype(jnp.bfloat16)
    wd = (0.1 * jax.random.normal(ks[4], (e, f, d))).astype(jnp.bfloat16)
    got = moe_experts_pallas(x, gates, wg, wu, wd, interpret=True)
    want = moe_experts_ref(x, gates, wg, wu, wd)
    # both round their output to bf16 once, from float32 sums in
    # different orders: at most one bf16 step apart
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=1e-2)


def test_mla_kernel_matches_the_gathered_view():
    """The latent-decode kernel (interpret mode) against the XLA path on
    a stacked pool, slots past a chunk of pages, empty and inactive."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    b, h, w, r, ps, np_ = 4, 4, 128, 32, 8, 20
    pool = jax.random.normal(ks[0], (2, b * np_ + 1, ps, w))
    q = 0.3 * jax.random.normal(ks[1], (b, h, w))
    new = jax.random.normal(ks[2], (b, w))
    phys = jnp.asarray(np.random.RandomState(2).permutation(b * np_)
                       .reshape(b, np_), jnp.int32)
    pos = jnp.asarray([150, 0, -1, 9], jnp.int32)
    want = mla_decode_paged(q, pool, new, phys, pos, r, layer=1)
    got = mla_decode_paged(q, pool, new, phys, pos, r, layer=1,
                           impl="pallas", interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # an inactive or empty lane attends only its own row
    np.testing.assert_allclose(got[1:3], jnp.broadcast_to(
        new[1:3, None, :r], (2, h, r)), atol=1e-6)
