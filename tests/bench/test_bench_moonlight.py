"""The benchmark's pieces for Moonlight-16B-A3B's chip share: the work
counts of the latent-attention and routed-expert kernels on shapes worked
by hand, their matchers on op texts taken from the compiled decode and
prefill programs at the configuration's widths (``data/moonlight_ops.
json``, as a trace names them: each instruction without its metadata),
the two readers, and the program preset that the configuration names
against every size of that file."""
import json
from types import SimpleNamespace

import pytest

import bench_smoke
import harness
import spans
from work import flash_decode, mla_decode, moe_ffn, vq_amm

DATA = bench_smoke.ROOT / "tests" / "bench" / "data"
CONFIG = bench_smoke.BENCH / "configs" / "moonlight-16b-a3b-ep8.json"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_mla_decode_work_by_hand():
    # slots with 3 and 5 cached positions, 2 heads, latent 4, RoPE 2:
    # t = 8 positions of a 6-wide row; scores 2*8*2*6 = 192, weighted
    # sum 2*8*2*4 = 128; rows 8*6*2 = 96 B, queries 2*2*6*2 = 48 B,
    # fresh rows 2*6*2 = 24 B, float32 outputs 2*2*4*4 = 64 B
    assert mla_decode.work([3, 5], heads=2, rank=4, rope=2) == (320, 232)
    # at the cell's widths a call is bound by the rows it reads
    ops, nb = mla_decode.work([500] * 128, 16, 512, 64)
    assert nb == 128 * 500 * 576 * 2 + 128 * 16 * 576 * 2 \
        + 128 * 576 * 2 + 128 * 16 * 512 * 4
    assert ops / PEAK["bf16_flops"] < nb / PEAK["hbm_bytes_per_s"]


def test_moe_ffn_work_by_hand():
    # 10 pairs through 3 experts of d 4, ff 5: 6*4*5*10 = 1200 ops;
    # 3 experts * 3 matrices * 4*5 * 2 B = 360 B and 10 rows in and out,
    # 2*4*2*10 = 160 B
    assert moe_ffn.work(10, 3, d=4, ff=5) == (1200, 520)
    # a decode step of 128 slots, 6 choices over 64 experts: 8 of the
    # share's experts in each of 26 layers, 12 pairs each on average
    ops, nb = moe_ffn.work(26 * 96, 26 * 8, 2048, 1408)
    assert nb > 26 * 8 * 3 * 2048 * 1408 * 2
    assert ops / PEAK["bf16_flops"] < nb / PEAK["hbm_bytes_per_s"]


def test_matchers_on_the_compiled_programs_ops():
    ops = json.loads((DATA / "moonlight_ops.json").read_text())
    assert mla_decode.matches(ops["mla"])
    assert moe_ffn.matches(ops["experts"])
    for key in ("experts", "shared", "shared_chunk", "experts_chunk",
                "mla_consumer", "experts_consumer"):
        assert not mla_decode.matches(ops[key]), key
    for key in ("mla", "shared", "shared_chunk", "experts_chunk",
                "mla_consumer", "experts_consumer"):
        assert not moe_ffn.matches(ops[key]), key
    # the kernels the other cells read claim neither
    for key in ops:
        assert not flash_decode.matches(ops[key]), key
        assert not vq_amm.matches(ops[key]), key
    old = json.loads((DATA / "step_ops.json").read_text())
    for op in old.values():
        assert not mla_decode.matches(op) and not moe_ffn.matches(op)


def _run(**kw):
    base = dict(traced=SimpleNamespace(kernel_s={}), peaks=PEAK,
                traced_steps=[], sizes=harness.reference_module(
                    json.loads(CONFIG.read_text())).sizes_from_config(
                        json.loads(CONFIG.read_text())))
    base.update(kw)
    return SimpleNamespace(**base)


def _reader(name):
    return harness.load_module(bench_smoke.BENCH / "metrics" / f"{name}.py")


def test_mla_roofline_reader():
    """Least time of each traced decode step's 27 calls over the kernel's
    time; nothing where the trace names no such kernel."""
    read = _reader("mla_decode_roofline.moonlight").read
    steps = [harness.StepWork(0.0, [], [101] * 128),
             harness.StepWork(0.0, [(0, 32)], [201] * 120)]
    run = _run(traced_steps=steps)
    assert read(run) is None
    least = sum(27 * max(o / PEAK["bf16_flops"], b / PEAK["hbm_bytes_per_s"])
                for o, b in [mla_decode.work([100] * 128, 16, 512, 64),
                             mla_decode.work([200] * 120, 16, 512, 64)])
    run.traced.kernel_s["mla_decode"] = 4 * least
    assert read(run) == pytest.approx(25.0)
    assert read(_run(traced=None)) is None


def test_moe_roofline_reader(monkeypatch):
    """Least time of the routed pairs the traced steps' spans carry over
    the kernel's time; nothing without the spans' counts or the kernel."""
    read = _reader("moe_ffn_roofline.moonlight").read
    sp = spans.Spans(window_s=1.0, modules={}, gaps=None, host=[], steps=[
        {"decode_slots": 128, "moe_pairs": 26 * 96,
         "moe_experts_hit": 26 * 8}, {"decode_slots": 0}])
    monkeypatch.setattr(spans, "of_run", lambda run: sp)
    run = _run()
    assert read(run) is None
    o, b = moe_ffn.work(26 * 96, 26 * 8, 2048, 1408)
    run.traced.kernel_s["moe_ffn"] = 2 * b / PEAK["hbm_bytes_per_s"]
    assert read(run) == pytest.approx(50.0)
    sp.steps = [{"decode_slots": 128}]
    assert read(run) is None


def test_program_preset_agrees_with_every_size_of_the_configuration():
    """The preset the configuration names holds each of the file's sizes,
    those the harness passes on and those it does not (latent widths,
    experts, router), and the reference reads the same file alike."""
    from repro.configs import get_config
    cfg = json.loads(CONFIG.read_text())
    p = get_config(cfg["program_preset"])
    want = {
        "num_hidden_layers": p.num_layers, "hidden_size": p.d_model,
        "num_attention_heads": p.num_heads,
        "num_key_value_heads": p.num_kv_heads,
        "intermediate_size": p.d_ff, "vocab_size": p.vocab_size,
        "kv_lora_rank": p.kv_lora_rank,
        "qk_nope_head_dim": p.qk_nope_head_dim,
        "qk_rope_head_dim": p.qk_rope_head_dim, "v_head_dim": p.v_head_dim,
        "moe_intermediate_size": p.moe_d_ff,
        "n_routed_experts": p.experts_held,
        "num_experts_per_tok": p.top_k,
        "n_shared_experts": p.num_shared_experts,
        "first_k_dense_replace": p.first_k_dense,
        "routed_scaling_factor": p.routed_scale,
        "scoring_func": p.router_scoring, "rms_norm_eps": p.norm_eps,
        "rope_theta": p.rope_theta,
        "tie_word_embeddings": p.tie_embeddings, "torch_dtype": p.dtype}
    for key, value in want.items():
        assert cfg[key] == value, key
    assert cfg["published"]["n_routed_experts"] == p.num_experts == 64
    assert cfg["deployment"]["first_held_expert"] == p.first_held == 0
    assert cfg["topk_method"] == "noaux_tc" and p.router_bias
    assert cfg["q_lora_rank"] is None and cfg["n_group"] == 1
    assert (cfg["deployment"]["expert_parallel"]
            * cfg["n_routed_experts"]) == p.num_experts
    s = harness.reference_module(cfg).sizes_from_config(cfg)
    assert (s.layers, s.d, s.heads, s.rank, s.nope, s.rope, s.v_dim,
            s.dense_ff, s.expert_ff, s.experts, s.held, s.top_k, s.shared,
            s.dense_layers, s.vocab) == (
        p.num_layers, p.d_model, p.num_heads, p.kv_lora_rank,
        p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim, p.d_ff,
        p.moe_d_ff, p.num_experts, p.experts_held, p.top_k,
        p.num_shared_experts, p.first_k_dense, p.vocab_size)
    assert s.row == p.latent_row == 640
    assert harness.model_config(cfg) == p
