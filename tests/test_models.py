"""Per-architecture smoke tests (reduced configs): forward/train/decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, SMOKE_REGISTRY, get_config
from repro.core import precompute_model
from repro.core.lut import DENSE, QuantConfig
from repro.models.model import Model

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=16):
    if cfg.family == "audio":
        return {"embeds": jax.random.normal(KEY, (B, S, cfg.d_model)),
                "labels": jax.random.randint(
                    KEY, (B, S, cfg.num_codebooks), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        return {"patch_embeds": jax.random.normal(
                    KEY, (B, cfg.num_patches, cfg.d_model)),
                "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    return {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}


# the heaviest smoke configs (deep grouped scans) run in the slow CI job;
# the default run keeps one representative per family fast
_SLOW_SMOKE = {"gemma3-4b", "gemma3-27b", "zamba2-1.2b", "dbrx-132b"}


@pytest.mark.parametrize(
    "name", [pytest.param(n, marks=pytest.mark.slow) if n in _SLOW_SMOKE
             else n for n in ARCH_NAMES])
def test_smoke_forward_and_train_step(name):
    cfg = SMOKE_REGISTRY[name]()
    m = Model(cfg)
    params = m.init(KEY, DENSE)
    batch = make_batch(cfg)
    logits, aux = m.forward(params, batch, DENSE)
    assert not bool(jnp.any(jnp.isnan(logits)))
    if cfg.family == "audio":
        assert logits.shape[2:] == (cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape[-1] == cfg.vocab_size
    loss, metrics = m.loss(params, batch, DENSE)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: m.loss(p, batch, DENSE)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in
             jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_matches_assignment(name):
    """The full configs carry the exact published numbers."""
    cfg = get_config(name)
    expected = {
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "deepseek-moe-16b": (28, 2048, 16, 16, 10944, 102400),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "moonlight-16b-a3b-ep8": (27, 2048, 16, 16, 11264, 163840),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
    }[name]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (got, expected)
    # family-specific assignment details
    if name == "dbrx-132b":
        assert (cfg.num_experts, cfg.top_k) == (16, 4)
    if name == "deepseek-moe-16b":
        assert (cfg.num_experts, cfg.num_shared_experts, cfg.top_k) \
            == (64, 2, 6)
        assert (cfg.moe_d_ff, cfg.first_k_dense) == (1408, 1)
    if name.startswith("moonlight-16b-a3b"):
        assert (cfg.num_experts, cfg.num_shared_experts, cfg.top_k,
                cfg.moe_d_ff, cfg.first_k_dense) == (64, 2, 6, 1408, 1)
        assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                cfg.v_head_dim) == (512, 128, 64, 128)
        assert (cfg.router_scoring, cfg.router_bias, cfg.routed_scale) \
            == ("sigmoid", True, 2.446)
        assert cfg.experts_held == (8 if name.endswith("-ep8") else 64)
    if name == "zamba2-1.2b":
        assert cfg.ssm_state == 64 and cfg.family == "hybrid"
    if name == "mamba2-2.7b":
        assert cfg.ssm_state == 128 and cfg.family == "ssm"
    if name.startswith("gemma3"):
        assert cfg.global_every == 6 and cfg.sliding_window == 1024
    if name == "musicgen-large":
        assert cfg.num_codebooks == 4
    if name == "paligemma-3b":
        assert cfg.num_patches == 256


@pytest.mark.parametrize("name", ["gemma3-27b", "zamba2-1.2b",
                                  "deepseek-moe-16b", "musicgen-large",
                                  "paligemma-3b"])
def test_decode_matches_forward(name):
    cfg = SMOKE_REGISTRY[name]().replace(attn_impl="naive")
    m = Model(cfg)
    params = m.init(KEY, DENSE)
    B, S, PRE = 2, 12, 8
    batch = make_batch(cfg, B, S)
    logits_full, _ = m.forward(params, batch, DENSE)
    if cfg.family == "audio":
        pre = {"embeds": batch["embeds"][:, :PRE]}
    elif cfg.family == "vlm":
        pre = {"patch_embeds": batch["patch_embeds"],
               "tokens": batch["tokens"][:, :PRE]}
    else:
        pre = {"tokens": batch["tokens"][:, :PRE]}
    cache = m.init_cache(B, 32)
    lg, cache = m.prefill(params, pre, cache, DENSE)
    off = cfg.num_patches if cfg.family == "vlm" else 0
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_full[:, off + PRE - 1]),
                               rtol=3e-4, atol=3e-4)
    for t in range(PRE, S):
        tok = (batch["embeds"][:, t:t + 1] if cfg.family == "audio"
               else batch["tokens"][:, t:t + 1])
        lg, cache = m.decode(params, tok, cache, DENSE)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_full[:, off + t]),
                                   rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize(
    "name", ["qwen1.5-4b",
             pytest.param("dbrx-132b", marks=pytest.mark.slow),
             "mamba2-2.7b"])
def test_lut_mode_train_and_infer(name):
    cfg = SMOKE_REGISTRY[name]().replace(attn_impl="naive")
    m = Model(cfg)
    qc_t = QuantConfig(mode="lut_train", v=4, c=8)
    qc_i = QuantConfig(mode="lut_infer", v=4, c=8, impl="ref")
    params = m.init(KEY, qc_t)
    batch = make_batch(cfg)
    loss, metrics = m.loss(params, batch, qc_t)
    assert bool(jnp.isfinite(loss)) and float(metrics["recon"]) > 0
    pi = precompute_model(params, qc_i)
    lt, _ = m.forward(params, batch, qc_t)
    li, _ = m.forward(pi, batch, qc_i)
    np.testing.assert_allclose(np.asarray(lt), np.asarray(li),
                               rtol=5e-3, atol=5e-3)


def test_param_count_sanity():
    cfg = get_config("gemma3-27b")
    n = cfg.param_count()
    assert 25e9 < n < 32e9, n       # ~27B
    cfg = get_config("dbrx-132b")
    assert 120e9 < cfg.param_count() < 140e9
    assert cfg.active_param_count() < 0.4 * cfg.param_count()  # top-4 of 16
    cfg = get_config("mamba2-2.7b")
    assert 2.0e9 < cfg.param_count() < 3.4e9
    # Moonlight-16B-A3B: 15.96 B published (untied head), 3.36 B in one
    # chip's share of an 8-way expert-parallel deployment
    assert 15.9e9 < get_config("moonlight-16b-a3b").param_count() < 16.0e9
    share = get_config("moonlight-16b-a3b-ep8")
    assert 3.3e9 < share.param_count() < 3.4e9
    assert share.active_param_count() \
        == get_config("moonlight-16b-a3b").active_param_count()


def test_gemma3_local_global_pattern():
    cfg = get_config("gemma3-27b")
    pattern = [cfg.layer_is_global(i) for i in range(12)]
    assert pattern == [False] * 5 + [True] + [False] * 5 + [True]
    assert not cfg.pure_full_attention           # runs long_500k
    assert get_config("qwen1.5-4b").pure_full_attention
    assert not get_config("mamba2-2.7b").pure_full_attention
    assert not get_config("zamba2-1.2b").pure_full_attention
