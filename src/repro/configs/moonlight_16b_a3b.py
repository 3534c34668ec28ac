"""moonlight-16b-a3b [moe, latent attention]: 27L d_model=2048, 16 heads of
MLA (no q LoRA, kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128, rope_theta
5e4); layer 0 is a dense MLP of width 11264, layers 1-26 hold 2 shared + 64
routed experts of width 1408, top-6, sigmoid router with a choice-only
correction bias, normalised gates scaled by 2.446; vocab 163840, untied head
(hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3).

``ep8_config`` is one chip's share of an expert-parallel deployment over 8
chips: every layer at full width, experts 0-7 of each MoE layer held (the
router keeps its 64 outputs and its top-6)."""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=11264, vocab_size=163840,
        num_experts=64, num_shared_experts=2, top_k=6, moe_d_ff=1408,
        first_k_dense=1, router_scoring="sigmoid", router_bias=True,
        routed_scale=2.446, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, norm_eps=1e-5,
        rope_theta=50000.0, dtype="bfloat16", tie_embeddings=False)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=128, vocab_size=256,
        num_experts=8, num_shared_experts=2, top_k=2, moe_d_ff=32,
        first_k_dense=1, router_scoring="sigmoid", router_bias=True,
        routed_scale=2.446, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, norm_eps=1e-5,
        rope_theta=50000.0, dtype="float32", tie_embeddings=False)


def ep8_config() -> ModelConfig:
    return config().replace(name="moonlight-16b-a3b-ep8", held_experts=8)


def ep8_smoke_config() -> ModelConfig:
    """A share of a quarter of the smoke layer's 8 experts."""
    return smoke_config().replace(name="moonlight-smoke-share",
                                  held_experts=2)
