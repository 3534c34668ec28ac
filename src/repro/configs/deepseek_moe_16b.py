"""deepseek-moe-16b [moe]: 28L d_model=2048 16H (kv=16); layer 0 is a dense
MLP of width 10944, layers 1-27 hold 2 shared + 64 routed experts of width
1408, top-6 (arXiv:2401.06066, hf:deepseek-ai/deepseek-moe-16b-base)."""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-moe-16b", family="moe",
        num_layers=28, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=10944, vocab_size=102400,
        num_experts=64, num_shared_experts=2, top_k=6, moe_d_ff=1408,
        first_k_dense=1, dtype="bfloat16", attn_impl="chunked",
        tie_embeddings=False)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-smoke", family="moe",
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=32, vocab_size=256,
        num_experts=8, num_shared_experts=2, top_k=2,
        dtype="float32", tie_embeddings=False)
