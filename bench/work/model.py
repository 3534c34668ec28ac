"""Dense-equivalent model FLOPs per token (the yardstick of ``mfu.*``).

2 x (projection parameters of every layer + the LM head), plus attention
at 4 * layers * (heads * head_dim) * context. The same count for the
LUT and bf16 configurations, so their MFU compare directly."""


def flops_per_token(layers: int, d: int, heads: int, kv_heads: int,
                    head_dim: int, ff: int, vocab: int, context: int) -> int:
    q, kv = heads * head_dim, kv_heads * head_dim
    proj = d * q + 2 * d * kv + q * d + 3 * d * ff
    return 2 * (layers * proj + d * vocab) + 4 * layers * q * context
