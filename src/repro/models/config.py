"""Unified model configuration covering all assigned architecture families."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None   # default: d_model // num_heads
    qkv_bias: bool = False
    # attention pattern (gemma3): every `global_every`-th layer is global,
    # the rest use `sliding_window`. 0 = all layers global (full causal).
    global_every: int = 0
    sliding_window: int = 0

    # MoE. ``num_experts`` is the router's width; an expert-parallel
    # share holds ``held_experts`` of them (0 = all), from ``first_held``
    # on. Layers ``i < first_k_dense`` keep a dense MLP of width d_ff;
    # the experts' own width is ``moe_d_ff`` (0 = d_ff).
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0
    held_experts: int = 0
    first_held: int = 0
    router_scoring: str = "softmax"  # softmax | sigmoid
    router_bias: bool = False        # choice-only score correction bias
    routed_scale: float = 1.0        # scale of the normalised gates

    # latent attention (MLA): K/V are one shared latent row of
    # ``kv_lora_rank`` plus a ``qk_rope_head_dim`` RoPE key per position;
    # 0 = per-head K/V attention
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_ngroups: int = 1
    ssm_conv: int = 4

    # hybrid (zamba2): one *shared* attention block applied every N layers
    shared_attn_every: int = 0

    # audio (musicgen): number of parallel codebook heads; inputs are
    # precomputed frame embeddings from the (stubbed) EnCodec frontend.
    num_codebooks: int = 0

    # vlm (paligemma): number of precomputed patch embeddings prepended to
    # the token sequence (SigLIP frontend is a stub).
    num_patches: int = 0

    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # "heads": classic (B,S,H,HD) layout, half-rotation RoPE.
    # "hd": head_dim-major (B,S,HD,H) layout + interleaved RoPE — head_dim
    #       TP-shards cleanly (projection columns are hd-major contiguous)
    #       and the interleaved rotation is local to any even-sized hd
    #       shard, eliminating resharding collectives.
    head_layout: str = "heads"
    dtype: str = "float32"           # params/activations dtype
    tie_embeddings: bool = True
    # attention softmax/score implementation: "naive" or "chunked"
    attn_impl: str = "naive"
    attn_chunk: int = 1024
    remat: bool = False              # activation checkpointing per block

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.num_heads, 1))

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def pure_full_attention(self) -> bool:
        """True if every layer is unwindowed full attention (no SSM/local
        structure) — these archs skip the long_500k shape."""
        if self.family in ("ssm", "hybrid"):
            return False
        return self.global_every == 0 or self.sliding_window == 0

    @property
    def mla(self) -> bool:
        """Latent attention (a latent page pool) rather than per-head K/V."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Width of a latent pool row: the latent and the RoPE key, padded
        with zeros to a whole number of 128-lane tiles (the padding the
        TPU's tiled layout gives such a row in HBM anyway)."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // 128) * 128

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> int:
        return self.held_experts or self.num_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def layer_is_global(self, i: int) -> bool:
        if self.global_every <= 0:
            return True
        return (i % self.global_every) == (self.global_every - 1)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), for 6ND math."""
        d, f, L = self.d_model, self.d_ff, self.num_layers
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        attn = self._attn_params()
        mlp = 3 * d * f
        if self.num_experts:
            moe = (self.experts_held + self.num_shared_experts) * 3 * d \
                * self.expert_ff + d * self.num_experts
            return emb + L * attn + self.first_k_dense * mlp \
                + self.num_moe_layers * moe
        if self.family == "ssm":
            return emb + L * self._ssm_block_params()
        if self.family == "hybrid":
            # shared attention block counted once
            return emb + L * self._ssm_block_params() + (attn + 3 * d * f)
        per_layer = attn + mlp
        total = emb + L * per_layer
        if self.family == "audio":
            total += self.num_codebooks * self.vocab_size * d
        return total

    def _attn_params(self) -> int:
        d, h = self.d_model, self.num_heads
        if self.mla:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            return (d * h * (self.qk_nope_head_dim + rope) + d * (r + rope)
                    + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d)
        hd = self.head_dim
        return d * (h * hd) + 2 * d * (self.num_kv_heads * hd) + (h * hd) * d

    def _ssm_block_params(self) -> int:
        d = self.d_model
        din = self.d_inner
        n = self.ssm_state
        g = self.ssm_ngroups
        h = self.ssm_nheads
        in_proj = d * (2 * din + 2 * g * n + h)
        out_proj = din * d
        conv = (din + 2 * g * n) * self.ssm_conv
        return in_proj + out_proj + conv + 2 * h + din

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only routed top-k + shared)."""
        if not self.num_experts:
            return self.param_count()
        d, L = self.d_model, self.num_layers
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        moe_active = (self.top_k + self.num_shared_experts) * 3 * d \
            * self.expert_ff + d * self.num_experts
        return (emb + L * self._attn_params()
                + self.first_k_dense * 3 * d * self.d_ff
                + self.num_moe_layers * moe_active)
