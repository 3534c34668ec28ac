"""Plain reference of a DeepSeek-V3-style decoder (Moonlight-16B-A3B), and
its seeded weights, for one chip's share of its experts.

Written from the architecture's published description (``modeling_
deepseek.py`` of the ``deepseek_v3`` model type), in plain ``jax.numpy``
and float32 at full matmul precision, with what a deployment holds in
the configuration's dtype rounded to it (``qwen2._store``): the norms',
projections' and RoPE outputs, the latent, the heads' attention output,
the SwiGLU hidden, each FFN's output and the residual stream. The per-
head keys and values expanded from the latent, the scores and
probabilities, and each expert's output before the gated sum, which an
absorbed, fused deployment never holds, stay in float32. No kernel,
cache, paging, batching or absorption of the program under test, and
nothing of the program is imported. Layer by layer:

* multi-head latent attention, expanded: ``c = RMSNorm(x W_kv_a[:, :R])``
  and ``k_r = RoPE(x W_kv_a[:, R:])``; each head's key ``[c W_kb, k_r]``
  and value ``c W_vb`` are formed from the latent (``W_kv_b``), the query
  ``[q_nope, RoPE(q_r)] = x W_q`` (no q LoRA) scores them at the scale
  ``(D_nope + D_rope) ** -0.5``, causal, and ``W_o`` maps the heads back;
* layers ``< first_k_dense_replace``: a SwiGLU MLP of ``intermediate_size``;
* the others: a sigmoid router over all ``published.n_routed_experts``
  experts, its correction bias added for the choice of the top
  ``num_experts_per_tok`` only, the chosen scores normalised and scaled by
  ``routed_scaling_factor``; the SwiGLU experts of ``moe_intermediate_
  size`` that this share holds (``n_routed_experts`` of them, from
  ``deployment.first_held_expert``), each weighted by its gate where
  chosen; plus the shared experts as one SwiGLU MLP of ``n_shared_experts
  * moe_intermediate_size``. What the experts held elsewhere add is left
  out, as on the chip that runs this share.

Departures from the published model, each with its reason:

* RoPE uses the half-rotation layout over the ``qk_rope_head_dim`` dims,
  not DeepSeek's interleaved one; with random weights the two are the same
  up to a fixed permutation of the RoPE columns of ``W_q`` and ``W_kv_a``;
* RMSNorm weights are ``1 + gamma`` with gamma drawn as zeros (the
  program's parametrisation; the same function at these weights);
* the correction bias is drawn from the seed, small and non-zero, so that
  the choice-only path is exercised (a trained model's bias is learned).

:func:`init_params` draws the weights in the program's parameter layout
(``dense_blocks`` for the leading dense layers, ``blocks`` for the MoE
layers); the reference draws each layer again itself (:func:`layer_
params`). The control variant ``w8a8`` (every weight int8 per output
column, every activation int8 per row, as in ``qwen2``) is the same
forward one precision step lower.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _sibling(name: str):
    """Another reference module of this directory, loaded once."""
    path = Path(__file__).resolve().with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_reference_{name}", path)
    if spec.name not in sys.modules:
        sys.modules[spec.name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[spec.name])
    return sys.modules[spec.name]


_q = _sibling("qwen2")
seed_key = _q.seed_key
served_gaps = _q.served_gaps
_rms, _rope, _dense, _store = _q._rms, _q._rope, _q._dense, _q._store


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int
    heads: int
    rank: int                 # kv_lora_rank
    nope: int
    rope: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    experts: int              # the router's width (published)
    held: int                 # experts this share holds
    first_held: int
    top_k: int
    shared: int
    dense_layers: int
    vocab: int
    eps: float
    theta: float
    routed_scale: float
    tie: bool
    dtype: str

    @property
    def row(self) -> int:
        """The program's latent row width (latent + RoPE key, zero-padded
        to whole 128-lane tiles); the reference does not use it."""
        return -(-(self.rank + self.rope) // 128) * 128


def sizes_from_config(cfg: dict) -> Sizes:
    """:class:`Sizes` from a configuration file of ``bench/configs``."""
    if cfg["projection"]["mode"] != "dense":
        raise ValueError("the deepseek_v3 reference is dense only")
    if (cfg.get("q_lora_rank") is not None or cfg["scoring_func"] != "sigmoid"
            or not cfg["norm_topk_prob"]):
        raise ValueError("q LoRA, softmax routers and unnormalised gates "
                         "are not written here")
    return Sizes(
        layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
        heads=cfg["num_attention_heads"], rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"],
        experts=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"],
        first_held=cfg["deployment"].get("first_held_expert", 0),
        top_k=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        dense_layers=cfg["first_k_dense_replace"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        routed_scale=cfg["routed_scaling_factor"],
        tie=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _w(s: Sizes, key, *shape):
    """A weight of ``shape`` (.., K, N), N(0, 1/K), in the served dtype."""
    return {"w": (shape[-2] ** -0.5 * jax.random.normal(key, shape))
            .astype(s.dtype)}


def layer_params(s: Sizes, key, moe: bool) -> dict:
    """One decoder layer's weights from its layer key."""
    dt = jnp.dtype(s.dtype)
    ka, kf = jax.random.split(key)
    ks = jax.random.split(ka, 4)
    h = s.heads
    attn = {"wq": _w(s, ks[0], s.d, h * (s.nope + s.rope)),
            "wkv_a": _w(s, ks[1], s.d, s.rank + s.rope),
            "wkv_b": _w(s, ks[2], s.rank, h * (s.nope + s.v_dim)),
            "wo": _w(s, ks[3], h * s.v_dim, s.d),
            "norm": jnp.zeros((s.d,), dt),
            "kv_norm": jnp.zeros((s.rank,), dt)}
    km = jax.random.split(kf, 8)
    if not moe:
        return {"attn": attn, "mlp": {
            "wg": _w(s, km[0], s.d, s.dense_ff),
            "wu": _w(s, km[1], s.d, s.dense_ff),
            "wd": _w(s, km[2], s.dense_ff, s.d),
            "norm": jnp.zeros((s.d,), dt)}}
    f = s.expert_ff
    return {"attn": attn, "moe": {
        "router": (s.d ** -0.5 * jax.random.normal(km[0], (s.d, s.experts))
                   ).astype(dt),
        "router_bias": 0.03 * jax.random.normal(km[7], (s.experts,),
                                                jnp.float32),
        "wg": _w(s, km[1], s.held, s.d, f),
        "wu": _w(s, km[2], s.held, s.d, f),
        "wd": _w(s, km[3], s.held, f, s.d),
        "shared_wg": _w(s, km[4], s.shared, s.d, f),
        "shared_wu": _w(s, km[5], s.shared, s.d, f),
        "shared_wd": _w(s, km[6], s.shared, f, s.d),
        "norm": jnp.zeros((s.d,), dt)}}


def _keys(s: Sizes, key):
    ke, kb, kh, _ = jax.random.split(key, 4)
    return ke, jax.random.split(kb, s.layers), kh


def _embed(s: Sizes, ke):
    return (0.02 * jax.random.normal(ke, (s.vocab, s.d))).astype(s.dtype)


def _head(s: Sizes, kh):
    return (0.02 * jax.random.normal(kh, (s.d, s.vocab))).astype(s.dtype)


def init_params(s: Sizes, key) -> dict:
    """All weights in the serving engine's layout: ``embed``, ``head``,
    ``final_norm``, the leading dense layers stacked under
    ``dense_blocks`` and the MoE layers under ``blocks``. Jit it."""
    ke, lk, kh = _keys(s, key)
    k = s.dense_layers
    p = {"final_norm": jnp.zeros((s.d,), s.dtype), "embed": _embed(s, ke),
         "blocks": jax.vmap(lambda kk: layer_params(s, kk, True))(lk[k:])}
    if k:
        p["dense_blocks"] = jax.vmap(
            lambda kk: layer_params(s, kk, False))(lk[:k])
    if not s.tie:
        p["head"] = _head(s, kh)
    return p


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _swiglu(s: Sizes, x, wg, wu, wd, variant: str):
    """SwiGLU with its gate, up and product in the activations' dtype and
    its output in float32 (the caller rounds it where the model does)."""
    st = _store(s)
    g = st(_dense(x, {"w": wg}, variant))
    u = st(_dense(x, {"w": wu}, variant))
    return _dense(st(jax.nn.silu(g) * u), {"w": wd}, variant)


def _attention(s: Sizes, x, a, variant: str):
    st = _store(s)
    b, t, _ = x.shape
    h, r = s.heads, s.rank
    xn = st(_rms(x, a["norm"], s.eps))
    q = st(_dense(xn, a["wq"], variant)).reshape(b, t, h, s.nope + s.rope)
    kv = st(_dense(xn, a["wkv_a"], variant))
    c = st(_rms(kv[..., :r], a["kv_norm"], s.eps))
    k_r = st(_rope(kv[..., None, r:], s.theta))            # (B, T, 1, Dr)
    q_r = st(_rope(q[..., s.nope:], s.theta))
    kvb = _dense(c, a["wkv_b"], variant).reshape(b, t, h, s.nope + s.v_dim)
    k = jnp.concatenate(
        [kvb[..., :s.nope], jnp.broadcast_to(k_r, (b, t, h, s.rope))], -1)
    v = kvb[..., s.nope:]
    qf = jnp.concatenate([q[..., :s.nope], q_r], -1)
    sc = (jnp.einsum("bqhd,bkhd->bhqk", qf, k, precision=HI)
          * (s.nope + s.rope) ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = st(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                      precision=HI)).reshape(b, t, h * s.v_dim)
    return st(_dense(o, a["wo"], variant))


def _moe(s: Sizes, xn, m, variant: str):
    """The share's routed experts plus the shared experts, of normed xn."""
    st = _store(s)
    logits = _dense(xn, {"w": m["router"]}, variant)       # (B, T, E)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + m["router_bias"], s.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * s.routed_scale
    routed = jnp.zeros_like(xn)
    for e in range(s.held):
        gate = jnp.sum(jnp.where(idx == s.first_held + e, w, 0.0), -1)
        y = _swiglu(s, xn, m["wg"]["w"][e], m["wu"]["w"][e],
                    m["wd"]["w"][e], variant)
        routed = routed + gate[..., None] * y
    # the shared experts as DeepSeek writes them: one MLP of their summed
    # width (the intermediate dims of the stored experts side by side)
    cat = lambda t, ax: jnp.concatenate(list(t), axis=ax)
    shared = _swiglu(s, xn, cat(m["shared_wg"]["w"], 1),
                     cat(m["shared_wu"]["w"], 1),
                     cat(m["shared_wd"]["w"], 0), variant)
    return st(routed + shared)


def _layer(s: Sizes, x, key, moe: bool, variant: str):
    """One decoder layer over (B, T, d), causal from position 0."""
    st = _store(s)
    p = layer_params(s, key, moe)
    x = st(x + _attention(s, x, p["attn"], variant))
    f = p["moe"] if moe else p["mlp"]
    xn = st(_rms(x, f["norm"], s.eps))
    if moe:
        return st(x + _moe(s, xn, f, variant))
    return st(x + st(_swiglu(s, xn, f["wg"]["w"], f["wu"]["w"],
                             f["wd"]["w"], variant)))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _layer_jit(s: Sizes, x, key, moe: bool, variant: str):
    return _layer(s, x, key, moe, variant)


@functools.partial(jax.jit, static_argnums=0)
def _embed_rows(s: Sizes, ke, tokens):
    return _embed(s, ke)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=0)
def _final_norm(s: Sizes, x):
    return _store(s)(_rms(x, jnp.zeros((s.d,), jnp.float32), s.eps))


@functools.partial(jax.jit, static_argnums=0)
def _head_of(s: Sizes, ke, kh):
    return _embed(s, ke).T if s.tie else _head(s, kh)


@dataclasses.dataclass
class Reference:
    """The reference over a batch of sequences, for one weight seed."""
    s: Sizes
    seed: int

    def __post_init__(self):
        self._ke, self._lk, self._kh = _keys(self.s, seed_key(self.seed))

    def hidden(self, tokens, variant: str = "ref"):
        """(B, T) int32 tokens -> (B, T, d) final-normed states, one layer
        resident at a time."""
        x = _embed_rows(self.s, self._ke, jnp.asarray(tokens))
        for li in range(self.s.layers):
            x = _layer_jit(self.s, x, self._lk[li],
                           li >= self.s.dense_layers, variant)
        return _final_norm(self.s, x)

    def head(self) -> jax.Array:
        """(d, vocab) output head, in the served dtype."""
        return _head_of(self.s, self._ke, self._kh)
