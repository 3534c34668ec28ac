"""Mixture-of-Experts FFN: top-k routing, an expert share, shared experts.

The layer holds ``cfg.experts_held`` of the router's ``cfg.num_experts``
experts, from ``cfg.first_held`` on: one chip's share of an
expert-parallel deployment (all of them when nothing is set). It routes
every token over all experts, keeps the gates of the (token, choice) pairs
whose expert it holds, normalised over the token's whole top-k, and
computes its own experts' part of the output; what the absent experts add
is left to the chips that hold them (on one chip the layer runs without
its exchange). Shared experts are computed here in full.

Dispatch is drop-free: each held expert runs over every token of the step
and its gate column zeroes the tokens not routed to it, so no (token,
choice) is ever dropped and a token's output does not depend on the
other tokens of the call (decode batch, prefill chunk width). On a TPU
the held experts' SwiGLU, weighted and summed, is one Mosaic kernel
(``kernels/moe_experts.py``, named ``moe_experts`` in the trace), and
so is the shared experts' (``moe_shared``); elsewhere, and for LUT
projections, the same sums run in XLA. Both sums stay in float32 until
the layer's output is rounded once.

Routers: ``softmax`` (DeepSeek-MoE, DBRX) or ``sigmoid`` scores, an
optional correction bias added for the choice only (DeepSeek-V3's
``noaux_tc`` with one group), gates normalised over the top-k and scaled
by ``cfg.routed_scale``.

Expert FFNs support LUT-DLA quantisation with *per-expert* codebooks and
LUTs (shape (E, nc, c, v) / (E, nc, c, N)) — the paper's technique
extended to the MoE family.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.lut import QuantConfig
from repro.core.similarity import ste_quantize_subspaces
from repro.kernels import ops as kops
from repro.kernels.moe_experts import (EXPERTS_NAME, SHARED_NAME,
                                      moe_experts)
from .layers import rms_norm

Params = Dict


def init_expert_proj(key, e: int, k: int, n: int, qc: QuantConfig, dtype):
    kw, kz = jax.random.split(key)
    p = {"w": (jax.random.normal(kw, (e, k, n)) / (k ** 0.5)).astype(dtype)}
    if qc.is_lut:
        nc = k // qc.v
        p["z"] = (0.02 * jax.random.normal(kz, (e, nc, qc.c, qc.v))
                  ).astype(dtype)
    return p


def expert_proj(p: Params, x: jax.Array, qc: QuantConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """Batched per-expert projection.

    Args:
      p: {"w": (E, K, N)} plus, in LUT modes, "z" (E, nc, c, v) and — after
        ``precompute_model`` — "lut" (E, nc, c, N) / "lut_scale" (E, N).
      x: (E, T, K) each expert's input rows.
      qc: operating point; in ``lut_infer`` the per-expert codebooks ride
        the same fused/two-pass kernel dispatch as every other projection.

    Returns: ((E, T, N) outputs, scalar recon loss — nonzero only in
    ``lut_train``). Mirrors ``lut_linear_apply`` vmapped over experts.
    """
    zero = jnp.zeros((), jnp.float32)
    if qc.mode == "dense" or "z" not in p:
        return jnp.einsum("ecd,edf->ecf", x, p["w"]), zero
    e, t, k = x.shape
    xs = x.reshape(e, t, k // qc.v, qc.v)
    if qc.mode == "lut_train":
        x_hat = jax.vmap(
            lambda xx, zz: ste_quantize_subspaces(xx, zz, qc.metric)
        )(xs, p["z"]).reshape(e, t, k).astype(x.dtype)
        out_q = jnp.einsum("ecd,edf->ecf", x_hat, p["w"])
        out_d = jnp.einsum("ecd,edf->ecf", x, p["w"])
        sg = jax.lax.stop_gradient
        recon = (jnp.mean((sg(out_q) - out_d) ** 2)
                 + jnp.mean((out_q - sg(out_d)) ** 2)).astype(jnp.float32)
        return out_d + sg(out_q - out_d), recon
    # lut_infer — per-expert codebooks through the shared kernel dispatch,
    # so experts ride the same Pallas/fused paths as every other projection.
    lut = p.get("lut")
    if lut is None:
        lut = jax.vmap(lambda w, z: jnp.einsum(
            "kcv,kvn->kcn", z.astype(jnp.float32),
            w.reshape(z.shape[0], qc.v, -1).astype(jnp.float32)))(
                p["w"], p["z"])
    scale = p.get("lut_scale")           # (E, N) when the LUT is int8
    s_ax = None if scale is None else 0  # None is an empty pytree under vmap
    if qc.fuse:
        out = jax.vmap(
            lambda xx, zz, ll, ss: kops.vq_amm(
                xx, zz, ll, ss, qc.metric, impl=qc.impl),
            in_axes=(0, 0, 0, s_ax))(xs, p["z"], lut, scale)
    else:
        idx = jax.vmap(lambda xx, zz: kops.vq_assign(
            xx, zz, qc.metric, impl=qc.impl))(xs, p["z"])
        out = jax.vmap(
            lambda ii, ll, ss: kops.lut_matmul(ii, ll, ss, impl=qc.impl),
            in_axes=(0, 0, s_ax))(idx, lut, scale)
    return out.astype(x.dtype), zero


def init_moe(key, cfg, qc: QuantConfig, dtype):
    d, f = cfg.d_model, cfg.expert_ff
    e, held = cfg.num_experts, cfg.experts_held
    ks = jax.random.split(key, 8)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) / (d ** 0.5)).astype(dtype),
        "wg": init_expert_proj(ks[1], held, d, f, qc, dtype),
        "wu": init_expert_proj(ks[2], held, d, f, qc, dtype),
        "wd": init_expert_proj(ks[3], held, f, d, qc, dtype),
        "norm": jnp.zeros((d,), dtype),
    }
    if cfg.router_bias:
        p["router_bias"] = 0.03 * jax.random.normal(ks[7], (e,), jnp.float32)
    if cfg.num_shared_experts:
        se = cfg.num_shared_experts
        p["shared_wg"] = init_expert_proj(ks[4], se, d, f, qc, dtype)
        p["shared_wu"] = init_expert_proj(ks[5], se, d, f, qc, dtype)
        p["shared_wd"] = init_expert_proj(ks[6], se, f, d, qc, dtype)
    return p


def route(p: Params, tokens: jax.Array, cfg):
    """Top-k routing of ``tokens`` (T, D) over all ``cfg.num_experts``.

    Returns (gates (T, held) float32: each token's normalised, scaled
    weight of every held expert, 0 where not chosen; hits (T, held)
    int32: 1 where chosen; the load-balancing aux loss)."""
    e, k = cfg.num_experts, cfg.top_k
    logits = jnp.dot(tokens.astype(jnp.float32),
                     p["router"].astype(jnp.float32))          # (T, E)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores + p["router_bias"] if "router_bias" in p else scores
    _, idx = jax.lax.top_k(choice, k)                          # (T, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-20)
    w = w * cfg.routed_scale
    # one_hot of an index outside [0, held) is a zero row: the choices
    # of experts held elsewhere fall out here
    oh = jax.nn.one_hot(idx - cfg.first_held, cfg.experts_held,
                        dtype=jnp.float32)                     # (T, k, held)
    gates = jnp.sum(w[..., None] * oh, axis=1)          # f32, not an MXU pass
    hits = jnp.sum(oh, axis=1).astype(jnp.int32)
    # load-balancing aux loss (mean_e f_e * p_e * E) over all experts
    share = scores / jnp.sum(scores, -1, keepdims=True)
    ce = jnp.mean(jax.nn.one_hot(idx, e).sum(1), axis=0) / k
    aux = e * jnp.sum(jnp.mean(share, axis=0) * ce)
    return gates, hits, aux


EXPERT_KEYS = ("wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd")


def dense_experts(p: Params, qc: QuantConfig) -> bool:
    """Whether the experts run as dense SwiGLUs (the Mosaic kernel on a
    TPU) rather than through LUT projections."""
    return qc.mode == "dense" or "z" not in p["wg"]


def moe_ffn(p: Params, x: jax.Array, cfg, qc: QuantConfig, live=None,
            layer=None) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Routed share + shared experts. x (B, S, D) -> (out (B, S, D), recon,
    aux_loss, load (held,) int32: the (token, choice) pairs routed to each
    held expert, over the tokens where ``live`` (B*S,) holds, or all).

    ``layer``: the expert weights of ``p`` (``EXPERT_KEYS``, dense only)
    are stacked over the MoE layers and this layer's are at ``layer``; the
    kernel reads them there (a per-layer slice of them would be copied
    out for the Mosaic call: 17 MB an expert, every layer and step)."""
    b, s, d = x.shape
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    tokens = xn.reshape(b * s, d)
    gates, hits, aux = route(p, tokens, cfg)
    if live is not None:
        hits = hits * live.reshape(-1, 1).astype(jnp.int32)
    load = jnp.sum(hits, axis=0)
    chunk = "_chunk" if s > 1 else ""
    out, recon = experts_sum(p, tokens, gates, qc, "", EXPERTS_NAME + chunk,
                             layer)
    if "shared_wg" in p:
        ones = jnp.ones((b * s, p["shared_wg"]["w"].shape[-3]), jnp.float32)
        sy, r2 = experts_sum(p, tokens, ones, qc, "shared_",
                             SHARED_NAME + chunk, layer)
        out, recon = out + sy, recon + r2
    return (out.astype(x.dtype).reshape(b, s, d), recon,
            aux.astype(jnp.float32), load)


def experts_sum(p: Params, tokens: jax.Array, gates: jax.Array,
                qc: QuantConfig, prefix: str, name: str, layer=None
                ) -> Tuple[jax.Array, jax.Array]:
    """sum_e gates[:, e] * SwiGLU_e(tokens) over the experts
    ``p[prefix + "wg"/"wu"/"wd"]`` (stacked over layers, at ``layer``, if
    given): (out (T, D) float32, recon). ``name`` names the kernel in the
    trace (see ``kernels/moe_experts.py``)."""
    wg, wu, wd = (p[prefix + k] for k in ("wg", "wu", "wd"))
    if dense_experts(p, qc):
        return (moe_experts(tokens, gates, wg["w"], wu["w"], wd["w"], name,
                            layer=layer), jnp.zeros((), jnp.float32))
    n = gates.shape[1]
    xin = jnp.broadcast_to(tokens[None], (n,) + tokens.shape)
    g, r1 = expert_proj(wg, xin, qc)
    u, r2 = expert_proj(wu, xin, qc)
    y, r3 = expert_proj(wd, jax.nn.silu(g) * u, qc)          # (E, T, D)
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), gates)
    return out, r1 + r2 + r3
