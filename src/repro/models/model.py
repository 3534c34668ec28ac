"""Unified model: one class covering all six assigned architecture families.

Layer stacks are ``jax.lax.scan`` over stacked block parameters (leading
layer axis) — this keeps HLO size and CPU compile time tractable at
62-layer × 512-device dry-run scale. Per-layer heterogeneity (gemma3's 5:1
local:global pattern, zamba2's shared attention block) is expressed as
per-layer scalars fed through the scan.

API (all functional, params are plain pytrees):

  init(key, qc)                     -> params
  forward(params, batch, qc)        -> (logits, aux)
  loss(params, batch, qc)           -> (scalar, metrics)
  init_cache(batch, max_seq)        -> cache
  prefill(params, batch, cache, qc) -> (next_logits, cache)
  decode(params, tokens, cache, qc) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.kv_codebook import (CODEBOOK_KEY, kv_decode_stacked,
                                    kv_encode_stacked)
from repro.core.lut import DENSE, QuantConfig
from repro.kernels.flash_decode import resolve_flash_impl
from .config import ModelConfig
from .layers import (attention, init_attention, init_mlp, mlp, rms_norm)
from .mamba2 import init_mamba2, mamba2_block, mamba2_decode
from .moe import init_moe, moe_ffn

Params = Dict[str, Any]

ATTN_FAMILIES = ("dense", "moe", "audio", "vlm")


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    @property
    def dtype(self):
        return jnp.dtype(self.cfg.dtype)

    def _init_block(self, key, qc: QuantConfig):
        cfg, dtype = self.cfg, self.dtype
        if cfg.family in ("ssm", "hybrid"):
            return init_mamba2(key, cfg, qc, dtype)
        ka, kf = jax.random.split(key)
        block = {"attn": init_attention(ka, cfg, qc, dtype)}
        if cfg.family == "moe":
            block["moe"] = init_moe(kf, cfg, qc, dtype)
        else:
            block["mlp"] = init_mlp(kf, cfg.d_model, cfg.d_ff, cfg, qc, dtype)
        return block

    def init(self, key: jax.Array, qc: QuantConfig = DENSE) -> Params:
        """Random params from ``key``. Jitted, so each stacked weight is
        drawn and cast to ``cfg.dtype`` in one fused program (no fp32
        copy of the full stack is ever resident)."""
        return jax.jit(self._init, static_argnums=1)(key, qc)

    def _init(self, key: jax.Array, qc: QuantConfig) -> Params:
        cfg, dtype = self.cfg, self.dtype
        ke, kb, kh, ks = jax.random.split(key, 4)
        params: Params = {"final_norm": jnp.zeros((cfg.d_model,), dtype)}
        if cfg.family != "audio":
            params["embed"] = (0.02 * jax.random.normal(
                ke, (cfg.vocab_size, cfg.d_model))).astype(dtype)
        layer_keys = jax.random.split(kb, cfg.num_layers)
        params["blocks"] = jax.vmap(
            lambda k: self._init_block(k, qc))(layer_keys)
        if cfg.family == "hybrid":
            ka, km = jax.random.split(ks)
            params["shared_attn"] = {
                "attn": init_attention(ka, cfg, qc, dtype),
                "mlp": init_mlp(km, cfg.d_model, cfg.d_ff, cfg, qc, dtype),
            }
        if cfg.family == "audio":
            params["heads"] = (0.02 * jax.random.normal(
                kh, (cfg.num_codebooks, cfg.d_model, cfg.vocab_size))
            ).astype(dtype)
            # audio inputs are stub frame embeddings; a learned input proj
            # stands in for the EnCodec codebook-sum embedding.
            params["in_proj"] = (0.02 * jax.random.normal(
                ke, (cfg.d_model, cfg.d_model))).astype(dtype)
        elif not cfg.tie_embeddings:
            params["head"] = (0.02 * jax.random.normal(
                kh, (cfg.d_model, cfg.vocab_size))).astype(dtype)
        return params

    # ------------------------------------------------------------------
    # embedding / head per family
    # ------------------------------------------------------------------
    def _embed(self, params: Params, batch: Dict) -> Tuple[jax.Array, int]:
        """Returns (x (B, S, D), prefix_len)."""
        cfg = self.cfg
        if cfg.family == "audio":
            x = batch["embeds"].astype(self.dtype) @ params["in_proj"]
            return x, 0
        if cfg.family == "vlm":
            tok = params["embed"][batch["tokens"]]
            patches = batch["patch_embeds"].astype(self.dtype)
            return jnp.concatenate([patches, tok], axis=1), cfg.num_patches
        return params["embed"][batch["tokens"]], 0

    def _head(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "audio":
            return jnp.einsum("bsd,qdv->bsqv", x, params["heads"])
        if cfg.tie_embeddings:
            return x @ params["embed"].T
        return x @ params["head"]

    # ------------------------------------------------------------------
    # per-layer static metadata
    # ------------------------------------------------------------------
    def _windows(self) -> jax.Array:
        cfg = self.cfg
        return jnp.array(
            [0 if cfg.layer_is_global(i) else cfg.sliding_window
             for i in range(cfg.num_layers)], jnp.int32)

    def _attn_slot_list(self):
        """Hybrid: shared-attention invocation slot per layer (-1 = none)."""
        cfg = self.cfg
        slots, s = [], 0
        for i in range(cfg.num_layers):
            if cfg.shared_attn_every and (i % cfg.shared_attn_every
                                          == cfg.shared_attn_every - 1):
                slots.append(s)
                s += 1
            else:
                slots.append(-1)
        return slots

    def _attn_slots(self) -> jax.Array:
        return jnp.array(self._attn_slot_list(), jnp.int32)

    @property
    def num_attn_slots(self) -> int:
        return sum(1 for s in self._attn_slot_list() if s >= 0)

    # ------------------------------------------------------------------
    # block runners
    # ------------------------------------------------------------------
    def _run_blocks(self, params: Params, x: jax.Array, qc: QuantConfig,
                    q_offset, prefix_len,
                    cache: Optional[Params] = None,
                    kv_start=0, valid_len=None, return_slabs: bool = False,
                    multi_slab: bool = False,
                    paged_phys=None, flash_impl: str = "ref"):
        """Scan over the layer stack. Returns (x, recon, moe_aux, new_cache).

        q_offset: scalar, or (B,) per-row decode positions (paged serving).
        kv_start: scalar or (B,) — mask cache rows < kv_start (left-padded
          batch prompts; see ``layers.attention``).
        valid_len: scalar — right-padded chunked prefill; only the SSM path
          consumes it (attention pads are handled by causality + the
          caller's write masking).
        return_slabs: single-token decode only — return the per-layer
          new-token KV slabs instead of writing them into ``cache`` at
          ``q_offset`` (the paged-cache caller scatters them itself; this
          is what makes per-slot write positions possible).
        multi_slab: treat a MULTI-token input like the decode slab path
          (speculative verify): the cache stays read-only, per-row
          q_offset positions are honoured, and each layer emits a
          (B, S, KVH, HD) fresh-KV slab. Attention families only;
          requires ``return_slabs``.
        paged_phys / flash_impl: single-token decode only — ``cache`` is
          the raw paged POOL (``{"k": (L, P+1, page, KVH, HD), ...}``)
          and each layer's attention runs the flash-decode kernel
          through the page table instead of over a gathered view (see
          ``decode_paged`` and kernels/flash_decode.py).
        """
        cfg = self.cfg
        windows = self._windows()
        decode = cache is not None and (x.shape[1] == 1 or multi_slab)

        if cfg.family in ATTN_FAMILIES:
            # Cache handling [§Perf I3/I5]:
            #  * decode: the cache is a scan INVARIANT (read-only per-layer
            #    slices are free); each layer emits only its new-token KV
            #    slab via ys, and the cache is updated ONCE after the scan.
            #  * prefill: the cache travels in the carry and each layer
            #    updates its slice in place — streaming it through xs/ys
            #    would rebuild the full stacked buffer every layer.
            # Layer grouping [§Perf I8]: local:global patterns (gemma3) scan
            # over groups of `global_every` with the window STATIC per
            # sub-layer, enabling the block-local attention fast path.
            slab_mode = decode and cfg.head_layout != "hd"

            def layer_fn(h, recon, aux, c_full, p_l, win, li):
                src = cache if slab_mode else c_full
                c_l = None
                if src is not None:
                    c_l = jax.tree_util.tree_map(
                        lambda t: jax.lax.dynamic_index_in_dim(
                            t, li, 0, keepdims=False), src)
                a, r1, new_c = attention(p_l["attn"], h, cfg, qc,
                                         q_offset=q_offset, window=win,
                                         prefix_len=prefix_len, cache=c_l,
                                         decode_slab=slab_mode,
                                         kv_start=kv_start,
                                         paged_phys=paged_phys,
                                         flash_impl=flash_impl)
                h = h + a
                if cfg.family == "moe":
                    f, r2, a2 = moe_ffn(p_l["moe"], h, cfg, qc)
                    aux = aux + a2
                else:
                    f, r2 = mlp(p_l["mlp"], h, cfg, qc)
                h = h + f
                slab = None
                if slab_mode:
                    slab = new_c
                elif c_full is not None:
                    c_full = jax.tree_util.tree_map(
                        lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                            full, upd.astype(full.dtype), li, 0),
                        c_full, new_c)
                return h, recon + r1 + r2, aux, c_full, slab

            ge = cfg.global_every
            # grouping pays off where the static window enables the
            # block-local path (train/prefill); decode keeps the flat scan
            # (slab path) — grouping there only perturbs fusion patterns.
            grouped = (ge > 1 and cfg.sliding_window > 0
                       and cfg.num_layers >= ge and not decode)
            carry_cache = cache is not None and not slab_mode
            z0 = jnp.zeros((), jnp.float32)

            if grouped:
                n_groups, tail = divmod(cfg.num_layers, ge)
                gp = jax.tree_util.tree_map(
                    lambda t: t[:n_groups * ge].reshape(
                        n_groups, ge, *t.shape[1:]), params["blocks"])
                tail_p = jax.tree_util.tree_map(
                    lambda t: t[n_groups * ge:], params["blocks"])

                def gbody(carry, xs):
                    if carry_cache:
                        h, recon, aux, c_full = carry
                    else:
                        h, recon, aux = carry
                        c_full = None
                    g_params, gid = xs
                    slabs = []
                    for j in range(ge):
                        p_l = jax.tree_util.tree_map(
                            lambda t: t[j], g_params)
                        win = 0 if j == ge - 1 else cfg.sliding_window
                        li = gid * ge + j
                        h, recon, aux, c_full, slab = layer_fn(
                            h, recon, aux, c_full, p_l, win, li)
                        slabs.append(slab)
                    ys = (jax.tree_util.tree_map(
                        lambda *t: jnp.stack(t), *slabs)
                        if slab_mode else None)
                    if carry_cache:
                        return (h, recon, aux, c_full), ys
                    return (h, recon, aux), ys

                if cfg.remat:
                    gbody = jax.checkpoint(gbody)
                gids = jnp.arange(n_groups, dtype=jnp.int32)
                carry0 = (x, z0, z0, cache) if carry_cache else (x, z0, z0)
                carry, ys = jax.lax.scan(gbody, carry0, (gp, gids))
                if carry_cache:
                    x, recon, aux, new_cache = carry
                else:
                    x, recon, aux = carry
                    new_cache = cache if slab_mode else None
                slab_list = []
                if slab_mode and ys is not None:
                    flat = jax.tree_util.tree_map(
                        lambda t: t.reshape(-1, *t.shape[2:]), ys)
                    slab_list.append(flat)
                # tail layers (num_layers % global_every), unscanned
                c_full = new_cache if carry_cache else None
                for j in range(tail):
                    li = n_groups * ge + j
                    p_l = jax.tree_util.tree_map(lambda t: t[j], tail_p)
                    win = 0 if cfg.layer_is_global(li) else \
                        cfg.sliding_window
                    x, recon, aux, c_full, slab = layer_fn(
                        x, recon, aux, c_full, p_l, win, jnp.int32(li))
                    if slab_mode:
                        slab_list.append(jax.tree_util.tree_map(
                            lambda t: t[None], slab))
                if carry_cache:
                    new_cache = c_full
                if slab_mode:
                    slabs = jax.tree_util.tree_map(
                        lambda *t: jnp.concatenate(t, 0), *slab_list)
                    new_cache = {
                        key: jax.lax.dynamic_update_slice_in_dim(
                            cache[key], slabs[key].astype(cache[key].dtype),
                            q_offset, axis=2)
                        for key in ("k", "v")}
                return x, recon, aux, new_cache

            def body(carry, xs):
                if carry_cache:
                    h, recon, aux, c_full = carry
                else:
                    h, recon, aux = carry
                    c_full = None
                p_l, win, li = xs
                h, recon, aux, c_full, slab = layer_fn(
                    h, recon, aux, c_full, p_l, win, li)
                if carry_cache:
                    return (h, recon, aux, c_full), slab
                return (h, recon, aux), slab

            if cfg.remat:
                body = jax.checkpoint(body)
            layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
            xs = (params["blocks"], windows, layer_ids)
            carry0 = (x, z0, z0, cache) if carry_cache else (x, z0, z0)
            carry, slabs = jax.lax.scan(body, carry0, xs)
            if carry_cache:
                x, recon, aux, new_cache = carry
                return x, recon, aux, new_cache
            x, recon, aux = carry
            if slab_mode:
                if return_slabs:          # (L, B, 1, KVH, HD) per key
                    return x, recon, aux, slabs
                new_cache = {
                    key: jax.lax.dynamic_update_slice_in_dim(
                        cache[key], slabs[key], q_offset, axis=2)
                    for key in ("k", "v")}
                return x, recon, aux, new_cache
            return x, recon, aux, None

        # ssm / hybrid. Mamba states are FULLY replaced every step, so the
        # optimal cache movement is xs/ys streaming (one read + one write of
        # each layer's state); carry-DUS would rebuild the stacked buffer
        # per layer. (The attention KV cache is the opposite case — see the
        # slab path above.) [§Perf I7]
        slots = self._attn_slots() if cfg.family == "hybrid" else None
        shared = params.get("shared_attn")

        def body(carry, xs):
            if cfg.family == "hybrid":
                h, recon, aux, attn_cache = carry
                if cache is None:
                    p_l, slot, li = xs
                    c_l = None
                else:
                    p_l, slot, li, c_l = xs
            else:
                h, recon, aux = carry
                attn_cache = None
                if cache is None:
                    p_l, li = xs
                    c_l = None
                else:
                    p_l, li, c_l = xs
            if decode:
                o, r, new_c = mamba2_decode(p_l, h, cfg, qc, c_l)
            else:
                o, r, new_c = mamba2_block(p_l, h, cfg, qc, c_l,
                                           valid_len=valid_len)
            h = h + o
            recon = recon + r

            if cfg.family == "hybrid":
                # decode: attn cache is read-only; each invocation emits a
                # new-token slab through ys (zeros on non-attn layers), and
                # the slot rows are written back once after the scan. [I5b]
                slab_mode = decode and attn_cache is not None \
                    and cfg.head_layout != "hd"

                def with_attn(operand):
                    h, attn_cache, recon = operand
                    if attn_cache is None:
                        c_a = None
                    else:
                        c_a = jax.tree_util.tree_map(
                            lambda t: jax.lax.dynamic_index_in_dim(
                                t, jnp.maximum(slot, 0), 0, keepdims=False),
                            attn_cache)
                    a, r1, new_a = attention(shared["attn"], h, cfg, qc,
                                             q_offset=q_offset, window=0,
                                             prefix_len=prefix_len, cache=c_a,
                                             decode_slab=slab_mode,
                                             kv_start=kv_start)
                    h = h + a
                    f, r2 = mlp(shared["mlp"], h, cfg, qc)
                    h = h + f
                    if attn_cache is not None and not slab_mode:
                        attn_cache = jax.tree_util.tree_map(
                            lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                                full, upd.astype(full.dtype),
                                jnp.maximum(slot, 0), 0),
                            attn_cache, new_a)
                    if slab_mode:
                        return h, attn_cache, recon + r1 + r2, new_a
                    return h, attn_cache, recon + r1 + r2, None

                def no_attn(operand):
                    h, attn_cache, recon = operand
                    if slab_mode:
                        b = h.shape[0]
                        kvh, hd = cfg.num_kv_heads, cfg.head_dim
                        dt = attn_cache["k"].dtype
                        zero_slab = {
                            "k": jnp.zeros((b, 1, kvh, hd), dt),
                            "v": jnp.zeros((b, 1, kvh, hd), dt)}
                        return h, attn_cache, recon, zero_slab
                    return h, attn_cache, recon, None

                h, attn_cache, recon, slab = jax.lax.cond(
                    slot >= 0, with_attn, no_attn, (h, attn_cache, recon))
                return (h, recon, aux, attn_cache), (new_c, slab)
            return (h, recon, aux), new_c

        if cfg.remat:
            body = jax.checkpoint(body)

        z0 = jnp.zeros((), jnp.float32)
        layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        if cfg.family == "hybrid":
            attn_cache0 = cache["attn"] if cache is not None else None
            xs = (params["blocks"], slots, layer_ids)
            if cache is not None:
                xs = xs + (cache["mamba"],)
            (x, recon, aux, attn_cache), (new_mamba, slabs) = jax.lax.scan(
                body, (x, z0, z0, attn_cache0), xs)
            if decode and attn_cache0 is not None \
                    and cfg.head_layout != "hd":
                # gather the slab rows at the attn layers (static indices)
                # and write all slots' new-token KV in one update
                slot_layers = jnp.array(
                    [i for i, s in enumerate(self._attn_slot_list())
                     if s >= 0], jnp.int32)
                slab_rows = {
                    key: slabs[key][slot_layers].astype(
                        attn_cache0[key].dtype)
                    for key in ("k", "v")}       # (n_inv, B, 1, KVH, HD)
                if return_slabs:
                    return x, recon, aux, {"mamba": new_mamba,
                                           "attn_slab": slab_rows}
                attn_cache = {
                    key: jax.lax.dynamic_update_slice_in_dim(
                        attn_cache0[key], slab_rows[key], q_offset, axis=2)
                    for key in ("k", "v")}
            new_cache = (None if cache is None
                         else {"mamba": new_mamba, "attn": attn_cache})
            return x, recon, aux, new_cache

        xs = (params["blocks"], layer_ids)
        if cache is not None:
            xs = xs + (cache,)
        (x, recon, aux), new_cache = jax.lax.scan(body, (x, z0, z0), xs)
        return x, recon, aux, new_cache

    # ------------------------------------------------------------------
    # train forward + loss
    # ------------------------------------------------------------------
    def forward(self, params: Params, batch: Dict, qc: QuantConfig = DENSE):
        x, prefix_len = self._embed(params, batch)
        x, recon, moe_aux, _ = self._run_blocks(
            params, x, qc, q_offset=0, prefix_len=prefix_len)
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = self._head(params, x)
        return logits, {"recon": recon, "moe_aux": moe_aux}

    def loss(self, params: Params, batch: Dict, qc: QuantConfig = DENSE):
        cfg = self.cfg
        logits, aux = self.forward(params, batch, qc)
        if cfg.family == "audio":
            labels = batch["labels"]                    # (B, S, Q)
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(
                lp, labels[:, 1:, :, None], axis=-1)[..., 0]
            ce = jnp.mean(nll)
        elif cfg.family == "vlm":
            # loss only over the text region (after the image prefix)
            p = cfg.num_patches
            text_logits = logits[:, p - 1:-1]           # predicts tokens
            labels = batch["tokens"]
            lp = jax.nn.log_softmax(text_logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
            ce = jnp.mean(nll)
        else:
            labels = batch["tokens"][:, 1:]
            lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
            ce = jnp.mean(nll)
        total = (ce + qc.recon_weight * aux["recon"]
                 + 0.01 * aux["moe_aux"])
        metrics = {"ce": ce, "recon": aux["recon"], "moe_aux": aux["moe_aux"],
                   "loss": total}
        return total, metrics

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_seq: int,
                   dtype=None) -> Params:
        cfg = self.cfg
        dtype = dtype or self.dtype
        l, b, t = cfg.num_layers, batch_size, max_seq
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        pos = jnp.zeros((), jnp.int32)
        if cfg.family in ATTN_FAMILIES:
            return {"layers": {
                "k": jnp.zeros((l, b, t, kvh, hd), dtype),
                "v": jnp.zeros((l, b, t, kvh, hd), dtype)},
                "pos": pos}
        conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        mamba = {
            "conv": jnp.zeros((l, b, cfg.ssm_conv - 1, conv_dim), dtype),
            "h": jnp.zeros((l, b, cfg.ssm_nheads, cfg.ssm_headdim,
                            cfg.ssm_state), jnp.float32)}
        if cfg.family == "ssm":
            return {"layers": mamba, "pos": pos}
        n_inv = self.num_attn_slots
        return {"layers": {
            "mamba": mamba,
            "attn": {"k": jnp.zeros((n_inv, b, t, kvh, hd), dtype),
                     "v": jnp.zeros((n_inv, b, t, kvh, hd), dtype)}},
            "pos": pos}

    def prefill(self, params: Params, batch: Dict, cache: Params,
                qc: QuantConfig = DENSE, pad_lens=None):
        """Process the prompt; returns (next-token logits (B, V...), cache).

        Args:
          batch: {"tokens": (B, S)} (audio: "embeds"; vlm: + patch_embeds).
          cache: dense cache from :meth:`init_cache`.
          pad_lens: (B,) — prompts are LEFT-padded (right-aligned, the
            batch-to-completion convention); cache rows < pad_lens[b] are
            masked out of attention for row b. The continuous engine does
            not use this entry point — its RIGHT-padded chunked prefill
            goes through :meth:`prefill_paged`.
        """
        x, prefix_len = self._embed(params, batch)
        s = x.shape[1]
        kv_start = pad_lens if pad_lens is not None else 0
        x, _, _, new_layers = self._run_blocks(
            params, x, qc, q_offset=0, prefix_len=prefix_len,
            cache=cache["layers"], kv_start=kv_start)
        x = rms_norm(x[:, -1:], params["final_norm"], self.cfg.norm_eps)
        logits = self._head(params, x)[:, 0]
        return logits, {"layers": new_layers,
                        "pos": jnp.asarray(s, jnp.int32)}

    def decode(self, params: Params, tokens: jax.Array, cache: Params,
               qc: QuantConfig = DENSE, pad_lens=None):
        """One decode step. tokens (B, 1) int32 (audio: embeds (B, 1, D);
        vlm: text token ids). Returns (logits (B, V...), cache).

        pad_lens: (B,) — left-pad widths from a right-aligned batched
        prefill; cache rows < pad_lens[b] stay masked during decode."""
        cfg = self.cfg
        pos = cache["pos"]
        if cfg.family == "audio":
            x = tokens.astype(self.dtype) @ params["in_proj"]
        else:
            x = params["embed"][tokens]
        kv_start = pad_lens if pad_lens is not None else 0
        x, _, _, new_layers = self._run_blocks(
            params, x, qc, q_offset=pos, prefix_len=0, cache=cache["layers"],
            kv_start=kv_start)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)[:, 0]
        return logits, {"layers": new_layers, "pos": pos + 1}

    # ------------------------------------------------------------------
    # paged serving (continuous batching; see src/repro/serve/)
    # ------------------------------------------------------------------
    def init_paged_cache(self, num_slots: int, max_seq: int, page_size: int,
                         num_pages: int, dtype=None, codebook=None) -> Params:
        """Physical cache storage for the paged serving engine.

        Attention families return a page pool ``{"k": (L, num_pages+1,
        page_size, KVH, HD), "v": ...}`` — one extra *trash* page (the
        last id) absorbs writes from padded / inactive positions. SSM
        state is O(1) per sequence, so it stays slot-indexed
        (``(L, num_slots, ...)``) and is recycled on eviction; the hybrid
        family keeps its few shared-attention invocations slot-dense.

        codebook: optional :class:`repro.core.kv_codebook.KVCodebook` —
        the pool then stores uint8 per-subspace centroid indices
        ``(L, num_pages+1, page_size, KVH, nc)`` instead of fp rows, and
        the codebook pytree rides the cache under ``"codebook"`` (every
        paged entry point detects quantization by that key). Attention
        families only.
        """
        cfg = self.cfg
        dtype = dtype or self.dtype
        l, kvh, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        if codebook is not None and cfg.family not in ATTN_FAMILIES:
            raise NotImplementedError(
                "KV quantization applies to paged attention pools only; "
                f"the {cfg.family!r} family has recurrent state")
        if cfg.family in ATTN_FAMILIES:
            if codebook is not None:
                if (codebook.num_layers, codebook.head_dim) != (l, hd):
                    raise ValueError(
                        f"codebook (L={codebook.num_layers}, "
                        f"HD={codebook.head_dim}) does not match model "
                        f"(L={l}, HD={hd})")
                shape = (l, num_pages + 1, page_size, kvh, codebook.nc)
                # each cache owns PRIVATE copies of the codebook leaves:
                # the serving jits donate the cache pytree, and donation
                # deletes buffers — sharing one KVCodebook's arrays across
                # caches would let one engine's step invalidate another's.
                cb_tree = {key: jnp.array(leaf, copy=True)
                           for key, leaf in codebook.tree().items()}
                return {"k": jnp.zeros(shape, jnp.uint8),
                        "v": jnp.zeros(shape, jnp.uint8),
                        CODEBOOK_KEY: cb_tree}
            shape = (l, num_pages + 1, page_size, kvh, hd)
            return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        mamba = {
            "conv": jnp.zeros((l, num_slots, cfg.ssm_conv - 1, conv_dim),
                              dtype),
            "h": jnp.zeros((l, num_slots, cfg.ssm_nheads, cfg.ssm_headdim,
                            cfg.ssm_state), jnp.float32)}
        if cfg.family == "ssm":
            return mamba
        # hybrid: slot-dense shared-attn cache with one extra TRASH row
        # (index max_seq) absorbing writes from non-decoding lanes — the
        # slot-dense analogue of the attention pool's trash page. Rows
        # >= pos are never attended (kj < pos mask), so the extra row is
        # invisible to reads.
        n_inv = self.num_attn_slots
        shape = (n_inv, num_slots, max_seq + 1, kvh, hd)
        return {"mamba": mamba,
                "attn": {"k": jnp.zeros(shape, dtype),
                         "v": jnp.zeros(shape, dtype)}}

    def _paged_view(self, kv: Params, phys: jax.Array):
        """Gather pages into a dense (L, B, NP*page, KVH, HD) KV view.

        phys: (B, NP) physical page ids (already trash-redirected).
        A quantized pool (``"codebook"`` in kv) is gathered as uint8
        codes and decoded to an fp view — the returned dict is always
        plain ``{"k", "v"}`` fp, so every gathered-view consumer
        (prefill, verify, the legacy gather decode path) reuses the
        dense attention math unchanged."""
        l = kv["k"].shape[0]
        ps = kv["k"].shape[2]
        b, np_ = phys.shape
        kvh, w = kv["k"].shape[3], kv["k"].shape[4]

        def view(pages):
            return pages[:, phys].reshape(l, b, np_ * ps, kvh, w)
        cb = kv.get(CODEBOOK_KEY)
        if cb is None:
            return {"k": view(kv["k"]), "v": view(kv["v"])}
        dt = self.dtype
        return {"k": kv_decode_stacked(view(kv["k"]), cb["zk"], cb["sk"], dt),
                "v": kv_decode_stacked(view(kv["v"]), cb["zv"], cb["sv"], dt)}

    def _encode_rows(self, kv: Params, key: str, rows: jax.Array):
        """Fresh fp K/V rows -> pool representation for stream ``key``.

        Identity on an fp pool; per-subspace codebook assignment (uint8
        codes) on a quantized one. rows (L, ..., KVH, HD)."""
        cb = kv.get(CODEBOOK_KEY)
        if cb is None:
            return rows
        z, s = (cb["zk"], cb["sk"]) if key == "k" else (cb["zv"], cb["sv"])
        return kv_encode_stacked(rows, z, s)

    def prefill_paged(self, params: Params, tokens: jax.Array, kv: Params,
                      page_table: jax.Array, slot, pos, valid_len,
                      qc: QuantConfig = DENSE, act_sharding=None):
        """One RIGHT-padded prefill chunk for a single slot.

        Args:
          tokens: (1, C) int32 — chunk of the prompt, right-padded to the
            static chunk width C; only the first ``valid_len`` are real.
          kv: paged cache pytree from :meth:`init_paged_cache`.
          page_table: (num_slots, pages_per_slot) int32, -1 = unallocated.
            Pages covering positions [0, pos+valid_len) of ``slot`` must
            already be allocated.
          slot: scalar slot index; pos: scalar absolute start position.
          act_sharding: optional sharding (``NamedSharding``) pinned onto
            the embedded activations — the sharded serving engine passes a
            replicated spec so GSPMD keeps activations whole and partitions
            the projections (column-parallel LUT lookups shard N, row-
            parallel ones shard subspaces and all-reduce partial sums).

        Returns (logits (1, V) at the last real token, updated kv).
        Padded positions scatter to the trash page; the SSM path makes
        them recurrence-neutral via ``valid_len`` (see mamba2_block).
        """
        cfg = self.cfg
        if cfg.head_layout == "hd":
            raise NotImplementedError("paged serving requires head_layout="
                                      "'heads'")
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                "paged serving covers token-prompt families only")
        x = params["embed"][tokens]
        if act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, act_sharding)
        c = tokens.shape[1]
        if cfg.family in ATTN_FAMILIES:
            trash = kv["k"].shape[1] - 1
            ps = kv["k"].shape[2]
            row = jax.lax.dynamic_index_in_dim(page_table, slot, 0,
                                               keepdims=False)    # (NP,)
            phys = jnp.where(row >= 0, row, trash)
            view = self._paged_view(kv, phys[None])
            x, _, _, new_view = self._run_blocks(
                params, x, qc, q_offset=pos, prefix_len=0, cache=view)
            # extract this chunk's fresh K/V rows and scatter them to pages
            tok_pos = pos + jnp.arange(c)
            page, off = tok_pos // ps, tok_pos % ps
            live = jnp.arange(c) < valid_len
            tgt = jnp.where(live, phys[page], trash)              # (C,)
            new_kv = dict(kv)       # codebook (if any) passes through
            for key in ("k", "v"):
                rows = jax.lax.dynamic_slice_in_dim(
                    new_view[key][:, 0], pos, c, axis=1)          # (L,C,..)
                rows = self._encode_rows(kv, key, rows)
                new_kv[key] = kv[key].at[:, tgt, off].set(rows)
        else:
            cache_view, write_back = self._slot_state_view(kv, slot, pos)
            x, _, _, new_state = self._run_blocks(
                params, x, qc, q_offset=pos, prefix_len=0,
                cache=cache_view, valid_len=valid_len)
            new_kv = write_back(new_state)
        x_last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
        x_last = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x_last)[:, 0]
        return logits, new_kv

    def _slot_state_view(self, kv: Params, slot, pos):
        """(B=1) view of one slot's recurrent state + write-back closure.

        The first chunk of a new occupant (``pos == 0``) reads zeros
        instead of the previous occupant's state — this is how evicted
        Mamba2 state slots are recycled without a separate reset pass.
        """
        continuing = pos > 0                  # pos == 0 → recycled slot

        def take(t):                          # (L, slots, ...) -> (L, 1, ...)
            sl = jax.lax.dynamic_slice_in_dim(t, slot, 1, axis=1)
            return jnp.where(continuing, sl, jnp.zeros_like(sl))

        def put(t, new):
            return jax.lax.dynamic_update_slice_in_dim(
                t, new.astype(t.dtype), slot, axis=1)

        if self.cfg.family == "ssm":
            view = {"conv": take(kv["conv"]), "h": take(kv["h"])}

            def write_back(new):
                return {"conv": put(kv["conv"], new["conv"]),
                        "h": put(kv["h"], new["h"])}
            return view, write_back

        # hybrid: recurrent mamba state + slot-dense shared-attn KV. The
        # attention rows need no zeroing: chunk writes land at q_offset
        # before any read, and decode masks rows >= pos.
        attn_view = {key: jax.lax.dynamic_slice_in_dim(
            kv["attn"][key], slot, 1, axis=1) for key in ("k", "v")}
        view = {"mamba": {"conv": take(kv["mamba"]["conv"]),
                          "h": take(kv["mamba"]["h"])},
                "attn": attn_view}

        def write_back(new):
            return {"mamba": {"conv": put(kv["mamba"]["conv"],
                                          new["mamba"]["conv"]),
                              "h": put(kv["mamba"]["h"], new["mamba"]["h"])},
                    "attn": {key: put(kv["attn"][key], new["attn"][key])
                             for key in ("k", "v")}}
        return view, write_back

    def decode_paged(self, params: Params, tokens: jax.Array, kv: Params,
                     page_table: jax.Array, positions: jax.Array,
                     qc: QuantConfig = DENSE, act_sharding=None):
        """One decode step over ALL slots at per-slot positions.

        Args:
          tokens: (num_slots, 1) int32 — inactive lanes carry a dummy id.
          positions: (num_slots,) int32 sequence length of each DECODING
            slot; lanes that are not decoding this step (free slots, but
            also slots mid-prefill — whose pages hold real prompt KV that
            must not be clobbered) carry -1. Row b's query gets RoPE
            position positions[b] and attends cache rows < positions[b]
            (none, for -1).
          page_table: (num_slots, pages_per_slot) int32, -1 = unallocated.
          act_sharding: optional sharding constraint for the embedded
            activations (see :meth:`prefill_paged`).

        Returns (logits (num_slots, V), updated kv). The new-token KV slab
        is scattered at each decoding slot's own (page, offset); lanes
        with positions < 0 scatter to the trash page (attention pool) /
        trash row (hybrid slot-dense cache). SSM states of inactive lanes
        do get garbage updates — harmless, because admission re-reads
        them as zeros (see :meth:`_slot_state_view`).
        """
        cfg = self.cfg
        if cfg.head_layout == "hd":
            raise NotImplementedError("paged serving requires head_layout="
                                      "'heads'")
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                "paged serving covers token-prompt families only")
        b = tokens.shape[0]
        x = params["embed"][tokens]
        if act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, act_sharding)
        live = positions >= 0                 # decoding lanes only
        pos_c = jnp.maximum(positions, 0)
        if cfg.family in ATTN_FAMILIES:
            trash = kv["k"].shape[1] - 1
            ps = kv["k"].shape[2]
            phys = jnp.where(page_table >= 0, page_table, trash)  # (B, NP)
            # dispatch knob (mirrors QuantConfig.fuse): "gather" is the
            # legacy dense-view path; "pallas"/"ref" run flash decode
            # straight off the page pool (kernels/flash_decode.py).
            flash = resolve_flash_impl(qc.flash)
            if flash == "gather":
                view = self._paged_view(kv, phys)
                x, _, _, slabs = self._run_blocks(
                    params, x, qc, q_offset=positions, prefix_len=0,
                    cache=view, return_slabs=True)
            else:
                x, _, _, slabs = self._run_blocks(
                    params, x, qc, q_offset=positions, prefix_len=0,
                    cache=kv, return_slabs=True,
                    paged_phys=phys, flash_impl=flash)
            page, off = pos_c // ps, pos_c % ps
            # non-decoding lanes MUST NOT write through their page table:
            # a mid-prefill slot's pages hold real prompt KV.
            tgt = jnp.where(live, phys[jnp.arange(b), page], trash)
            new_kv = dict(kv)
            for key in ("k", "v"):
                slab = self._encode_rows(kv, key, slabs[key][:, :, 0])
                new_kv[key] = kv[key].at[:, tgt, off].set(slab)
        elif cfg.family == "ssm":
            x, _, _, upd = self._run_blocks(
                params, x, qc, q_offset=positions, prefix_len=0, cache=kv)
            # recurrent state is live for EVERY occupied lane (a slot
            # mid-prefill carries real state between chunks): lanes that
            # are not decoding keep their old state.
            new_kv = _merge_live_states(kv, upd, live)
        else:                                 # hybrid
            x, _, _, upd = self._run_blocks(
                params, x, qc, q_offset=positions, prefix_len=0,
                cache=kv, return_slabs=True)
            trash_row = kv["attn"]["k"].shape[2] - 1   # see init_paged_cache
            row = jnp.where(live, pos_c, trash_row)
            attn = {key: kv["attn"][key].at[:, jnp.arange(b), row].set(
                        upd["attn_slab"][key][:, :, 0])
                    for key in ("k", "v")}
            new_kv = {"mamba": _merge_live_states(kv["mamba"], upd["mamba"],
                                                  live),
                      "attn": attn}
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)[:, 0]
        return logits, new_kv

    def verify_paged(self, params: Params, tokens: jax.Array, kv: Params,
                     page_table: jax.Array, positions: jax.Array,
                     n_live: jax.Array, qc: QuantConfig = DENSE,
                     act_sharding=None):
        """Score T proposed tokens per slot in ONE call (speculative verify).

        Row b feeds tokens[b, 0:T] at absolute positions positions[b] ..
        positions[b]+T-1: column 0 is the slot's committed-but-undecoded
        next token, columns 1.. are draft proposals. Token t's query
        attends committed cache rows < positions[b] plus proposed tokens
        0..t (their K/V computed fresh in this call — target numerics),
        so logits[b, t] is exactly the target distribution after
        consuming tokens[b, :t+1], bit-for-bit the context a sequential
        :meth:`decode_paged` chain would build.

        Args:
          tokens: (num_slots, T) int32 proposals; dead columns carry dummy
            ids.
          positions: (num_slots,) committed length of each participating
            slot; -1 = lane not in this verify (free / mid-prefill).
          n_live: (num_slots,) live token columns per row (0 for -1
            lanes). Columns >= n_live[b] scatter their KV to the trash
            page and their logits are garbage the caller must ignore.

        Returns (logits (num_slots, T, V), updated kv). Live columns'
        fresh KV is written through the page table at positions[b]+t —
        pages covering positions[b]+n_live[b] tokens must be allocated.
        The caller commits the accepted prefix by advancing ``slot.pos``
        and rolls back the rejected tail by NOT advancing over it: rows
        >= pos are never attended and are overwritten before ``pos``
        crosses them again (docs/speculative.md).

        Attention families only: Mamba2/hybrid recurrent state is a
        single evolving tensor that cannot be rewound page-style.
        """
        cfg = self.cfg
        if cfg.family not in ATTN_FAMILIES:
            raise NotImplementedError(
                "verify_paged needs rewindable (paged) KV state; the "
                f"{cfg.family!r} family's recurrent state cannot roll "
                "back rejected draft tokens")
        if cfg.head_layout == "hd":
            raise NotImplementedError("paged serving requires head_layout="
                                      "'heads'")
        if cfg.family in ("audio", "vlm"):
            raise NotImplementedError(
                "paged serving covers token-prompt families only")
        b, t_v = tokens.shape
        x = params["embed"][tokens]
        if act_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, act_sharding)
        pos_c = jnp.maximum(positions, 0)
        trash = kv["k"].shape[1] - 1
        ps = kv["k"].shape[2]
        max_seq = page_table.shape[1] * ps
        phys = jnp.where(page_table >= 0, page_table, trash)      # (B, NP)
        view = self._paged_view(kv, phys)
        x, _, _, slabs = self._run_blocks(
            params, x, qc, q_offset=positions, prefix_len=0,
            cache=view, return_slabs=True, multi_slab=True)
        # scatter the T fresh rows per slot; dead columns -> trash page
        tok_pos = pos_c[:, None] + jnp.arange(t_v)[None, :]       # (B, T)
        live = (positions >= 0)[:, None] \
            & (jnp.arange(t_v)[None, :] < n_live[:, None])
        tok_pos = jnp.minimum(tok_pos, max_seq - 1)   # dead cols: clamp
        page, off = tok_pos // ps, tok_pos % ps
        tgt = jnp.where(live, jnp.take_along_axis(phys, page, axis=1),
                        trash)                                    # (B, T)
        new_kv = dict(kv)
        for key in ("k", "v"):
            slab = self._encode_rows(kv, key, slabs[key])
            new_kv[key] = kv[key].at[:, tgt, off].set(slab)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)                            # (B, T, V)
        return logits, new_kv


def _merge_live_states(old, new, live: jax.Array):
    """Per-lane select on slot-indexed state pytrees.

    old/new: trees of (L, num_slots, ...) arrays; live: (num_slots,) bool.
    Lanes with live=False keep their old state — decode steps must not
    clobber the recurrent state of slots that are mid-prefill."""
    return jax.tree_util.tree_map(
        lambda o, n: jnp.where(
            live.reshape((1, -1) + (1,) * (o.ndim - 2)), n.astype(o.dtype), o),
        old, new)


@functools.lru_cache(maxsize=None)
def _registry():
    from repro import configs
    return configs.REGISTRY


def build_model(cfg_or_name) -> Model:
    if isinstance(cfg_or_name, str):
        cfg_or_name = _registry()[cfg_or_name]()
    return Model(cfg_or_name)
