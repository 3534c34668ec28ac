#!/usr/bin/env python3
"""Bring-up check: serve Qwen1.5-4B at its published widths on a TPU.

Drives the continuous-batching serving path once through its public
entry points (``Model``, ``precompute_model``, ``repro.serve.Engine``)
with random weights drawn from ``--seed``, checks what comes out, and
prints one JSON object as the last line of standard output::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage (from the repository root, on a machine with a TPU)::

    python chip_smoke.py             # one chip: dense serve, convert to
                                     # int8 LUTs, LUT serve, parity
    python chip_smoke.py --chips 4   # 2x2 mesh only: two TP-2 LUT
                                     # replicas against one chip

Every phase prints compile and run seconds, tokens produced, peak device
memory and whether the compiled step programs hold a Pallas kernel
(``tpu_custom_call``). Any failed check raises, so the script exits
non-zero and prints no result line. With no TPU it exits at once with
code 2. The phases are functions of a ``ModelConfig`` so that a test can
run them at a small size on the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import precompute_model, strip_model  # noqa: E402
from repro.core.codebook import init_centroids  # noqa: E402
from repro.core.lut import (DENSE, QuantConfig, missing_luts,  # noqa: E402
                            quantize_lut_int8)
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.flash_decode import flash_decode_paged  # noqa: E402
from repro.kernels.fused_amm import vq_amm_pallas  # noqa: E402
from repro.kernels.tuning import select_blocks  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models.layers import attention, mlp, rms_norm  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serve import Engine, ReplicaRouter, Request  # noqa: E402

#: the serving operating point: default (v=8, c=16), int8 tables — LUT
#: bytes equal the bf16 weight bytes (v=4 would double them)
LUT_QC = QuantConfig(mode="lut_infer", lut_dtype="int8")

# Parity bounds. A CPU run (interpret-mode kernels, tests/test_chip_smoke.py)
# measures 0 for every one of them.
# KERNEL_TOL: relative L2 error ||got - want|| / ||want|| of a kernel
# against kernels/ref.py on the same input. It leaves room for the chip's
# default MXU precision (an f32 operand such as the softmax weights is
# rounded to bf16: ~3e-3) but not for a wrong kernel, which is off by O(1).
KERNEL_TOL = 1e-2
# The LUT kernel is checked layer by layer, each layer fed the oracle's
# residual (teacher forcing), on every projection's real input:
# * given the centroids it picked, its int8 sums must equal the oracle's
#   lookup of those centroids exactly (they are exact in f32);
# * it may pick another centroid than the oracle ("flip") only where the
#   two distances tie within f32 rounding. Each side computes
#   ||x||^2 - 2<x,z> + ||z||^2 from exact bf16 products and is off by at
#   most ~9u*S (u = 2^-24, S = ||x||^2 + 2 sum|x_i z_i| + ||z||^2), so a
#   flip between centroids a and b needs |d_a - d_b| <= 9u(S_a + S_b);
#   TIE_ULPS bounds |d_a - d_b| / (u(S_a + S_b)) with some margin;
# * flips stay rare: FLIP_FRAC of a layer's assignments.
# Both checks run on the oracle layer's projection inputs and on the
# kernel layer's own. The layer's update (output minus residual) is then
# bounded by LAYER_TOL, a backstop set far from both sides: one near-tie
# flip changes the rest of its row's inputs by far more than rounding,
# and on the chip a single flip moved a layer's update by up to 0.123
# (random weights, seed 0), while a wrong kernel moves it by O(1) (1.05
# for the misassigning kernel of tests/test_chip_smoke.py).
TIE_ULPS = 16
FLIP_FRAC = 1e-3
LAYER_TOL = 0.5
# an all-gather this large moves LUT- or KV-pool-sized data per step
GATHER_LIMIT = 4 << 20
CHUNK = 32          # prefill chunk width: the LUT kernels run at M = 32


def on_tpu() -> bool:
    """Whether the Mosaic kernels compile to ``tpu_custom_call`` here
    (elsewhere they run in interpret mode, as plain HLO)."""
    return jax.devices()[0].platform == "tpu"


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None, None
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


def check_peak(phase: str) -> None:
    peak, limit = peak_bytes()
    log(phase, peak_bytes_in_use=peak, bytes_limit=limit)
    if peak is not None and limit is not None and peak >= limit:
        raise RuntimeError(f"{phase}: peak {peak} B >= limit {limit} B")


def make_requests(cfg, seed: int, n: int = 8, lo: int = 64, hi: int = 512,
                  max_new: int = 32):
    """``n`` greedy requests with seeded prompts of ``lo``..``hi`` tokens."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, n)
    return [Request(tokens=rng.integers(0, cfg.vocab_size, int(t)).tolist(),
                    max_new_tokens=max_new) for t in lens]


def fresh(requests):
    return [Request(tokens=list(r.tokens), max_new_tokens=r.max_new_tokens)
            for r in requests]


def step_hlo(eng: Engine) -> dict:
    """Compiled text of the engine's prefill-chunk and decode programs."""
    eps = eng.jit_entry_points()
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    table = eng.kv.table_device()
    scope = (contextlib.nullcontext() if eng.mesh is None
             else jax.set_mesh(eng.mesh))    # the engine traces under it
    with scope:
        pre = eps["prefill"].lower(
            eng.params, jnp.zeros((1, eng.prefill_chunk), jnp.int32),
            eng.kv.data, table, i32(0), i32(0), i32(1))
        dec = eps["decode"].lower(
            eng.params, jnp.zeros((eng.num_slots, 1), jnp.int32),
            eng.kv.data, table, jnp.full((eng.num_slots,), -1, jnp.int32))
    return {"prefill": pre.compile().as_text(),
            "decode": dec.compile().as_text()}


def largest_all_gather(hlo: str) -> int:
    """Bytes of the largest all-gather result in an HLO text."""
    size = {"f32": 4, "bf16": 2, "s32": 4, "s8": 1, "u8": 1, "f16": 2,
            "pred": 1, "u32": 4}
    most = 0
    for line in hlo.splitlines():
        if "all-gather" not in line or "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        for dt, dims in re.findall(r"\b(\w+)\[([\d,]*)\]", rhs.split(
                "all-gather", 1)[0]):
            if dt in size:
                n = int(np.prod([int(d) for d in dims.split(",") if d]))
                most = max(most, n * size[dt])
    return most


def serve(phase: str, model, params, qc, requests, *, batch_size: int,
          max_seq: int, expect_kernels: bool):
    """Serve ``requests`` to completion on one engine and check them.

    A one-chunk warm-up request compiles the prefill, decode and sample
    steps first (reported as compile seconds, one tiny run included).
    Returns the per-request output tokens."""
    eng = Engine(model, params, qc, batch_size=batch_size, max_seq=max_seq,
                 prefill_chunk=CHUNK)
    t0 = time.perf_counter()
    eng.run([Request(tokens=[1] * eng.prefill_chunk, max_new_tokens=2)])
    t_compile = time.perf_counter() - t0
    reqs = fresh(requests)
    t0 = time.perf_counter()
    eng.run(reqs)
    t_run = time.perf_counter() - t0
    for i, r in enumerate(reqs):
        if len(r.out_tokens) != r.max_new_tokens:
            raise RuntimeError(f"{phase}: request {i} got "
                               f"{len(r.out_tokens)}/{r.max_new_tokens}")
    hlo = step_hlo(eng)
    kernels = {k: "tpu_custom_call" in v for k, v in hlo.items()}
    log(phase, compile_s=f"{t_compile:.3f}", run_s=f"{t_run:.3f}",
        requests=f"{len(reqs)}/{len(reqs)}",
        tokens=sum(len(r.out_tokens) for r in reqs),
        prefill_tokens=sum(len(r.tokens) for r in reqs),
        tpu_custom_call=kernels)
    if expect_kernels and not all(kernels.values()):
        raise RuntimeError(f"{phase}: no Pallas kernel in {kernels}")
    check_peak(phase)
    out = [list(r.out_tokens) for r in reqs]
    del eng
    gc.collect()
    return out


def convert(params, qc: QuantConfig = LUT_QC):
    """Dense params (with centroids) -> stripped int8-LUT serving params."""
    t0 = time.perf_counter()
    lut = strip_model(precompute_model(params, qc))
    jax.block_until_ready(lut)
    dt = time.perf_counter() - t0
    leaves = jax.tree_util.tree_leaves_with_path(lut)
    names = lambda key: [jax.tree_util.keystr(p) for p, _ in leaves
                         if getattr(p[-1], "key", None) == key]
    n, kept = len(names("lut")), names("w")
    if not n or missing_luts(lut) or len(names("lut_scale")) != n or kept:
        raise RuntimeError(f"convert: {n} int8 tables, missing "
                           f"{missing_luts(lut)[:3]}, weights kept "
                           f"{kept[:3]}")
    log("convert", seconds=f"{dt:.3f}", lut_layers=n,
        lut_bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(lut)))
    check_peak("convert")
    return lut


def _paged_inputs(model, prompt, batch_size: int):
    """A fresh page pool and page table for one slot holding ``prompt``
    plus one decoded token, and the decode positions (slot 0 only)."""
    page, c = 16, len(prompt)
    pages = -(-(c + 1) // page)
    kv = model.init_paged_cache(batch_size, pages * page, page,
                                batch_size * pages)
    table = jnp.full((batch_size, pages), -1, jnp.int32)
    table = table.at[0].set(jnp.arange(pages, dtype=jnp.int32))
    positions = jnp.full((batch_size,), -1, jnp.int32).at[0].set(c)
    return kv, table, positions


def model_parity(model, params, qc, prompt, batch_size: int = 8):
    """One prefill chunk and one decode step through the kernels
    (fused LUT, Pallas flash decode) against the XLA oracles, at the
    model's full depth.

    Both chains run every other op identically, so what differs is the
    kernels alone. Each chain decodes the token the kernel chain's
    prefill picked. Returns the logits' relative errors and whether the
    top-1 tokens agree."""
    c = len(prompt)
    toks = jnp.asarray([prompt], jnp.int32)
    out = {}
    nxt = None
    for name, q in (("kernel", qc.replace(impl="fused", flash="pallas")),
                    ("oracle", qc.replace(impl="ref", flash="ref"))):
        kv, table, positions = _paged_inputs(model, prompt, batch_size)
        pre = jax.jit(lambda p, t, kv, pt: model.prefill_paged(
            p, t, kv, pt, 0, 0, c, q))
        dec = jax.jit(lambda p, t, kv, pt, pos: model.decode_paged(
            p, t, kv, pt, pos, q))
        lp, kv = pre(params, toks, kv, table)
        if nxt is None:                   # both chains decode one token
            nxt = int(jnp.argmax(lp[0]))
        dtoks = jnp.zeros((batch_size, 1), jnp.int32).at[0, 0].set(nxt)
        ld, _ = dec(params, dtoks, kv, table, positions)
        out[name] = (np.asarray(lp[0], np.float32),
                     np.asarray(ld[0], np.float32))
    errs = {"prefill": rel_err(out["kernel"][0], out["oracle"][0]),
            "decode": rel_err(out["kernel"][1], out["oracle"][1])}
    top1 = {k: int(np.argmax(out["kernel"][i]) == np.argmax(out["oracle"][i]))
            for i, k in enumerate(("prefill", "decode"))}
    return errs, top1


def _probe_table(nc: int, c: int):
    """(nc, c, nc) int8 table under which the fused kernel writes, in
    column ``k``, 1 + the centroid it picked for subspace ``k``."""
    own = jnp.arange(nc)[:, None, None] == jnp.arange(nc)[None, None, :]
    return jnp.where(own, jnp.arange(1, c + 1)[None, :, None],
                     0).astype(jnp.int8)


def _check_projection(x, z, lut, interpret: bool):
    """The fused LUT kernel on one projection's input against the oracle.

    Returns the kernel's and the oracle's (M, nc) centroid indices, and
    the largest difference between the kernel's int8 sums (before the
    per-column scale, which is one shared multiply) and the oracle's
    lookup of the kernel's own indices."""
    m, nc, _ = x.shape
    c, n = z.shape[1], lut.shape[2]
    # the probe runs the served kernel on the served row/subspace tiles,
    # so its distances and argmin are the served ones
    blk = select_blocks("fused", m, nc, c, n, lut.dtype.itemsize)
    got = vq_amm_pallas(x, z, lut, interpret=interpret)
    picked = vq_amm_pallas(x, z, _probe_table(nc, c), block_m=blk.block_m,
                           block_k=blk.block_k, interpret=interpret)
    idx = picked.astype(jnp.int32) - 1
    gap = jnp.max(jnp.abs(got - kref.lut_gemm_onehot(idx, lut)))
    return idx, kref.assign_ref(x, z), gap


def tie_ratios(x, z, got, want) -> np.ndarray:
    """For each flip (``got != want``), |d_a - d_b| / (u (S_a + S_b)) in
    float64: the gap between the two centroids' squared distances in
    units of the f32 rounding bound (see TIE_ULPS)."""
    rows, subs = np.nonzero(got != want)
    if not len(rows):
        return np.zeros(0)
    xs = np.asarray(jax.device_get(x)).astype(np.float64)[rows, subs]
    zs = np.asarray(jax.device_get(z)).astype(np.float64)
    dist, size = [], []
    for idx in (want, got):
        zj = zs[subs, idx[rows, subs]]
        dist.append(((xs - zj) ** 2).sum(-1))
        size.append((xs ** 2).sum(-1) + 2 * np.abs(xs * zj).sum(-1)
                    + (zj ** 2).sum(-1))
    return np.abs(dist[0] - dist[1]) / (2.0 ** -24 * (size[0] + size[1]))


def layer_parity(model, params, qc, prompt, batch_size: int = 8):
    """Teacher-forced per-layer check of the kernels at every depth.

    Each layer of the prefill chunk and of one decode step gets the
    oracle chain's residual. The layer runs once with the fused LUT
    kernel and once with the oracle (flash decode on its XLA ref in
    both, so that the difference is the LUT kernel's), and each
    projection's input, taken from the oracle chain, goes through
    :func:`_check_projection`. On the decode step the Pallas flash
    kernel is compared with the ref on the layer's own queries and pool.
    Returns one dict of measurements per (step, layer)."""
    cfg = model.cfg
    interpret = not on_tpu()
    oracle_qc = qc.replace(impl="ref", flash="ref")
    kernel_qc = qc.replace(impl="fused", flash="ref")
    blocks = params["blocks"]
    c = len(prompt)
    toks = jnp.asarray([prompt], jnp.int32)
    kv, table, positions = _paged_inputs(model, prompt, batch_size)
    lp, kv = jax.jit(lambda p, t, kv, pt: model.prefill_paged(
        p, t, kv, pt, 0, 0, c, oracle_qc))(params, toks, kv, table)
    dtoks = jnp.zeros((batch_size, 1), jnp.int32).at[0, 0].set(
        jnp.argmax(lp[0]))
    phys = jnp.where(table >= 0, table, kv["k"].shape[1] - 1)
    pool = {k: kv[k] for k in ("k", "v")}
    eye = lambda n, dt: {"w": jnp.eye(n, dtype=dt)}   # a plain x @ I

    def layer(p, pool, li, h, q, flash, decode):
        p = jax.tree_util.tree_map(lambda a: a[li], p)
        kw = {}
        if decode:
            kw = dict(q_offset=positions, decode_slab=True, paged_phys=phys,
                      flash_impl=flash, cache=dict(pool, layer=li))
        a, _, _ = attention(p["attn"], h, cfg, q, **kw)
        f, _ = mlp(p["mlp"], h + a, cfg, q)
        # the attention core: the same attention with wo = identity
        core, _, _ = attention(
            dict(p["attn"], wo=eye(cfg.num_heads * cfg.head_dim, h.dtype)),
            h, cfg, q, **kw)
        xa = rms_norm(h, p["attn"]["norm"], cfg.norm_eps)
        xm = rms_norm(h + a, p["mlp"]["norm"], cfg.norm_eps)
        xd, _ = mlp(dict(p["mlp"], wd=eye(cfg.d_ff, h.dtype)), h + a, cfg, q)
        inputs = {"wq": xa, "wk": xa, "wv": xa, "wo": core,
                  "wg": xm, "wu": xm, "wd": xd}
        return h + a + f, inputs

    run = jax.jit(layer, static_argnums=(4, 5, 6))
    check = jax.jit(lambda x, z, lut: _check_projection(x, z, lut,
                                                         interpret))
    rows = []
    for step, h in (("prefill", params["embed"][toks]),
                    ("decode", params["embed"][dtoks])):
        dec = step == "decode"
        for li in range(cfg.num_layers):
            lj = jnp.int32(li)
            want, inputs = run(blocks, pool, lj, h, oracle_qc, "ref", dec)
            got, served = run(blocks, pool, lj, h, kernel_qc, "ref", dec)
            f64 = lambda a: np.asarray(a, np.float64)
            row = {"step": step, "layer": li,
                   "layer_rel_err": rel_err(f64(got) - f64(h),
                                            f64(want) - f64(h)),
                   # the two programs' first projection input, rms_norm
                   # of the same residual: bit-identical or not
                   "same_norm": bool((inputs["wq"] == served["wq"]).all()),
                   "flips": 0, "served_flips": 0, "assignments": 0,
                   "max_tie_ratio": 0.0, "max_lookup_gap": 0.0}
            # each projection on the oracle layer's inputs and on the
            # kernel layer's own (they part after the first flip)
            for key, taps in (("flips", inputs), ("served_flips", served)):
                for name, x in taps.items():
                    p = blocks["mlp" if name in ("wg", "wu", "wd")
                                else "attn"]
                    p = jax.tree_util.tree_map(lambda a: a[li], p[name])
                    x = x.reshape(-1, p["z"].shape[0], p["z"].shape[2])
                    idx, ref_idx, gap = check(x, p["z"], p["lut"])
                    idx, ref_idx = np.asarray(idx), np.asarray(ref_idx)
                    ratios = tie_ratios(x, p["z"], idx, ref_idx)
                    row[key] += len(ratios)
                    row["assignments"] += idx.size
                    row["max_tie_ratio"] = max(
                        row["max_tie_ratio"], float(ratios.max(initial=0)))
                    row["max_lookup_gap"] = max(row["max_lookup_gap"],
                                                float(gap))
            if dec:
                _, flash = run(blocks, pool, lj, h, oracle_qc, "pallas", True)
                row["flash_rel_err"] = rel_err(flash["wo"], inputs["wo"])
            rows.append(row)
            log("layer_parity", **row)
            h = want
    return rows


def kernel_parity(cfg, seed: int, rows=(8, 32), page: int = 16,
                  slots: int = 8, max_seq: int = 1024):
    """The fused LUT kernel and the Pallas flash decode at the model's
    projection and attention shapes against ``kernels/ref.py``."""
    key = jax.random.PRNGKey(seed)
    errs = {}
    v, c = LUT_QC.v, LUT_QC.c
    for k, n in ((cfg.d_model, cfg.num_heads * cfg.head_dim),
                 (cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        kz, kl, ks, key = jax.random.split(key, 4)
        z = init_centroids(kz, k, LUT_QC.spec, dtype=jnp.bfloat16)
        lut, scale = quantize_lut_int8(
            jax.random.normal(kl, (k // v, c, n), jnp.float32))
        for m in rows:
            x = jax.random.normal(jax.random.fold_in(ks, m), (m, k // v, v),
                                  jnp.bfloat16)
            got = vq_amm_pallas(x, z, lut, scale,
                                interpret=jax.default_backend() != "tpu")
            want = kref.vq_amm_ref(x, z, lut, scale)
            errs[f"vq_amm_m{m}_k{k}_n{n}"] = rel_err(got, want)
    kvh, d = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kvh
    np_ = max_seq // page
    ks = jax.random.split(key, 6)
    pool = (2, slots * np_ + 1, kvh, page, d)   # two layers, head-major
    kp = jax.random.normal(ks[0], pool, jnp.bfloat16)
    vp = jax.random.normal(ks[1], pool, jnp.bfloat16)
    q = jax.random.normal(ks[2], (slots, 1, kvh * g, d), jnp.bfloat16)
    kn = jax.random.normal(ks[3], (slots, 1, kvh, d), jnp.bfloat16)
    vn = jax.random.normal(ks[4], (slots, 1, kvh, d), jnp.bfloat16)
    pos = jax.random.randint(ks[5], (slots,), 1, max_seq - 1)
    phys = jnp.arange(slots * np_, dtype=jnp.int32).reshape(slots, np_)
    got = flash_decode_paged(q, kp, vp, kn, vn, phys, pos, layer=1,
                             impl="pallas",
                             interpret=jax.default_backend() != "tpu")
    with jax.default_matmul_precision("highest"):
        want = kref.flash_decode_ref(q, kp, vp, kn, vn, phys, pos, layer=1)
    errs[f"flash_decode_kvh{kvh}_d{d}"] = rel_err(got, want)
    return errs


def parity(model, params, qc, requests, seed: int, chunk: int) -> None:
    """Kernel-level, per-layer and full-depth parity; raises past the
    bounds."""
    cfg = model.cfg
    prompt = requests[0].tokens[:chunk]
    bad = {}
    kerrs = kernel_parity(cfg, seed)
    log("parity", kernel_rel_err=kerrs, tol=KERNEL_TOL)
    bad.update({k: e for k, e in kerrs.items() if not e <= KERNEL_TOL})
    rows = layer_parity(model, params, qc, prompt)
    for r in rows:
        at = f"{r['step']}_layer{r['layer']}"
        checks = {
            "lookup_gap": (r["max_lookup_gap"], 0.0),
            "tie_ratio": (r["max_tie_ratio"], TIE_ULPS),
            "flips": (r["flips"] + r["served_flips"],
                      FLIP_FRAC * r["assignments"]),
            "layer_rel_err": (r["layer_rel_err"], LAYER_TOL),
            "flash_rel_err": (r.get("flash_rel_err", 0.0), KERNEL_TOL)}
        bad.update({f"{at}_{k}": v for k, (v, tol) in checks.items()
                    if not v <= tol})
    flipped = [f"{r['step']}{r['layer']}" for r in rows
               if r["flips"] + r["served_flips"]]
    log("parity", layers=cfg.num_layers,
        flips=sum(r["flips"] for r in rows),
        served_flips=sum(r["served_flips"] for r in rows),
        layers_with_flips=flipped,
        max_tie_ratio=max(r["max_tie_ratio"] for r in rows),
        max_layer_rel_err=max(r["layer_rel_err"] for r in rows),
        max_flash_rel_err=max(r.get("flash_rel_err", 0.0) for r in rows),
        tol=dict(tie_ratio=TIE_ULPS, flip_frac=FLIP_FRAC,
                 layer_rel_err=LAYER_TOL, flash_rel_err=KERNEL_TOL))
    errs, top1 = model_parity(model, params, qc, prompt)
    log("parity", layers=cfg.num_layers, logit_rel_err=errs,
        top1_agree=top1)
    bad.update({f"top1_{k}": v for k, v in top1.items() if not v})
    if bad:
        raise RuntimeError(f"parity: over tolerance {bad}")


def one_chip(cfg, seed: int, requests, *, batch_size: int = 8,
             max_seq: int = 1024, qc: QuantConfig = LUT_QC) -> None:
    """Dense serve, conversion to int8 LUTs, LUT serve and parity."""
    model = Model(cfg)
    t0 = time.perf_counter()
    params = model.init(jax.random.PRNGKey(seed), qc)
    jax.block_until_ready(params)
    log("init", seconds=f"{time.perf_counter() - t0:.3f}",
        param_bytes=sum(x.nbytes for x in jax.tree_util.tree_leaves(params)))
    serve("dense", model, params, DENSE, requests, batch_size=batch_size,
          max_seq=max_seq, expect_kernels=False)
    lut = convert(params, qc)
    del params
    gc.collect()
    serve("lut", model, lut, qc, requests, batch_size=batch_size,
          max_seq=max_seq, expect_kernels=on_tpu())
    parity(model, lut, qc, requests, seed, CHUNK)


def mesh_phase(cfg, seed: int, requests, *, batch_size: int = 8,
               max_seq: int = 1024, qc: QuantConfig = LUT_QC) -> None:
    """Two TP-2 LUT replicas on a (2, 2) (data, model) mesh against one
    chip: tokens must match, and no step program may gather a table."""
    model = Model(cfg)
    lut = convert(model.init(jax.random.PRNGKey(seed), qc), qc)
    want = serve("mesh_reference_1chip", model, lut, qc, requests,
                 batch_size=batch_size, max_seq=max_seq,
                 expect_kernels=on_tpu())
    # place the replicas from host memory: sharding an array that lives
    # on chip 0 slices all of it there first, which chip 0 cannot hold
    # next to the tables themselves
    lut = jax.device_get(lut)
    gc.collect()
    mesh = make_test_mesh((2, 2), ("data", "model"))
    t0 = time.perf_counter()
    router = ReplicaRouter.from_mesh(model, lut, qc, mesh=mesh,
                                     batch_size=batch_size, max_seq=max_seq,
                                     prefill_chunk=CHUNK)
    del lut
    reqs = fresh(requests)
    for r in reqs:
        router.submit(r)
    router.run_until_idle()
    t_total = time.perf_counter() - t0
    got = [list(r.out_tokens) for r in reqs]
    same = sum(a == b for a, b in zip(got, want))
    hlo = step_hlo(router.engines[0])
    kernels = {k: v.count("tpu_custom_call") for k, v in hlo.items()}
    gathers = {k: largest_all_gather(v) for k, v in hlo.items()}
    log("mesh_tp2x2", seconds_incl_compile=f"{t_total:.3f}",
        replicas=len(router.engines), token_match=f"{same}/{len(want)}",
        tokens=sum(len(t) for t in got), tpu_custom_calls=kernels,
        largest_all_gather_bytes=gathers)
    check_peak("mesh_tp2x2")
    if same != len(want):
        raise RuntimeError("mesh: TP-2 replicas disagree with one chip")
    if on_tpu() and not all(kernels.values()):
        raise RuntimeError(f"mesh: no Pallas kernel in {kernels}")
    if max(gathers.values()) > GATHER_LIMIT:
        raise RuntimeError(f"mesh: table-sized all-gather {gathers}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} visible",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    cfg = get_config("qwen1.5-4b")
    log("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), config=cfg.name, layers=cfg.num_layers,
        d_model=cfg.d_model, seed=args.seed)
    requests = make_requests(cfg, args.seed)
    if args.chips == 4:
        mesh_phase(cfg, args.seed, requests)
    else:
        one_chip(cfg, args.seed, requests)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
