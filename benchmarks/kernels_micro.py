"""Microbenchmarks of the core ops on this host (CPU, ref impl + Pallas
interpret) — wall-time sanity, not TPU numbers (those come from the
dry-run roofline).

Run:  PYTHONPATH=src python -m benchmarks.kernels_micro [--snapshot auto]

``--snapshot PATH`` (or ``auto`` = repo-root ``BENCH_kernels.json``)
writes every emitted row plus run metadata as a JSON perf snapshot —
the kernel-side half of the ROADMAP item 5 trajectory (serve_bench
writes the serving half to ``BENCH_serve.json``).
"""
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.lut import build_lut
from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.ops import lut_matmul, vq_assign
from repro.models.layers import _sdpa_decode_combine

try:                                 # `python -m benchmarks.kernels_micro`
    from .common import emit, snapshot, time_jax, time_jax_pair
except ImportError:                  # `python benchmarks/kernels_micro.py`
    from common import emit, snapshot, time_jax, time_jax_pair


def _bench_fused_vs_two_pass(x, z, lut, tag: str) -> None:
    """micro/fused_amm_* rows: one jitted fused program (assignment feeds the
    LUT contraction with no materialised index tensor) against the two-pass
    pipeline that writes the (M, nc) int32 indices out between kernels.

    The two variants are timed interleaved (best-of-N) so host scheduler
    noise hits both equally — the ratio is the signal, not the wall time.
    """
    m, nc, _ = x.shape

    assign_j = jax.jit(lambda a, b: ref.assign_ref(a, b, "l2"))
    lookup_j = jax.jit(ref.lut_gemm_onehot)

    def two_pass(a, b, l):
        idx = assign_j(a, b)            # (M, nc) int32 round-trip
        return lookup_j(idx, l)

    fused_j = jax.jit(lambda a, b, l: ref.vq_amm_ref(a, b, l, metric="l2"))

    t_two, t_fused = time_jax_pair(two_pass, fused_j, x, z, lut,
                                   warmup=3, iters=30)
    idx_bytes = m * nc * 4
    emit(f"micro/two_pass_amm_{tag}", t_two,
         f"idx intermediate {idx_bytes/1e3:.1f}KB")
    emit(f"micro/fused_amm_{tag}", t_fused,
         f"idx bytes eliminated {idx_bytes/1e3:.1f}KB; "
         f"{t_two/t_fused:.2f}x vs two-pass")


def _bench_flash_decode() -> None:
    """micro/flash_* rows: paged decode attention off the page table.

    A/B at an 8k-token context: the legacy gather formulation (pool ->
    dense per-slot KV view -> ``_sdpa_decode_combine``, re-materialised
    every step) against ``flash_decode_paged(impl="ref")``, which scores
    the pool in place and never builds the view (scores are ~2*D/G
    smaller per token than K+V rows). Interleaved best-of-N — the ratio
    is the signal. A small ``impl="pallas"`` interpret-mode row rides
    along as a correctness canary for the real kernel's grid/index maps
    (interpret wall-time is NOT indicative of TPU performance).
    """
    key = jax.random.PRNGKey(11)
    b, kvh, g, d, ps, np_ = 2, 4, 2, 64, 16, 512       # 8192 tokens/slot
    h = kvh * g
    pool = b * np_ + 1                                 # last page = trash
    ks = {}
    for i, nm in enumerate(("k", "v", "q", "kn", "vn")):
        ks[nm] = jax.random.fold_in(key, i)
    k_pages = jax.random.normal(ks["k"], (pool, ps, kvh, d)) * 0.3
    v_pages = jax.random.normal(ks["v"], (pool, ps, kvh, d)) * 0.3
    q = jax.random.normal(ks["q"], (b, 1, h, d))
    k_new = jax.random.normal(ks["kn"], (b, 1, kvh, d)) * 0.3
    v_new = jax.random.normal(ks["vn"], (b, 1, kvh, d)) * 0.3
    phys = jnp.arange(b * np_, dtype=jnp.int32).reshape(b, np_)
    pos = jnp.array([np_ * ps - 1] * b, jnp.int32)     # pos 8191: full ctx

    def gather(q, kp, vp, kn, vn, ph, po):
        view_k = kp[ph].reshape(b, np_ * ps, kvh, d)   # the HBM gather
        view_v = vp[ph].reshape(b, np_ * ps, kvh, d)
        return _sdpa_decode_combine(q, view_k, view_v, kn, vn, po, 0, 0)

    def flash(q, kp, vp, kn, vn, ph, po):
        return flash_decode_paged(q, kp, vp, kn, vn, ph, po, impl="ref")

    gather_j, flash_j = jax.jit(gather), jax.jit(flash)
    out_g = gather_j(q, k_pages, v_pages, k_new, v_new, phys, pos)
    out_f = flash_j(q, k_pages, v_pages, k_new, v_new, phys, pos)
    diff = float(jnp.max(jnp.abs(out_g - out_f)))
    assert diff < 2e-4, f"flash ref diverged from gather oracle: {diff}"
    t_g, t_f = time_jax_pair(gather_j, flash_j, q, k_pages, v_pages,
                             k_new, v_new, phys, pos, warmup=3, iters=20)
    view_mb = 2 * b * np_ * ps * kvh * d * 4 / 1e6
    tag = f"{b}x{np_ * ps}x{h}h{d}"
    emit(f"micro/flash_gather_decode_{tag}", t_g,
         f"dense KV view {view_mb:.1f}MB/step")
    emit(f"micro/flash_ref_decode_{tag}", t_f,
         f"view eliminated; {t_g / t_f:.2f}x vs gather; "
         f"max|diff|={diff:.1e}")

    # interpret-mode Pallas canary: tiny shapes (interpret is slow), the
    # row proves the scalar-prefetch page-table kernel stays oracle-exact
    sb, snp = 2, 8                                     # 128-token context
    s_phys = jnp.arange(sb * snp, dtype=jnp.int32).reshape(sb, snp)
    s_pos = jnp.array([snp * ps - 1] * sb, jnp.int32)
    sq = q[:sb]
    s_pool = sb * snp + 1
    flash_p = jax.jit(lambda q_, kp, vp, kn, vn, ph, po: flash_decode_paged(
        q_, kp, vp, kn, vn, ph, po, impl="pallas",
        interpret=jax.default_backend() != "tpu"))
    args_p = (sq, k_pages[:s_pool], v_pages[:s_pool], k_new[:sb],
              v_new[:sb], s_phys, s_pos)
    out_p = flash_p(*args_p)
    ref_p = flash_j(sq, k_pages[:s_pool], v_pages[:s_pool], k_new[:sb],
                    v_new[:sb], s_phys, s_pos)
    diff_p = float(jnp.max(jnp.abs(out_p - ref_p)))
    assert diff_p < 2e-4, f"pallas kernel diverged from ref: {diff_p}"
    t_p = time_jax(flash_p, *args_p, warmup=1, iters=3)
    mode = "tpu" if jax.default_backend() == "tpu" else "interpret"
    emit(f"micro/flash_pallas_{mode}_{sb}x{snp * ps}x{h}h{d}", t_p,
         f"max|diff| vs ref={diff_p:.1e}")


def run() -> None:
    key = jax.random.PRNGKey(0)
    m, k, n, v, c = 512, 768, 768, 8, 16
    nc = k // v
    x = jax.random.normal(key, (m, nc, v))
    z = jax.random.normal(jax.random.fold_in(key, 1), (nc, c, v))
    w = jax.random.normal(jax.random.fold_in(key, 2), (k, n))
    lut = build_lut(w, z)

    assign_j = jax.jit(lambda a, b: ref.assign_ref(a, b, "l2"))
    t = time_jax(assign_j, x, z)
    emit("micro/assign_l2_512x768", t, f"{m*nc*c*2/t*1e6/1e9:.1f} GFLOP/s")

    idx = assign_j(x, z)
    lookup_j = jax.jit(ref.lut_gemm_onehot)
    t = time_jax(lookup_j, idx, lut)
    emit("micro/lut_gemm_onehot_512x768x768", t,
         f"{2*m*nc*c*n/t*1e6/1e9:.1f} GFLOP/s")

    dense_j = jax.jit(lambda a, b: a @ b)
    xf = x.reshape(m, k)
    t_dense = time_jax(dense_j, xf, w)
    emit("micro/dense_gemm_512x768x768", t_dense,
         f"{2*m*k*n/t_dense*1e6/1e9:.1f} GFLOP/s")

    # int8 table halves the bytes the lookup streams (the TPU decode win)
    from repro.core.lut import quantize_lut_int8
    lut8, scale = quantize_lut_int8(lut)
    lookup8_j = jax.jit(lambda i, l, s: ref.lut_gemm_onehot(i, l, s))
    t8 = time_jax(lookup8_j, idx, lut8, scale)
    emit("micro/lut_gemm_int8", t8,
         f"bytes {lut8.nbytes/1e6:.1f}MB vs bf16 weights {w.nbytes*0.5/1e6:.1f}MB")

    # fused assign+lookup vs the two-pass pipeline, prefill + decode shapes
    _bench_fused_vs_two_pass(x, z, lut, f"{m}x{k}x{n}")
    md = 8                                            # decode-shaped batch
    xd = jax.random.normal(jax.random.fold_in(key, 3), (md, nc, v))
    _bench_fused_vs_two_pass(xd, z, lut, f"{md}x{k}x{n}")

    # paged flash-decode attention vs the legacy gather path
    _bench_flash_decode()


def main(argv=None) -> None:
    enable_compile_cache()
    import argparse
    import os
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--snapshot", default="",
                    help="write a BENCH_kernels.json perf snapshot to this "
                         "path ('auto' = repo root)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run()
    if args.snapshot:
        path = args.snapshot
        if path == "auto":
            path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "BENCH_kernels.json")
        snapshot(os.path.normpath(path), bench="kernels_micro")


if __name__ == "__main__":
    main()
