import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below runs with 512 placeholder CPU devices ---------------
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Optional  # noqa: E402

import jax                                   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_NAMES, get_config          # noqa: E402
from repro.core.lut import QuantConfig                     # noqa: E402
from repro.launch import roofline as rl                    # noqa: E402
from repro.launch.mesh import (data_axes,                  # noqa: E402
                               make_production_mesh)
from repro.launch.specs import (SHAPES, cell_is_runnable,   # noqa: E402
                                serve_input_specs, train_input_specs)
from repro.models.model import Model                        # noqa: E402
from repro.parallel.sharding import (batch_pspecs, cache_pspecs,  # noqa: E402
                                     param_pspecs)
from repro.train.trainer import TrainConfig, make_train_step  # noqa: E402


def _shard(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def _opt_pspecs(p_pspecs, tc: TrainConfig):
    out = {"adam": {"m": p_pspecs, "v": p_pspecs, "count": P()}}
    if tc.compress_grads:
        out["ef"] = p_pspecs
    return out


def quant_config(mode: str, kind: str) -> QuantConfig:
    """The paper's technique operating point per step kind."""
    if mode == "dense":
        return QuantConfig(mode="dense")
    lut_mode = "lut_train" if kind == "train" else "lut_infer"
    return QuantConfig(mode=lut_mode, v=8, c=16, metric="l2",
                       lut_dtype="int8" if kind != "train" else "float32",
                       impl="ref")


def run_cell(arch: str, shape_name: str, mesh, quant: str = "lut",
             tc: Optional[TrainConfig] = None, verbose: bool = True,
             cfg_overrides: Optional[dict] = None):
    """Lower + compile one (arch × shape) cell on `mesh`. Returns a dict."""
    case = SHAPES[shape_name]
    cfg = get_config(arch)
    runnable, why = cell_is_runnable(cfg, shape_name)
    result = {"arch": arch, "shape": shape_name, "quant": quant,
              "mesh": "x".join(str(s) for s in mesh.devices.shape),
              "chips": mesh.devices.size}
    if not runnable:
        result.update({"status": "skipped", "reason": why})
        return result

    overrides = dict(cfg_overrides or {})
    if case.kind == "train":
        overrides.setdefault("remat", True)
    cfg = cfg.replace(**overrides)
    model = Model(cfg)
    qc = quant_config(quant, case.kind)
    da = data_axes(mesh)
    dp = 1
    for a in da:
        dp *= mesh.shape[a]

    t0 = time.perf_counter()
    if case.kind == "train":
        tc = tc or TrainConfig()
        p_s, o_s, b_s, step_s = train_input_specs(model, qc, case, tc)
        p_spec = param_pspecs(p_s, cfg, model_axis_size=mesh.shape["model"])
        in_specs = (_shard(mesh, p_spec),
                    _shard(mesh, _opt_pspecs(p_spec, tc)),
                    _shard(mesh, batch_pspecs(cfg, da)),
                    NamedSharding(mesh, P()))
        metrics_spec = {"loss": P(), "ce": P(), "recon": P(),
                        "moe_aux": P(), "grad_norm": P(), "lr": P()}
        out_specs = (_shard(mesh, p_spec),
                     _shard(mesh, _opt_pspecs(p_spec, tc)),
                     _shard(mesh, metrics_spec))
        step_fn = make_train_step(model, qc, tc, stage=3)
        jitted = jax.jit(step_fn, in_shardings=in_specs,
                         out_shardings=out_specs)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(p_s, o_s, b_s, step_s)
    else:
        specs = serve_input_specs(model, qc, case)
        p_s, in2_s, cache_s = specs
        p_spec = param_pspecs(p_s, cfg, model_axis_size=mesh.shape["model"])
        cache_spec = cache_pspecs(cfg, case.batch, mesh, da)
        batch_first = case.batch % dp == 0 and case.batch >= dp
        dlead = (da if len(da) > 1 else da[0]) if batch_first else None
        if case.kind == "prefill":
            in2_spec = batch_pspecs(cfg, da)
            in2_spec.pop("labels", None)
            fn = lambda p, b, c: model.prefill(p, b, c, qc)  # noqa: E731
        else:
            if cfg.family == "audio":
                in2_spec = P(dlead, None, None)
            else:
                in2_spec = P(dlead, None)
            fn = lambda p, t, c: model.decode(p, t, c, qc)   # noqa: E731
        vshard = "model" if cfg.vocab_size % mesh.shape["model"] == 0 \
            else None
        logits_spec = (P(dlead, None, vshard) if cfg.family == "audio"
                       else P(dlead, vshard))
        in_specs = (_shard(mesh, p_spec), _shard(mesh, in2_spec),
                    _shard(mesh, cache_spec))
        out_specs = (_shard(mesh, logits_spec), _shard(mesh, cache_spec))
        jitted = jax.jit(fn, in_shardings=in_specs, out_shardings=out_specs,
                         donate_argnums=(2,))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(p_s, in2_s, cache_s)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
        if not mem and ma is not None:
            mem["repr"] = str(ma)
    except Exception as e:                      # pragma: no cover
        mem["error"] = repr(e)

    def _tree_bytes(tree):
        return float(sum(x.size * x.dtype.itemsize
                         for x in jax.tree_util.tree_leaves(tree)))

    param_bytes = _tree_bytes(p_s)
    cache_bytes = _tree_bytes(cache_s) if case.kind != "train" else 0.0
    mf = rl.model_flops_for(cfg, case.kind, case.batch, case.seq)
    mb = rl.model_bytes_for(cfg, case.kind, case.batch, case.seq,
                            param_bytes, cache_bytes)
    report = rl.analyze(compiled, chips=mesh.devices.size, model_flops=mf,
                        model_bytes=mb)
    result.update({
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "roofline": report.to_dict(),
    })
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']} ({quant}) "
              f"OK — lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"bottleneck={report.bottleneck} "
              f"frac={report.roofline_fraction:.3f}")
        print(f"  memory: {mem}")
        print(f"  flops={report.flops:.3e} bytes={report.bytes_accessed:.3e} "
              f"coll={report.total_coll_bytes:.3e}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="LUT-DLA multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--quant", default="lut", choices=["lut", "dense"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have results")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_tag = "multi" if multi else "single"
        for arch in archs:
            for shape in shapes:
                tag = f"{mesh_tag}__{args.quant}__{arch}__{shape}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[dryrun] {tag}: cached, skipping")
                    continue
                try:
                    res = run_cell(arch, shape, mesh, args.quant)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "quant": args.quant,
                           "mesh": mesh_tag, "status": "error",
                           "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures.append(tag)
                    print(f"[dryrun] {tag}: FAILED — {e!r}")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all requested cells passed")


if __name__ == "__main__":
    main()
