"""LUTBoost model conversion (paper §V): dense LM → LUT-based LM.

Stage ① k-means init from calibration activations, stage ② centroid-only
training, stage ③ joint fine-tune, then int8-LUT precompute + evaluation of
every similarity metric.

Run: PYTHONPATH=src python examples/lutboost_convert.py [--steps N]
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.core import precompute_model
from repro.core.lut import DENSE, QuantConfig
from repro.core.lutboost import LutBoostSchedule, convert
from repro.data import SyntheticDataset
from repro.models.model import Model
from repro.train import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--v", type=int, default=4)
    ap.add_argument("--c", type=int, default=16)
    args = ap.parse_args()

    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    model = Model(cfg)
    ds = SyntheticDataset(cfg, global_batch=16, seq_len=64)

    # 0) a trained dense model (the conversion input)
    params = model.init(jax.random.PRNGKey(0), DENSE)
    dense_tc = TrainConfig(total_steps=args.steps, lr=3e-3, warmup=10,
                           log_every=10**9)
    params, _, dh = Trainer(model, ds, DENSE, dense_tc).run(params)
    dense_loss = float(np.mean(dh["loss"][-10:]))
    print(f"dense model CE: {dense_loss:.4f}")

    for metric in ("l2", "l1", "chebyshev"):
        qc = QuantConfig(mode="lut_train", v=args.v, c=args.c, metric=metric,
                         recon_weight=0.05)
        # stage ①
        lut_params = convert(lambda p, b: model.forward(p, b, DENSE)[0],
                             params, ds.batch(0), qc)
        # stages ② + ③
        sched = LutBoostSchedule(stage2_steps=30, stage3_steps=70)
        tc = TrainConfig(total_steps=100, lr=1e-3, warmup=0, log_every=10**9)
        lut_params, _, hist = Trainer(model, ds, qc, tc,
                                      lutboost=sched).run(lut_params)
        # deploy at int8 tables
        qi = qc.replace(mode="lut_infer", lut_dtype="int8", impl="ref")
        pi = precompute_model(lut_params, qi)
        ev = float(np.mean([float(model.loss(pi, ds.batch(200 + i), qi)[0])
                            for i in range(4)]))
        print(f"  {metric:9s}: converted CE {ev:.4f} "
              f"(drop {ev - dense_loss:+.4f}, "
              f"equivalent bits {np.ceil(np.log2(args.c)) / args.v:.2f})")


if __name__ == "__main__":
    main()
