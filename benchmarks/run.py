"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``--only <prefix>`` runs a
subset (e.g. ``--only table1``); accuracy benches (table2/table45) train
small proxies and take a few minutes each.
"""
from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="prefix filter: table1|table2|table45|table789|"
                         "fig13|micro")
    ap.add_argument("--skip-training", action="store_true",
                    help="skip the training-based accuracy benches "
                         "(table2/table45)")
    args = ap.parse_args()

    from . import (fig13_e2e, kernels_micro, table1_dataflow, table2_lutboost,
                   table45_accuracy, table789_hardware)
    suites = [
        ("table1", table1_dataflow.run),
        ("table789", table789_hardware.run),
        ("fig13", fig13_e2e.run),
        ("micro", kernels_micro.run),
        ("table2", table2_lutboost.run),
        ("table45", table45_accuracy.run),
    ]
    training = {"table2", "table45"}
    print("name,us_per_call,derived")
    for name, fn in suites:
        if args.only and not name.startswith(args.only):
            continue
        if args.skip_training and name in training:
            print(f"{name}/SKIPPED,0.0,--skip-training")
            continue
        try:
            fn()
        except Exception as e:      # pragma: no cover
            print(f"{name}/ERROR,0.0,{e!r}", file=sys.stderr)
            raise


if __name__ == "__main__":
    main()
