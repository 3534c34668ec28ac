#!/usr/bin/env python3
"""Readings for the limit of the correctness check: the program's
served-token gaps and the control's, on several seeds, in one process.

    python3 bench/readings.py --workload bf16-chat --seconds 15 \
        --seeds 11,12,13

Each seed is one whole run of the cell (set-up, a window at the cell's
own load, the check); the check also runs the control, the reference one
precision step below the configuration's (``bench/reference``), over
the same served sequences. One JSON line per seed. The benchmark's own
runs never run the control.
"""
import time

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 t0, control=True)
        except harness.NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "gaps": r["gaps"], "control_gaps": r["control_gaps"],
            "served_tokens_checked":
                r["checks"]["served_tokens_checked"]["value"],
            "attempted": r["attempted"], "failed": r["failed"],
            "metrics": r["metrics"], "device": r["device"],
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
