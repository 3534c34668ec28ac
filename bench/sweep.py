#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one process, one
set-up, one window per offered rate.

    python3 bench/sweep.py --workload bf16-chat --seed 1 --seconds 30 \
        --rates 0.5,1,1.5,2

For each rate it prints one JSON line: requests due in the window, how
many finished by its close, the queue at the close, TTFT and queue-wait
percentiles, and the TTFT p90 of the window's first and second halves (a
sustained rate keeps them alike; above it the queue, and the second half,
grow). Between rates the engine drains, unmeasured. The chosen rate, 0.8
of the highest sustained one, goes into ``bench/cells/<cell>.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
from contextlib import nullcontext  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import harness
    import measure
    try:
        cell = harness.load_cell(args.workload, False)
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    eng = harness.build_engine(cell.config, args.seed, False)
    harness.warm_up(eng)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        extra = dict(cell.extra, rate_per_s=rate)
        src = harness.make_traffic(dataclasses.replace(cell, extra=extra),
                                   args.seed, cell.config["vocab_size"],
                                   eng.num_slots)
        drv = harness.Feeder(eng, src, args.seconds, False,
                             lambda name: nullcontext())
        drv.drain_cap = 0.0
        drv.run()
        run = harness.Run(cell=cell, setup_s=0.0, recs=drv.recs,
                          window=drv.window, steps=drv.steps)
        counted = run.counted()
        half = args.seconds / 2
        ttft = measure.ttft_ms(run)
        first = [t for r, t in zip([r for r in counted if r.token_t], ttft)
                 if r.spec.due < half]
        second = [t for r, t in zip([r for r in counted if r.token_t], ttft)
                  if r.spec.due >= half]
        print(json.dumps({
            "rate_per_s": rate, "due": len(counted),
            "finished_by_close": sum(r.done for r in counted),
            "queue_at_close": len(eng.scheduler.waiting),
            "steps": len(drv.steps),
            "ttft_p50_ms": measure.pct(ttft, 50),
            "ttft_p90_ms": measure.pct(ttft, 90),
            "ttft_p90_first_half_ms": measure.pct(first, 90),
            "ttft_p90_second_half_ms": measure.pct(second, 90),
            "itl_p95_ms": measure.pct(measure.itl_ms(run), 95),
            "queue_wait_p90_ms": measure.pct(measure.queue_wait_ms(run), 90),
        }), flush=True)
        eng.run_until_idle()
    return 0


if __name__ == "__main__":
    sys.exit(main())
