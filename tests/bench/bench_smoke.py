"""A benchmark tree at smoke size, for running the harness on the CPU.

:func:`make_root` copies a benchmark's data as it is (``BENCHMARK.json``
and every file of ``bench/traffic`` and ``bench/cells``) and writes each of
its configurations at smoke widths: the same keys, deployment (slots,
``max_seq``, pages) and head, with two layers of width 64 and a vocabulary
of 256, and the check's limit set for that size. Every cell then runs
under its own name with its own traffic, at its own rate and lengths.
Nothing here names a cell or a mix, so a cell added as data runs here as
it is. The harness's code (the generator, the reference, the metric
readers, the work counts) is the real one."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: the sizes a configuration is cut to here
SMOKE = {"num_hidden_layers": 2, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "intermediate_size": 128, "vocab_size": 256}

#: the paper's projection (int8 tables, v=8, c=16, L2), for the test of a
#: LUT configuration
LUT = {"mode": "lut_infer", "v": 8, "c": 16, "metric": "l2",
       "lut_dtype": "int8"}

#: the limit of the check (mean gap of the served tokens) at these sizes:
#: on the CPU, over every cell and 10 seeds, the program read at most
#: 0.000046, and the int8 control on the cell that serves the most tokens
#: at least 0.000256
LIMIT = 0.0001


def smoke_config(real: dict, limit: float, projection=None) -> dict:
    cfg = dict(real, **SMOKE, check={"mean_logit_gap": limit})
    cfg.pop("head_dim", None)
    if projection is not None:
        cfg["projection"] = projection
    return cfg


def make_root(path: Path, src: Path = ROOT, limit: float = LIMIT,
              projection=None) -> Path:
    """Write the smoke tree of the benchmark at ``src`` into ``path``."""
    bench = json.loads((src / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        real = json.loads((src / c["file"]).read_text())
        out = path / c["file"]
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(smoke_config(real, limit, projection)))
    for sub in ("traffic", "cells"):
        (path / "bench" / sub).mkdir(parents=True, exist_ok=True)
        for f in sorted((src / "bench" / sub).iterdir()):
            body = f.read_text()
            if sub == "cells" and f.suffix == ".json":
                extra = json.loads(body)
                if "check" in extra:
                    extra["check"] = {"mean_logit_gap": limit}
                body = json.dumps(extra)
            (path / "bench" / sub / f.name).write_text(body)
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return path


def cells(src: Path = ROOT) -> list:
    return [w["name"] for w in
            json.loads((src / "BENCHMARK.json").read_text())["workloads"]]


def end_to_end(cell: str, src: Path = ROOT) -> set:
    """Names of the end-to-end metrics that ``cell`` reports."""
    bench = json.loads((src / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}


def longest_output_cell(src: Path = ROOT) -> str:
    """The cell whose mix serves the most tokens per request."""
    bench = json.loads((src / "BENCHMARK.json").read_text())

    def out_max(w):
        mix = json.loads((src / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
        return mix["output"]["max"]
    return max(bench["workloads"], key=out_max)["name"]


def run(path: Path, cell: str, seed: int = 2**33 + 3, seconds: float = 1.5,
        control: bool = False) -> dict:
    """One harness run of a cell of a smoke tree on the CPU (no chip
    check, no persistent compilation cache)."""
    import time
    import harness
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            chips_required=False, root=path,
                            control=control, cache=False)
