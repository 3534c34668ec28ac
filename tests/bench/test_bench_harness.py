"""The harness's cell loop on the CPU at smoke size, cells added as data
alone, and the command's refusal of a host without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_smoke

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_smoke.make_root(tmp_path_factory.mktemp("bench"))


def _assert_contract_line(result, cell, src=bench_smoke.ROOT):
    line = json.loads(json.dumps(result))
    assert list(line) == KEYS                 # checks comes last
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == bench_smoke.end_to_end(cell, src)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert line["checks"]["served_tokens_checked"]["value"] >= 1


@pytest.mark.parametrize("cell", bench_smoke.cells())
def test_cell_loop_prints_the_contract_line(root, cell):
    _assert_contract_line(bench_smoke.run(root, cell), cell)


def test_lut_configuration_runs_the_program_conversion(tmp_path):
    root = bench_smoke.make_root(tmp_path, projection=bench_smoke.LUT)
    cell = bench_smoke.longest_output_cell()
    line = bench_smoke.run(root, cell, seconds=1.0)
    assert list(line) == KEYS and line["failed"] == 0
    assert set(line["metrics"]) == bench_smoke.end_to_end(cell)
    assert line["checks"]["served_tokens_checked"]["value"] > 100


def _source_with(tmp_path, workload: dict, files: dict):
    """A copy of the benchmark's data with one more cell: its
    ``workloads`` entry, its name added to every metric that lists the
    cell it is modelled on, and its new files."""
    src = tmp_path / "src"
    for sub in ("configs", "traffic", "cells"):
        shutil.copytree(bench_smoke.BENCH / sub, src / "bench" / sub)
    bench = json.loads((bench_smoke.ROOT / "BENCHMARK.json").read_text())
    like = workload.pop("like")
    bench["workloads"].append(workload)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(workload["name"])
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, body in files.items():
        (src / rel).write_text(body)
    return src


def _chat(bench_dir=bench_smoke.BENCH):
    bench = json.loads((bench_smoke.ROOT / "BENCHMARK.json").read_text())
    w = next(w for w in bench["workloads"]
             if json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                           .read_text())["loop"] == "open")
    return w, (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()


def test_a_cell_added_as_data_alone_runs(tmp_path):
    """PERF.md's worked example: the chat cell at twice its load, from a
    copy of its mix under a new name and a new cell file."""
    w, mix = _chat()
    rate = json.loads((bench_smoke.BENCH / "cells" / f"{w['name']}.json")
                      .read_text())["rate_per_s"]
    new = {"name": w["name"] + "-2x", "config": w["config"],
           "traffic": w["traffic"] + "-2x", "chips": 1,
           "why": "the chat mix at twice the load", "like": w["name"]}
    src = _source_with(tmp_path, dict(new), {
        f"bench/traffic/{new['traffic']}.json": mix,
        f"bench/cells/{new['name']}.json":
            json.dumps({"rate_per_s": 2 * rate})})
    root = bench_smoke.make_root(tmp_path / "smoke", src)
    result = bench_smoke.run(root, new["name"])
    _assert_contract_line(result, new["name"], src)
    assert result["attempted"] == round(2 * rate * 1.5)


#: a mix that needs a source of its own: every request of the window due
#: at its start, one burst
BURST = '''
import traffic


class Traffic(traffic.Traffic):
    def open_schedule(self, seconds, after):
        specs = super().open_schedule(seconds, after)
        for s in specs:
            if s.in_window:
                s.due = 0.0
        return specs
'''


def test_a_mix_with_its_own_generator_is_found_by_name(tmp_path):
    import harness
    w, mix = _chat()
    new = {"name": w["name"] + "-burst", "config": w["config"],
           "traffic": w["traffic"] + "-burst", "chips": 1,
           "why": "the window's chat requests all at once",
           "like": w["name"]}
    src = _source_with(tmp_path, dict(new), {
        f"bench/traffic/{new['traffic']}.json": mix,
        f"bench/traffic/{new['traffic']}.py": BURST,
        f"bench/cells/{new['name']}.json": json.dumps({"rate_per_s": 4.0})})
    root = bench_smoke.make_root(tmp_path / "smoke", src)
    cell = harness.load_cell(new["name"], False, root)
    specs = harness.make_traffic(cell, 5, 256, 8).open_schedule(1.5, 1.0)
    assert [s.due for s in specs if s.in_window] == [0.0] * 6
    _assert_contract_line(bench_smoke.run(root, new["name"]), new["name"],
                          src)


def test_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(bench_smoke.BENCH / "run.py"), "--workload",
         bench_smoke.cells()[0], "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=bench_smoke.ROOT)
    assert res.returncode == 2
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr
