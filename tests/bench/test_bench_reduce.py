"""Trace reduction on a small trace recorded on a TPU v5e chip
(``data/tiny.xplane.pb``, made by ``data/record_tiny_trace.py``), and the
kernel matchers on HLO op texts taken from a trace of the LUT decode step
at Qwen1.5-4B's widths (``data/step_ops.json``)."""
import json
from pathlib import Path

import pytest

import bench_smoke  # noqa: F401  (puts bench/ on the path)
import reduce
from work import flash_decode, vq_amm

DATA = Path(__file__).resolve().parent / "data"
KERNELS = {"vq_amm": vq_amm.matches, "flash_decode": flash_decode.matches}


@pytest.fixture(scope="module")
def tiny():
    return reduce.reduce_trace(str(DATA / "tiny.xplane.pb"), KERNELS)


def test_window_busy_and_idle(tiny):
    assert tiny.devices == 1
    assert tiny.window_s == pytest.approx(0.013374, abs=1e-6)
    assert tiny.busy_s == pytest.approx(7.4051e-05, rel=1e-6)
    assert 0.99 < tiny.idle_share < 1.0


def test_kernels_found_once_per_call(tiny):
    # the profiler records the second and third of the three rounds
    assert tiny.kernel_events == {"vq_amm": 2, "flash_decode": 2}
    assert tiny.kernel_s["vq_amm"] > 0 and tiny.kernel_s["flash_decode"] > 0
    assert sum(tiny.kernel_s.values()) <= tiny.busy_s


def test_breakdown(tiny):
    assert len(tiny.top_ops) <= 10 and len(tiny.idle_gaps) <= 10
    names = [n for n, _ in tiny.top_ops]
    assert names[0].startswith("flash_decode: ")
    assert any(n.startswith("vq_amm: vq_amm_pallas f32[8,512]")
               for n in names)
    secs = [s for _, s in tiny.top_ops]
    assert secs == sorted(secs, reverse=True) and sum(secs) <= tiny.busy_s
    gaps = dict(tiny.idle_gaps)
    assert gaps["sample"] > 0.006          # three 2 ms pauses
    assert sum(gaps.values()) == pytest.approx(
        tiny.window_s - tiny.busy_s, rel=1e-6)


def test_matchers_on_the_step_programs_ops():
    ops = json.loads((DATA / "step_ops.json").read_text())
    assert flash_decode.matches(ops["flash"])
    assert not vq_amm.matches(ops["flash"])
    assert vq_amm.matches(ops["vq"]) and not flash_decode.matches(ops["vq"])
    for m in KERNELS.values():
        assert not m(ops["copy"])
    assert reduce.label(ops["copy"]) == "copy bf16[40,513,16,20,128]"
    assert reduce.label(ops["vq"]) == "vq_amm_pallas f32[8,7168]"


def test_idle_time_goes_to_the_innermost_host_span():
    spans = [(0, 10, "step"), (2, 4, "decode"), (6, 9, "sample")]
    assert reduce._leaf_segments(spans) == [
        (0, 2, "step"), (2, 4, "decode"), (4, 6, "step"), (6, 9, "sample"),
        (9, 10, "step")]
