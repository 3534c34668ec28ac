"""The readers of the program's own names (``bench/spans.py``): on a trace
recorded on a TPU v5e chip (``data/named.xplane.pb``, made by
``data/record_named_trace.py``: a two-layer engine at Qwen1.5-4B's widths
serving three requests inside a ``bench.window`` span), with the expected
values counted from that trace's events one by one; on the older
``data/tiny.xplane.pb``, whose program names none of its work; and on
spans made by hand."""
import shutil
from types import SimpleNamespace

import pytest

import bench_smoke
import harness
import reduce
import spans
from work import flash_decode

DATA = bench_smoke.ROOT / "tests" / "bench" / "data"
NAMED = DATA / "named.xplane.pb"
READERS = ["decode_step_ms.decode", "prefill_chunk_ms.chat",
           "prefill_step_share.chat", "read_back_idle_ms.decode",
           "upload_idle_ms.decode"]


@pytest.fixture(scope="module")
def named():
    return spans.parse(str(NAMED))


def test_idle_goes_to_the_innermost_span_of_the_named_kinds():
    sp = spans.Spans(
        window_s=100e-9, modules={}, steps=[{"decode_slots": 2}, {}],
        gaps=[(0, 10), (20, 40), (90, 100)],
        host=[(5, 95, "bench.step"), (15, 60, "engine.step"),
              (25, 35, "engine.upload"), (36, 38, "PjitFunction(x)")])
    assert sp.idle_s == pytest.approx(40e-9)
    assert sp.idle_by(("engine.",)) == pytest.approx(
        {"(no span)": 20e-9, "engine.step": 10e-9, "engine.upload": 10e-9})
    assert sp.idle_by(("engine.", "bench.")) == pytest.approx(
        {"(no span)": 10e-9, "bench.step": 10e-9, "engine.step": 10e-9,
         "engine.upload": 10e-9})
    assert sp.decode_steps() == 1
    assert spans.idle_ms_per_decode_step(sp, "engine.upload") == \
        pytest.approx(10e-6)
    assert spans.idle_ms_per_decode_step(sp, "engine.device_read") == 0.0
    no_device = spans.Spans(window_s=1.0, modules={}, steps=sp.steps,
                            gaps=None, host=sp.host)
    assert no_device.idle_s is None and no_device.idle_by(("engine.",)) == {}
    assert spans.idle_ms_per_decode_step(no_device, "engine.upload") is None


def test_a_trace_without_the_programs_names_reads_nothing():
    sp = spans.parse(str(DATA / "tiny.xplane.pb"))
    assert sp.steps == [] and "jit_decode_step" not in sp.modules
    assert sp.idle_s == pytest.approx(0.013374 - 7.4051e-05, rel=1e-4)
    assert spans.program_ms(sp, "jit_decode_step") is None
    assert spans.prefill_step_share(sp) is None
    assert spans.idle_ms_per_decode_step(sp, "engine.upload") is None
    assert spans.program_ms(None, "jit_decode_step") is None


def test_metric_files_read_the_traced_runs_trace(tmp_path, monkeypatch):
    """The five readers find the trace where the harness leaves it, and
    an untraced run reads as nothing."""
    where = tmp_path / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(NAMED, where / "named.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    sp = spans.parse(str(NAMED))
    want = [spans.program_ms(sp, "jit_decode_step"),
            spans.program_ms(sp, "jit_prefill_chunk"),
            spans.prefill_step_share(sp),
            spans.idle_ms_per_decode_step(sp, "engine.device_read"),
            spans.idle_ms_per_decode_step(sp, "engine.upload")]
    for name, value in zip(READERS, want):
        reader = harness.load_module(bench_smoke.BENCH / "metrics" /
                                     f"{name}.py")
        assert value is not None and value > 0, name
        assert reader.read(SimpleNamespace(traced=object())) == value, name
        assert reader.read(SimpleNamespace(traced=None)) is None, name


def test_step_programs_by_name(named):
    assert "jit__lambda" not in named.modules
    assert {k: len(v) for k, v in named.modules.items()
            if k.startswith(("jit_decode", "jit_prefill", "jit_sample"))} \
        == {"jit_decode_step": 10, "jit_prefill_chunk": 4,
            "jit_sample_tokens": 10}
    # medians of the ten and the four device durations (ns), by hand:
    # (1929361 + 1929367) / 2 and (1863439 + 1865407) / 2
    assert spans.program_ms(named, "jit_decode_step") == \
        pytest.approx(1.929364, abs=1e-9)
    assert spans.program_ms(named, "jit_prefill_chunk") == \
        pytest.approx(1.864423, abs=1e-9)


def test_step_arguments(named):
    """The window's ten steps (3 to 12: the warm-up took 0 to 2): the
    8-token prompt, the 20-token one, then the 40-token one in two
    chunks, each decoding from the step its prompt finished."""
    assert [a["step"] for a in named.steps] == list(range(3, 13))
    assert [a["prefill_tokens"] for a in named.steps] == \
        [8, 20, 32, 8, 0, 0, 0, 0, 0, 0]
    assert [a["decode_slots"] for a in named.steps] == \
        [1, 2, 2, 3, 3, 3, 3, 2, 1, 1]
    assert [a["live_pages"] for a in named.steps] == \
        [6, 6, 6, 6, 6, 6, 5, 3, 3, 0]
    assert named.decode_steps() == 10
    assert spans.prefill_step_share(named) == 40.0


def test_idle_charged_to_the_engine_spans(named):
    """Each read-back's and upload's length less the ``XLA Ops`` time in
    it, summed over the window's 13 read-backs and 14 uploads, over its
    ten decoding steps; almost nothing is left outside the spans."""
    assert spans.idle_ms_per_decode_step(named, "engine.device_read") == \
        pytest.approx(2.5237625, rel=1e-9)
    assert spans.idle_ms_per_decode_step(named, "engine.upload") == \
        pytest.approx(0.3585474, rel=1e-9)
    by = named.idle_by(("engine.", "bench."))
    assert sum(by.values()) == pytest.approx(named.idle_s)
    assert by[spans.NO_SPAN] < 0.01 * named.idle_s


def test_flash_matcher_claims_one_call_per_layer_per_decode_step(named):
    red = reduce.reduce_trace(str(NAMED),
                              {"flash_decode": flash_decode.matches})
    assert red.kernel_events["flash_decode"] == \
        2 * len(named.modules["jit_decode_step"]) == 20
    assert any(n.startswith("flash_decode: flash_splits f32[")
               for n, _ in red.top_ops)
