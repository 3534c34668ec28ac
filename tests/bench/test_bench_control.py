"""The control, at a size a test run holds: the reference one precision
step below the configuration's, put in the program's place, must come out
not correct under a limit that the program's own runs pass.

At the chip's sizes the readings of both come from ``bench/readings.py``
(PERF.md gives them and the limits set from them). Here the
configurations are cut to smoke size (control: int8 weights and
activations) and the cell is the one whose mix serves the most tokens per
request, whose hundreds of served tokens separate the two: on the CPU the
program's mean gap read at most 0.000046 there and the control's at least
0.000256 (10 seeds), on either side of the smoke limit."""
import pytest

import bench_smoke

LIMIT = bench_smoke.LIMIT


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_smoke.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("seed", [2, 3])
def test_control_fails_where_the_program_passes(root, seed):
    result = bench_smoke.run(root, bench_smoke.longest_output_cell(),
                             seed=seed, control=True)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["mean_logit_gap"]["value"] <= LIMIT
    assert result["control_gaps"]["mean"] > LIMIT
