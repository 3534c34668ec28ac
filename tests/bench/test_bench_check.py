"""The pieces of the harness that choose what the check compares and
which code a name finds, without running a model."""
import types

import pytest

import bench_smoke
import harness
import traffic


def _rec(n_out: int, prompt_len: int = 4):
    spec = traffic.Spec(0.0, [1] * prompt_len, n_out)
    req = types.SimpleNamespace(out_tokens=[7] * n_out, done=True)
    return harness.Rec(spec=spec, req=req)


@pytest.mark.parametrize("seed", [1, 2**33 + 7, 3_100_000_005])
def test_sample_keeps_the_longest_and_follows_the_seed(seed):
    recs = [_rec(n) for n in (3, 0, 9, 5, 2, 8, 4, 6, 1, 7, 12, 10)]
    a = harness.sample_for_check(recs, seed)
    assert a == harness.sample_for_check(recs, seed)
    assert a[0] is recs[10]                       # the longest, 12 tokens
    assert len(a) == harness.REF_SEQS
    assert all(r.req.out_tokens for r in a)       # nothing unserved
    assert len({id(r) for r in a}) == len(a)


def test_modules_are_loaded_once_per_path(tmp_path):
    for sub in ("a", "b"):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "mix.py").write_text(f"WHO = {sub!r}\n")
    a = harness.load_module(tmp_path / "a" / "mix.py")
    b = harness.load_module(tmp_path / "b" / "mix.py")
    assert (a.WHO, b.WHO) == ("a", "b")
    assert harness.load_module(tmp_path / "a" / "mix.py") is a


def test_a_mix_without_code_uses_the_general_generator(tmp_path):
    root = bench_smoke.make_root(tmp_path)
    cell = harness.load_cell(bench_smoke.cells()[0], False, root)
    src = harness.make_traffic(cell, 3, 256, 8)
    assert type(src) is traffic.Traffic
    assert src.max_len() == traffic.max_len(cell.mix)
