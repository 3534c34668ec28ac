"""The traffic generator: reproducible, inside its ranges, and the same
work for every seed."""
import json
from collections import Counter

import pytest

import bench_smoke
import traffic

MIXES = sorted(p.stem for p in (bench_smoke.BENCH / "traffic").glob("*.json"))
MAX_SEQ = 1024


def _mix(name):
    return json.loads((bench_smoke.BENCH / "traffic" / f"{name}.json")
                      .read_text())


def _specs(name, seed, seconds=20.0):
    mix = _mix(name)
    t = traffic.Traffic(mix, {"rate_per_s": 2.0}, seed, 151936, 8)
    if mix["loop"] == "open":
        return [s for s in t.open_schedule(seconds, 5.0) if s.in_window]
    it = t.backlog()
    return [next(it) for _ in range(24)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = _specs(name, 2**33 + 1), _specs(name, 2**33 + 1)
    assert [(s.due, s.prompt, s.max_new) for s in a] == \
        [(s.due, s.prompt, s.max_new) for s in b]
    c = _specs(name, 2**33 + 2)
    assert [s.prompt for s in a] != [s.prompt for s in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_fit(name):
    mix = _mix(name)
    for s in _specs(name, 7):
        assert mix["prompt"]["min"] <= len(s.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= s.max_new <= mix["output"]["max"]
        assert len(s.prompt) + s.max_new <= MAX_SEQ
        assert all(0 <= t < 151936 for t in s.prompt)
    assert traffic.max_len(mix) <= MAX_SEQ


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    a, b = _specs(name, 11), _specs(name, 12)
    assert Counter(len(s.prompt) for s in a) == \
        Counter(len(s.prompt) for s in b)
    assert Counter(s.max_new for s in a) == Counter(s.max_new for s in b)


@pytest.mark.parametrize("name", [m for m in MIXES
                                  if _mix(m)["loop"] == "closed"])
def test_closed_loops_give_every_seed_the_same_lengths_in_order(name):
    a, b = _specs(name, 11), _specs(name, 12)
    assert [(len(s.prompt), s.max_new) for s in a] == \
        [(len(s.prompt), s.max_new) for s in b]


def test_open_schedule_counts_and_spans_the_window():
    t = traffic.Traffic(_mix("chat-short"), {"rate_per_s": 1.5}, 3, 100, 8)
    specs = t.open_schedule(40.0, 10.0)
    inside = [s for s in specs if s.in_window]
    assert len(inside) == 60
    assert inside[0].due == 0.0 and all(s.due < 40.0 for s in inside)
    assert all(s.due >= 40.0 for s in specs if not s.in_window)
    dues = [s.due for s in specs]
    assert dues == sorted(dues)


def test_quantiles_follow_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 64, "sigma": 0.8,
                           "min": 16, "max": 256}, 1001)
    assert sorted(q)[500] == 64
    u = traffic.quantiles({"dist": "uniform", "min": 512, "max": 992}, 481)
    assert sorted(u) == list(range(512, 993))
