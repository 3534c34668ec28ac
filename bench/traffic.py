"""The one traffic generator: turns a mix's parameter file into requests.

A mix (``bench/traffic/<mix>.json``) gives length distributions and a
loop kind (a mix that needs another kind of source brings its own
``bench/traffic/<mix>.py`` defining ``Traffic`` with this class's
interface; see ``bench/harness.py``); a cell file (``bench/cells/<cell>.json``) gives what differs
between cells that share a mix, such as the rate. Every seed gets the same
multiset of lengths and inter-arrival gaps, drawn at evenly spaced
quantiles of each distribution, and token ids drawn from the seed: the
seed changes which request is which, not how much work a run holds.

* ``"loop": "open"``: Poisson arrivals at ``rate_per_s`` (independent
  users), lengths and gaps in an order drawn from the seed. Exactly
  ``round(rate * seconds)`` requests are scheduled inside the window;
  arrivals go on after it, so the load holds while the window's requests
  finish, but those later requests are not counted.
* ``"loop": "closed"``: a backlog that keeps every slot busy; lengths are
  stratified within each group of ``slots`` consecutive requests, in one
  fixed order for every seed: how long prompts coincide decides the page
  pool's pressure and with it the engine's prefill budget, so an order
  drawn from the seed changed the prefill cell's throughput by 3.7%
  between seeds against 0.01% between runs of one seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request as generated: when it is due (seconds from the start of
    the window, open loop only), its prompt and its output budget."""
    due: float
    prompt: List[int]
    max_new: int
    in_window: bool = True


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a length
    distribution, clipped to its ``min``/``max``: ``lognormal``
    (``median``, ``sigma``) or ``uniform`` (integers ``min``..``max``)."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    elif dist["dist"] == "uniform":
        x = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def max_len(mix: dict) -> int:
    """Longest prompt plus output the mix can make."""
    return mix["prompt"]["max"] + mix["output"]["max"]


class Traffic:
    """Requests of one mix for one seed.

    ``vocab`` bounds the token ids; ``slots`` is the engine's concurrency
    (the closed loop's stratification group)."""

    def __init__(self, mix: dict, cell: dict, seed: int, vocab: int,
                 slots: int):
        self.mix, self.cell = mix, cell
        self.loop = mix["loop"]
        self.vocab, self.slots = vocab, slots
        self.rng = np.random.default_rng(int(seed) % (1 << 63))

    def max_len(self) -> int:
        """Longest prompt plus output this source can make."""
        return max_len(self.mix)

    def _prompt(self, n: int) -> List[int]:
        return self.rng.integers(0, self.vocab, int(n)).tolist()

    def _lengths(self, n: int):
        p = self.rng.permutation(quantiles(self.mix["prompt"], n))
        o = self.rng.permutation(quantiles(self.mix["output"], n))
        return p, o

    def open_schedule(self, seconds: float, after: float) -> List[Spec]:
        """Arrivals for a window of ``seconds``, then ``after`` seconds of
        further arrivals (drawn alike, not counted)."""
        rate = float(self.cell["rate_per_s"])
        specs: List[Spec] = []
        start = 0.0
        for span, counted in ((seconds, True), (after, False)):
            n = max(1, round(rate * span))
            q = (np.arange(n) + 0.5) / n
            gaps = self.rng.permutation(-np.log1p(-q))
            due = start + span * np.concatenate(
                [[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
            p, o = self._lengths(n)
            specs += [Spec(float(t), self._prompt(a), int(b), counted)
                      for t, a, b in zip(due, p, o)]
            start += span
        return specs

    def backlog(self) -> Iterator[Spec]:
        """An endless closed-loop backlog (lengths in the fixed order)."""
        order = np.random.default_rng(0)
        while True:
            p = order.permutation(quantiles(self.mix["prompt"], self.slots))
            o = order.permutation(quantiles(self.mix["output"], self.slots))
            for a, b in zip(p, o):
                yield Spec(0.0, self._prompt(a), int(b))
