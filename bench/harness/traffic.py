"""The one traffic generator: reads a mix file and makes the requests of a
run from its seed.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in another order, so that two seeds offer the same work and a spread
between runs is the system's own. Sizes are stratified quantiles of the
mix's distributions: item ``i`` of ``n`` takes the quantile at
``(i + 0.5) / n``, and the seed permutes them. Token ids are drawn from the
seed.

Mix keys (``bench/traffic/<mix>.json``):

* ``kind``: ``serve`` or ``train``.
* serve, ``loop``: ``open`` (Poisson arrivals at ``rate_per_s``) or
  ``closed`` (``outstanding`` requests in the system; a new one is sent
  when one finishes, drawn in blocks of ``block`` requests).
* ``prompt``, ``output``: length distributions, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}`` (both ends included).
* ``prefix`` (optional): ``{"groups", "len": <distribution>, "zipf_s"}``,
  tenant system prompts put in front of the prompt. A tenant's prompt
  length is fixed by its popularity rank, so it does not move with the
  seed.
* ``max_context``: no prompt plus output may exceed it.
* train: ``batch``, ``seq_len`` (rows of uniform tokens, one per step,
  all different); ``ticks_per_call``, the ticks handed to the engine in
  one call of its run loop, which stages a tick's rows while the device
  runs the tick before.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Req:
    """One request of a run: when it is due (seconds after the window
    opens; ``None`` in a closed loop), its prompt and its output budget."""
    idx: int
    due: Optional[float]
    prompt: np.ndarray
    max_tokens: int
    prefix_len: int = 0


def quantiles(dist: dict, n: int) -> List[int]:
    """``n`` stratified integer quantiles of ``dist``, ascending."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + math.floor(u * (hi - lo + 1))
        elif dist["dist"] == "lognormal":
            v = round(math.exp(math.log(dist["median"])
                               + dist["sigma"] * _NORMAL.inv_cdf(u)))
        else:
            raise ValueError(f"unknown distribution {dist['dist']!r}")
        out.append(min(max(v, lo), hi))
    return out


def exp_gaps(rate: float, n: int) -> List[float]:
    """``n`` stratified quantiles of the exponential gap of a Poisson
    process at ``rate`` per second."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def zipf_ranks(groups: int, s: float, n: int) -> List[int]:
    """Tenant rank (0 = most popular) of ``n`` requests under Zipf(s),
    stratified: the counts are the same for every seed."""
    w = np.array([(k + 1) ** -s for k in range(groups)])
    cdf = np.cumsum(w / w.sum())
    return [int(min(np.searchsorted(cdf, (i + 0.5) / n), groups - 1))
            for i in range(n)]


class ServeTraffic:
    """The requests of one serving run."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix.get("kind") != "serve":
            raise ValueError("not a serving mix")
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.max_context = int(mix["max_context"])
        self.prefixes: List[np.ndarray] = []
        pre = mix.get("prefix")
        if pre:
            lens = quantiles(pre["len"], pre["groups"])
            # a fixed order (not the seed's): tenant rank k always gets
            # the same length, so the prefix-cache work is the same
            order = np.random.default_rng(0).permutation(pre["groups"])
            self.prefixes = [self._tokens(lens[int(k)]) for k in order]
        self._n = 0

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def _block(self, n: int, dues: Optional[List[float]]) -> List[Req]:
        mix = self.mix
        users = self.rng.permutation(quantiles(mix["prompt"], n))
        outs = self.rng.permutation(quantiles(mix["output"], n))
        ranks = (self.rng.permutation(zipf_ranks(
            mix["prefix"]["groups"], mix["prefix"]["zipf_s"], n))
                 if self.prefixes else [None] * n)
        reqs = []
        for j in range(n):
            pre = (self.prefixes[ranks[j]] if ranks[j] is not None
                   else np.zeros(0, np.int32))
            prompt = np.concatenate([pre, self._tokens(int(users[j]))])
            if len(prompt) + int(outs[j]) > self.max_context:
                raise ValueError(f"request of {len(prompt)} + {outs[j]} "
                                 f"tokens exceeds max_context "
                                 f"{self.max_context}")
            reqs.append(Req(self._n, None if dues is None else dues[j],
                            prompt, int(outs[j]), len(pre)))
            self._n += 1
        return reqs

    def open_loop(self, seconds: float) -> List[Req]:
        """Every request due in a window of ``seconds``, by due time."""
        rate = float(self.mix["rate_per_s"])
        n = max(1, math.ceil(rate * seconds))
        gaps = self.rng.permutation(exp_gaps(rate, n))
        dues = np.cumsum(gaps)
        return self._block(n, [float(d) for d in dues])

    def closed_loop(self) -> Iterator[Req]:
        """An endless stream, drawn ``block`` requests at a time."""
        block = int(self.mix["block"])
        while True:
            yield from self._block(block, None)


@dataclasses.dataclass(frozen=True)
class _FeedShape:
    local_batch: int
    seq_len: int


class TrainTraffic:
    """Rows of a training run: batch ``i`` is a pure function of the seed
    and ``i``; every row differs. It has the interface of the program's
    token pipeline (``cfg``, ``batch_at``, ``restore``), so the train
    engine reads it directly and the reference reads the same rows."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix.get("kind") != "train":
            raise ValueError("not a training mix")
        self.batch, self.seq_len = int(mix["batch"]), int(mix["seq_len"])
        self.seed, self.vocab = seed, vocab
        self.cfg = _FeedShape(self.batch, self.seq_len)

    def restore(self, state: dict) -> None:
        """Batches are addressed by step; there is no cursor to move."""

    def batch_at(self, i: int) -> dict:
        rng = np.random.default_rng([self.seed, i])
        rows = rng.integers(0, self.vocab, size=(self.batch, self.seq_len + 1),
                            dtype=np.int32)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
