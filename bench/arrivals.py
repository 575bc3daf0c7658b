"""Request schedules from a traffic file and ``--seed``.

One generator for every mix.  A traffic file gives lognormal length
distributions (median, sigma, clip range) for prompts and outputs and, for an
open loop, a Poisson arrival rate in requests per second.

Every seed gets the same work.  Lengths and inter-arrival gaps are taken at
stratified quantiles, ``(j + 0.5) / block`` for ``j < block``, of their
distributions; each block of ``block`` consecutive requests holds every one
of those values once, in an order drawn from the traffic file's own
``order_seed`` (independently for prompt lengths, output lengths and gaps).
So the lengths and due times of the n-th request are a property of the mix,
the same in every run.  ``--seed`` draws the prompt token ids (and, in the
harness, the weights and the faults): two seeds differ in what is computed,
not in how much.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(median: float, sigma: float, lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal, rounded and clipped to [lo, hi]."""
    z = np.array([_NORMAL.inv_cdf((j + 0.5) / n) for j in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the gap between Poisson arrivals (s)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _lengths(dist: dict, n: int) -> np.ndarray:
    return lognormal_quantiles(dist["median"], dist["sigma"], dist["min"], dist["max"], n)


@dataclasses.dataclass
class Req:
    index: int
    due_s: float            # seconds after the schedule's start (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


class Schedule:
    """Endless request stream for one run."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.loop = traffic["loop"]
        if self.loop not in ("open", "backlog"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.block = int(traffic["block"])
        self.prompt_lens = _lengths(traffic["prompt"], self.block)
        self.output_lens = _lengths(traffic["output"], self.block)
        self.gaps = (exponential_quantiles(float(traffic["rate_rps"]), self.block)
                     if self.loop == "open" else np.zeros(self.block))
        self.vocab = vocab
        self._order = np.random.default_rng(int(traffic["order_seed"]))
        self._tokens = np.random.default_rng(seed)
        self._n = 0
        self._t = 0.0
        self._perm: tuple[np.ndarray, ...] = ()

    def next(self) -> Req:
        j = self._n % self.block
        if j == 0:
            self._perm = tuple(self._order.permutation(self.block) for _ in range(3))
        pp, po, pg = self._perm
        self._t += float(self.gaps[pg[j]])
        plen = int(self.prompt_lens[pp[j]])
        req = Req(self._n, self._t,
                  self._tokens.integers(0, self.vocab, plen, dtype=np.int32),
                  int(self.output_lens[po[j]]))
        self._n += 1
        return req


def mean_service_steps(traffic: dict) -> float:
    """Mean slot occupancy per request in server steps: one step per prompt
    token, then one per output token after the first (which rides the last
    prompt step)."""
    n = int(traffic["block"])
    return float(_lengths(traffic["prompt"], n).mean() + _lengths(traffic["output"], n).mean() - 1)
