"""The one traffic generator: every mix is a data file of its parameters.

Open loop (independent users): arrivals on a schedule, whether or not
earlier requests have finished; each request is timed from its scheduled
arrival.  Closed loop (callers that wait): ``clients`` sessions, each sending
its next request when the last one returns.

Every seed gets the same work in another order: prompt lengths are the
stratified quantiles of the mix's length distribution and inter-arrival gaps
the stratified quantiles of the exponential distribution at the mix's rate,
each permuted by the seed.  Token ids are drawn from the seed.  So runs with
different seeds differ in order and content, not in how much work they hold.

Derived from ``benchmarks/serving.py``'s open loop (Poisson gaps at a fixed
rate, requests stamped with their scheduled arrival).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

# independent random streams per seed
_LENGTHS, _GAPS, _TOKENS, _LEAD, _TAIL = range(5)


def _rng(seed: int, stream: int, *rest: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), stream, *rest])


def stratified_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` prompt lengths at the stratified quantiles (i + 1/2) / n of a
    log-normal with the given median and sigma, clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lens = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(lens), spec["min"], spec["max"]).astype(np.int64)


def stratified_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at ``rate`` per second, at the
    stratified quantiles; their sum is n / rate up to the quantile grid."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclass
class Arrival:
    rid: int
    offset: float        # seconds from the window's start (negative: lead-in)
    length: int
    counted: bool        # arrives inside the measured window
    tokens: np.ndarray


def _tokens(seed: int, rid: int, n: int, vocab: int) -> np.ndarray:
    return _rng(seed, _TOKENS, rid).integers(0, vocab, size=n,
                                             dtype=np.int64).astype(np.int32)


def _phase(mix: Dict, seed: int, stream: int, seconds: float) -> (
        np.ndarray, np.ndarray):
    """Offsets in [0, seconds) and lengths for one phase: round(rate *
    seconds) arrivals, gaps scaled so the phase spans exactly ``seconds``."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    rng = _rng(seed, stream)
    gaps = rng.permutation(stratified_gaps(mix["rate_rps"], n))
    gaps = gaps * (seconds / gaps.sum())
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lens = rng.permutation(stratified_lengths(mix["prompt"], n))
    return offsets, lens


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int
              ) -> List[Arrival]:
    """Lead-in arrivals (``lead_s`` before the window, uncounted) fill the
    server to its steady state; the window's arrivals are counted; tail
    arrivals (up to ``drain_cap_s`` after it, uncounted) keep the load on
    while the counted requests finish."""
    phases = [(-mix["lead_s"], mix["lead_s"], _LEAD, False),
              (0.0, seconds, _GAPS, True),
              (seconds, mix["drain_cap_s"], _TAIL, False)]
    out: List[Arrival] = []
    for start, span, stream, counted in phases:
        if span <= 0:
            continue
        offsets, lens = _phase(mix, seed, stream, span)
        for off, n in zip(offsets, lens):
            rid = len(out)
            out.append(Arrival(rid, start + float(off), int(n), counted,
                               _tokens(seed, rid, int(n), vocab)))
    return out


def closed_loop(mix: Dict, seed: int, vocab: int) -> List[Arrival]:
    """The queue the ``clients`` sessions draw their requests from, in
    order: blocks of ``block`` prompts, each block the same stratified
    lengths in its own order, so that whatever a run gets through holds
    nearly the same work for every seed."""
    n, block = int(mix["requests"]), int(mix["block"])
    lens = stratified_lengths(mix["prompt"], block)
    order = np.concatenate([_rng(seed, _LENGTHS, b).permutation(lens)
                            for b in range(-(-n // block))])[:n]
    return [Arrival(i, 0.0, int(L), True, _tokens(seed, i, int(L), vocab))
            for i, L in enumerate(order)]


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics (numpy's
    default), of a non-empty sample."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))
