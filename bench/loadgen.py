"""The one traffic generator: reads a traffic mix's parameters (a
``bench/traffic/<mix>.json`` file) and makes the requests of one run
from ``--seed``.

Every seed gets the same work in another order.  Lengths are the
quantiles of the mix's distribution at evenly spaced probabilities, and
the gaps between arrivals are the quantiles of an exponential
distribution at the mix's rate (a Poisson process); the seed permutes
them and draws the token ids.  So runs with different seeds differ in
order and content, not in how much they ask of the system.

Kinds of mix:

- ``open_loop``: requests arrive on a schedule, whatever the system
  does; ``rate_per_s`` times the window's seconds requests in all.
- ``closed_loop``: a standing backlog of ``backlog`` queued requests,
  topped up from a pool of ``pool`` requests that is cycled.
- ``ring_rounds``: no requests; the parameters of a message exchange
  (lanes per rank, message bytes, how many rounds may be in flight).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator makes it."""
    prompt: np.ndarray            # int32 token ids
    max_new: int
    arrival: Optional[float]      # seconds after the window opens


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths: a lognormal's quantiles at (i + 0.5) / n, clipped to
    [min, max] and rounded to the grid."""
    nd = NormalDist()
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(p)) for p in u])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    grid = dist.get("grid", 1)
    x = np.round(x / grid) * grid
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _requests(mix: Dict[str, Any], n: int, rng: np.random.Generator,
              vocab: int) -> List[Planned]:
    prompts = rng.permutation(quantile_lengths(mix["prompt"], n))
    outputs = rng.permutation(quantile_lengths(mix["output"], n))
    return [Planned(rng.integers(0, vocab, size=int(p)).astype(np.int32),
                    int(o), None) for p, o in zip(prompts, outputs)]


def open_loop(mix: Dict[str, Any], seconds: float, seed: int,
              vocab: int) -> List[Planned]:
    """The requests of one window, in order of arrival; the first
    arrives as the window opens and every one before it closes."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    reqs = _requests(mix, n, rng, vocab)
    gaps = rng.permutation(exponential_gaps(mix["rate_per_s"], n))
    t = np.concatenate([[0.0], np.cumsum(gaps[1:])])
    # quantiles at (i + 0.5)/n sum to less than n / rate, so every
    # arrival but a rounding's worth falls inside the window
    t = np.minimum(t, seconds * (1 - 1e-9))
    for r, a in zip(reqs, t):
        r.arrival = float(a)
    return reqs


def closed_loop(mix: Dict[str, Any], seed: int,
                vocab: int) -> List[Planned]:
    """The pool that a closed loop cycles through."""
    return _requests(mix, int(mix["pool"]), np.random.default_rng(seed),
                     vocab)


def longest(mix: Dict[str, Any]) -> int:
    """The most positions a request of the mix fills."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])


def describe(reqs: List[Planned]) -> str:
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    return (f"{len(reqs)} requests, prompt tokens median "
            f"{int(np.median(p))} [{p.min()}, {p.max()}], output tokens "
            f"median {int(np.median(o))} [{o.min()}, {o.max()}]")
