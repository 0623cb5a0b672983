"""The one traffic generator: a mix file of parameters in, an open-loop
schedule out.

A mix (``bench/traffic/<name>.json``) gives

* ``arrivals``: ``rate_per_s`` and the coefficient of variation ``cv`` of a
  Gamma renewal process (``cv`` 1 is Poisson; above 1 it is bursty);
* ``prompt_len`` and ``max_new_tokens``: a lognormal by its ``median`` and
  ``sigma``, clipped to ``[min, max]`` and rounded up to a ``multiple``
  (the prompt lengths' multiples form the grid that set-up warms up);
* ``sampling``: ``temperature`` and ``top_k`` (0 for greedy decoding).

A run of ``seconds`` offers ``round(rate * seconds)`` requests. Their
lengths and gaps are the distributions' stratified quantiles, the same for
every seed, so every seed offers the same work; the seed only shuffles
which request gets which length and which gap, and draws the prompts'
tokens. The gaps are scaled so that the run's requests are all due inside
its window, the first at its start.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np
from scipy.special import gammaincinv

WARMUP_RID0 = 1 << 30        # warm-up requests' ids, apart from the window's


@dataclass(frozen=True)
class Planned:
    rid: int
    due_s: float             # offset from the window's start
    prompt: np.ndarray       # (S,) int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lognormal lengths, clipped and rounded up."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    return (np.ceil(x / m) * m).astype(np.int64)


def grid(spec: dict) -> list[int]:
    """Every length ``lengths`` can give: the shapes set-up compiles."""
    m = spec.get("multiple", 1)
    lo = math.ceil(spec["min"] / m) * m
    hi = math.ceil(spec["max"] / m) * m
    return list(range(lo, hi + 1, m))


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """``n`` stratified Gamma inter-arrival gaps with mean 1/rate."""
    shape = 1.0 / arrivals["cv"] ** 2
    g = gammaincinv(shape, _quantiles(n)) / shape
    return g / arrivals["rate_per_s"]


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, round(mix["arrivals"]["rate_per_s"] * seconds))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list[Planned]:
    """The window's requests in order of their due times."""
    n = n_requests(mix, seconds)
    rng = np.random.default_rng(seed)
    plen = rng.permutation(lengths(mix["prompt_len"], n))
    new = rng.permutation(lengths(mix["max_new_tokens"], n))
    g = rng.permutation(gaps(mix["arrivals"], n))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]]) * (seconds / g.sum())
    return [
        Planned(
            rid=i, due_s=float(due[i]),
            prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
            max_new=int(new[i]),
        )
        for i in range(n)
    ]


def warmup(mix: dict, slots: int, vocab: int, max_new: int = 2) -> list[Planned]:
    """Requests that compile every prompt length of the grid and pass
    through every slot: as many as the larger of the two, lengths cycling
    over the grid, all due at once."""
    lens = grid(mix["prompt_len"])
    rng = np.random.default_rng(0)
    return [
        Planned(
            rid=WARMUP_RID0 + i, due_s=0.0,
            prompt=rng.integers(0, vocab, lens[i % len(lens)], dtype=np.int32),
            max_new=max_new,
        )
        for i in range(max(len(lens), slots))
    ]


def max_new_cap(mix: dict) -> int:
    """The longest output the mix can ask for: the slots' output width."""
    return grid(mix["max_new_tokens"])[-1]
