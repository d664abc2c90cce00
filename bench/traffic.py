"""The one traffic generator: a mix's parameters and a seed -> requests.

Sizes and gaps are drawn by stratified quantiles: a run of ``n`` requests
takes the ``(i + 1/2) / n`` quantiles of each distribution, and the seed
only shuffles them and picks the prompt tokens. So every seed offers the
same work in another order, and two seeds differ by where the long
requests fall, not by how many there are.

Traffic file keys (``bench/traffic/<mix>.json``):

* ``kind`` — the driver that serves it (``bench/drivers/<kind>.py``);
* ``arrivals`` — ``{"process": "poisson", "rate_per_s": r}`` for an open
  loop over the window, or ``{"process": "backlog", "requests": n}`` for n
  requests queued at once;
* ``prompt`` / ``output`` — ``{"median", "sigma", "min", "max"}`` of a
  log-normal, with an optional ``grid`` the prompt lengths round up to;
* ``rows``, ``cache_len``, ``page_len`` — the serving geometry;
* ``check_requests`` — how many finished requests the reference checks.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request: arrival offset from the window's open, prompt, budget."""

    at_s: float
    prompt: np.ndarray
    max_new: int


def _lognormal_quantiles(d: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = d["median"] * np.exp(d["sigma"] * z)
    grid = d.get("grid", 1)
    v = np.ceil(v / grid) * grid
    return np.clip(v, d["min"], d["max"]).astype(np.int64)


def lengths(d: dict) -> list[int]:
    """Every length the distribution can give (what set-up must warm)."""
    grid = d.get("grid", 1)
    lo = int(math.ceil(d["min"] / grid) * grid)
    return list(range(lo, d["max"] + 1, grid))


def n_requests(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        return max(1, int(round(arr["rate_per_s"] * seconds)))
    if arr["process"] == "backlog":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab_size: int,
             n: int | None = None) -> list[Item]:
    """The requests of one run, in arrival order."""
    n = n or n_requests(mix, seconds)
    rng = np.random.default_rng(int(seed))
    p_len = rng.permutation(_lognormal_quantiles(mix["prompt"], n))
    o_len = rng.permutation(_lognormal_quantiles(mix["output"], n))
    arr = mix["arrivals"]
    if arr["process"] == "poisson":
        rate = arr["rate_per_s"]
        # exponential quantiles sum to a little under n / rate = seconds,
        # so the first request comes at the open and the last before close
        gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
        at = np.cumsum(gaps) - gaps[0]
    else:
        at = np.zeros(n)
    return [Item(at_s=float(at[i]),
                 prompt=rng.integers(0, vocab_size, int(p_len[i]),
                                     dtype=np.int32),
                 max_new=int(o_len[i])) for i in range(n)]
