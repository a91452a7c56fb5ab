"""The one generator every traffic mix goes through.

A mix is a data file beside this one, ``<traffic>.json``:

* ``{"loop": "closed", "callers": 1}``: one caller that sends its next
  request when the previous one has come back.
* ``{"loop": "open", "rate_per_s": r, "lengths": {"dist": "log_uniform",
  "min": a, "max": b}, "x_range": [lo, hi], "noise": s}``: independent
  senders at r requests a second, with Poisson arrivals.

For an open loop, every seed gets the same work: the same multiset of
series lengths (the quantiles of the length distribution) and the same
multiset of gaps between arrivals (the quantiles of the exponential
distribution), which the seed shuffles, each on its own.  The seed also
draws the payloads.  So two seeds differ in order and in data, never in
how much work arrives.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, directory: str = HERE) -> dict:
    path = os.path.join(directory, f"{name}.json")
    with open(path) as f:
        params = json.load(f)
    if params.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: 'loop' must be 'open' or 'closed'")
    return params


@dataclasses.dataclass
class Schedule:
    due_s: np.ndarray       # (k,) seconds after the window opens, ascending
    lengths: np.ndarray     # (k,) points per request, in arrival order


def _quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def length_set(spec: dict, k: int) -> np.ndarray:
    """The k series lengths every seed shares, before shuffling."""
    if spec["dist"] != "log_uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = np.log(spec["min"]), np.log(spec["max"])
    return np.floor(np.exp(lo + _quantiles(k) * (hi - lo))).astype(np.int64)


def schedule(params: dict, seed: int, seconds: float) -> Schedule:
    """The arrivals of one window of ``seconds``: round(rate × seconds)
    requests, all due inside the window."""
    if params["loop"] != "open":
        raise ValueError("only an open loop has a schedule")
    rate = float(params["rate_per_s"])
    k = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 1])
    gaps = rng.permutation(-np.log1p(-_quantiles(k)) / rate)
    due = np.cumsum(gaps)
    due *= seconds * (1.0 - 0.5 / k) / due[-1]
    lengths = rng.permutation(length_set(params["lengths"], k))
    return Schedule(due_s=due, lengths=lengths)


def payloads(params: dict, seed: int, lengths: np.ndarray,
             degree: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """One (x, y) series per request: x uniform on ``x_range``, y a
    polynomial of ``degree`` with N(0, 1) coefficients drawn from the seed,
    plus N(0, noise²) noise.  Made in bulk, then split."""
    rng = np.random.default_rng([seed, 2])
    coef = rng.normal(0.0, 1.0, degree + 1)
    total = int(np.sum(lengths))
    lo, hi = params["x_range"]
    x = rng.uniform(lo, hi, total).astype(np.float32)
    y = np.zeros(total, np.float64)
    for c in coef[::-1]:
        y = y * x + c
    y = (y + rng.normal(0.0, params["noise"], total)).astype(np.float32)
    cuts = np.cumsum(lengths)[:-1]
    return list(zip(np.split(x, cuts), np.split(y, cuts)))
