"""The work a least-squares fit needs, counted from the problem's shape.

The count depends on the points, the degree and the batch alone, never on
the path the plan picks (Pallas plain, Pallas packed or the XLA
reference): a roofline share is the least time of the work over the time
the device took, so a path that moves more bytes than the problem needs
scores lower, as it should.

* Bytes: every point is read once, one f32 x and one f32 y.  Weights,
  padding and any copies an implementation makes are not work.
* FLOP: the extended Gram [V | y]ᵀ[V | y] of the matricized fit, one
  multiply and one add for each of its (m + 2)² entries at every point.
  The solve, O(m³) per series, is left out.
"""
from __future__ import annotations

import dataclasses

from bench.peaks import Peaks

BYTES_PER_POINT = 8   # f32 x and f32 y


@dataclasses.dataclass(frozen=True)
class Work:
    bytes: float
    flops: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.bytes + other.bytes, self.flops + other.flops)

    def __mul__(self, k: float) -> "Work":
        return Work(self.bytes * k, self.flops * k)

    def least_time_s(self, peaks: Peaks) -> float:
        """The least time the chip could take: the larger of the bytes
        over its bandwidth and the FLOP over its peak."""
        return max(self.bytes / peaks.hbm_bytes_per_s,
                   self.flops / peaks.flops_per_s)

    def bound(self, peaks: Peaks) -> str:
        """Which of the two bounds sets ``least_time_s``."""
        return ("memory" if self.bytes / peaks.hbm_bytes_per_s
                >= self.flops / peaks.flops_per_s else "compute")


def fit_work(points: int, degree: int, batch: int = 1) -> Work:
    """The work of fitting ``batch`` series of ``points`` points each at
    ``degree``."""
    total = float(points) * float(batch)
    return Work(bytes=BYTES_PER_POINT * total,
                flops=2.0 * (degree + 2) ** 2 * total)


def plan_work(plan) -> Work:
    """The work of the problem a ``FitPlan`` describes (its batch, its
    series length and its degree); the plan's path does not enter."""
    batch = 1
    for b in plan.batch:
        batch *= b
    return fit_work(plan.n, plan.degree, batch)
