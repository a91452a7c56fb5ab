"""The plain reference every cell's output is held to: float64 least
squares on the host, in numpy.  It imports nothing of the program and
takes nothing the program made.

Each series' augmented design [V | y] (V the monomial Vandermonde rows of
x) is reduced chunk by chunk to its Gram G = [V | y]ᵀ[V | y] in float64.
Then any coefficient vector c has SSE(c) = zᵀ G z with z = [c; −1], and
the least-squares coefficients c* solve G[:m+1, :m+1] c* = G[:m+1, m+1].
A served or fitted answer is judged by its excess SSE, SSE(c) / SSE(c*)
− 1, which is 0 for the exact fit and grows as the square of its error.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 1 << 22
THREADS = min(16, os.cpu_count() or 1)


def _chunk_gram(x: np.ndarray, y: np.ndarray, degree: int) -> np.ndarray:
    a = np.empty((degree + 2, x.size))
    a[0] = 1.0
    if degree:
        a[1] = x
    for j in range(2, degree + 1):
        np.multiply(a[j - 1], a[1], out=a[j])
    a[-1] = y
    return a @ a.T


def raw_monomial(coeffs, shift, scale) -> np.ndarray:
    """Monomial coefficients in raw x of Σ c_j t^j with t = scale·(x −
    shift), the domain map a fitted polynomial carries."""
    c = np.asarray(coeffs, np.float64).reshape(-1)
    shift, scale = float(shift), float(scale)
    if shift == 0.0 and scale == 1.0:
        return c
    t = np.polynomial.Polynomial([-scale * shift, scale])
    raw = np.polynomial.Polynomial(c)(t).coef
    return np.pad(raw, (0, c.size - raw.size))


class F64Fit:
    """Float64 least squares of one series of (x, y)."""

    def __init__(self, x: np.ndarray, y: np.ndarray, degree: int):
        x = np.asarray(x).reshape(-1)
        y = np.asarray(y).reshape(-1)
        if x.shape != y.shape or x.size <= degree:
            raise ValueError(f"need equal x, y of more than {degree} "
                             f"points, got {x.shape} and {y.shape}")
        starts = range(0, x.size, CHUNK)

        def one(lo):
            return _chunk_gram(x[lo:lo + CHUNK], y[lo:lo + CHUNK], degree)

        if len(starts) == 1:
            grams = [one(0)]
        else:
            with ThreadPoolExecutor(THREADS) as pool:
                grams = list(pool.map(one, starts))
        self.gram = np.sum(grams, axis=0)
        m1 = degree + 1
        self.coeffs = np.linalg.solve(self.gram[:m1, :m1],
                                      self.gram[:m1, m1])
        self.best_sse = self.sse(self.coeffs)

    def sse(self, coeffs) -> float:
        z = np.append(np.asarray(coeffs, np.float64).reshape(-1), -1.0)
        return float(z @ self.gram @ z)

    def excess_sse(self, coeffs) -> float:
        """SSE(coeffs) / SSE(c*) − 1."""
        return self.sse(coeffs) / self.best_sse - 1.0

    def sse_gap(self, coeffs, reported_sse: float) -> float:
        """|reported SSE / SSE(coeffs) − 1|: how far the SSE the program
        reported for its own coefficients lies from their true SSE."""
        return abs(float(reported_sse) / self.sse(coeffs) - 1.0)
