"""Polynomial bases and domain normalization for matricized LSE fitting.

The paper (Dasgupta 2015) works in the raw monomial basis ``1, x, x^2, ...``.
That is the *paper-faithful* path. Beyond the paper we add an affine domain
normalization (maps the sample range to [-1, 1]) and a Chebyshev basis option;
both dramatically improve the conditioning of the normal-equation Gram matrix
``A = V^T V`` for higher orders / wider domains while leaving the fitted
function mathematically unchanged.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

MONOMIAL = "monomial"
CHEBYSHEV = "chebyshev"
_BASES = (MONOMIAL, CHEBYSHEV)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Domain:
    """Affine map t = scale * (x - shift) applied before basis evaluation.

    ``identity()`` is the paper-faithful no-op domain.
    """

    shift: jax.Array  # scalar
    scale: jax.Array  # scalar

    @staticmethod
    def identity(dtype=jnp.float32) -> "Domain":
        return Domain(jnp.zeros((), dtype), jnp.ones((), dtype))

    @staticmethod
    def from_data(x: jax.Array) -> "Domain":
        """Map [min(x), max(x)] -> [-1, 1] (degenerate range -> identity scale)."""
        lo = jnp.min(x)
        hi = jnp.max(x)
        shift = (hi + lo) / 2.0
        half = (hi - lo) / 2.0
        scale = jnp.where(half > 0, 1.0 / jnp.where(half > 0, half, 1.0), 1.0)
        return Domain(shift.astype(x.dtype), scale.astype(x.dtype))

    def apply(self, x: jax.Array) -> jax.Array:
        return (x - self.shift) * self.scale


def vandermonde(x: jax.Array, degree: int, basis: str = MONOMIAL) -> jax.Array:
    """Design matrix V with shape ``x.shape + (degree + 1,)``.

    monomial:  V[..., k] = x^k           (paper's construction)
    chebyshev: V[..., k] = T_k(x)        (recurrence T_k = 2x T_{k-1} - T_{k-2})

    Powers are built by iterated multiplication, never ``pow`` — this is the
    same trick the paper's CUDA kernel uses and what the Pallas kernel mirrors.
    """
    return jnp.stack(basis_columns(x, degree, basis), axis=-1)


def basis_columns(x: jax.Array, degree: int,
                  basis: str = MONOMIAL) -> list[jax.Array]:
    """The columns of ``vandermonde``, each shaped like x."""
    if basis not in _BASES:
        raise ValueError(f"unknown basis {basis!r}; expected one of {_BASES}")
    if degree < 0:
        raise ValueError("degree must be >= 0")
    cols = [jnp.ones_like(x)]
    if degree >= 1:
        cols.append(x)
    if basis == MONOMIAL:
        for _ in range(2, degree + 1):
            cols.append(cols[-1] * x)
    else:
        for _ in range(2, degree + 1):
            cols.append(2.0 * x * cols[-1] - cols[-2])
    return cols


@partial(jax.jit, static_argnames=("degree", "basis"))
def evaluate(coeffs: jax.Array, x: jax.Array, *, degree: int | None = None,
             basis: str = MONOMIAL, domain: Domain | None = None) -> jax.Array:
    """Evaluate a fitted polynomial at x. coeffs[..., k] multiplies basis k.

    Horner's rule for monomials, Clenshaw's for Chebyshev — both O(m) with no
    explicit Vandermonde materialization (decode-path friendly).
    """
    deg = (coeffs.shape[-1] - 1) if degree is None else degree
    if domain is not None:
        x = domain.apply(x)
    # batched coeffs (..., m+1) broadcast against x (..., n) on a new axis
    c = ((lambda k: coeffs[..., k, None]) if coeffs.ndim > 1
         else (lambda k: coeffs[..., k]))
    if basis == MONOMIAL:
        acc = jnp.zeros_like(x) + c(deg)
        for k in range(deg - 1, -1, -1):
            acc = acc * x + c(k)
        return acc
    # Clenshaw for Chebyshev
    b1 = jnp.zeros_like(x)
    b2 = jnp.zeros_like(x)
    for k in range(deg, 0, -1):
        b1, b2 = 2.0 * x * b1 - b2 + c(k), b1
    return x * b1 - b2 + c(0)


def monomial_coeffs_from_domain(coeffs: jax.Array, domain: Domain,
                                degree: int) -> jax.Array:
    """Convert coefficients fitted on t = scale*(x-shift) (monomial basis) back
    to raw-x monomial coefficients, so normalized fits report paper-comparable
    coefficients. Pure host-side (small m), uses binomial expansion."""
    import numpy as np

    c = np.asarray(coeffs, dtype=np.float64)
    s = float(domain.scale)
    h = float(domain.shift)
    out = np.zeros(degree + 1, dtype=np.float64)
    # t^k = s^k (x - h)^k = s^k Σ_j C(k,j) x^j (-h)^{k-j}
    from math import comb

    for k in range(degree + 1):
        for j in range(k + 1):
            out[j] += c[k] * (s ** k) * comb(k, j) * ((-h) ** (k - j))
    return jnp.asarray(out, dtype=coeffs.dtype)
