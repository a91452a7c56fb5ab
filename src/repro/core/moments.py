"""Matricized moment / Gram accumulation — the paper's core primitive.

The paper's normal-equation matrix is the Hankel matrix of power sums
``A[j,k] = S_{j+k} = Σ_i x_i^{j+k}`` and the RHS is ``B[j] = T_j = Σ_i x_i^j y_i``.
With the Vandermonde matrix ``V[i,k] = x_i^k`` these are exactly

    A = Vᵀ V          (Gram)
    B = Vᵀ y

which is the TPU-native (MXU) formulation of the Pallas kernels in
``repro.kernels.moments``. ``power_sums`` is the paper-literal one;
``gram_moments``, the pure-jnp reference path, assembles the Gram from basis
sums without a matmul (see its docstring for why). They agree to fp
tolerance and the tests assert it.

Moments are *additive* across data shards and across time. That property is
what makes the fit (a) embarrassingly data-parallel (one tiny psum) and (b)
streamable with O(1) state (see ``repro.core.streaming``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import basis as basis_lib


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Moments:
    """Sufficient statistics of an LSE fit. Additive: m1 + m2 fits the union.

    ``count`` is the TRUE number of contributing points (nonzero combined
    weight, padding excluded) on every producing path — jnp and kernel alike
    — so states from different paths mix freely.  The weighted mass Σw lives
    in ``weight_sum`` (== gram[..., 0, 0] for weight-1 bases); it is what
    decays under exponential forgetting, while ``count`` keeps counting raw
    points seen.
    """

    gram: jax.Array        # (..., m+1, m+1)  == Vᵀ V
    vty: jax.Array         # (..., m+1)       == Vᵀ y
    yty: jax.Array         # (...,)           == Σ w y²  (residual/R without refit)
    count: jax.Array       # (...,)           == # points with nonzero weight
    weight_sum: jax.Array  # (...,)           == Σ w

    def __add__(self, other: "Moments") -> "Moments":
        return Moments(self.gram + other.gram, self.vty + other.vty,
                       self.yty + other.yty, self.count + other.count,
                       self.weight_sum + other.weight_sum)

    @property
    def degree(self) -> int:
        return self.gram.shape[-1] - 1

    def condition(self) -> jax.Array:
        """Estimated κ₂ of the normal-equation matrix, from the O(m²) state.

        This is the quantity the condition-aware solver stack keys on
        (``core.solve.solve_with_fallback``): it costs O(m³) on the tiny
        sufficient statistics — nothing next to the O(n·m²) accumulation —
        so streaming/serving paths can re-check it every solve.  +inf means
        singular (fewer distinct x than coefficients, zero-weight state).
        The estimate is scale-invariant: a decayed stream whose weighted
        mass has shrunk toward underflow reports the κ of its SHAPE, so
        refilled streams return to the fast solver rungs instead of being
        pinned to the SVD fallback by spurious +inf."""
        from repro.core import solve as solve_lib
        return solve_lib.condition_estimate(self.gram)

    def regularized(self, ridge: float) -> "Moments":
        """Moments with λI added to the Gram (Tikhonov / early-stream
        stabilizer).  Shared by streaming and the fit server's pooled
        solve, which must tolerate all-zero idle slots."""
        eye = jnp.eye(self.degree + 1, dtype=self.gram.dtype)
        return dataclasses.replace(self, gram=self.gram + ridge * eye)

    def truncate(self, degree: int) -> "Moments":
        """The degree-``degree`` sufficient statistics nested inside this
        state: leading (degree+1)×(degree+1) Gram submatrix, leading
        (degree+1) slice of Vᵀy; yty/count/weight_sum are degree-free and
        shared.  Exact for the monomial and Chebyshev bases (column k of V
        depends only on k), which is what makes a single degree-M
        accumulation carry the *whole* ladder d = 0..M — the basis of
        ``repro.select``'s one-pass model selection."""
        if not 0 <= degree <= self.degree:
            raise ValueError(f"cannot truncate degree-{self.degree} moments "
                             f"to degree {degree}")
        m1 = degree + 1
        return dataclasses.replace(self, gram=self.gram[..., :m1, :m1],
                                   vty=self.vty[..., :m1])

    @staticmethod
    def zeros(degree: int, batch: tuple[int, ...] = (), dtype=jnp.float32) -> "Moments":
        m1 = degree + 1
        return Moments(
            gram=jnp.zeros(batch + (m1, m1), dtype),
            vty=jnp.zeros(batch + (m1,), dtype),
            yty=jnp.zeros(batch, dtype),
            count=jnp.zeros(batch, dtype),
            weight_sum=jnp.zeros(batch, dtype),
        )


def decay_ladder(n: int, decay, dtype) -> jax.Array:
    """The exponential-forgetting age ladder for one n-point chunk:
    ``decay ** [n-1, ..., 1, 0]`` — newest point gets γ⁰.  The ONE home of
    that convention: every surface (eager fit, streaming update, serve
    ingest, IRLS base weights, distributed shards) multiplies this in, so
    a γ-weighted fit means the same thing everywhere."""
    return jnp.asarray(decay, dtype) ** jnp.arange(n - 1, -1, -1,
                                                   dtype=dtype)


@partial(jax.jit, static_argnames=("degree",))
def power_sums(x: jax.Array, degree: int, *, weights: jax.Array | None = None) -> jax.Array:
    """Paper-literal power sums S_0..S_{2m} (shape (2*degree+1,)).

    Iterated-multiply power ladder, summed per power — exactly the quantity the
    paper's CUDA threads accumulate."""
    w = jnp.ones_like(x) if weights is None else weights
    sums = []
    p = jnp.ones_like(x)
    for _ in range(2 * degree + 1):
        sums.append(jnp.sum(p * w))
        p = p * x
    return jnp.stack(sums)


def hankel_from_power_sums(s: jax.Array, degree: int) -> jax.Array:
    """Assemble the paper's A matrix from power sums: A[j,k] = S[j+k]."""
    idx = jnp.arange(degree + 1)
    return s[idx[:, None] + idx[None, :]]


@partial(jax.jit, static_argnames=("degree", "basis"))
def moment_vector(x: jax.Array, y: jax.Array, degree: int,
                  basis: str = basis_lib.MONOMIAL) -> jax.Array:
    """Paper-literal B[j] = Σ x^j y, j = 0..m."""
    v = basis_lib.vandermonde(x, degree, basis)
    return jnp.einsum("...nk,...n->...k", v, y)


@partial(jax.jit, static_argnames=("degree", "basis", "accum_dtype"))
def gram_moments(x: jax.Array, y: jax.Array, degree: int, *,
                 basis: str = basis_lib.MONOMIAL,
                 weights: jax.Array | None = None,
                 accum_dtype=None) -> Moments:
    """Moments A = VᵀV, B = Vᵀy over the last axis of x/y.

    Supports arbitrary leading batch axes (batched curve fitting): x, y of
    shape (..., n) produce Moments with batch shape (...,).

    ``accum_dtype`` lets callers accumulate in a wider dtype than the inputs
    (e.g. bf16 data, f32 sums) — the numerical-hardening path beyond the paper.

    Every entry is a sum of elementwise products, never a matmul.  A comes
    from the 2m+1 basis sums S_k = Σ w φ_k(x): the paper's Hankel matrix
    A[j,k] = S_{j+k} for the monomial basis, ½(S_{j+k} + S_{|j-k|}) for
    Chebyshev (T_j T_k = ½(T_{j+k} + T_{|j-k|})).  On TPU an f32 einsum here
    runs on the MXU: at the default precision it rounds each operand to bf16
    (a lone degree-3 fit of 245 points came out 5e-3 above the f64 least-
    squares SSE on TPU v5e), and at HIGHEST it lost digits on long series
    (2e-2 at 2^23 points).  The sums stayed within 6e-9 from 35 to 2^27
    points, and at 2^27 ran in a tenth of the einsum's time.
    """
    if accum_dtype is not None:
        x = x.astype(accum_dtype)
        y = y.astype(accum_dtype)
    phi = basis_lib.basis_columns(x, 2 * degree, basis)
    wphi = phi if weights is None else [p * weights for p in phi]
    s = jnp.stack([jnp.sum(p, axis=-1) for p in wphi], axis=-1)
    j = np.arange(degree + 1)[:, None]
    k = j.T
    gram = s[..., j + k]
    if basis == basis_lib.CHEBYSHEV:
        gram = 0.5 * (gram + s[..., np.abs(j - k)])
    vty = jnp.stack([jnp.sum(p * y, axis=-1) for p in wphi[:degree + 1]],
                    axis=-1)
    yty = jnp.sum((weights * y if weights is not None else y) * y, axis=-1)
    if weights is None:
        count = jnp.full(x.shape[:-1], x.shape[-1], (accum_dtype or x.dtype))
        weight_sum = count
    else:
        # true contributing-point count (kernel-path semantics); Σw separately
        count = jnp.sum((weights != 0).astype(gram.dtype), axis=-1)
        weight_sum = jnp.sum(weights, axis=-1)
    return Moments(gram=gram, vty=vty, yty=yty,
                   count=count.astype(gram.dtype),
                   weight_sum=weight_sum.astype(gram.dtype))


@partial(jax.jit, static_argnames=("degree", "basis", "block", "accum_dtype"))
def gram_moments_blocked(x: jax.Array, y: jax.Array, degree: int, *,
                         basis: str = basis_lib.MONOMIAL,
                         block: int = 1 << 16,
                         accum_dtype=None) -> Moments:
    """Chunked accumulation for datasets too large to materialize V at once.

    Mirrors the Pallas kernel's grid structure (one Gram update per block) in
    pure JAX; used as the large-n host path and as the kernel's shape oracle.
    Tail is zero-padded; padding contributes nothing because both V-rows and y
    are zeroed there (weights mask).
    """
    n = x.shape[-1]
    nblk = -(-n // block)
    pad = nblk * block - n
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    yp = jnp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, pad)])
    mask = jnp.pad(jnp.ones_like(x), [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xp.reshape(x.shape[:-1] + (nblk, block))
    yb = yp.reshape(y.shape[:-1] + (nblk, block))
    mb = mask.reshape(x.shape[:-1] + (nblk, block))

    def body(carry: Moments, inp):
        xi, yi, mi = inp
        m = gram_moments(xi, yi, degree, basis=basis, weights=mi,
                         accum_dtype=accum_dtype)
        return carry + m, None

    # scan over the block axis (moved to front)
    move = lambda a: jnp.moveaxis(a, -2, 0)
    init = Moments.zeros(degree, x.shape[:-1],
                         dtype=(accum_dtype or x.dtype))
    out, _ = jax.lax.scan(body, init, (move(xb), move(yb), move(mb)))
    return out
