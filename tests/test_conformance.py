"""Differential conformance suite: ``polyfit`` vs ``numpy.polyfit``.

Golden-value tests across degrees 1–9, float32/float64, monomial vs
Chebyshev basis, identity vs normalized domain, and every engine path —
with tolerances *scaled by the estimated condition number* the fit itself
reports (``Polynomial.diagnostics.condition``), so the suite is tight
where the numerics allow it and honest where they cannot.

Also holds the two headline acceptance scenarios of the condition-aware
solver stack:

* a degree-9 fit on a wide un-normalized domain whose pure-Gaussian-
  elimination solve exceeds 1e-2 relative coefficient error is
  automatically rescued by the plan (auto-normalization + solver
  escalation) to ≤ 1e-3;
* ``robust_polyfit`` recovers true coefficients within 5% under 20%
  outlier contamination where plain ``polyfit`` misses by > 50%.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.kernels.moments import FLAT_BLOCK_N, SLAB


DEGREES = list(range(1, 10))
# the conformance grid stays on a modest domain so numpy.polyfit (QR on the
# raw Vandermonde, f64) is itself a trustworthy golden reference at degree
# 9; wide-domain behavior is pinned by the rescue test against analytic
# truth below, where numpy is no longer golden either.
LO, HI = -1.5, 1.5


def _data(seed: int, n: int, degree: int, noise: float = 0.02,
          batch: tuple = ()):
    rng = np.random.default_rng(seed)
    x = np.sort(rng.uniform(LO, HI, batch + (n,)), axis=-1)
    coeffs = rng.normal(0.0, 1.0, batch + (degree + 1,))
    y = (np.vectorize(np.polyval, signature="(m),(n)->(n)")
         (coeffs[..., ::-1], x) + noise * rng.normal(0, 1, x.shape))
    return x, y


def _np_polyfit(x, y, degree, weights=None):
    # numpy weights multiply the residuals, ours multiply their squares
    w = None if weights is None else np.sqrt(weights.astype(np.float64))
    return np.polyfit(x.astype(np.float64), y.astype(np.float64), degree,
                      w=w)


def _np_fit_values(x: np.ndarray, y: np.ndarray, degree: int,
                   weights: np.ndarray | None = None) -> np.ndarray:
    """Golden fitted values: numpy.polyfit in float64."""
    c = _np_polyfit(x, y, degree, weights)
    return np.polyval(c, x.astype(np.float64))


def _np_coeffs(x: np.ndarray, y: np.ndarray, degree: int,
               weights: np.ndarray | None = None) -> np.ndarray:
    return _np_polyfit(x, y, degree, weights)[::-1].copy()


def _check_against_numpy(x: np.ndarray, y: np.ndarray, degree: int,
                         dtype, *, basis: str, normalize: bool,
                         engine: str = "reference",
                         weights: np.ndarray | None = None) -> None:
    xj = jnp.asarray(x, dtype)
    yj = jnp.asarray(y, dtype)
    wj = None if weights is None else jnp.asarray(weights, dtype)
    poly = core.polyfit(xj, yj, degree, weights=wj, basis=basis,
                        normalize=normalize, engine=engine)
    assert poly.diagnostics is not None
    cond = float(poly.diagnostics.condition)
    assert np.isfinite(cond) and cond >= 1.0
    eps = float(jnp.finfo(dtype).eps)

    # value space: both fits minimize the same Σe², so fitted values agree
    # to ~eps·√κ(Gram) relative (κ(V) = √κ(VᵀV)) — scaled by the measured
    # condition estimate, floored at a few ulps of the value scale
    gold = _np_fit_values(x, y, degree, weights)
    ours = np.asarray(poly(xj), np.float64)
    scale = float(np.linalg.norm(gold)) + 1e-30
    rel_gap = float(np.linalg.norm(ours - gold)) / scale
    tol_val = max(200.0 * eps * np.sqrt(cond), 50.0 * eps)
    assert rel_gap <= tol_val, (
        f"value gap {rel_gap:.3e} > tol {tol_val:.3e} "
        f"(cond={cond:.2e}, {poly.diagnostics.solver})")

    # coefficient space: only meaningful where the conditioning leaves
    # digits to compare — the honest part of "tolerances scaled by κ"
    pred_rel = 100.0 * eps * cond
    if basis == core.MONOMIAL and pred_rel < 1e-2:
        gold_c = _np_coeffs(x, y, degree, weights)
        ours_c = np.asarray(poly.monomial_coeffs(), np.float64)
        rel_c = (np.linalg.norm(ours_c - gold_c)
                 / (np.linalg.norm(gold_c) + 1e-30))
        assert rel_c <= max(pred_rel, 1e3 * eps), (
            f"coeff gap {rel_c:.3e} (pred {pred_rel:.3e}, cond={cond:.2e})")


@pytest.mark.parametrize("degree", DEGREES)
def test_conformance_float32(degree):
    x, y = _data(degree, 256, degree)
    for basis in (core.MONOMIAL, core.CHEBYSHEV):
        for normalize in (False, True):
            _check_against_numpy(x, y, degree, jnp.float32,
                                 basis=basis, normalize=normalize)


@pytest.mark.parametrize("degree", DEGREES)
def test_conformance_float64(degree):
    x, y = _data(100 + degree, 256, degree)
    with jax.enable_x64(True):
        for basis in (core.MONOMIAL, core.CHEBYSHEV):
            for normalize in (False, True):
                _check_against_numpy(x, y, degree, jnp.float64,
                                     basis=basis, normalize=normalize)


KERNEL_DEGREES = [1, 2, 3, 5, 7, 9]
# Lone-series lengths at the edges of the plain kernel's blocking
# (kernels.moments.moments_flat sums one SLAB at a time over blocks of up
# to FLAT_BLOCK_N points, masking the ragged end in-kernel).  The short
# ones run at every degree: part of one slab, a series that only just
# determines the fit, and one point either side of a slab.
_SHORT = {"n256": lambda d: 256, "d_plus_1": lambda d: d + 1,
          "slab_minus_1": lambda d: SLAB - 1,
          "slab_plus_1": lambda d: SLAB + 1}
# The long ones, one point either side of a block and a length no slab
# divides over three blocks, stream up to half a million points through
# interpret-mode Pallas, so they run at the large-series cell's degree
# (3) only.
_LONG = {"block_minus_1": FLAT_BLOCK_N - 1, "block_plus_1": FLAT_BLOCK_N + 1,
         "three_blocks_ragged": 2 * FLAT_BLOCK_N + 3 * SLAB + 5}
KERNEL_PLAIN_CASES = (
    [pytest.param(d, n(d), id=f"{d}-{name}")
     for name, n in _SHORT.items() for d in KERNEL_DEGREES]
    + [pytest.param(3, n, id=f"3-{name}") for name, n in _LONG.items()])


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("degree, n", KERNEL_PLAIN_CASES)
def test_conformance_kernel_engines(degree, n, weighted):
    """The lone-series Pallas path (plain layout, interpret mode off-TPU)
    conforms to the same numpy gold as the reference path (monomial/f32 —
    the kernels' domain) at every length its blocking can leave ragged;
    a weighted fit streams the weights as a third array."""
    x, y = _data(200 + degree, n, degree)
    w = (np.random.default_rng(n).uniform(0.25, 2.0, n) if weighted
         else None)
    _check_against_numpy(x, y, degree, jnp.float32, basis=core.MONOMIAL,
                         normalize=True, engine="kernel_plain", weights=w)


@pytest.mark.parametrize("degree", KERNEL_DEGREES)
def test_conformance_packed_kernel_engine(degree):
    """The packed Pallas path (a batch of series per tile) conforms to the
    same numpy gold, series by series."""
    xb, yb = _data(300 + degree, 256, degree, batch=(3,))
    poly = core.polyfit(jnp.asarray(xb, jnp.float32),
                        jnp.asarray(yb, jnp.float32), degree,
                        normalize=True, engine="kernel_packed")
    eps = float(jnp.finfo(jnp.float32).eps)
    for i in range(xb.shape[0]):
        gold = _np_fit_values(xb[i], yb[i], degree)
        ours = np.asarray(poly(jnp.asarray(xb, jnp.float32))[i], np.float64)
        cond = float(poly.diagnostics.condition[i])
        tol = max(200.0 * eps * np.sqrt(cond), 50.0 * eps)
        gap = np.linalg.norm(ours - gold) / (np.linalg.norm(gold) + 1e-30)
        assert gap <= tol, f"series {i}: {gap:.3e} > {tol:.3e}"


# --------------------------------------------------- acceptance scenarios
def test_degree9_wide_domain_is_rescued():
    """ISSUE-3 acceptance: degree-9 on a wide un-normalized domain — pure
    GE normal equations exceed 1e-2 relative coefficient error; the
    condition-aware default routes around it and lands ≤ 1e-3."""
    with jax.enable_x64(True):
        worst_ge, worst_auto = 0.0, 0.0
        for seed in (1, 7, 42):
            rng = np.random.default_rng(seed)
            true = rng.normal(0, 1, 10)
            x = jnp.asarray(np.linspace(0.0, 8.0, 400))
            y = jnp.asarray(np.polyval(true[::-1], np.linspace(0.0, 8.0,
                                                               400)))

            def rel(c):
                c = np.asarray(c, np.float64)
                return float(np.linalg.norm(c - true) / np.linalg.norm(true))

            # the paper's literal path: plain elimination, guard off
            ge = core.polyfit(x, y, 9, solver="gauss", fallback=None)
            # condition-aware default: auto-normalization + solver ladder
            auto = core.polyfit(x, y, 9)
            worst_ge = max(worst_ge, rel(ge.monomial_coeffs()))
            worst_auto = max(worst_auto, rel(auto.monomial_coeffs()))
            # the plan must actually have escalated, not gotten lucky
            assert auto.diagnostics.solver != "gauss"
            assert float(auto.domain_scale) != 1.0   # auto-normalized
        assert worst_ge > 1e-2, f"GE unexpectedly fine: {worst_ge:.2e}"
        assert worst_auto <= 1e-3, f"rescue too weak: {worst_auto:.2e}"


def test_robust_polyfit_survives_contamination():
    """ISSUE-3 acceptance: 20% gross outliers — plain polyfit misses the
    true coefficients by > 50%, robust_polyfit lands within 5%."""
    rng = np.random.default_rng(3)
    true = np.array([1.0, -2.0, 0.5, 0.8])
    n = 400
    x = rng.uniform(-2.0, 2.0, n)
    y = np.polyval(true[::-1], x) + rng.normal(0, 0.05, n)
    out = rng.choice(n, n // 5, replace=False)
    y[out] += rng.choice([-1.0, 1.0], out.size) * rng.uniform(30.0, 80.0,
                                                              out.size)
    xj = jnp.asarray(x, jnp.float32)
    yj = jnp.asarray(y, jnp.float32)

    def rel(c):
        c = np.asarray(c, np.float64)
        return float(np.linalg.norm(c - true) / np.linalg.norm(true))

    plain = core.polyfit(xj, yj, 3)
    rfit = core.robust_polyfit(xj, yj, 3, loss=core.TUKEY)
    assert rel(core.fit_report(plain, xj, yj).coeffs) > 0.5
    assert bool(rfit.converged)
    assert rel(rfit.poly.monomial_coeffs()) < 0.05


def test_lspia_matches_lse_fit():
    """LSPIA (never forms the Gram) converges to the same polynomial the
    explicit normal-equation solve produces."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.uniform(-3.0, 3.0, 512), jnp.float32)
    y = jnp.asarray(np.sin(np.asarray(x)) + 0.02 * rng.normal(0, 1, 512),
                    jnp.float32)
    lf = core.lspia_fit(x, y, 5, basis=core.CHEBYSHEV, tol=1e-6)
    assert bool(lf.converged)
    assert int(lf.iterations) < 5000
    ref = core.polyfit(x, y, 5, basis=core.CHEBYSHEV, normalize=True)
    xs = jnp.linspace(-3.0, 3.0, 101)
    gap = float(jnp.max(jnp.abs(lf.poly(xs) - ref(xs))))
    assert gap < 1e-3, f"LSPIA vs LSE value gap {gap:.2e}"
    # and via the polyfit front door
    front = core.polyfit(x, y, 5, solver="lspia", basis=core.CHEBYSHEV)
    assert float(jnp.max(jnp.abs(front(xs) - ref(xs)))) < 1e-3
