"""Faithful reproduction of the paper's accuracy analysis (Tables I-V).

The paper fits orders 1-3 on the Table I dataset with the matricized
normal-equation method (Gaussian elimination) and compares against MATLAB
polyfit (QR on the Vandermonde). We assert our generated coefficients match
the paper's published values and that Σe² for the order-3 fit reproduces the
paper's 128.1999 (paper's polyfit column: 129.6512 — their polyfit ran at a
lower effective precision; in f64 both methods coincide, which we also
assert, and in f32 they diverge in the 3rd-4th decimal as the paper shows).

x64 is enabled per-test via the jax.enable_x64 context so the rest of the
suite keeps default f32 semantics.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core

X64 = [39.206, 29.74, 21.31, 12.087, 1.812, 0.001]
Y64 = [751.912, 567.121, 403.746, 221.738, 18.8418, 1.88672]

# Paper Tables II-IV
PAPER_POLYFIT = {
    1: [-8.356, 19.3496],
    2: [-6.5109, 18.8735, 0.0127],
    3: [-4.7551, 17.5109, 0.1086, -0.0016],
}
PAPER_SSE_F = 128.199937   # paper's Σe_f²
PAPER_FITTED_ORDER3 = [751.18396, 569.500305, 402.053284, 219.903793,
                       27.321678, -4.736779]


def _data():
    return (jnp.asarray(X64, jnp.float64), jnp.asarray(Y64, jnp.float64))


@pytest.mark.parametrize("order", [1, 2, 3])
def test_generated_coefficients_match_paper(order):
    with jax.enable_x64(True):
        x, y = _data()
        poly = core.polyfit(x, y, order)          # paper-faithful path
        got = np.asarray(poly.coeffs)
    np.testing.assert_allclose(got, PAPER_POLYFIT[order], atol=2.5e-4)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_gauss_equals_qr_in_f64(order):
    """In f64 the normal-equation and QR solutions coincide — the paper's
    accuracy gap is a precision artifact, which is itself informative."""
    with jax.enable_x64(True):
        x, y = _data()
        a = np.asarray(core.polyfit(x, y, order).coeffs)
        b = np.asarray(
            core.polyfit(x, y, order, solver="qr_vandermonde").coeffs)
    np.testing.assert_allclose(a, b, rtol=1e-8, atol=1e-10)


def test_order3_sse_matches_paper():
    with jax.enable_x64(True):
        x, y = _data()
        poly = core.polyfit(x, y, 3)
        rep = core.fit_report(poly, x, y)
        assert abs(float(rep.sse) - PAPER_SSE_F) < 5e-3


def test_order3_fitted_values_match_table_v():
    """Paper's Table V f(x) column was computed with their lower-precision
    coefficients; agreement holds to ~1e-2 absolute (4-5 significant
    digits), consistent with their printed rounding."""
    with jax.enable_x64(True):
        x, y = _data()
        fitted = np.asarray(core.polyfit(x, y, 3)(x))
    np.testing.assert_allclose(fitted, PAPER_FITTED_ORDER3, atol=2e-2)


def test_correlation_coefficient_high():
    with jax.enable_x64(True):
        x, y = _data()
        for order in (1, 2, 3):
            rep = core.fit_report(core.polyfit(x, y, order), x, y)
            assert float(rep.r) > 0.999   # paper: 0.9996-0.9998


def test_f32_reproduces_papers_precision_gap():
    """In f32, normal equations vs QR differ in the low decimals (the paper's
    Tables III/IV show exactly this scale of divergence)."""
    x32 = jnp.asarray(X64, jnp.float32)
    y32 = jnp.asarray(Y64, jnp.float32)
    a = np.asarray(core.polyfit(x32, y32, 3).coeffs, np.float64)
    b = np.asarray(
        core.polyfit(x32, y32, 3, solver="qr_vandermonde").coeffs,
        np.float64)
    gap = np.max(np.abs(a - b))
    assert 0 < gap < 0.5  # differ, but bounded


def test_power_sum_hankel_identity():
    """A == VᵀV and B == Vᵀy: the matricization is exact."""
    with jax.enable_x64(True):
        x, y = _data()
        m = core.gram_moments(x, y, 3)
        s = core.power_sums(x, 3)
        np.testing.assert_allclose(
            np.asarray(m.gram),
            np.asarray(core.hankel_from_power_sums(s, 3)), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(m.vty), np.asarray(core.moment_vector(x, y, 3)),
            rtol=1e-12)


@pytest.mark.parametrize("basis", ["monomial", "chebyshev"])
@pytest.mark.parametrize("weighted", [False, True])
def test_gram_from_basis_sums_is_vtwv(basis, weighted):
    """The basis-sum Gram equals the explicit VᵀWV, Vᵀ(w·y) and Σw·y² over
    the last axis of a batch."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        x = rng.uniform(-1.0, 1.0, (3, 200))
        y = rng.normal(size=(3, 200))
        w = rng.uniform(0.0, 2.0, (3, 200)) if weighted else np.ones((3, 200))
        m = core.gram_moments(jnp.asarray(x), jnp.asarray(y), 5, basis=basis,
                              weights=jnp.asarray(w) if weighted else None)
        v = np.asarray(core.vandermonde(jnp.asarray(x), 5, basis))
        np.testing.assert_allclose(
            np.asarray(m.gram), np.einsum("bnj,bn,bnk->bjk", v, w, v),
            rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(
            np.asarray(m.vty), np.einsum("bnj,bn->bj", v, w * y),
            rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(m.yty),
                                   np.sum(w * y * y, axis=-1), rtol=1e-12)


def test_sse_from_moments_identity():
    """Σe² computed from sufficient statistics alone (no data pass)."""
    with jax.enable_x64(True):
        x, y = _data()
        poly = core.polyfit(x, y, 3)
        m = core.gram_moments(x, y, 3)
        direct = float(core.fit_report(poly, x, y).sse)
        from_moments = float(core.sse_from_moments(m, poly.coeffs))
        assert abs(direct - from_moments) < 1e-6


def test_normalized_fit_recovers_raw_coefficients():
    """Beyond-paper hardened path (x→[-1,1]) converts back to the same raw
    monomial coefficients."""
    with jax.enable_x64(True):
        x, y = _data()
        raw = np.asarray(core.polyfit(x, y, 3).coeffs)
        norm = np.asarray(core.polyfit(x, y, 3, normalize=True)
                          .monomial_coeffs())
    np.testing.assert_allclose(raw, norm, rtol=1e-7, atol=1e-8)
