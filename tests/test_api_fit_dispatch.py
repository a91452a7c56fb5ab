"""api.fit's dispatch of a fixed-degree LSE fit.

The jitted program returns only what the data decides; the host puts back
what the spec fixes (a pinned or identity domain as device constants
shared by every call, the report's coefficients as the polynomial's own
array) and resolves the profiler span's path once per (spec, shape,
dtype, weighted).  These tests hold the answers to a fresh jit of the same
pipeline steps returning every leaf, bit for bit, on the first call and on
later ones; and the dispatch counter to what the cache did.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, core
from repro import engine as engine_lib
from repro.api import executors
from repro.core import basis as basis_lib
from repro.core import fit as fit_lib
from repro.core import solve as solve_lib

rng = np.random.default_rng(18)
N = 512
X = jnp.asarray(rng.uniform(-2.0, 2.0, N), jnp.float32)
Y = jnp.asarray(1.0 - 0.5 * X + 0.3 * X ** 3
                + 0.05 * rng.standard_normal(N), jnp.float32)
W = jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32)
XB = jnp.asarray(rng.uniform(-1.0, 3.0, (4, 256)), jnp.float32)
YB = jnp.asarray(0.5 + XB - 0.2 * XB ** 2
                 + 0.05 * rng.standard_normal((4, 256)), jnp.float32)

AUTO = api.NumericsPolicy(solver="auto")
CASES = {
    "identity": (api.FitSpec(degree=3), X, Y, None),
    "pinned": (api.FitSpec(degree=3, domain=(0.25, 0.5)), X, Y, None),
    "normalize": (api.FitSpec(degree=3, numerics=dataclasses.replace(
        AUTO, normalize=True)), X, Y, None),
    "weighted": (api.FitSpec(degree=3), X, Y, W),
    "ridge": (api.FitSpec(degree=3, ridge=1e-3), X, Y, None),
    "decay": (api.FitSpec(degree=3, decay=0.99), X, Y, None),
    "chebyshev": (api.FitSpec(degree=4, basis=core.CHEBYSHEV), X, Y, None),
    "qr_vandermonde": (api.FitSpec(degree=3, numerics=api.NumericsPolicy(
        solver="qr_vandermonde", fallback=None)), X, Y, None),
    "batched": (api.FitSpec(degree=3), XB, YB, None),
}


@pytest.fixture(autouse=True)
def fresh_dispatch():
    """Each test starts with no cached path or constant and a zero count."""
    executors._LSE_PATHS.clear()
    executors._DOMAIN_CONSTANTS.clear()
    executors.reset_dispatch_counter()
    yield
    executors.reset_dispatch_counter()


@partial(jax.jit, static_argnames=("spec",))
def _pipeline(x, y, weights, spec):
    """The fixed-degree LSE pipeline's steps, every leaf an output."""
    if spec.numerics.solver in api.RAW_DATA_SOLVERS:
        dom = executors._spec_domain(spec, x, spec.numerics.normalize)
        v = basis_lib.vandermonde(dom.apply(x), spec.degree, spec.basis)
        w = weights
        if spec.decay < 1.0:
            lad = executors._decay_ladder(x, spec.decay)
            w = lad if w is None else w * lad
        if w is not None:
            v, y = v * jnp.sqrt(w)[..., :, None], y * jnp.sqrt(w)
        return fit_lib.Polynomial(
            coeffs=solve_lib.qr_solve_vandermonde(v, y),
            domain_shift=dom.shift, domain_scale=dom.scale,
            basis=spec.basis), None
    plan = spec.plan(x.shape, x.dtype, weighted=weights is not None)
    pol = plan.numerics
    dom = executors._spec_domain(spec, x, pol.normalize)
    w = weights
    if spec.decay < 1.0:
        lad = executors._decay_ladder(x, spec.decay)
        w = lad if w is None else w * lad
    m = engine_lib.compute_moments(plan, dom.apply(x), y, w)
    poly = fit_lib.fit_from_moments(
        m.regularized(spec.ridge) if spec.ridge else m, solver=pol.solver,
        fallback=pol.fallback, cond_cap=pol.cond_cap, domain=dom,
        basis=spec.basis, normalized=pol.normalize or spec.domain is not None)
    return poly, fit_lib.report_from_moments(m, poly.coeffs)


def _bits(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [np.asarray(a).tobytes() for a in leaves]


def _fit(name):
    spec, x, y, w = CASES[name]
    return api.fit(x, y, spec, weights=w)


# ------------------------------------------------ answers and structure
@pytest.mark.parametrize("name", list(CASES))
def test_cached_calls_match_the_pipeline_bit_for_bit(name):
    spec, x, y, w = CASES[name]
    first, second, third = _fit(name), _fit(name), _fit(name)
    poly, rep = _pipeline(x, y, w, spec)
    want = _bits((poly, rep))
    for got in (first, second, third):
        assert _bits((got.poly, got.report)) == want
    assert executors.dispatch_counter()["cached"] == 2
    if rep is not None:
        assert first.report.coeffs is first.poly.coeffs


def test_identity_domain_is_shared_constants_and_six_outputs():
    spec, x, y, _ = CASES["identity"]
    results = [api.fit(x, y, spec) for _ in range(3)]
    for r in results:
        assert r.report.coeffs is r.poly.coeffs
        assert r.poly.domain_shift is results[0].poly.domain_shift
        assert r.poly.domain_scale is results[0].poly.domain_scale
    assert float(results[0].poly.domain_shift) == 0.0
    assert float(results[0].poly.domain_scale) == 1.0
    out = executors._fit_lse_fixed.lower(x, y, None, spec).out_info
    assert len(jax.tree_util.tree_leaves(out)) == 6
    assert executors.dispatch_counter() == {
        "calls": 3, "cached": 2, "static_domain": 3}


def test_pinned_domain_constants_are_shared_across_specs():
    a = api.fit(X, Y, api.FitSpec(degree=3, domain=(0.25, 0.5)))
    b = api.fit(X, Y, api.FitSpec(degree=2, domain=(0.25, 0.5)))
    assert a.poly.domain_shift is b.poly.domain_shift
    assert (float(a.poly.domain_shift), float(a.poly.domain_scale)) \
        == (0.25, 0.5)


def test_deleted_constants_are_made_again():
    spec = api.FitSpec(degree=3)
    first = api.fit(X, Y, spec)
    first.poly.domain_shift.delete()
    again = api.fit(X, Y, spec)
    assert again.poly.domain_shift is not first.poly.domain_shift
    assert float(again.poly.domain_shift) == 0.0


def test_normalized_domain_follows_the_data():
    spec = CASES["normalize"][0]
    a = api.fit(X, Y, spec)
    b = api.fit(X * 3.0 + 1.0, Y, spec)
    for r, x in ((a, X), (b, X * 3.0 + 1.0)):
        want = basis_lib.Domain.from_data(x)
        assert float(r.poly.domain_shift) == float(want.shift)
        assert float(r.poly.domain_scale) == float(want.scale)
    assert float(a.poly.domain_shift) != float(b.poly.domain_shift)
    assert executors.dispatch_counter()["static_domain"] == 0
    out = executors._fit_lse_fixed.lower(X, Y, None, spec).out_info
    assert len(jax.tree_util.tree_leaves(out)) == 8


# ---------------------------------------------- counter and traced inputs
def test_equal_calls_count_one_miss():
    spec = api.FitSpec(degree=3)
    for _ in range(5):
        api.fit(X, Y, api.FitSpec(degree=3))
    assert executors.dispatch_counter() == {
        "calls": 5, "cached": 4, "static_domain": 5}
    api.fit(X, Y, spec)
    assert executors.dispatch_counter()["cached"] == 5


@pytest.mark.parametrize("change", ["shape", "dtype", "spec", "weighted"])
def test_a_new_key_counts_a_miss(change):
    # f32 sums, so that the bfloat16 series is solved where LAPACK can
    spec = api.FitSpec(degree=3, numerics=dataclasses.replace(
        AUTO, accum_dtype=jnp.float32))
    api.fit(X, Y, spec)
    x, y, w = X, Y, None
    if change == "shape":
        x, y = X[:256], Y[:256]
    elif change == "dtype":
        x, y = X.astype(jnp.bfloat16), Y.astype(jnp.bfloat16)
    elif change == "spec":
        spec = dataclasses.replace(spec, degree=2)
    else:
        w = W
    api.fit(x, y, spec, weights=w)
    api.fit(x, y, spec, weights=w)
    assert executors.dispatch_counter()["calls"] == 3
    assert executors.dispatch_counter()["cached"] == 1


def _ref_poly(x, y):
    return _pipeline(x, y, None, api.FitSpec(degree=3))[0]


@pytest.mark.parametrize("transform", ["jit", "vmap", "grad"])
def test_traced_inputs_keep_the_answer_and_count_nothing(transform):
    if transform == "jit":
        got = jax.jit(lambda x, y: core.polyfit(x, y, 3))(X, Y)
        want = jax.jit(_ref_poly)(X, Y)
    elif transform == "vmap":
        got = jax.vmap(lambda x, y: core.polyfit(x, y, 3))(XB, YB)
        want = jax.vmap(_ref_poly)(XB, YB)
        assert got.domain_shift.shape == (XB.shape[0],)
    else:
        got = jax.grad(lambda y: core.polyfit(X, y, 3).coeffs.sum())(Y)
        want = jax.grad(lambda y: _ref_poly(X, y).coeffs.sum())(Y)
    assert _bits(got) == _bits(want)
    assert executors.dispatch_counter() == {
        "calls": 0, "cached": 0, "static_domain": 0}


def test_mesh_sharded_inputs_keep_the_answer_and_count_nothing():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    sharding = jax.sharding.NamedSharding(mesh,
                                          jax.sharding.PartitionSpec("data"))
    x, y = jax.device_put(X, sharding), jax.device_put(Y, sharding)
    got = api.fit(x, y, api.FitSpec(degree=3))
    assert _bits((got.poly, got.report)) == _bits(
        _pipeline(X, Y, None, api.FitSpec(degree=3)))
    assert executors.dispatch_counter()["calls"] == 0


def test_the_polynomial_evaluates_converts_and_flattens():
    poly = api.fit(X, Y, api.FitSpec(degree=3)).poly
    want = _ref_poly(X, Y)
    np.testing.assert_array_equal(np.asarray(poly(X)), np.asarray(want(X)))
    np.testing.assert_array_equal(np.asarray(poly.monomial_coeffs()),
                                  np.asarray(want.monomial_coeffs()))
    leaves, treedef = jax.tree_util.tree_flatten(poly)
    assert len(leaves) == 5
    again = jax.tree_util.tree_unflatten(treedef, leaves)
    assert again.coeffs is poly.coeffs
    assert again.domain_shift is poly.domain_shift

