"""The lone-series plain moment pass reads the caller's (n,) arrays in
place (``kernels.moments.moments_flat``): interpret-mode parity with
``core.moments.gram_moments`` and with a float64 numpy fit at every kind of
length the block may leave ragged, and jaxpr checks that nothing of the
series' size is padded or broadcast on the way in and that no matmul runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import moments as moments_lib
from repro.kernels import moments as kernel
from repro.kernels import ops

DEGREE = 3
LENGTHS = {
    "below_one_block": 1000,
    "block_multiple": 2 * kernel.DEFAULT_BLOCK_N,
    "1024_multiple_not_4096": 5 * 1024,
    "no_128_multiple": 9_001,
    "three_blocks_ragged": 2 * kernel.FLAT_BLOCK_N + 9_001,
}


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-2.0, 2.0, n).astype(np.float32)
    y = (1.0 - 2.0 * x + 0.5 * x**2 + 0.25 * x**3
         + rng.normal(0.0, 0.5, n)).astype(np.float32)
    w = rng.uniform(0.0, 2.0, n).astype(np.float32)
    w[::7] = 0.0    # points left out, which the true count skips
    return x, y, w


def _check_against_the_references(n, degree, weighted, compensated):
    x, y, w = _data(n)
    weights = jnp.asarray(w) if weighted else None
    got = ops.moments(jnp.asarray(x), jnp.asarray(y), degree,
                      weights=weights, packing="plain",
                      compensated=compensated)
    want = moments_lib.gram_moments(jnp.asarray(x), jnp.asarray(y), degree,
                                    weights=weights)
    for f in ("gram", "vty", "yty", "weight_sum"):
        np.testing.assert_allclose(np.asarray(getattr(got, f), np.float64),
                                   np.asarray(getattr(want, f), np.float64),
                                   rtol=2e-5, atol=1e-3, err_msg=f)
    # the true count: points with nonzero weight, none of the ragged tail
    assert float(got.count) == float(want.count)
    assert float(got.count) == (np.count_nonzero(w) if weighted else n)

    # the kernel's normal equations, solved in float64, give numpy's
    # float64 least-squares fit: its SSE at any degree, and its
    # coefficients where the monomial basis on [-2, 2] is well conditioned
    coeffs = np.linalg.solve(np.asarray(got.gram, np.float64),
                             np.asarray(got.vty, np.float64))
    sw = np.sqrt(w.astype(np.float64)) if weighted else np.ones(n)
    v = np.vander(x.astype(np.float64), degree + 1,
                  increasing=True) * sw[:, None]
    exact = np.linalg.lstsq(v, y * sw, rcond=None)[0]
    sse = lambda c: np.sum((v @ c - y * sw) ** 2)
    assert sse(coeffs) / sse(exact) - 1 < 1e-5
    if degree <= 3:
        np.testing.assert_allclose(coeffs, exact, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("compensated", [False, True],
                         ids=["plain_sum", "compensated"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("n", LENGTHS.values(), ids=LENGTHS.keys())
def test_in_place_pass_matches_the_references(n, weighted, compensated):
    _check_against_the_references(n, DEGREE, weighted, compensated)


@pytest.mark.parametrize("degree, n", [(1, LENGTHS["no_128_multiple"]),
                                       (9, LENGTHS["1024_multiple_not_4096"])])
def test_in_place_pass_matches_the_references_at_other_degrees(degree, n):
    _check_against_the_references(n, degree, weighted=True,
                                  compensated=False)


def _big_eqns(jaxpr, n):
    """(primitive, shape) of every pad or broadcast_in_dim outside the
    kernel body whose result holds n or more elements."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("pad", "broadcast_in_dim"):
            for v in eqn.outvars:
                if np.prod(v.aval.shape, dtype=np.int64) >= n:
                    found.append((eqn.primitive.name, v.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _big_eqns(sub, n)
    return found


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("n", LENGTHS.values(), ids=LENGTHS.keys())
def test_flat_plain_path_makes_no_series_sized_copy(n, weighted):
    arg = jax.ShapeDtypeStruct((n,), jnp.float32)
    args = (arg, arg, arg) if weighted else (arg, arg)

    def fn(x, y, w=None):
        return ops.moments(x, y, DEGREE, weights=w, packing="plain",
                           interpret=False)

    closed = jax.make_jaxpr(fn)(*args)
    assert "pallas_call" in str(closed)
    assert _big_eqns(closed.jaxpr, n) == []


def _pallas_calls(jaxpr):
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += _pallas_calls(sub)
    return calls


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
def test_only_a_weighted_pass_streams_weights(weighted):
    arg = jax.ShapeDtypeStruct((5000,), jnp.float32)
    args = (arg, arg, arg) if weighted else (arg, arg)
    closed = jax.make_jaxpr(
        lambda *a: kernel.moments_flat(*a, degree=DEGREE,
                                       interpret=True))(*args)
    calls = _pallas_calls(closed.jaxpr)
    assert len(calls) == 1
    assert [v.aval.shape for v in calls[0].invars] == [(5000,)] * len(args)


def _primitives(jaxpr):
    """Names of every primitive in jaxpr and its sub-jaxprs, the bodies of
    pallas_calls included."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("n", [4096, 1 << 17])
def test_in_place_pass_uses_no_matmul(n):
    arg = jax.ShapeDtypeStruct((n,), jnp.float32)
    closed = jax.make_jaxpr(
        lambda a, b: kernel.moments_flat(a, b, degree=DEGREE,
                                         interpret=True))(arg, arg)
    assert len(_pallas_calls(closed.jaxpr)) == 1
    prims = _primitives(closed.jaxpr)
    assert "mul" in prims           # the search reaches the kernel's body
    assert "dot_general" not in prims

    # f32 power sums, each Gram entry near the float64 Gram of the same
    # data; the error is measured against the sum of the entry's absolute
    # terms, since the odd power sums cancel
    x, y, _ = _data(n)
    g = np.asarray(kernel.moments_flat(jnp.asarray(x), jnp.asarray(y),
                                       degree=DEGREE, interpret=True),
                   np.float64)[:DEGREE + 1, :DEGREE + 1]
    v = np.vander(x.astype(np.float64), DEGREE + 1, increasing=True)
    err = np.abs(g - v.T @ v) / (np.abs(v).T @ np.abs(v))
    assert err.max() < 1e-6
