"""The lone-series plain moment pass reads the caller's (n,) arrays in
place (``kernels.moments.moments_flat``): interpret-mode parity with
``core.moments.gram_moments`` and with a float64 numpy fit at every kind of
length the block may leave ragged, and a jaxpr check that nothing of the
series' size is padded or broadcast on the way in."""
import re

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
}


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-2.0, 2.0, n).astype(np.float32)
    y = (1.0 - 2.0 * x + 0.5 * x**2 + 0.25 * x**3
         + rng.normal(0.0, 0.5, n)).astype(np.float32)
    w = rng.uniform(0.0, 2.0, n).astype(np.float32)
    w[::7] = 0.0    # points left out, which the true count skips
    return x, y, w


@pytest.mark.parametrize("compensated", [False, True],
                         ids=["plain_sum", "compensated"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("n", LENGTHS.values(), ids=LENGTHS.keys())
def test_in_place_pass_matches_the_references(n, weighted, compensated):
    x, y, w = _data(n)
    weights = jnp.asarray(w) if weighted else None
    got = ops.moments(jnp.asarray(x), jnp.asarray(y), DEGREE,
                      weights=weights, packing="plain",
                      compensated=compensated)
    want = moments_lib.gram_moments(jnp.asarray(x), jnp.asarray(y), DEGREE,
                                    weights=weights)
    for f in ("gram", "vty", "yty", "weight_sum"):
        np.testing.assert_allclose(np.asarray(getattr(got, f), np.float64),
                                   np.asarray(getattr(want, f), np.float64),
                                   rtol=2e-5, atol=1e-3, err_msg=f)
    # the true count: points with nonzero weight, none of the ragged tail
    assert float(got.count) == float(want.count)
    assert float(got.count) == (np.count_nonzero(w) if weighted else n)

    # the kernel's normal equations, solved in float64, give numpy's
    # float64 least-squares fit
    coeffs = np.linalg.solve(np.asarray(got.gram, np.float64),
                             np.asarray(got.vty, np.float64))
    sw = np.sqrt(w.astype(np.float64)) if weighted else np.ones(n)
    v = np.vander(x.astype(np.float64), DEGREE + 1, increasing=True)
    exact = np.linalg.lstsq(v * sw[:, None], y * sw, rcond=None)[0]
    np.testing.assert_allclose(coeffs, exact, rtol=1e-3, atol=1e-3)


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


@pytest.mark.parametrize("n, want", [(4096, "HIGHEST"),
                                     (kernel.DEFAULT_PRECISION_MIN_N,
                                      "DEFAULT")])
def test_in_place_pass_keeps_the_precision_rule(n, want):
    arg = jax.ShapeDtypeStruct((n,), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda a, b: kernel.moments_flat(a, b, degree=DEGREE,
                                         interpret=True))(arg, arg))
    assert set(re.findall(r"precision=\(Precision\.(\w+)", text)) == {want}
