"""Pallas moments kernel: allclose vs the pure-jnp oracle across shapes,
degrees, dtypes, block sizes — plus hypothesis property sweeps."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import core
from repro.kernels import moments as kernel
from repro.kernels import ops, ref

settings.register_profile("kern", deadline=None, max_examples=20)
settings.load_profile("kern")


def _data(seed, b, n, dtype):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-2, 2, (b, n)), dtype)
    y = jnp.asarray(rng.normal(0, 1, (b, n)), dtype)
    return x, y


def _assert_moments_close(mk, mr, rtol=2e-5, atol=1e-3):
    for f in ("gram", "vty", "yty", "count"):
        np.testing.assert_allclose(
            np.asarray(getattr(mk, f), np.float64),
            np.asarray(getattr(mr, f), np.float64),
            rtol=rtol, atol=atol, err_msg=f)


@pytest.mark.parametrize("b,n,deg", [
    (1, 6, 3), (1, 128, 0), (2, 300, 2), (4, 1024, 5),
    (1, 8192, 1), (3, 4096, 8), (1, 5000, 3),
])
def test_kernel_matches_oracle_f32(b, n, deg):
    x, y = _data(0, b, n, jnp.float32)
    _assert_moments_close(ops.moments(x, y, deg),
                          ref.moments_reference(x, y, deg))


@pytest.mark.parametrize("deg", [1, 3])
def test_kernel_bf16_inputs_f32_accumulate(deg):
    x, y = _data(1, 2, 2048, jnp.bfloat16)
    mk = ops.moments(x, y, deg)
    mr = ref.moments_reference(x, y, deg)
    _assert_moments_close(mk, mr, rtol=1e-4, atol=5e-2)
    assert mk.gram.dtype == jnp.float32   # accumulation dtype


@pytest.mark.parametrize("block_n", [128, 512, 4096])
def test_kernel_block_size_invariance(block_n):
    x, y = _data(2, 1, 8192, jnp.float32)
    mk = ops.moments(x, y, 3, block_n=block_n)
    mr = ref.moments_reference(x, y, 3)
    _assert_moments_close(mk, mr)


def test_kernel_weights_mask():
    """Zero-weighted (padded) points contribute nothing."""
    x, y = _data(3, 1, 256, jnp.float32)
    w = jnp.concatenate([jnp.ones((1, 200)), jnp.zeros((1, 56))], axis=1)
    mk = ops.moments(x, y, 2, weights=w)
    mr = ref.moments_reference(x[:, :200], y[:, :200], 2)
    _assert_moments_close(mk, mr)


def test_kernel_flat_input():
    x, y = _data(4, 1, 777, jnp.float32)
    mk = ops.moments(x[0], y[0], 2)
    assert mk.gram.shape == (3, 3)
    mr = jax.tree.map(lambda a: a[0], ref.moments_reference(x, y, 2))
    _assert_moments_close(mk, mr)


def test_extended_gram_raw_output():
    """The kernel's raw 128x128 output equals the oracle extended Gram,
    including the zero padding."""
    x, y = _data(5, 2, 512, jnp.float32)
    w = jnp.ones_like(x)
    g = kernel.moments_extended(x, y, w, degree=3, block_n=256,
                                interpret=True)
    gr = ref.extended_gram(x, y, 3)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-5, atol=1e-3)
    # padding region is exactly zero
    assert np.all(np.asarray(g)[:, 6:, :] == 0)
    assert np.all(np.asarray(g)[:, :, 6:] == 0)


@given(st.integers(0, 10_000), st.integers(1, 64), st.integers(0, 6))
def test_kernel_property_sweep(seed, n, deg):
    x, y = _data(seed, 1, n, jnp.float32)
    _assert_moments_close(ops.moments(x, y, deg),
                          ref.moments_reference(x, y, deg),
                          rtol=1e-4, atol=1e-3)


@given(st.integers(0, 10_000))
def test_kernel_end_to_end_fit(seed):
    """polyfit(use_kernel=True) == polyfit(use_kernel=False)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-2, 2, 512), jnp.float32)
    y = jnp.asarray(rng.normal(0, 1, 512), jnp.float32)
    a = core.polyfit(x, y, 3, use_kernel=True).coeffs
    b = core.polyfit(x, y, 3, use_kernel=False).coeffs
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("n, want", [(4096, "HIGHEST"),
                                     (kernel.DEFAULT_PRECISION_MIN_N,
                                      "DEFAULT")])
@pytest.mark.parametrize("packed", [False, True])
def test_gram_precision_follows_series_length(n, want, packed):
    """Both moment kernels take HIGHEST on short series and DEFAULT from
    DEFAULT_PRECISION_MIN_N points a call."""
    if packed:
        arg = jax.ShapeDtypeStruct((1, kernel.packing_factor(3), n),
                                   jnp.float32)
        fn = kernel.moments_packed_extended
    else:
        arg = jax.ShapeDtypeStruct((1, n), jnp.float32)
        fn = kernel.moments_extended
    text = str(jax.make_jaxpr(
        lambda a, b, c: fn(a, b, c, degree=3, interpret=True))(arg, arg, arg))
    assert set(re.findall(r"precision=\(Precision\.(\w+)", text)) == {want}
