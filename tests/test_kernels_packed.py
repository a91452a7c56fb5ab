"""Packed multi-series Pallas kernel + fused report pass: parity with the
pure-jnp reference paths across degrees, ragged shapes, dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import streaming
from repro.kernels import moments as kernel
from repro.kernels import ops, ref


def _data(seed, b, n, dtype=jnp.float32):
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


def _jnp_moments(x, y, deg, weights=None):
    m = core.gram_moments(x, y, deg, weights=weights,
                          accum_dtype=jnp.float32)
    # kernel path reports the true contributing-point count, the jnp path Σw;
    # compare against the true count
    n_live = (x.shape[-1] if weights is None
              else jnp.sum(weights != 0, axis=-1))
    import dataclasses
    return dataclasses.replace(
        m, count=jnp.broadcast_to(n_live, m.count.shape).astype(m.count.dtype))


@pytest.mark.parametrize("deg", [1, 3, 7, 12])
@pytest.mark.parametrize("b,n", [
    (1, 300),        # single series (auto falls back to plain)
    (7, 1000),       # ragged n, batch < P for every degree here
    (26, 257),       # odd n; 26 not divisible by P at any tested degree
    (50, 128),       # exactly 2 packs at degree 3
])
def test_packed_matches_gram_moments_f32(deg, b, n):
    x, y = _data(deg * 100 + b, b, n)
    mk = ops.moments(x, y, deg)
    # high degrees produce ~1e9-magnitude power sums; blocked-vs-einsum f32
    # rounding alone reaches a few e-5 relative there
    rtol = 2e-5 if deg < 10 else 2e-4
    _assert_moments_close(mk, _jnp_moments(x, y, deg), rtol=rtol)


@pytest.mark.parametrize("deg", [1, 3, 12])
def test_packed_forced_vs_plain(deg):
    """packing='packed' == packing='plain' == jnp, even for b=1."""
    x, y = _data(10 + deg, 1, 513)
    mp = ops.moments(x, y, deg, packing="packed")
    ms = ops.moments(x, y, deg, packing="plain")
    _assert_moments_close(mp, ms, rtol=1e-5, atol=1e-4)
    _assert_moments_close(mp, _jnp_moments(x, y, deg))


@pytest.mark.parametrize("b", [3, 8, 12])
def test_plain_batched_rows(b):
    """The plain kernel takes ROW_BLOCK series per grid step; a batch past
    it is padded with zero-weight rows that are sliced away."""
    x, y = _data(30 + b, b, 700)
    _assert_moments_close(ops.moments(x, y, 3, packing="plain"),
                          _jnp_moments(x, y, 3))


@pytest.mark.parametrize("deg", [1, 3])
def test_packed_bf16_inputs_f32_accumulate(deg):
    x, y = _data(20 + deg, 9, 2048, jnp.bfloat16)
    mk = ops.moments(x, y, deg)
    mr = _jnp_moments(x.astype(jnp.float32), y.astype(jnp.float32), deg)
    _assert_moments_close(mk, mr, rtol=1e-2, atol=2e-1)
    assert mk.gram.dtype == jnp.float32


def test_packed_raw_tile_matches_oracle():
    """The packed kernel's raw (G,128,128) tile — diagonal blocks AND the
    never-read cross-series products — equals the explicit construction."""
    deg = 3
    p = kernel.packing_factor(deg)
    x, y = _data(3, 2 * p, 512)
    shape = (2, p, 512)
    w = jnp.ones(shape, jnp.float32)
    g = kernel.moments_packed_extended(
        x.reshape(shape), y.reshape(shape), w, degree=deg, block_n=256,
        interpret=True)
    gr = ref.packed_extended_gram(x.reshape(shape), y.reshape(shape), deg)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-5, atol=1e-3)
    # remainder sublanes (128 mod K) are exactly zero
    live = p * (deg + 2)
    assert np.all(np.asarray(g)[:, live:, :] == 0)
    assert np.all(np.asarray(g)[:, :, live:] == 0)


def test_packed_tail_series_masked():
    """Batch not divisible by P: zero-weight tail series vanish exactly."""
    deg = 7                       # P = 14
    b = kernel.packing_factor(deg) + 3
    x, y = _data(4, b, 321)
    _assert_moments_close(ops.moments(x, y, deg), _jnp_moments(x, y, deg))


def test_weights_and_true_count():
    """Weighted fits: gram/vty weighted, count = #points with w != 0."""
    x, y = _data(5, 6, 400)
    w = jnp.concatenate([jnp.ones((6, 300)), jnp.zeros((6, 100))], axis=1)
    w = w * jnp.asarray(np.random.default_rng(5).uniform(.5, 2, (6, 400)),
                        jnp.float32)
    mk = ops.moments(x, y, 3, weights=w)
    mr = core.gram_moments(x, y, 3, weights=w, accum_dtype=jnp.float32)
    for f in ("gram", "vty", "yty"):
        np.testing.assert_allclose(np.asarray(getattr(mk, f)),
                                   np.asarray(getattr(mr, f)),
                                   rtol=2e-5, atol=1e-3, err_msg=f)
    np.testing.assert_array_equal(np.asarray(mk.count), 300.0)
    # Σw (the old `count`) is still reachable as gram[..., 0, 0]
    np.testing.assert_allclose(np.asarray(mk.gram[:, 0, 0]),
                               np.asarray(jnp.sum(w, axis=-1)), rtol=2e-5)


@pytest.mark.parametrize("compensated", [False, True])
def test_compensated_accumulator(compensated):
    """Kahan path matches plain within tolerance; at many blocks it should
    be at least as close to the f64 truth."""
    x, y = _data(6, 4, 8192)
    mk = ops.moments(x, y, 3, block_n=256, compensated=compensated)
    _assert_moments_close(mk, _jnp_moments(x, y, 3))


def test_polyfit_use_kernel_batched_packed():
    """End-to-end: batched polyfit through the packed kernel == jnp path."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.uniform(-2, 2, (33, 512)), jnp.float32)
    y = jnp.asarray(rng.normal(0, 1, (33, 512)), jnp.float32)
    a = core.polyfit(x, y, 3, use_kernel=True).coeffs
    b = core.polyfit(x, y, 3, use_kernel=False).coeffs
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("b,n,deg", [(1, 777, 3), (5, 500, 2), (8, 1024, 5),
                                     (12, 300, 3)])
def test_fused_report_matches_fit_report(b, n, deg):
    rng = np.random.default_rng(b * 10 + deg)
    x = jnp.asarray(rng.uniform(-2, 2, (b, n)), jnp.float32)
    y = jnp.asarray(np.asarray(x) ** 2 + rng.normal(0, .3, (b, n)),
                    jnp.float32)
    poly = core.polyfit(x, y, deg)
    srep = core.fit_report_streamed(poly, x, y)
    rep = core.fit_report(poly, x, y)
    np.testing.assert_allclose(np.asarray(srep.sse), np.asarray(rep.sse),
                               rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(srep.r), np.asarray(rep.r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(srep.count), n)


def test_fused_report_normalized_domain():
    """Domain-normalized fits evaluate through the fused kernel too."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.uniform(0, 40, 600), jnp.float32)
    y = jnp.asarray(0.1 * np.asarray(x) ** 2 + rng.normal(0, .1, 600),
                    jnp.float32)
    poly = core.polyfit(x, y, 2, normalize=True)
    srep = core.fit_report_streamed(poly, x, y)
    rep = core.fit_report(poly, x, y)
    np.testing.assert_allclose(np.asarray(srep.sse), np.asarray(rep.sse),
                               rtol=2e-4, atol=1e-3)


def test_streaming_update_kernel_path():
    """Kernel-backed streaming update == jnp update (decay-weighted)."""
    st = streaming.StreamState.create(2, (5,), decay=0.999)
    x, y = _data(11, 5, 384)
    s_j = streaming.update(st, x, y)
    s_k = streaming.update(st, x, y, use_kernel=True)
    for f in ("gram", "vty", "yty"):
        np.testing.assert_allclose(np.asarray(getattr(s_j.moments, f)),
                                   np.asarray(getattr(s_k.moments, f)),
                                   rtol=2e-5, atol=1e-3, err_msg=f)
    # fits solved from both states agree
    np.testing.assert_allclose(
        np.asarray(streaming.current_fit(s_j, ridge=1e-6).coeffs),
        np.asarray(streaming.current_fit(s_k, ridge=1e-6).coeffs),
        rtol=5e-3, atol=5e-3)


# ------------------------------------------------- double-buffered DMA kernel
@pytest.mark.parametrize("deg,b,n,nbuf", [
    (1, 4, 700, 2),
    (3, 7, 1000, 2),
    (3, 7, 1000, 3),
    (5, 3, 2500, 4),
    (9, 2, 640, 2),
])
def test_double_buffered_bit_equals_grid_streamed(deg, b, n, nbuf):
    """The multi-buffered DMA pipeline shares ``_packed_tile_update`` with
    the grid-streamed kernel, so at the SAME block_n the two are bit-equal
    (identical summation grouping), not merely close."""
    x, y = _data(17 + deg, b, n)
    block_n = 256
    m0 = ops.moments(x, y, deg, packing="packed", block_n=block_n)
    m1 = ops.moments(x, y, deg, packing="packed", block_n=block_n, nbuf=nbuf)
    for f in ("gram", "vty", "yty", "count", "weight_sum"):
        np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                      np.asarray(getattr(m1, f)), err_msg=f)


def test_double_buffered_weighted_and_compensated():
    x, y = _data(23, 5, 900)
    rng = np.random.default_rng(23)
    w = jnp.asarray(rng.uniform(0, 2, x.shape), jnp.float32)
    for comp in (False, True):
        m0 = ops.moments(x, y, 3, weights=w, packing="packed",
                         block_n=256, compensated=comp)
        m1 = ops.moments(x, y, 3, weights=w, packing="packed",
                         block_n=256, compensated=comp, nbuf=2)
        for f in ("gram", "vty", "yty", "weight_sum"):
            np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                          np.asarray(getattr(m1, f)),
                                          err_msg=f"{f} comp={comp}")


def test_double_buffered_matches_jnp_reference():
    x, y = _data(29, 6, 1234)        # odd length: tail padding in play
    mk = ops.moments(x, y, 3, packing="packed", block_n=512, nbuf=2)
    _assert_moments_close(mk, _jnp_moments(x, y, 3))


def test_nbuf_validation():
    x, y = _data(31, 4, 256)
    with pytest.raises(ValueError):
        ops.moments(x, y, 3, packing="packed", nbuf=1)
    with pytest.raises(ValueError):
        ops.moments(x, y, 3, packing="plain", nbuf=2)


# ------------------------------------------------------------------- autotune
def test_autotune_feasible_and_cached():
    from repro.kernels import tune
    tune.clear_cache()
    try:
        ticks = iter(range(1000))
        bn = tune.autotune_block_n(3, 4096, reps=1,
                                   timer=lambda: next(ticks) * 1e-3)
        assert bn in tune.CANDIDATE_BLOCKS
        assert bn in tune.feasible_blocks(3)
        # cache hit: no more timer draws
        before = next(ticks)
        assert tune.autotune_block_n(3, 4096) == bn
        assert next(ticks) == before + 1
    finally:
        tune.clear_cache()


def test_autotune_vmem_model_monotone():
    from repro.kernels import tune
    assert (tune.ring_vmem_bytes(3, 2048) < tune.ring_vmem_bytes(3, 4096)
            < tune.ring_vmem_bytes(3, 4096, nbuf=3))
    # every feasible candidate respects the budget
    for deg in (1, 3, 9):
        for bn in tune.feasible_blocks(deg):
            assert tune.ring_vmem_bytes(deg, bn) <= tune.VMEM_BUDGET
