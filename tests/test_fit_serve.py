"""Continuous-batching fit server: parity with direct polyfit on ragged
traces, chunked ingest of long series, and the no-recompile invariant."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.serve import FitRequest, FitServeConfig, FitServeEngine


def _trace(seed, n_reqs, lo, hi, degree=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_reqs):
        n = int(rng.integers(lo, hi + 1))
        x = rng.uniform(-2, 2, n).astype(np.float32)
        coef = rng.normal(0, 1, degree + 1)
        y = (np.polyval(coef[::-1], x)
             + rng.normal(0, 0.1, n)).astype(np.float32)
        out.append((x, y))
    return out


def _assert_matches_polyfit(reqs: list[FitRequest], degree, atol=5e-4):
    for r in reqs:
        assert r.done and r.count == r.n
        ref = core.polyfit(jnp.asarray(r.x), jnp.asarray(r.y), degree)
        np.testing.assert_allclose(r.coeffs, np.asarray(ref.coeffs),
                                   rtol=5e-3, atol=atol,
                                   err_msg=f"req {r.uid} n={r.n}")


def _assert_matches_numpy(reqs: list[FitRequest], degree, atol=5e-4):
    """Served coefficients against numpy's float64 least-squares fit of
    the same float32 series."""
    for r in reqs:
        assert r.done and r.count == r.n
        ref = np.polyfit(r.x.astype(np.float64), r.y.astype(np.float64),
                         degree)[::-1]
        np.testing.assert_allclose(r.coeffs, ref, rtol=5e-3, atol=atol,
                                   err_msg=f"req {r.uid} n={r.n}")


@pytest.fixture
def small_server():
    return FitServeEngine(FitServeConfig(degree=3, n_slots=4,
                                         buckets=(64, 256), ridge=1e-9))


@pytest.fixture(scope="module")
def default_server():
    """One server with the fit server's default settings (degree 3, 8
    slots, buckets 256 and 2048), warmed once for the whole module."""
    eng = FitServeEngine(FitServeConfig())
    eng.warmup()
    return eng


# One request per length class at the edges of the default server: the
# fewest points a degree-3 pool takes, one chunk of the small bucket, one
# point under it, exactly it, one point over it (one chunk of the wide
# bucket), exactly the wide bucket, one point over it (a 1-point tail),
# and multi-chunk ingest ending in a full or a 1-point tail, up to the
# 8192 points the served traffic reaches.
DEFAULT_SERVER_LENGTHS = (4, 16, 255, 256, 257, 2047, 2048, 2049, 4096,
                          4097, 6144, 8191, 8192)
# (server fixture, _trace arguments: seed, requests, min and max length)
RAGGED_TRACES = (
    [pytest.param("small_server", (0, 25, 5, 700), 3, id="ragged_mix")]
    + [pytest.param("default_server", (n, 1, n, n), d, id=f"n{n}-deg{d}")
       for n in DEFAULT_SERVER_LENGTHS for d in (1, 2, 3)])


@pytest.mark.parametrize("server, trace, degree", RAGGED_TRACES)
def test_ragged_trace_matches_direct_polyfit(request, server, trace, degree):
    """Served fits answer as numpy's float64 polyfit: a ragged mix over
    both buckets of a small server, and one request of each length class
    on the default server at its pool degree and at the nested degrees it
    serves from the truncated moments."""
    eng = request.getfixturevalue(server)
    spec = (None if degree == eng.cfg.degree
            else dataclasses.replace(eng.fixed_spec, degree=degree))
    series = _trace(*trace, degree=degree)
    done = eng.fits_done
    reqs = [eng.submit(x, y, spec=spec) for x, y in series]
    eng.run()
    assert eng.fits_done - done == len(series)
    _assert_matches_numpy(reqs, degree)


def test_long_series_streams_through_small_bucket():
    """A series much longer than every bucket ingests chunk-by-chunk."""
    eng = FitServeEngine(FitServeConfig(degree=2, n_slots=2,
                                        buckets=(128,), ridge=1e-9))
    (x, y), = _trace(1, 1, 5000, 5000, degree=2)
    req = eng.submit(x, y)
    eng.run()
    assert req.done and req.count == 5000
    _assert_matches_polyfit([req], 2)


def test_zero_recompiles_across_request_churn():
    eng = FitServeEngine(FitServeConfig(degree=3, n_slots=3,
                                        buckets=(64, 256), ridge=1e-9))
    warm = eng.warmup()
    # one fused ingest+fixed-solve per bucket + one auto-degree sweep +
    # one plain mid-series ingest for the widest bucket (the default
    # fixed solve is inlined into the fused executable, so the
    # standalone solve cache stays empty until a NOVEL spec arrives)
    assert warm == len(eng.buckets) + 2
    for x, y in _trace(2, 8, 5, 500):
        eng.submit(x, y)
    eng.run()
    assert eng.compiled_executables() == warm
    reqs = [eng.submit(x, y) for x, y in _trace(3, 30, 5, 500)]
    autos = [eng.submit(x, y, degree="auto")
             for x, y in _trace(4, 6, 5, 500)]
    eng.run()
    assert eng.compiled_executables() == warm
    assert all(r.done and r.degree is not None for r in autos)
    _assert_matches_polyfit(reqs, 3)


def test_slot_reuse_isolates_requests():
    """Back-to-back occupants of the same slot don't contaminate each other:
    serve a constant series after a wild one, slot pool of 1."""
    eng = FitServeEngine(FitServeConfig(degree=1, n_slots=1,
                                        buckets=(32,), ridge=1e-9))
    rng = np.random.default_rng(4)
    wild_x = rng.uniform(-100, 100, 200).astype(np.float32)
    wild_y = rng.normal(0, 1000, 200).astype(np.float32)
    eng.submit(wild_x, wild_y)
    x = np.linspace(-1, 1, 30).astype(np.float32)
    clean = eng.submit(x, (2.0 + 3.0 * x).astype(np.float32))
    eng.run()
    np.testing.assert_allclose(clean.coeffs, [2.0, 3.0], rtol=1e-4,
                               atol=1e-4)


def test_kernel_engine_path():
    """Forced packed-kernel ingest (interpret mode on CPU) serves correctly."""
    eng = FitServeEngine(FitServeConfig(degree=3, n_slots=3, buckets=(128,),
                                        engine="kernel", ridge=1e-9))
    reqs = [eng.submit(x, y) for x, y in _trace(5, 4, 20, 200)]
    eng.run()
    _assert_matches_polyfit(reqs, 3)


def test_report_quality_fields():
    eng = FitServeEngine(FitServeConfig(degree=2, n_slots=2, buckets=(256,),
                                        ridge=1e-9))
    rng = np.random.default_rng(6)
    x = rng.uniform(-2, 2, 400).astype(np.float32)
    y = (x ** 2 + rng.normal(0, 0.05, 400)).astype(np.float32)
    req = eng.submit(x, y)
    eng.run()
    rep = core.fit_report(core.polyfit(jnp.asarray(x), jnp.asarray(y), 2),
                          jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(req.sse, float(rep.sse), rtol=5e-3, atol=1e-3)
    np.testing.assert_allclose(req.r, float(rep.r), rtol=1e-3)


def test_submit_validation():
    eng = FitServeEngine(FitServeConfig(n_slots=1, buckets=(32,)))
    with pytest.raises(ValueError):
        eng.submit(np.ones(3), np.ones(4))
    with pytest.raises(ValueError):
        eng.submit(np.ones(0), np.ones(0))
    with pytest.raises(ValueError, match="determine"):
        # degree-3 default: an underdetermined request is rejected up front
        eng.submit(np.ones(2), np.ones(2))
    with pytest.raises(ValueError):
        FitServeEngine(FitServeConfig(buckets=(256, 64)))


def test_fused_solve_matches_standalone_solve():
    """The fused ingest+solve answers the default spec from the SAME
    ``_spec_solve_from_state`` the standalone per-spec solve traces, so
    re-solving the bucket's post-ingest state standalone reproduces the
    served result."""
    eng = FitServeEngine(FitServeConfig(degree=3, n_slots=2,
                                        buckets=(128,), ridge=1e-9))
    reqs = [eng.submit(x, y) for x, y in _trace(13, 2, 100, 100, degree=3)]
    eng.run()
    assert all(r.done for r in reqs)
    b = eng.buckets[0]
    coeffs, sse, r, count, cond, fb = (np.asarray(a) for a in
                                       eng._solve(b.state, eng.fixed_spec))
    for s, req in enumerate(reqs):
        np.testing.assert_array_equal(req.coeffs, coeffs[s, :4])
        np.testing.assert_array_equal(req.sse, sse[s])
        np.testing.assert_array_equal(req.r, r[s])
