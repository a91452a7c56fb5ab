"""Single-pass automatic model selection (``repro.select``).

* ISSUE-4 acceptance: ``select_degree`` / ``degree="auto"`` recover the
  planted degree on noisy synthetic data (degrees 2–6, ≥ 95% of trials at
  SNR ≥ 10) from EXACTLY ONE pass over the data — verified by the
  instrumented counter on moment-producing calls — and the moment-space
  k-fold CV scores match explicit held-out refits to fp tolerance.
* Nesting property (hypothesis): ``fit_from_moments(m.truncate(d))`` of a
  degree-8 state matches a direct ``polyfit(x, y, d)`` across degrees
  0–8, f32/f64, monomial/Chebyshev, identity/normalized domains, jnp and
  kernel engines — κ-scaled tolerances, same style as test_conformance.
* Plumbing: streaming ``current_selection()``, the fit server's
  auto-degree requests, the distributed fold-psum path, criteria edge
  cases (underdetermined rungs score +inf).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro import core, engine, select
from repro.core import streaming
from repro.select import criteria, crossval


settings.register_profile("select", deadline=None, max_examples=20)
settings.load_profile("select")


def _planted(seed: int, degree: int, n: int, snr: float = 10.0,
             lo: float = -1.0, hi: float = 1.0):
    """Noisy series with an unambiguous planted degree.

    The signal is drawn in the CHEBYSHEV basis with the leading
    coefficient bounded away from zero: that guarantees the degree-d
    component is genuinely present (orthogonally to all lower degrees)
    above the noise floor.  A raw-monomial draw does not — x^d on [-1,1]
    is almost entirely explained by lower degrees (the orthogonal residual
    of x^6 is ~0.07·c₆), so its "planted degree" can be statistically
    absent, which no selector can recover (measured table in
    EXPERIMENTS.md §Degree selection)."""
    rng = np.random.default_rng(seed)
    c = rng.normal(0.0, 0.5, degree + 1)
    c[degree] = rng.choice([-1.0, 1.0]) * rng.uniform(0.5, 1.5)
    x = rng.uniform(lo, hi, n)
    sig = np.polynomial.chebyshev.chebval(
        (2.0 * x - (hi + lo)) / (hi - lo), c)
    y = sig + (np.std(sig) / snr) * rng.normal(0, 1, n)
    return (jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32), sig)


# ---------------------------------------------------------------- truncate
def test_truncate_slices_leading_submatrix():
    x, y, _ = _planted(0, 3, 200)
    m = core.gram_moments(x, y, 6)
    t = m.truncate(2)
    assert t.degree == 2
    np.testing.assert_array_equal(np.asarray(t.gram),
                                  np.asarray(m.gram[:3, :3]))
    np.testing.assert_array_equal(np.asarray(t.vty), np.asarray(m.vty[:3]))
    np.testing.assert_array_equal(np.asarray(t.yty), np.asarray(m.yty))
    np.testing.assert_array_equal(np.asarray(t.count), np.asarray(m.count))
    with pytest.raises(ValueError, match="truncate"):
        m.truncate(7)


@given(st.integers(0, 8), st.booleans(), st.booleans(),
       st.sampled_from(["f32_reference", "f32_kernel", "f64_reference"]))
def test_truncated_maxdegree_moments_match_direct_fit(degree, chebyshev,
                                                      normalize, mode):
    """The nesting property behind the whole subsystem: a degree-8 state,
    truncated to d, solves to the same polynomial a direct degree-d
    polyfit produces — every basis/domain/engine/dtype combination, with
    κ-scaled tolerances (test_conformance style)."""
    basis = core.CHEBYSHEV if chebyshev else core.MONOMIAL
    engine_name = "kernel" if mode == "f32_kernel" else "reference"
    if chebyshev and engine_name == "kernel":
        return  # the Pallas kernels are monomial-only (validated centrally)
    dtype = jnp.float64 if mode == "f64_reference" else jnp.float32
    ctx = jax.enable_x64(True) if mode == "f64_reference" else None

    rng = np.random.default_rng(1000 + degree)
    n = 160
    x = np.sort(rng.uniform(-1.5, 1.5, n))
    y = (np.polyval(rng.normal(0, 1, degree + 1)[::-1], x)
         + 0.02 * rng.normal(0, 1, n))
    try:
        if ctx is not None:
            ctx.__enter__()
        xj = jnp.asarray(x, dtype)
        yj = jnp.asarray(y, dtype)
        # explicit solver: keeps the numerics policy identical on both
        # sides (polyfit's solver="auto" would escalate normalization per
        # degree, which is a plan property, not a nesting property)
        direct = core.polyfit(xj, yj, degree, basis=basis,
                              normalize=normalize, engine=engine_name,
                              solver="svd")
        dom = (core.Domain.from_data(xj) if normalize
               else core.Domain.identity(dtype))
        plan = engine.plan_fit(xj.shape, 8, basis=basis, dtype=dtype,
                               engine=engine_name)
        m8 = engine.compute_moments(plan, dom.apply(xj), yj)
        nested = core.fit_from_moments(m8.truncate(degree), solver="svd",
                                       domain=dom, basis=basis,
                                       normalized=normalize)
        cond = float(nested.diagnostics.condition)
        eps = float(jnp.finfo(dtype).eps)
        tol = max(200.0 * eps * np.sqrt(max(cond, 1.0)), 50.0 * eps)
        xs = jnp.asarray(np.linspace(-1.5, 1.5, 64), dtype)
        gold = np.asarray(direct(xs), np.float64)
        ours = np.asarray(nested(xs), np.float64)
        gap = (np.linalg.norm(ours - gold)
               / (np.linalg.norm(gold) + 1e-30))
        assert gap <= tol, (f"deg={degree} {basis} norm={normalize} "
                            f"{mode}: {gap:.3e} > {tol:.3e} (κ={cond:.2e})")
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)


# ------------------------------------------------- acceptance: planted degree
def test_select_degree_recovers_planted_single_pass():
    """ISSUE-4 acceptance: degrees 2–6, SNR 10, ≥ 95% recovery across
    trials — and every trial costs exactly ONE moment-producing call."""
    trials = 0
    hits = 0
    for degree in range(2, 7):
        for t in range(8):
            x, y, _ = _planted(17 * degree + t, degree, 512)
            engine.reset_moment_counter()
            sel = core.select_degree(x, y, max_degree=8, folds=5)
            counter = engine.moment_counter()
            assert counter["calls"] == 1, (
                f"selection took {counter['calls']} moment passes")
            assert counter["points"] == 515  # 5 folds × 103 (incl. padding)
            trials += 1
            hits += int(sel.best_degree == degree)
    assert hits / trials >= 0.95, f"recovered {hits}/{trials}"


def test_polyfit_degree_auto_front_door():
    x, y, sig = _planted(5, 3, 512)
    poly = core.polyfit(x, y, "auto")
    assert poly.degree == 3
    # the winning fit is a real fit: values track the clean signal
    rel = (np.linalg.norm(np.asarray(poly(x), np.float64) - sig)
           / np.linalg.norm(sig))
    assert rel < 0.05, f"value error {rel:.3f}"
    custom = core.polyfit(x, y, core.DegreeSearch(max_degree=5, folds=3,
                                                  criterion="bic"))
    assert custom.degree == 3
    with pytest.raises(ValueError, match="auto"):
        core.polyfit(x, y, "automatic")


def test_select_degree_moment_criteria_no_folds():
    x, y, _ = _planted(9, 4, 512)
    engine.reset_moment_counter()
    sel = core.select_degree(x, y, max_degree=8, folds=0)
    assert engine.moment_counter()["calls"] == 1
    assert sel.criterion == "aicc"
    assert sel.best_degree == 4
    assert np.all(np.isinf(np.asarray(sel.sweep.scores.cv)))
    with pytest.raises(ValueError, match="folds"):
        core.select_degree(x, y, folds=0, criterion="cv")


# --------------------------------------------- acceptance: CV == explicit
def test_cv_scores_match_explicit_heldout_refits():
    """Moment-space k-fold CV == explicit held-out refits, to fp
    tolerance: for each fold, refit the complement FROM THE RAW DATA at
    every degree and score the held-out points directly."""
    k, max_deg, n = 4, 6, 240
    with jax.enable_x64(True):
        rng = np.random.default_rng(7)
        x = rng.uniform(-1.0, 1.0, n)
        y = (np.polyval([0.9, 0.3, -1.0, 0.5], x)
             + 0.05 * rng.normal(0, 1, n))
        xj = jnp.asarray(x, jnp.float64)
        yj = jnp.asarray(y, jnp.float64)
        folds = crossval.fold_moments(xj, yj, k, max_deg)
        got, _ = crossval.cv_scores(folds, solver="qr", fallback=None)
        got = np.asarray(got)
        want = np.zeros(max_deg + 1)
        fold_of = np.arange(n) % k
        for j in range(k):
            tr, ho = fold_of != j, fold_of == j
            for d in range(max_deg + 1):
                m = core.gram_moments(jnp.asarray(x[tr]),
                                      jnp.asarray(y[tr]), d)
                poly = core.fit_from_moments(m, solver="qr", fallback=None)
                e = y[ho] - np.asarray(poly(jnp.asarray(x[ho])))
                want[d] += float(e @ e)
        np.testing.assert_allclose(got, want, rtol=1e-8)


def test_fold_moments_sum_to_total():
    x, y, _ = _planted(11, 3, 200)
    folds = crossval.fold_moments(x, y, 5, 4)
    total = crossval.sum_folds(folds)
    direct = core.gram_moments(x, y, 4)
    np.testing.assert_allclose(np.asarray(total.gram),
                               np.asarray(direct.gram), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(total.count), 200.0)
    # complement + fold == total, leaf by leaf
    comp = crossval.complement_moments(folds, total)
    back = jax.tree.map(lambda a, b: a + b, comp, folds)
    for leaf_b, leaf_t in zip(jax.tree.leaves(back),
                              jax.tree.leaves(total)):
        np.testing.assert_allclose(np.asarray(leaf_b)[0],
                                   np.asarray(leaf_t), rtol=1e-4,
                                   atol=1e-5)


# ------------------------------------------------------------ criteria edges
def test_underdetermined_degrees_score_inf():
    x, y, _ = _planted(13, 1, 6)   # 6 points, ladder to degree 8
    sel = core.select_degree(x, y, max_degree=8, folds=0)
    scores = sel.sweep.scores
    assert np.all(np.isinf(np.asarray(scores.aicc)[6:]))   # n <= k
    assert sel.best_degree <= 4                            # AICc dof guard
    assert np.all(np.isfinite(np.asarray(scores.sse)))


def test_best_degree_rejects_monotone_criteria():
    x, y, _ = _planted(14, 2, 64)
    sel = core.select_degree(x, y, max_degree=4, folds=0)
    with pytest.raises(ValueError, match="monotone"):
        criteria.best_degree(sel.sweep.scores, "r2")
    with pytest.raises(ValueError, match="criterion"):
        core.select_degree(x, y, criterion="press")


def test_batched_select_padded_winner_layout():
    """Batched series with different planted degrees: per-series winners,
    zero-padded winning coefficients that evaluate correctly."""
    xs, ys = [], []
    for i, d in enumerate((1, 3)):
        x, y, _ = _planted(20 + i, d, 256)
        xs.append(x)
        ys.append(y)
    xb = jnp.stack(xs)
    yb = jnp.stack(ys)
    sel = core.select_degree(xb, yb, max_degree=6, folds=4)
    np.testing.assert_array_equal(sel.best_degree, [1, 3])
    assert sel.poly.coeffs.shape == (2, 7)         # padded M+1 layout
    np.testing.assert_array_equal(np.asarray(sel.poly.coeffs[0, 2:]), 0.0)


# ----------------------------------------------------------------- streaming
def test_streaming_current_selection_converges():
    x, y, _ = _planted(31, 3, 1200)
    st = streaming.StreamState.create(8, cv_folds=5)
    for lo in range(0, 1200, 50):
        st = streaming.update(st, x[lo:lo + 50], y[lo:lo + 50])
    sel = st.current_selection()
    assert sel.criterion == "cv"
    assert sel.best_degree == 3
    assert st.current_selection(criterion="aicc").best_degree == 3
    # fold partials really partition the stream: they sum to the total
    total = crossval.sum_folds(st.fold_moments)
    np.testing.assert_allclose(np.asarray(total.gram),
                               np.asarray(st.moments.gram), rtol=1e-5)


def test_streaming_selection_needs_folds_for_cv():
    st = streaming.StreamState.create(4)
    x, y, _ = _planted(33, 2, 64)
    st = streaming.update(st, x, y)
    assert st.fold_moments is None
    assert st.current_selection().criterion == "aicc"
    with pytest.raises(ValueError, match="cv_folds"):
        st.current_selection(criterion="cv")


# --------------------------------------------------------------- fit server
def test_serve_auto_degree_requests():
    from repro.serve import FitServeConfig, FitServeEngine
    eng = FitServeEngine(FitServeConfig(degree=6, n_slots=4, buckets=(128,),
                                        select_criterion="aicc"))
    execs = eng.warmup()
    rng = np.random.default_rng(40)
    x = rng.uniform(-2, 2, 300).astype(np.float32)
    y = (1.0 + 0.5 * x - 2.0 * x * x
         + 0.05 * rng.normal(0, 1, 300)).astype(np.float32)
    auto = eng.submit(x, y, degree="auto")
    fixed = eng.submit(x, y)
    eng.run()
    assert auto.done and fixed.done
    assert auto.degree == 2
    assert auto.coeffs.shape == (3,)
    np.testing.assert_allclose(auto.coeffs, [1.0, 0.5, -2.0], atol=0.05)
    assert set(select.MOMENT_CRITERIA) <= set(auto.scores)
    assert all(v.shape == (7,) for v in auto.scores.values())
    assert auto.condition_ladder.shape == (7,)
    assert np.isfinite(auto.condition)
    assert fixed.degree == 6                       # fixed path reports too
    # the auto path added no executables beyond warmup's
    assert eng.compiled_executables() == execs
    with pytest.raises(ValueError, match="auto"):
        eng.submit(x, y, degree=4)


def test_serve_auto_degree_sse_consistent_under_ridge():
    """A visible ridge stabilizer must not leak into the reported scores:
    the auto path solves on the regularized state but scores on the raw
    moments, exactly like the fixed-degree path."""
    from repro.serve import FitServeConfig, FitServeEngine
    eng = FitServeEngine(FitServeConfig(degree=3, n_slots=2, buckets=(128,),
                                        ridge=1e-3))
    x, y, _ = _planted(41, 3, 256)
    auto = eng.submit(np.asarray(x), np.asarray(y), degree="auto")
    fixed = eng.submit(np.asarray(x), np.asarray(y))
    eng.run()
    assert auto.degree == 3 == fixed.degree
    np.testing.assert_allclose(auto.sse, fixed.sse, rtol=1e-5)
    np.testing.assert_allclose(auto.r, fixed.r, rtol=1e-5)


def test_serve_rejects_cv_criterion():
    from repro.serve import FitServeConfig, FitServeEngine
    with pytest.raises(ValueError, match="fold"):
        FitServeEngine(FitServeConfig(select_criterion="cv"))


# -------------------------------------------------------------- distributed
def test_distributed_select_host_mesh():
    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_host_mesh(data=1, model=1)
    x, y, sig = _planted(50, 3, 600)
    sel_fn = core.make_distributed_select(mesh, 6, folds=4)
    poly, sweep, best = sel_fn(x, y)
    assert int(best) == 3
    assert np.asarray(sweep.scores.cv).shape == (7,)
    assert np.all(np.isfinite(np.asarray(sweep.scores.cv)))
    # the returned winning fit evaluates on RAW x (padded ladder layout)
    rel = (np.linalg.norm(np.asarray(poly(x), np.float64) - sig)
           / np.linalg.norm(sig))
    assert rel < 0.05, f"winning fit off by {rel:.3f}"
    # matches the single-host path on the same folds
    local = core.select_degree(x, y, max_degree=6, folds=4)
    np.testing.assert_allclose(np.asarray(sweep.scores.cv),
                               np.asarray(local.sweep.scores.cv),
                               rtol=1e-4)


def test_distributed_select_wide_domain_carries_domain():
    """The auto-normalized (degree >= 6, f32) distributed selection must
    return coefficients WITH their Domain — evaluating the winning poly on
    raw wide-domain x has to track the signal."""
    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_host_mesh(data=1, model=1)
    x, y, sig = _planted(51, 3, 600, lo=0.0, hi=40.0)
    poly, sweep, best = core.make_distributed_select(mesh, 8, folds=4)(x, y)
    assert int(best) == 3
    assert float(poly.domain_scale) != 1.0         # auto-normalization on
    rel = (np.linalg.norm(np.asarray(poly(x), np.float64) - sig)
           / np.linalg.norm(sig))
    assert rel < 0.05, f"domain lost: rel error {rel:.3f}"
