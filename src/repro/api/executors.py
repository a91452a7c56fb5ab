"""The four executors consuming one ``FitSpec``.

* ``fit(x, y, spec)``                eager/jit — the spec is the jit static
                                     arg, so the compile cache keys on spec
                                     identity;
* ``stream_state(spec)``             (= ``spec.streaming()``) an O(1)-state
                                     ``StreamState`` + ``stream_result``;
* ``make_distributed(spec, mesh)``   (= ``spec.distributed(mesh)``) a
                                     jitted shard_map program;
* the fit server's ``submit(x, y, spec=...)`` (``repro.serve.fit_engine``).

Each lowers through ``repro.engine.plan_fit`` (via ``FitSpec.plan``), so
execution-path and numerics-policy selection stay in one place no matter
which surface runs the spec.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as engine_lib
from repro import select as select_lib
from repro.api.spec import (FitResult, FitSpec, RAW_DATA_SOLVERS)
from repro.core import basis as basis_lib
from repro.core import distributed as distributed_lib
from repro.core import fit as fit_lib
from repro.core import lspia as lspia_lib
from repro.core import moments as moments_lib
from repro.core import robust as robust_lib
from repro.core import solve as solve_lib
from repro.core import streaming as streaming_lib
from repro.engine import plan as plan_lib

_span = jax.profiler.TraceAnnotation


def spec_from_legacy(degree, *, method: str | None = None,
                     basis: str = basis_lib.MONOMIAL,
                     normalize: bool = False, accum_dtype=None,
                     engine: str = "auto", solver: str = "auto",
                     fallback: str | None = "svd",
                     cond_cap: float | None = None,
                     decay: float = 1.0, ridge: float = 0.0) -> FitSpec:
    """Map the legacy ``polyfit``-style kwargs onto a ``FitSpec``.

    ``method=`` is the legacy spelling of ``solver=``; ``solver="lspia"``
    delegates to the iterative method on the normalized domain, exactly as
    ``polyfit`` always has."""
    if isinstance(degree, str):
        if degree != "auto":
            raise ValueError(f"degree={degree!r}; expected an int, 'auto', "
                             "or a repro.select.DegreeSearch")
        degree = select_lib.DegreeSearch()
    if method is not None:
        solver = method
    meth = "lse"
    if solver == "lspia":
        # matrix-free delegation; always on the normalized domain (LSPIA's
        # first-order convergence rate needs the bounded-domain κ)
        meth, solver, normalize = "lspia", "auto", True
    return FitSpec(
        degree=degree, basis=basis, method=meth,
        numerics=plan_lib.NumericsPolicy(accum_dtype=accum_dtype,
                                         normalize=normalize, solver=solver,
                                         fallback=fallback,
                                         cond_cap=cond_cap),
        decay=decay, ridge=ridge, engine=engine)


def _decay_ladder(x: jax.Array, decay: float) -> jax.Array:
    return moments_lib.decay_ladder(x.shape[-1], decay, x.dtype)


def _spec_domain(spec: FitSpec, x: jax.Array,
                 normalize: bool) -> basis_lib.Domain:
    return spec.domain_or(
        basis_lib.Domain.from_data(x) if normalize
        else basis_lib.Domain.identity(x.dtype), dtype=x.dtype)


def _data_only(poly: fit_lib.Polynomial, rep, spec: FitSpec,
               normalize: bool):
    """``(poly, rep)`` without the leaves the spec alone decides: a domain
    not taken from the data (pinned, or the identity) and the report's
    copy of the coefficients.  ``_fit_lse`` puts them back on the host."""
    if spec.domain is not None or not normalize:
        poly = dataclasses.replace(poly, domain_shift=None,
                                   domain_scale=None)
    if rep is not None:
        rep = dataclasses.replace(rep, coeffs=None)
    return poly, rep


@partial(jax.jit, static_argnames=("spec",))
def _fit_lse_fixed(x: jax.Array, y: jax.Array,
                   weights: jax.Array | None, spec: FitSpec):
    """The paper's pipeline for one fixed-degree LSE spec: plan → domain →
    moments → condition-aware solve (+ the free moment-space report).

    Returns only what the data decides (``_data_only``)."""
    degree = int(spec.degree)
    if spec.numerics.solver in RAW_DATA_SOLVERS:
        # the MATLAB-polyfit baseline: QR directly on the (weighted)
        # Vandermonde rows — no moments, no Gram squaring of κ
        dom = _spec_domain(spec, x, spec.numerics.normalize)
        xt = dom.apply(x)
        v = basis_lib.vandermonde(xt, degree, spec.basis)
        yy = y
        w = weights
        if spec.decay < 1.0:
            lad = _decay_ladder(x, spec.decay)
            w = lad if w is None else w * lad
        if w is not None:
            sw = jnp.sqrt(w)
            v = v * sw[..., :, None]
            yy = y * sw
        coeffs = solve_lib.qr_solve_vandermonde(v, yy)
        poly = fit_lib.Polynomial(coeffs=coeffs, domain_shift=dom.shift,
                                  domain_scale=dom.scale, basis=spec.basis)
        return _data_only(poly, None, spec, spec.numerics.normalize)
    plan = spec.plan(x.shape, x.dtype, weighted=weights is not None)
    pol = plan.numerics
    dom = _spec_domain(spec, x, pol.normalize)
    xt = dom.apply(x)
    w = weights
    if spec.decay < 1.0:
        lad = _decay_ladder(x, spec.decay)
        w = lad if w is None else w * lad
    m = engine_lib.compute_moments(plan, xt, y, w)
    ms = m.regularized(spec.ridge) if spec.ridge else m
    poly = fit_lib.fit_from_moments(
        ms, solver=pol.solver, fallback=pol.fallback, cond_cap=pol.cond_cap,
        domain=dom, basis=spec.basis,
        normalized=pol.normalize or spec.domain is not None)
    rep = fit_lib.report_from_moments(m, poly.coeffs)
    return _data_only(poly, rep, spec, pol.normalize)


# The host side of a fixed-degree LSE call: the span's path is resolved
# once per (spec, shape, dtype, weighted), and on concrete one-device
# arrays a spec-fixed domain is two device scalars made once per (dtype,
# values, device) and shared by every answer, so that after the first read
# their host values are cached on the arrays and a caller's copy back
# skips them.
_LSE_PATHS: dict = {}
_DOMAIN_CONSTANTS: dict = {}
_CACHE_ENTRIES = 4096
_DISPATCH_COUNTER = {"calls": 0, "cached": 0, "static_domain": 0}


def reset_dispatch_counter() -> None:
    for k in _DISPATCH_COUNTER:
        _DISPATCH_COUNTER[k] = 0


def dispatch_counter() -> dict:
    """Snapshot of the fixed-degree LSE dispatch counter: ``calls`` on
    concrete one-device inputs, how many of them found their path
    ``cached``, and how many had a ``static_domain`` (pinned or identity)
    returned as the shared constants.  Traced calls count nothing."""
    return dict(_DISPATCH_COUNTER)


def _domain_constants(spec: FitSpec, dtype,
                      sharding: jax.sharding.Sharding) -> basis_lib.Domain:
    """The spec-fixed domain as device scalars on ``sharding``'s device,
    uncommitted, so that they join computations on any device as the
    program's own outputs would on uncommitted inputs."""
    values = spec.domain or (0.0, 1.0)
    key = (dtype, values, sharding)
    dom = _DOMAIN_CONSTANTS.get(key)
    if dom is None or dom.shift.is_deleted() or dom.scale.is_deleted():
        if len(_DOMAIN_CONSTANTS) >= _CACHE_ENTRIES:
            _DOMAIN_CONSTANTS.clear()
        (device,) = sharding.device_set
        with jax.default_device(device):
            dom = basis_lib.Domain(
                *(jax.device_put(np.asarray(v, dtype)) for v in values))
        _DOMAIN_CONSTANTS[key] = dom
    return dom


def _on_one_device(a) -> bool:
    return (isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
            and isinstance(a.sharding, jax.sharding.SingleDeviceSharding))


def _fit_lse(x: jax.Array, y: jax.Array, weights: jax.Array | None,
             spec: FitSpec) -> FitResult:
    """A fixed-degree LSE call: nothing but a dict lookup precedes the
    enqueue once the path is cached.  The answer gets the spec-fixed domain
    back as the shared constants on concrete one-device inputs, and as
    fresh arrays in the caller's trace or placement otherwise."""
    weighted = weights is not None
    key = (spec, x.shape, x.dtype, weighted)
    path = _LSE_PATHS.get(key)
    cached = path is not None
    if not cached:
        if len(_LSE_PATHS) >= _CACHE_ENTRIES:
            _LSE_PATHS.clear()
        path = _LSE_PATHS[key] = (
            spec.numerics.solver if spec.numerics.solver in RAW_DATA_SOLVERS
            else spec.plan(x.shape, x.dtype, weighted=weighted).path)
    with _span("api.fit", path=path):
        poly, rep = _fit_lse_fixed(x, y, weights, spec)
        static = poly.domain_shift is None
        if (_on_one_device(x) and _on_one_device(y)
                and not isinstance(weights, jax.core.Tracer)):
            _DISPATCH_COUNTER["calls"] += 1
            _DISPATCH_COUNTER["cached"] += cached
            _DISPATCH_COUNTER["static_domain"] += static
            if static:
                dom = _domain_constants(spec, x.dtype, x.sharding)
        elif static:
            dom = spec.domain_or(basis_lib.Domain.identity(x.dtype),
                                 dtype=x.dtype)
        if static:
            poly = dataclasses.replace(poly, domain_shift=dom.shift,
                                       domain_scale=dom.scale)
        if rep is not None:
            rep = dataclasses.replace(rep, coeffs=poly.coeffs)
        return FitResult(poly=poly, report=rep)


def _fit_search(x: jax.Array, y: jax.Array,
                weights: jax.Array | None, spec: FitSpec) -> FitResult:
    """DegreeSearch specs: single-pass selection (eager at the top — the
    winning degree is read back to slice the coefficients).  Under
    ``method="irls"`` the robust weights are established first by IRLS at
    the max candidate degree — where contamination hurts most — and the
    one-pass weighted ladder rides on top of them: degree search under
    robust loss, from spec reuse of the weighted moment path."""
    ds = spec.degree
    iterations = converged = None
    if spec.decay < 1.0:
        lad = _decay_ladder(x, spec.decay)
        weights = lad if weights is None else weights * lad
    if spec.method == "irls":
        fixed = dataclasses.replace(spec, degree=ds.max_degree, decay=1.0)
        rfit, w_final = robust_lib.irls_fit(x, y, weights, fixed)
        weights = w_final
        iterations, converged = rfit.iterations, rfit.converged
    pol = spec.numerics
    solver = pol.solver if pol.solver != "auto" else ds.solver
    dom = spec.domain_or(None, dtype=x.dtype)
    if dom is not None:
        xs = dom.apply(x)
        normalize_arg: bool | None = False
    else:
        xs = x
        normalize_arg = True if pol.normalize else None
    sel = select_lib.select_degree(
        xs, y, ds.max_degree, folds=ds.folds, criterion=ds.criterion,
        weights=weights, basis=spec.basis, normalize=normalize_arg,
        engine=spec.engine, solver=solver, fallback=ds.fallback,
        cond_cap=ds.cond_cap, accum_dtype=pol.accum_dtype,
        ridge=spec.ridge)
    poly = sel.poly
    if dom is not None:
        poly = dataclasses.replace(poly, domain_shift=dom.shift,
                                   domain_scale=dom.scale)
        sel = dataclasses.replace(sel, poly=poly)
    return FitResult(poly=poly, selection=sel, iterations=iterations,
                     converged=converged)


def fit(x: jax.Array, y: jax.Array, spec: FitSpec | None = None, *,
        weights: jax.Array | None = None) -> FitResult:
    """Executor 1: one eager/jit call, any spec.

    The fixed-degree paths are jitted with the spec as the static arg —
    two calls with equal specs share one executable, two different specs
    compile once each and then coexist (the serve no-recompile invariant,
    extended to the whole API).  DegreeSearch specs are eager at the top
    like ``polyfit(..., "auto")`` always was.

    The host side of each call is the profiler span ``api.fit``; its
    ``path`` is the plan's execution path for a fixed-degree LSE spec
    (the raw-data solver's name for one that skips the moments), else
    ``search``, ``irls`` or ``lspia``.  A fixed-degree LSE call resolves
    that path once per (spec, shape, dtype, weighted), and its answer
    holds a domain the spec fixes as device constants shared by every
    call (``dispatch_counter``)."""
    spec = FitSpec() if spec is None else spec
    if not isinstance(x, jax.Array):
        x = jnp.asarray(x)
    if not isinstance(y, jax.Array):
        y = jnp.asarray(y)
    if not spec.is_search and spec.method == "lse":
        return _fit_lse(x, y, weights, spec)
    path = "search" if spec.is_search else spec.method
    with _span("api.fit", path=path):
        if spec.is_search:
            return _fit_search(x, y, weights, spec)
        if spec.method == "irls":
            rfit, _ = robust_lib.irls_fit(x, y, weights, spec)
            return FitResult(poly=rfit.poly, iterations=rfit.iterations,
                             converged=rfit.converged)
        lf = lspia_lib.lspia_fit_spec(x, y, weights, None, spec)
        return FitResult(poly=lf.poly, iterations=lf.iterations,
                         converged=lf.converged)


# ------------------------------------------------------------ streaming
def stream_state(spec: FitSpec, batch: tuple[int, ...] = (), *,
                 dtype=None) -> streaming_lib.StreamState:
    """Executor 2 state: an O(1) ``StreamState`` wired to the spec.

    The accumulation degree is the spec's max degree (a DegreeSearch's
    whole ladder nests inside it) and a DegreeSearch's ``folds`` become
    chunk-round-robin CV partials.  A domain-normalizing spec must PIN
    the domain (``FitSpec(domain=(shift, scale))``): a stream cannot
    derive min/max from data it has not seen yet."""
    if spec.numerics.solver in RAW_DATA_SOLVERS:
        raise ValueError(
            f"solver={spec.numerics.solver!r} needs the raw Vandermonde "
            "rows; the streaming surface only holds moments")
    dtype = dtype or spec.numerics.accum_dtype or jnp.float32
    pol = spec.plan((8,), dtype, weighted=True).numerics
    if pol.normalize and spec.domain is None:
        raise ValueError(
            "this spec normalizes the domain (explicitly or by the "
            "numerics policy's high-degree escalation), but a stream "
            "cannot derive min/max from unseen data — pin it with "
            "FitSpec(domain=(shift, scale))")
    return streaming_lib.StreamState.create(
        spec.max_degree, batch, decay=spec.decay, dtype=dtype,
        cv_folds=spec.folds, spec=spec)


def stream_result(state: streaming_lib.StreamState) -> FitResult:
    """Read the spec's answer out of a running stream state: fixed-degree
    solve, moment-space LSPIA, or the scored degree ladder — all O(m²)
    work on the sufficient statistics, zero re-reads of the stream."""
    spec = state.spec
    if spec is None or (not spec.is_search and spec.method != "lspia"):
        poly = streaming_lib.current_fit(state)
        return FitResult(poly=poly, report=fit_lib.report_from_moments(
            state.moments, poly.coeffs))
    if spec.is_search:
        ds = spec.degree
        criterion = ds.criterion
        if criterion is None:
            criterion = "cv" if state.fold_moments is not None else "aicc"
        if criterion == "cv" and state.fold_moments is None:
            raise ValueError("criterion='cv' needs fold partials; create "
                             "the state via spec.streaming() with "
                             "DegreeSearch(folds >= 2)")
        solver = (spec.numerics.solver if spec.numerics.solver != "auto"
                  else ds.solver)
        m = state.moments.regularized(spec.ridge) if spec.ridge \
            else state.moments
        sweep = select_lib.sweep_from_moments(
            m, fold_moments=state.fold_moments,
            score_moments=state.moments if spec.ridge else None,
            solver=solver, fallback=ds.fallback, cond_cap=ds.cond_cap,
            basis=spec.basis, normalized=spec.domain is not None)
        dom = spec.domain_or(None, dtype=state.moments.gram.dtype)
        sel = select_lib.selection_from_sweep(
            sweep, criterion, domain=dom, basis=spec.basis, solver=solver,
            fallback=ds.fallback)
        # score the winner in its zero-padded ladder layout (padding
        # contributes nothing; the sliced poly.coeffs would not broadcast
        # against the full-width moment state)
        best = jnp.asarray(sel.best_degree)
        if best.ndim == 0:
            padded = sweep.coeffs[..., int(best), :]
        else:
            padded = jnp.take_along_axis(
                sweep.coeffs, best[..., None, None], axis=-2)[..., 0, :]
        return FitResult(poly=sel.poly, selection=sel,
                         report=fit_lib.report_from_moments(
                             state.moments, padded))
    # moment-space LSPIA: Richardson on the accumulated normal equations
    m = state.moments.regularized(spec.ridge) if spec.ridge \
        else state.moments
    opts = spec.lspia
    coeffs, cond, conv, it = lspia_lib.lspia_solve_moments(
        m.gram, m.vty, tol=opts.tol, max_iter=opts.max_iter,
        power_iters=opts.power_iters, step=opts.step,
        momentum=opts.momentum)
    diag = fit_lib.FitDiagnostics(condition=cond, fallback_used=~conv,
                                  solver="lspia", fallback="none")
    dom = spec.domain_or(basis_lib.Domain.identity(state.moments.gram.dtype),
                         dtype=state.moments.gram.dtype)
    poly = fit_lib.Polynomial(coeffs=coeffs, domain_shift=dom.shift,
                              domain_scale=dom.scale, basis=spec.basis,
                              diagnostics=diag)
    return FitResult(poly=poly,
                     report=fit_lib.report_from_moments(state.moments,
                                                        coeffs),
                     iterations=it, converged=conv)


# ---------------------------------------------------------- distributed
def make_distributed(spec: FitSpec, mesh: jax.sharding.Mesh, *,
                     data_axes: tuple[str, ...] = ("data",)):
    """Executor 3: ``fn(x, y, weights=None) -> FitResult`` on a mesh.

    Inputs are globally sharded over ``data_axes``; the result is fully
    replicated.  The heavy lifting (method dispatch, the single O(m²)
    collective, IRLS-with-psum, moment-space LSPIA, the fold-stack psum
    of a DegreeSearch) lives in ``core.distributed.make_spec_executor``.
    """
    import numpy as np
    runner, kind = distributed_lib.make_spec_executor(
        spec, mesh, data_axes=data_axes)
    ds = spec.degree if spec.is_search else None
    if ds is not None:
        criterion = ds.criterion or ("cv" if ds.folds >= 2 else "aicc")

    def run(x, y, weights=None) -> FitResult:
        out = runner(x, y, weights)
        if kind == "search":
            poly, sweep, best = out
            best_np = np.asarray(best)
            sel = select_lib.Selection(
                sweep=sweep,
                best_degree=(int(best_np) if best_np.ndim == 0 else best_np),
                criterion=criterion, poly=poly)
            return FitResult(poly=poly, selection=sel)
        if kind == "iter":
            poly, m, it, conv = out
            return FitResult(poly=poly,
                             report=fit_lib.report_from_moments(
                                 m, poly.coeffs),
                             iterations=it, converged=conv)
        poly, m = out
        return FitResult(poly=poly,
                         report=fit_lib.report_from_moments(m, poly.coeffs))

    return run
