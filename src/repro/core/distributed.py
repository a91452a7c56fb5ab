"""Distributed matricized LSE fitting — the paper's parallelization, pod-scale.

The paper parallelizes moment accumulation across CUDA threads on one GPU.
Here the same additive structure is mapped onto a TPU pod mesh with
``jax.shard_map``: every device accumulates the Gram/moment partials of its
local data shard, a single ``psum`` of O(m²) floats combines them across all
data axes (including the cross-pod ``"pod"`` axis — DCN traffic is ~(m+1)²
floats TOTAL, independent of n), and the tiny (m+1) solve runs replicated.

``make_spec_executor`` is the one factory: it consumes a ``repro.api``
``FitSpec`` and builds the jitted shard_map program for ANY method ×
degree question — plain LSE, IRLS (the reweighting loop runs the psum
inside ``while_loop``; every sweep is one O(m²) collective), moment-space
LSPIA (Richardson on the psum'd normal equations), and single-pass degree
search (one O(k·m²) fold-stack psum) — with weights/decay/NumericsPolicy
riding in from the spec.  ``make_distributed_fit`` / ``make_distributed_-
select`` are the legacy-signature shims that construct the spec.

This module is mesh-agnostic: pass the axis names that partition the data.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import basis as basis_lib
from repro.core import fit as fit_lib
from repro.core import moments as moments_lib
from repro.core import solve as solve_lib

def local_moments(x: jax.Array, y: jax.Array, degree: int, *,
                  basis: str = basis_lib.MONOMIAL,
                  weights: jax.Array | None = None,
                  accum_dtype=None,
                  engine: str = "auto",
                  use_kernel: bool | None = None) -> moments_lib.Moments:
    """Per-shard moment accumulation (runs inside shard_map).

    Routes through ``repro.engine.plan_fit``, which validates the basis on
    kernel paths — forcing the kernel with a non-monomial basis raises here
    instead of silently fitting the wrong rows (the Pallas kernel only
    builds monomial powers)."""
    from repro import engine as engine_lib
    plan = engine_lib.plan_fit(
        x.shape, degree, basis=basis, dtype=x.dtype,
        weighted=weights is not None,
        engine=engine_lib.resolve_engine(engine, use_kernel),
        accum_dtype=accum_dtype)
    return engine_lib.compute_moments(plan, x, y, weights)


def psum_moments(m: moments_lib.Moments, axis_names) -> moments_lib.Moments:
    """The one collective of the whole algorithm: O(m²) bytes."""
    return jax.tree.map(lambda a: jax.lax.psum(a, axis_names), m)


def _global_domain(x: jax.Array, w: jax.Array,
                   data_axes) -> basis_lib.Domain:
    """Global [-1, 1] domain over all shards (weighted min/max + pmin/pmax
    — the second tiny collective of a normalized distributed fit).
    Zero-weight entries are excluded; a degenerate zero range keeps the
    identity scale."""
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    lo = jax.lax.pmin(jnp.min(jnp.where(w > 0, x, big)), data_axes)
    hi = jax.lax.pmax(jnp.max(jnp.where(w > 0, x, -big)), data_axes)
    shift = (hi + lo) / 2.0
    half = (hi - lo) / 2.0
    scale = jnp.where(half > 0, 1.0 / jnp.where(half > 0, half, 1.0), 1.0)
    return basis_lib.Domain(shift, scale)


# --------------------------------------------------------------------------
# the spec executor: every method × degree question, one shard_map factory
# --------------------------------------------------------------------------
def make_spec_executor(spec, mesh: jax.sharding.Mesh, *,
                       data_axes: tuple[str, ...] = ("data",)):
    """Build the jitted mesh program for a ``FitSpec``.

    Returns ``(runner, kind)``: ``runner(x, y, weights)`` takes globally
    sharded inputs and returns fully replicated outputs whose shape
    ``kind`` names —

    * ``"fixed"``:  ``(poly, moments)``                (method="lse")
    * ``"iter"``:   ``(poly, moments, iters, conv)``   (irls / lspia)
    * ``"search"``: ``(poly, sweep, best_degree)``     (DegreeSearch)

    ``repro.api.make_distributed`` wraps the tuple into a ``FitResult``;
    the legacy ``make_distributed_fit``/``_select`` shims return it raw.
    """
    from repro import select as select_lib
    from repro.core import robust as robust_lib
    from repro.select import crossval

    from repro.api import spec as spec_lib
    if spec.numerics.solver in spec_lib.RAW_DATA_SOLVERS:
        raise ValueError(
            f"solver={spec.numerics.solver!r} needs the raw Vandermonde "
            "rows and cannot run on the distributed moment surface; use "
            "the eager api.fit executor")
    search = spec.is_search
    md = spec.max_degree
    folds = spec.folds if search else 0
    accum = spec.numerics.accum_dtype
    # eager validation + numerics resolution (per-shard n is unknown, so
    # plan with a placeholder length: the path choice is re-made per shard
    # inside local_moments; the numerics policy IS resolved here, once)
    plan = spec.plan((max(folds, 1), 1) if search else (1,),
                     accum or jnp.float32, weighted=True,
                     workload="select" if search else "moments",
                     mesh=mesh, data_axes=data_axes)
    pol = plan.numerics
    normalized = pol.normalize or spec.domain is not None
    if search:
        ds = spec.degree
        criterion = ds.criterion
        if criterion is None:
            criterion = "cv" if folds >= 2 else "aicc"
        if criterion == "cv" and folds < 2:
            raise ValueError("criterion='cv' needs folds >= 2")
        ladder_solver = (spec.numerics.solver
                         if spec.numerics.solver != "auto" else ds.solver)
        ladder_fb, ladder_cap = ds.fallback, ds.cond_cap
    spec_in = P(data_axes)
    spec_rep = P()

    def shard_domain(x, w):
        pinned = spec.domain_or(None, dtype=x.dtype)
        if pinned is not None:
            return pinned
        if pol.normalize:
            return _global_domain(x, w, data_axes)
        return basis_lib.Domain.identity(x.dtype)

    devices_total = 1
    for ax in data_axes:
        devices_total *= mesh.shape[ax]

    def apply_decay(x, w):
        """spec.decay as the GLOBAL age ladder: each shard reconstructs
        its points' global positions from its mesh coordinates (shards of
        a P(data_axes)-sharded array are laid out row-major over the data
        axes), so the γ-weighting is identical to the eager surface's
        ``decay_ladder`` over the unsharded series."""
        if spec.decay == 1.0:
            return w
        pos = 0
        for ax in data_axes:
            pos = pos * mesh.shape[ax] + jax.lax.axis_index(ax)
        n_local = x.shape[-1]
        n_global = n_local * devices_total
        age = (n_global - 1
               - (pos * n_local + jnp.arange(n_local)).astype(x.dtype))
        return w * jnp.asarray(spec.decay, x.dtype) ** age

    def gmoments(xt, y, w):
        """One global accumulation: local shard moments + the psum."""
        return psum_moments(
            local_moments(xt, y, md, basis=spec.basis, weights=w,
                          accum_dtype=accum, engine=spec.engine),
            data_axes)

    def solve(m):
        ms = m.regularized(spec.ridge) if spec.ridge else m
        return solve_lib.solve_with_fallback(
            ms.gram, ms.vty, method=pol.solver, fallback=pol.fallback,
            cond_cap=pol.cond_cap)

    def mk_poly(coeffs, dom, diag):
        return fit_lib.Polynomial(coeffs=coeffs, domain_shift=dom.shift,
                                  domain_scale=dom.scale, basis=spec.basis,
                                  diagnostics=diag)

    def irls_weights_loop(xt, y, w):
        """The IRLS loop, mesh-wide: every sweep is one O(m²) psum; the
        convergence test runs on the replicated coefficients, so every
        device takes the same trip count.  The robust scale is the
        contributing-shard mean of per-shard MADs (an exact global median
        would need its own iterative collective; on shuffled shards the
        shard MADs agree to O(1/√n_shard))."""
        opts = spec.irls
        cval = robust_lib.resolve_tuning(opts.loss, opts.c)
        tol = max(float(opts.tol),
                  500.0 * float(jnp.finfo(xt.dtype).eps))

        def sigma_of(coeffs):
            r = y - basis_lib.evaluate(coeffs, xt, basis=spec.basis)
            sig = robust_lib.chunk_scale(r, w, y)[..., 0]
            has = jnp.any(w > 0).astype(xt.dtype)
            num = jax.lax.psum(sig * has, data_axes)
            den = jnp.maximum(jax.lax.psum(has, data_axes), 1.0)
            return r, (num / den)[..., None]

        def reweight(coeffs):
            r, sigma = sigma_of(coeffs)
            return robust_lib.robust_weights(r / sigma, opts.loss, cval) * w

        m0 = gmoments(xt, y, w)
        coeffs0, cond0, used0 = solve(m0)
        big = jnp.asarray(jnp.inf, xt.dtype)

        def cond_fn(carry):
            _, _, _, _, delta, it = carry
            return (it < opts.max_iter) & jnp.any(delta > tol)

        def body_fn(carry):
            coeffs, _, _, _, _, it = carry
            m = gmoments(xt, y, reweight(coeffs))
            new, cond, used = solve(m)
            scale = jnp.maximum(jnp.max(jnp.abs(new), axis=-1), 1.0)
            delta = jnp.max(jnp.abs(new - coeffs), axis=-1) / scale
            return new, cond, used, m, delta, it + 1

        init = (coeffs0, cond0, used0, m0,
                jnp.full(xt.shape[:-1], big), jnp.zeros((), jnp.int32))
        coeffs, cond, used, m, delta, it = jax.lax.while_loop(
            cond_fn, body_fn, init)
        return coeffs, cond, used, m, reweight(coeffs), delta <= tol, it

    # ------------------------------------------------------------ programs
    if search:
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(spec_in, spec_in, spec_in),
                 out_specs=(spec_rep, spec_rep, spec_rep), check_vma=False)
        def _run(x, y, w):
            w = apply_decay(x, w)
            dom = shard_domain(x, w)
            xt = dom.apply(x)
            if spec.method == "irls":
                # robust weights established mesh-wide at max_degree, then
                # the usual single-pass weighted ladder on top of them
                _, _, _, _, w_eff, _, _ = irls_weights_loop(xt, y, w)
            else:
                w_eff = w
            if folds >= 2:
                fm = crossval.fold_moments(xt, y, folds, md, weights=w_eff,
                                           basis=spec.basis,
                                           engine=spec.engine,
                                           accum_dtype=accum)
                fm = psum_moments(fm, data_axes)  # folds global: O(k·m²)
                total = crossval.sum_folds(fm)
            else:
                fm = None
                total = gmoments(xt, y, w_eff)
            mr = total.regularized(spec.ridge) if spec.ridge else total
            sweep = select_lib.sweep_from_moments(
                mr, fold_moments=fm,
                score_moments=total if spec.ridge else None,
                solver=ladder_solver,
                fallback=ladder_fb, cond_cap=ladder_cap, basis=spec.basis,
                normalized=normalized)
            best = sweep.best(criterion)
            # winning fit in the padded ladder layout (best is traced, so
            # the static-shape slice of selection_from_sweep is
            # unavailable) — crucially WITH its Domain, so raw-x
            # evaluation is correct
            diag = fit_lib.FitDiagnostics(
                condition=jnp.take(sweep.condition, best, axis=-1),
                fallback_used=jnp.take(sweep.fallback_used, best, axis=-1),
                solver=ladder_solver, fallback=ladder_fb or "none")
            poly = mk_poly(jnp.take(sweep.coeffs, best, axis=-2), dom, diag)
            return poly, sweep, best

    elif spec.method == "irls":
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(spec_in, spec_in, spec_in),
                 out_specs=(spec_rep, spec_rep, spec_rep, spec_rep),
                 check_vma=False)
        def _run(x, y, w):
            w = apply_decay(x, w)
            dom = shard_domain(x, w)
            xt = dom.apply(x)
            coeffs, cond, used, m, _, conv, it = irls_weights_loop(xt, y, w)
            diag = fit_lib.FitDiagnostics(
                condition=cond, fallback_used=used, solver=pol.solver,
                fallback=pol.fallback or "none")
            return mk_poly(coeffs, dom, diag), m, it, conv

    elif spec.method == "lspia":
        from repro.core import lspia as lspia_lib

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(spec_in, spec_in, spec_in),
                 out_specs=(spec_rep, spec_rep, spec_rep, spec_rep),
                 check_vma=False)
        def _run(x, y, w):
            # the distributed surface already pays the O(m²) psum, so the
            # fixed point is reached by Richardson on the psum'd normal
            # equations (the moment-space LSPIA) — matrix-free sweeps
            # would cost one collective per iteration instead of one total
            w = apply_decay(x, w)
            dom = shard_domain(x, w)
            xt = dom.apply(x)
            m = gmoments(xt, y, w)
            ms = m.regularized(spec.ridge) if spec.ridge else m
            opts = spec.lspia
            coeffs, cond, conv, it = lspia_lib.lspia_solve_moments(
                ms.gram, ms.vty, tol=opts.tol, max_iter=opts.max_iter,
                power_iters=opts.power_iters, step=opts.step,
                momentum=opts.momentum)
            diag = fit_lib.FitDiagnostics(condition=cond,
                                          fallback_used=~conv,
                                          solver="lspia", fallback="none")
            return mk_poly(coeffs, dom, diag), m, it, conv

    else:
        # plain matricized LSE — the paper's algorithm, pod-scale
        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(spec_in, spec_in, spec_in),
                 out_specs=(spec_rep, spec_rep), check_vma=False)
        def _run(x, y, w):
            w = apply_decay(x, w)
            dom = shard_domain(x, w)
            xt = dom.apply(x)
            m = gmoments(xt, y, w)
            ms = m.regularized(spec.ridge) if spec.ridge else m
            poly = fit_lib.fit_from_moments(ms, solver=pol.solver,
                                            fallback=pol.fallback,
                                            cond_cap=pol.cond_cap,
                                            domain=dom, basis=spec.basis,
                                            normalized=normalized)
            return poly, m

    def entry(x: jax.Array, y: jax.Array, weights: jax.Array | None = None):
        if weights is None:
            weights = jnp.ones_like(x)
        return _run(x, y, weights)

    kind = ("search" if search
            else "iter" if spec.method in ("irls", "lspia") else "fixed")
    return jax.jit(entry), kind


# --------------------------------------------------------------------------
# legacy-signature shims — construct a FitSpec, run the spec executor
# --------------------------------------------------------------------------
def make_distributed_fit(mesh: jax.sharding.Mesh, degree: int, *,
                         data_axes: tuple[str, ...] = ("data",),
                         method: str | None = None,
                         solver: str = "auto",
                         fallback: str | None = "svd",
                         basis: str = basis_lib.MONOMIAL,
                         normalize: bool = False,
                         accum_dtype=jnp.float32,
                         engine: str = "auto",
                         use_kernel: bool | None = None):
    """Build a jitted distributed fit: (x, y, weights) -> (Polynomial,
    Moments).  Thin shim over ``make_spec_executor`` — the kwargs
    assemble a ``FitSpec(method="lse")``.

    x, y, weights are globally sharded over ``data_axes``; weights masks
    padding (ragged global datasets). Polynomial comes out fully replicated.
    normalize=True computes the global min/max first (second tiny collective)
    and fits in the normalized domain.  ``use_kernel`` is a deprecated
    alias of ``engine=``; ``method=`` the legacy spelling of ``solver=``.
    """
    from repro import engine as engine_lib
    from repro.api import spec as spec_lib
    from repro.engine import plan as plan_lib
    engine = engine_lib.resolve_engine(engine, use_kernel)
    if method is not None:
        solver = method
    spec = spec_lib.FitSpec(
        degree=int(degree), basis=basis, method="lse",
        numerics=plan_lib.NumericsPolicy(accum_dtype=accum_dtype,
                                         normalize=normalize, solver=solver,
                                         fallback=fallback),
        engine=engine)
    runner, _ = make_spec_executor(spec, mesh, data_axes=data_axes)
    return runner


def make_distributed_select(mesh: jax.sharding.Mesh, max_degree: int, *,
                            folds: int = 5,
                            data_axes: tuple[str, ...] = ("data",),
                            criterion: str | None = None,
                            solver: str = "auto",
                            fallback: str | None = "svd",
                            cond_cap: float | None = None,
                            basis: str = basis_lib.MONOMIAL,
                            normalize: bool = False,
                            accum_dtype=jnp.float32,
                            engine: str = "auto"):
    """Mesh-parallel single-pass degree selection: (x, y, weights) ->
    (poly, sweep, best_degree), all fully replicated.  Thin shim over
    ``make_spec_executor`` — the kwargs assemble a
    ``FitSpec(degree=DegreeSearch(...))``.

    Each shard accumulates its local k-fold moment partials (round-robin
    within the shard — fold membership is an arbitrary partition, so local
    assignment is a valid global one) and ONE psum of the (k, m+1, m+1)
    fold stack makes the folds global: selection's collective cost is
    O(k·m²) floats, independent of n.  The ladder solve + scoring then run
    replicated on every device, so the chosen degree is identical
    mesh-wide with no extra synchronization.  ``folds < 2`` drops CV (one
    plain psum'd state; AICc/BIC/GCV still select).

    ``poly`` is the winning fit in the zero-padded (max_degree+1) layout
    (the chosen degree is data-dependent, hence not a static shape) and
    carries its Domain, so evaluating it on raw x is correct even when
    normalization mapped the fit to [-1, 1]; ``sweep.coeffs`` live in that
    same fitted domain/basis.
    """
    from repro import select as select_lib
    from repro.api import spec as spec_lib
    from repro.engine import plan as plan_lib
    spec = spec_lib.FitSpec(
        degree=select_lib.DegreeSearch(max_degree=int(max_degree),
                                       folds=int(folds),
                                       criterion=criterion, solver=solver,
                                       fallback=fallback,
                                       cond_cap=cond_cap),
        basis=basis, method="lse",
        numerics=plan_lib.NumericsPolicy(accum_dtype=accum_dtype,
                                         normalize=normalize,
                                         solver="auto", fallback=fallback,
                                         cond_cap=cond_cap),
        engine=engine)
    runner, _ = make_spec_executor(spec, mesh, data_axes=data_axes)
    return runner


def distributed_fit_input_specs(n_global: int, dtype=jnp.float32):
    """ShapeDtypeStruct stand-ins for the dry-run of the fit itself."""
    s = jax.ShapeDtypeStruct((n_global,), dtype)
    return dict(x=s, y=s, weights=s)


# --------------------------------------------------------------------------
# asynchronous LSPIA: barrier-free shard contributions (arXiv:2211.06556)
# --------------------------------------------------------------------------
#
# The shard_map executor above is a BARRIER program: every Richardson sweep
# waits for the slowest shard's psum.  The asynchronous-LSPIA result says it
# does not have to — gradient contributions computed against *stale*
# coefficient versions still drive the iteration to the same least-squares
# fixed point as long as the staleness is bounded.  This section realizes
# that on the fleet's virtual-tick mailbox substrate: one coordinator, N
# ``AsyncLSPIAShard`` workers (each wrappable by ``runtime.chaos``'s
# ``ChaosWorker`` — same protocol as ``serve.fleet``'s workers), per-shard
# sequence numbers for idempotent delivery, and a staleness window outside
# which a shard's delta is rejected and recomputed.  A chaos-stalled shard
# therefore delays CONVERGENCE (its contribution is missing until it
# catches up) but never blocks the coordinator's updates — the property
# the synchronous psum program cannot have.


@dataclasses.dataclass
class ShardSweep:
    """Coordinator → shard: "compute your normal-equation gradient against
    these version-``version`` coefficients".  ``seq`` is the per-shard
    sequence number (idempotent delivery: the coordinator accepts exactly
    one reply per outstanding seq).  ``kind="ingest"`` so the chaos
    injector's drop fault hits sweeps exactly as it hits fleet ingests."""

    shard: int
    seq: int
    version: int
    coeffs: np.ndarray
    kind: str = "ingest"


@dataclasses.dataclass
class ShardDelta:
    """Shard → coordinator: gᵢ = VᵢᵀWᵢ(yᵢ − Vᵢ c_version), stamped with
    the coefficient version it was computed against.  ``kind="result"``
    so the chaos poison fault can corrupt it (and the coordinator's
    finite-validation must catch that)."""

    shard: int
    seq: int
    version: int
    delta: np.ndarray
    worker: int = 0
    kind: str = "result"

    def poisoned(self) -> "ShardDelta":
        return dataclasses.replace(
            self, delta=np.full_like(self.delta, np.nan))


@partial(jax.jit, static_argnames=("degree", "basis"))
def _shard_gradient(xt, y, w, c, degree, basis):
    from repro.core import lspia as lspia_lib
    f = basis_lib.evaluate(c, xt, basis=basis)
    return lspia_lib.vt_apply(xt, w * (y - f), degree, basis=basis)


class AsyncLSPIAShard:
    """One data shard speaking the fleet mailbox protocol (``process(msg,
    tick) -> [reply]`` / ``reset()``), so ``runtime.chaos.ChaosWorker``
    wraps it unchanged.  Stateless between sweeps — the shard's partition
    IS its identity — so a chaos crash + revive loses nothing but the
    in-flight sweep (which the coordinator's retry resends)."""

    def __init__(self, shard_id: int, xt, y, w, degree: int, basis: str):
        self.shard_id = shard_id
        self._xt, self._y, self._w = xt, y, w
        self._degree, self._basis = degree, basis
        self.sweeps_done = 0

    def reset(self) -> None:
        self.sweeps_done = 0

    def process(self, msg, tick: int) -> list:
        if getattr(msg, "kind", None) != "ingest":
            return []
        c = jnp.asarray(msg.coeffs, self._xt.dtype)
        g = _shard_gradient(self._xt, self._y, self._w, c,
                            self._degree, self._basis)
        self.sweeps_done += 1
        return [ShardDelta(shard=self.shard_id, seq=msg.seq,
                           version=msg.version, delta=np.asarray(g),
                           worker=self.shard_id)]


@dataclasses.dataclass
class AsyncLSPIAFit:
    """An asynchronous LSPIA fit: polynomial + the coordinator's record.

    ``iterations`` counts coefficient versions applied (the async analogue
    of sweeps); ``stats`` surfaces every fault-path event — stale
    rejections, poisoned deltas, resends, straggler verdicts and the
    ``runtime.straggler`` reslice plan they imply, and crucially
    ``updates_during_stall``: coordinator updates applied while at least
    one shard was chaos-stalled (the no-global-barrier property, > 0 in
    any stalled run that converged)."""

    poly: fit_lib.Polynomial
    iterations: int
    ticks: int
    converged: bool
    grad_norm: float
    step: float
    stats: dict
    metrics: object | None = None   # the run's obs.MetricsRegistry


def async_lspia_fit(x, y, spec, *, n_shards: int = 4,
                    weights=None, chaos=None,
                    work_per_tick: int = 1,
                    max_ticks: int = 200_000,
                    retry_ticks: int = 8,
                    restart_ticks: int = 8,
                    straggler_every: int = 4,
                    straggler_threshold: float = 3.0,
                    registry=None) -> AsyncLSPIAFit:
    """Barrier-free distributed LSPIA on the virtual-tick mailbox substrate.

    ``spec`` must be ``FitSpec(method="lspia")``; its ``LSPIAOptions``
    supply tol / max-iteration budget / ``momentum`` (heavy-ball on the
    coordinator's updates) and ``staleness`` — the bounded-delay window of
    the asynchronous convergence result: a delta computed more than
    ``staleness`` coefficient versions ago is rejected (and excluded from
    the accumulated gradient until its shard refreshes), and convergence
    is only declared when the combined gradient is small AND every shard's
    contribution is within the window.  The coordinator's step is the
    synchronous safe step damped by the staleness bound
    (μ = μ_sync / (1 + s/2), the classic delayed-gradient stability
    margin), with the same divergence freeze guard as the eager path.

    ``chaos`` takes a ``runtime.chaos.ChaosSchedule``; every fault kind
    applies (sweeps are droppable "ingest"s, deltas poisonable "result"s,
    shards stall/crash/delay like fleet workers).  Straggler verdicts come
    from ``runtime.fault_tolerance.FailureDetector`` — the paper's own LSE
    fitting per-shard reply gaps — and each verdict is answered with a
    ``runtime.straggler.plan_reslice`` share plan in ``stats["reslice"]``.

    Requires ``spec.decay == 1.0``: asynchronous delivery has no global
    age order, so exponential forgetting is not defined on this surface.
    """
    from repro.core import lspia as lspia_lib
    from repro.runtime import chaos as chaos_lib
    from repro.runtime import straggler as straggler_lib
    from repro.runtime.fault_tolerance import FailureDetector

    if spec.method != "lspia":
        raise ValueError(f"async_lspia_fit needs method='lspia', got "
                         f"{spec.method!r}")
    if spec.is_search:
        raise ValueError("async_lspia_fit serves fixed degrees; run "
                         "DegreeSearch on the moment surfaces")
    if spec.decay != 1.0:
        raise ValueError(
            "async delivery has no global age order: decay must be 1.0 "
            f"(got {spec.decay})")
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if x.ndim != 1 or x.shape != y.shape:
        raise ValueError(f"expected equal 1-D x/y, got {x.shape} vs "
                         f"{y.shape}")
    if x.shape[0] < n_shards:
        raise ValueError(f"{x.shape[0]} points cannot fill {n_shards} "
                         "shards")
    degree = int(spec.degree)
    basis = spec.basis
    opts = spec.lspia
    staleness = int(opts.staleness)
    beta = float(opts.momentum)
    ridge = float(spec.ridge)
    w = (jnp.ones_like(x) if weights is None
         else jnp.asarray(weights, x.dtype))
    plan = spec.plan(x.shape, x.dtype, weighted=weights is not None,
                     workload="lspia")
    dom = spec.domain_or(
        basis_lib.Domain.from_data(x) if plan.numerics.normalize
        else basis_lib.Domain.identity(x.dtype), dtype=x.dtype)
    xt = dom.apply(x)

    # safe synchronous step (same settledness-gated trace clamp as the
    # eager path), then the bounded-delay damping
    tiny = float(jnp.finfo(x.dtype).tiny)
    lam, lam_prev = lspia_lib._lambda_max(xt, w, degree, basis,
                                          opts.power_iters, with_prev=True)
    lam = float(lam) + ridge
    tr_ub = float(lspia_lib._trace_normal(xt, w, degree, basis)) \
        + ridge * (degree + 1)
    settled = abs(lam - (float(lam_prev) + ridge)) <= 0.05 * lam
    lam_safe = lam if settled else max(lam, tr_ub)
    mu_sync = (1.0 / max(lam_safe, tiny) if opts.step is None
               else float(opts.step))
    mu = mu_sync / (1.0 + 0.5 * staleness)

    bvec = np.asarray(lspia_lib.vt_apply(xt, w * y, degree, basis=basis),
                      # reprolint: disable=RL-DTYPE — f64 LSPIA iterate
                      np.float64)
    gref = max(float(np.linalg.norm(bvec)), tiny)
    tol = max(float(opts.tol), 25.0 * float(jnp.finfo(x.dtype).eps))
    cap = lspia_lib._DIVERGE_FACTOR * gref

    bounds = np.linspace(0, x.shape[0], n_shards + 1).astype(int)
    schedule = chaos or chaos_lib.ChaosSchedule()
    workers = [
        chaos_lib.ChaosWorker(
            AsyncLSPIAShard(i, xt[bounds[i]:bounds[i + 1]],
                            y[bounds[i]:bounds[i + 1]],
                            w[bounds[i]:bounds[i + 1]], degree, basis),
            i, schedule.for_worker(i))
        for i in range(n_shards)]
    detector = FailureDetector(n_shards, timeout_s=float(max_ticks),
                               straggler_threshold=straggler_threshold)

    m1 = degree + 1
    c = np.zeros(m1, np.float64)  # reprolint: disable=RL-DTYPE — f64 iterate
    c_prev = c.copy()
    version = 0
    latest: list[np.ndarray | None] = [None] * n_shards
    latest_version = [-1] * n_shards
    next_seq = [0] * n_shards
    # outstanding[i] = (seq, sent_tick) of the sweep awaiting a reply
    outstanding: list[tuple[int, int] | None] = [None] * n_shards
    inbox: list[list] = [[] for _ in range(n_shards)]
    due: list[tuple[int, int, ShardDelta]] = []
    due_n = 0
    last_reply = [0] * n_shards
    died_at: dict[int, int] = {}
    gnorm = gref
    gprev = float("inf")
    # counters live in an obs registry (caller-supplied to share one
    # scrape surface, else private); the returned ``stats`` dict is a
    # view over it plus the non-counter records below
    from repro.obs import metrics as obs_metrics
    reg = registry if registry is not None else obs_metrics.MetricsRegistry()
    ctr = {k: reg.counter(k) for k in
           ("updates", "updates_during_stall", "stale_rejected",
            "poisoned", "resends", "duplicates", "crashes", "freezes")}
    lag_gauge = reg.gauge("staleness_lag")   # hwm = worst in-window lag
    straggler_verdicts: list = []
    reslice = None
    converged = False
    tick = 0

    def send_sweep(i: int) -> None:
        if len(inbox[i]) >= 4:      # bounded mailbox: a stalled shard's
            return                  # queue must not grow without limit
        next_seq[i] += 1
        outstanding[i] = (next_seq[i], tick)
        inbox[i].append(ShardSweep(shard=i, seq=next_seq[i],
                                   version=version, coeffs=c.copy()))

    while tick < max_ticks and not converged:
        tick += 1
        for i, wk in enumerate(workers):
            wk.begin_tick(tick)
            if not wk.alive and i not in died_at:
                died_at[i] = tick
                ctr["crashes"].inc()
            if not wk.alive and tick - died_at.get(i, tick) >= \
                    restart_ticks:
                wk.revive()
                inbox[i].clear()
                outstanding[i] = None
                del died_at[i]
        stalled_now = any(wk.stalled(tick) for wk in workers)
        # pump shard mailboxes (a stalled shard heartbeats but computes
        # nothing — its inbox just waits)
        for i, wk in enumerate(workers):
            if not wk.alive or wk.stalled(tick):
                continue
            for _ in range(work_per_tick):
                if not inbox[i]:
                    break
                msg = inbox[i].pop(0)
                for delay, rep in wk.process(msg, tick):
                    due.append((tick + delay, due_n, rep))
                    due_n += 1
        # deliver due replies
        due.sort()
        fresh = False
        while due and due[0][0] <= tick:
            _, _, rep = due.pop(0)
            i = rep.shard
            out = outstanding[i]
            if out is None or rep.seq != out[0]:
                ctr["duplicates"].inc()
                continue
            outstanding[i] = None
            last_reply[i] = tick
            if not np.all(np.isfinite(rep.delta)):
                ctr["poisoned"].inc()       # chaos poison: recompute
                continue
            if version - rep.version > staleness:
                ctr["stale_rejected"].inc()     # outside the bounded-
                continue                        # delay window: recompute
            # reprolint: disable=RL-DTYPE — deltas join the f64 iterate
            latest[i] = np.asarray(rep.delta, np.float64)
            latest_version[i] = rep.version
            fresh = True
        # staleness-bounded accumulation: only in-window contributions
        # enter the combined gradient (a stalled shard's ancient delta
        # must not keep steering the iterate)
        in_window = [i for i in range(n_shards)
                     if latest[i] is not None
                     and version - latest_version[i] <= staleness]
        # worst version lag among contributing shards (hwm = worst seen):
        # the live "how stale is the slowest voice in the gradient" gauge
        if in_window:
            lag_gauge.set(max(version - latest_version[i]
                              for i in in_window))
        if fresh and in_window:
            gsum = sum(latest[i] for i in in_window) - ridge * c
            gn = float(np.linalg.norm(gsum))
            if not np.isfinite(gn) or gn > cap:
                ctr["freezes"].inc()    # divergence freeze, as eager
            else:
                upd = c + mu * gsum + beta * (c - c_prev)
                c_prev, c = c, upd
                version += 1
                gprev, gnorm = gnorm, gn
                ctr["updates"].inc()
                if stalled_now:
                    ctr["updates_during_stall"].inc()
        # convergence: small combined gradient AND every shard current
        if (len(in_window) == n_shards and gnorm <= tol * gref
                and ctr["updates"].value > 0):
            converged = True
            break
        # refill / retry sweeps
        for i in range(n_shards):
            out = outstanding[i]
            if out is None:
                send_sweep(i)
            elif tick - out[1] > retry_ticks:
                ctr["resends"].inc()    # dropped/lost sweep: resend with
                send_sweep(i)           # a fresh seq (old reply ignored)
        # straggler verdicts from the paper's own LSE on reply gaps
        if tick % straggler_every == 0:
            gaps = [float(max(1, tick - last_reply[i]))
                    for i in range(n_shards)]
            detector.observe_step(tick // straggler_every, gaps,
                                  now=float(tick))
            v = detector.verdict(tick // straggler_every, now=float(tick))
            if v["stragglers"]:
                straggler_verdicts.append(
                    (tick, tuple(v["stragglers"])))
                try:
                    reslice = straggler_lib.plan_reslice(
                        detector.steptime, tick // straggler_every,
                        int(x.shape[0]), min_share=1).shares
                except ValueError:
                    pass

    if ctr["updates"].value >= 2 and gprev > 0 and np.isfinite(gprev):
        rho = gnorm / gprev
    else:
        rho = 0.0
    lam_mu = lam_safe * mu
    cond = (float("inf") if rho >= 1.0
            else max(lam_mu / (1.0 - rho), 1.0))
    stats = {"n_shards": n_shards, "staleness": staleness,
             **{k: c.value for k, c in ctr.items()},
             "straggler_verdicts": straggler_verdicts, "reslice": reslice,
             "sweeps_per_shard": [wk.inner.sweeps_done for wk in workers]}
    dtype = x.dtype
    diag = fit_lib.FitDiagnostics(
        condition=jnp.asarray(cond, dtype),
        fallback_used=jnp.asarray(not converged),
        solver="lspia", fallback="none")
    poly = fit_lib.Polynomial(coeffs=jnp.asarray(c, dtype),
                              domain_shift=dom.shift,
                              domain_scale=dom.scale, basis=basis,
                              diagnostics=diag)
    return AsyncLSPIAFit(poly=poly, iterations=version, ticks=tick,
                         converged=converged, grad_norm=gnorm, step=mu,
                         stats=stats, metrics=reg)
