"""Continuous-batching fit server: the paper's workload as a service.

Ragged per-request (x, y) series arrive, are bucketed by length onto
fixed-width slot pools, and ingest through the
matricized moment accumulator (packed P-series-per-tile Pallas kernel on
TPU, via ``repro.engine`` plan dispatch) with per-slot streaming
``StreamState`` — so a million-point series occupies one slot and folds in
chunk-by-chunk while short requests churn through the other slots.

vLLM-style static shapes: every bucket owns ONE compiled fused
ingest+solve executable of shape (n_slots, width) — on any step where a
request completes, the chunk accumulates into the slots' moments AND the
pool's default fixed spec is solved in the same program, so the Gram goes
matmul→solve without an HBM round-trip or a second host dispatch.
Mid-series steps (no completion — only the widest bucket ever takes
them) dispatch a plain ingest instead, skipping the wasted solve.  Both
are warmed once and reused across arbitrary request churn.  Padding rides in with weight 0 (contributes
nothing, by the additive-moments property), slot reuse zeroes the slot's
moments with a keep-mask inside the same compiled step, and per-slot IRLS
robustness is selected by RUNTIME mask/loss/c arrays — so request
arrival/departure, solver policy, and loss mix never change a shape and
never recompile.  ``compiled_executables()`` exposes the counter the serve
benchmark asserts on.

Requests carry their own ``repro.api.FitSpec`` (``submit(x, y,
spec=...)``): the solve side — solver/fallback/cond_cap ladder, ridge,
method (LSE / moment-space LSPIA), fixed degree ≤ the pool's (served from
the ``Moments.truncate`` view), or a DegreeSearch over the nested ladder —
is honored PER REQUEST.  Each distinct spec compiles its solve executable
once (the spec is the jit static arg) and coexists with every other spec
from then on: the no-recompile invariant keyed on spec identity.  The
accumulation side (basis, engine path, decay, pinned domain, max degree)
is necessarily pool-wide — it is baked into the slots' running moments —
and comes from ``FitServeConfig`` (or its ``spec=``).

Each bucket dispatch moves one array each way: ``_pack`` writes the
chunk's x, y and weights and the per-slot keep/IRLS vectors into ONE
float32 host buffer, copied to the device in one call and split inside
the compiled step (``split_step_buffer``), and the fused step returns the
default spec's six answers as ONE packed array (``pack_solved``), copied
back in one call and split on the host (``unpack_solved``).  A small
host-device copy costs the same whatever its size, so the step pays that
cost twice, not thirteen times; ``h2d_copies`` / ``d2h_copies`` count the
copy calls.

The host loop is deliberately synchronous/deterministic — the scheduling
substrate an async front-end would wrap.  Each step writes spans on the
JAX profiler's clock, the clock of the device trace: ``fit_engine.step``
and, per bucket, ``fit_engine.pack`` → ``put`` → ``launch`` →
``collect``.  With no profiler session active a span costs the entry and
exit of an inactive object; under ``jax.profiler.trace`` each idle gap of
the chip lines up with the host phase that kept it waiting.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import select as select_lib
from repro import obs as obs_lib
from repro.core import basis as basis_lib
from repro.core import fit as fit_lib
from repro.core import lspia as lspia_lib
from repro.core import moments as moments_lib
from repro.core import robust as robust_lib
from repro.core import solve as solve_lib
from repro.core import streaming

_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class FitRequest:
    """One fit job: a ragged series in, a polynomial + quality report out.

    ``spec`` is the request's ``FitSpec`` (the engine's default when the
    legacy ``degree=`` spelling was used).  DegreeSearch specs
    (``auto=True``) come back with the *chosen* degree plus the whole
    scored ladder: ``degree`` is the winner under the spec's criterion,
    ``scores`` maps each criterion name to its per-degree row, and
    ``condition_ladder`` carries κ(truncated Gram) per candidate degree —
    the response diagnostics of single-pass model selection."""

    uid: int
    x: np.ndarray                      # (n,) host-side series
    y: np.ndarray
    spec: Any = None                   # the request's FitSpec
    auto: bool = False                 # automatic degree selection requested
    coeffs: np.ndarray | None = None   # (degree+1,) when done
    sse: float | None = None
    r: float | None = None
    count: float | None = None         # points the fit actually used
    condition: float | None = None     # estimated κ(Gram) at solve time
    fallback_used: bool | None = None  # rescue solver produced the coeffs
    degree: int | None = None          # chosen degree (auto requests)
    scores: dict | None = None         # per-degree criterion rows (auto)
    condition_ladder: np.ndarray | None = None   # per-degree κ (auto)
    done: bool = False

    @property
    def n(self) -> int:
        return int(self.x.shape[0])


@dataclasses.dataclass(frozen=True)
class FitServeConfig:
    degree: int = 3                     # pool accumulation degree AND the
    # ceiling for per-request degrees / DegreeSearch ladders
    n_slots: int = 8                    # concurrent series per bucket
    buckets: tuple[int, ...] = (256, 2048)   # chunk widths, ascending
    solver: str = "auto"                # condition-aware solve (core.solve)
    fallback: str | None = "svd"        # rank-revealing rescue (None = off)
    method: str | None = None           # legacy spelling of solver=
    ridge: float = 1e-9                 # λI stabilizer for the pooled solve
    # (idle slots hold all-zero moments and degenerate series are accepted,
    # so the pooled solve must never be exactly singular)
    decay: float = 1.0                  # exponential forgetting (γ=1: off);
    # γ<1 assumes full chunks (ages are counted inside each ingest chunk)
    engine: str = "auto"                # repro.engine path selection
    select_criterion: str = "aicc"      # default auto-degree criterion
    # (moment-space only: the slot pool keeps no fold partials —
    # AIC/AICc/BIC/GCV; "cv" would need fold slots)
    dtype: Any = jnp.float32
    spec: Any = None                    # a FitSpec supplying the pool-wide
    # accumulation policy (degree/basis/engine/decay/domain/numerics) AND
    # the default per-request solve; overrides the flat fields above


@dataclasses.dataclass(frozen=True)
class PoolSpecs:
    """The server-side spec family one ``FitServeConfig`` implies: what the
    slots accumulate (``pool``, fixed max degree), the default fixed and
    auto-degree request specs, and the spec a bare ``submit(x, y)`` gets.

    Derived once by ``derive_pool_specs`` and shared by every serving
    surface — the single-process ``FitServeEngine`` and the replicated
    workers of ``serve.fleet`` — so "what does this server accumulate and
    how does it answer by default" has exactly one definition."""

    pool: Any
    fixed: Any
    auto: Any
    default: Any
    select_criterion: str


def validate_pool_spec(spec) -> None:
    # only an EXPLICIT normalize request is rejected: the plan layer's
    # high-degree auto-escalation is a before-the-Gram fix the server
    # cannot apply (min/max of unseen series), so — as the engine
    # always has — high-degree pools accumulate raw-domain moments and
    # lean on solve-time solver escalation + the rank-revealing
    # fallback instead (pin FitSpec.domain to get true normalization)
    from repro.api import spec as spec_lib
    if spec.numerics.solver in spec_lib.RAW_DATA_SOLVERS:
        raise ValueError(
            f"solver={spec.numerics.solver!r} needs the raw Vandermonde "
            "rows; the slot pools only hold moments")
    if spec.numerics.normalize and spec.domain is None:
        raise ValueError(
            "this spec normalizes the domain, but the server cannot "
            "derive min/max from series it has not seen — pin it with "
            "FitSpec(domain=(shift, scale))")


def derive_pool_specs(cfg: "FitServeConfig") -> PoolSpecs:
    """Map one ``FitServeConfig`` onto the ``PoolSpecs`` family."""
    from repro.api import spec as spec_lib
    from repro.engine import plan as plan_lib
    if cfg.select_criterion not in select_lib.MOMENT_CRITERIA:
        raise ValueError(
            f"select_criterion={cfg.select_criterion!r}; the slot pool "
            f"keeps no fold partials, so only moment-space criteria "
            f"{select_lib.MOMENT_CRITERIA} can serve auto-degree "
            "requests")
    if cfg.spec is not None:
        base = cfg.spec
    else:
        solver = cfg.method or cfg.solver
        base = spec_lib.FitSpec(
            degree=cfg.degree,
            numerics=plan_lib.NumericsPolicy(solver=solver,
                                             fallback=cfg.fallback),
            decay=cfg.decay, ridge=cfg.ridge, engine=cfg.engine)
    # the pool-wide spec: what the slots accumulate (fixed max degree)
    pool = (dataclasses.replace(base, degree=base.max_degree)
            if base.is_search else base)
    validate_pool_spec(pool)
    ds = (base.degree if base.is_search
          else select_lib.DegreeSearch(
              max_degree=pool.max_degree, folds=0,
              criterion=cfg.select_criterion,
              solver=pool.numerics.solver,
              fallback=pool.numerics.fallback,
              cond_cap=pool.numerics.cond_cap))
    # a DegreeSearch rides the condition-aware ladder solve; an LSPIA
    # pool's auto requests therefore search as LSE (the accumulated
    # moments are method-free — only the solve differs)
    auto = dataclasses.replace(
        base, degree=ds,
        method="lse" if base.method == "lspia" else base.method)
    default = base if base.is_search else pool
    return PoolSpecs(pool=pool, fixed=pool, auto=auto, default=default,
                     select_criterion=cfg.select_criterion)


def validate_request_spec(specs: PoolSpecs, spec) -> None:
    """Reject request specs the pool's accumulated state cannot serve."""
    from repro.api import spec as spec_lib
    pool = specs.pool
    if spec.numerics.solver in spec_lib.RAW_DATA_SOLVERS:
        raise ValueError(
            f"solver={spec.numerics.solver!r} needs the raw Vandermonde "
            "rows; the slot pools only hold moments")
    if spec.basis != pool.basis:
        raise ValueError(
            f"request basis={spec.basis!r} but the pool accumulates "
            f"{pool.basis!r} moments — basis is pool-wide "
            "(FitServeConfig.spec)")
    if spec.domain != pool.domain:
        raise ValueError(
            f"request domain={spec.domain!r} but the pool accumulates "
            f"in domain {pool.domain!r} — the domain map is baked into "
            "the slots' moments (FitServeConfig.spec)")
    if spec.decay != pool.decay:
        raise ValueError(
            f"request decay={spec.decay} but the pool decays at "
            f"{pool.decay} — forgetting is baked into the running "
            "state (FitServeConfig.spec)")
    if spec.max_degree > pool.max_degree:
        raise ValueError(
            f"request degree {spec.max_degree} exceeds the pool's "
            f"accumulation degree {pool.max_degree}; nested degrees "
            "<= cfg.degree are served from the truncated state")
    if (spec.method == "irls"
            and spec.irls.stream_sweeps != pool.irls.stream_sweeps):
        raise ValueError(
            f"request stream_sweeps={spec.irls.stream_sweeps} but the "
            f"pool's compiled ingest runs {pool.irls.stream_sweeps} — "
            "the sweep count is baked into the ingest executable "
            "(FitServeConfig.spec); per-request loss/c ARE honored")
    if spec.is_search:
        crit = spec.degree.criterion or specs.select_criterion
        if crit not in select_lib.MOMENT_CRITERIA:
            raise ValueError(
                f"criterion={crit!r}: the slot pool keeps no fold "
                f"partials, so only {select_lib.MOMENT_CRITERIA} can "
                "serve auto-degree requests")


def resolve_request_spec(specs: PoolSpecs, degree, spec):
    """Map the (degree=, spec=) submit spellings onto one FitSpec."""
    if spec is not None:
        if degree is not None:
            raise ValueError("pass degree= or spec=, not both")
        validate_request_spec(specs, spec)
        return spec
    if degree is None:
        return specs.default
    if degree == "auto":
        return specs.auto
    if int(degree) != specs.pool.max_degree:
        raise ValueError(
            f"degree={degree!r}: slot pools accumulate at the static "
            f"cfg.degree={specs.pool.max_degree}; pass degree='auto' for "
            "selection over the ladder 0..cfg.degree, or a FitSpec "
            "(spec=) for any nested degree <= cfg.degree")
    return specs.fixed


def validate_series(x, y, rspec) -> tuple[np.ndarray, np.ndarray]:
    """Shared submit-time series validation (engine AND fleet)."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    if x.ndim != 1 or x.shape != y.shape or x.shape[0] == 0:
        raise ValueError(f"expected equal non-empty 1-D x/y, got "
                         f"{x.shape} vs {y.shape}")
    if not rspec.is_search and x.shape[0] < int(rspec.degree) + 1:
        raise ValueError(
            f"series of {x.shape[0]} points cannot determine a "
            f"degree-{int(rspec.degree)} fit (need >= "
            f"{int(rspec.degree) + 1}); degree='auto' accepts short "
            "series (underdetermined rungs score +inf)")
    return x, y


def _spec_solve_from_state(state, spec, pool_degree: int):
    """The ONE definition of a per-request fixed-degree solve over a
    pool-degree state: the request's nested degree is a truncate view of
    the accumulated state; its numerics policy (solver rung, fallback,
    cond_cap, ridge) and method (LSE vs moment-space LSPIA) ride in the
    static spec.  Traced both standalone (``make_spec_solve``) and fused
    after the ingest body (``_Bucket.ingest_solve``) — same ops, same
    order, so the two executables agree bitwise."""
    d = int(spec.degree)
    m = (state.moments.truncate(d) if d < pool_degree
         else state.moments)
    ms = m.regularized(spec.ridge) if spec.ridge else m
    if spec.method == "lspia":
        opts = spec.lspia
        coeffs, cond, conv, _ = lspia_lib.lspia_solve_moments(
            ms.gram, ms.vty, tol=opts.tol, max_iter=opts.max_iter,
            power_iters=opts.power_iters, step=opts.step,
            momentum=opts.momentum)
        fb = ~conv
    else:
        rung = spec.numerics.solver
        if rung == "auto":
            rung = solve_lib.select_solver(
                d, state.moments.gram.dtype, basis=spec.basis,
                normalized=spec.domain is not None)
        coeffs, cond, fb = solve_lib.solve_with_fallback(
            ms.gram, ms.vty, method=rung,
            fallback=spec.numerics.fallback,
            cond_cap=spec.numerics.cond_cap)
    rep = fit_lib.report_from_moments(m, coeffs)
    return (coeffs, rep.sse, rep.r, state.moments.count, cond, fb)


def make_spec_solve(pool_degree: int):
    """Jitted wrapper of ``_spec_solve_from_state`` — the executable every
    serving surface (the slot-pool engine for NON-default specs, each
    fleet worker for every spec) answers a fixed-degree request with.
    Shape-polymorphic over the state's batch axes: (n_slots,) on the
    engine, () on a fleet worker's per-request state."""
    from functools import partial as _partial

    @_partial(jax.jit, static_argnames=("spec",))
    def solve(state, spec):
        return _spec_solve_from_state(state, spec, pool_degree)

    return solve


def make_spec_sweep(pool_degree: int):
    """The auto-degree ladder solve over a pool-degree state (see
    ``make_spec_solve`` for why this is a shared module-level factory)."""
    from functools import partial as _partial

    @_partial(jax.jit, static_argnames=("spec",))
    def sweep(state, spec):
        # the request's ladder 0..max_degree from the (truncated view of
        # the) accumulated running moments — same ridge stabilizer (idle
        # slots must stay solvable at every rung) but scored on the RAW
        # moments so sse/criteria agree with the fixed-degree path, plus
        # the per-degree R of the padded coefficient ladder for the
        # response report.
        ds = spec.degree
        m = (state.moments.truncate(ds.max_degree)
             if ds.max_degree < pool_degree else state.moments)
        ridge = spec.ridge
        mr = m.regularized(ridge) if ridge else m
        rung = (spec.numerics.solver
                if spec.numerics.solver != "auto" else ds.solver)
        sw = select_lib.sweep_from_moments(
            mr, score_moments=m if ridge else None, solver=rung,
            fallback=ds.fallback, cond_cap=ds.cond_cap,
            basis=spec.basis, normalized=spec.domain is not None)
        rep = fit_lib.report_from_moments(m, sw.coeffs)
        return sw, rep.r, state.moments.count

    return sweep


def fill_fixed_result(req: FitRequest, spec, solved, s=None) -> None:
    """Populate one request from a fixed-degree solve's (numpy) outputs.

    ``s`` indexes a batched (slot-pool) solve; ``None`` reads a scalar
    (fleet-worker) solve.  One definition of "what a served fit reports",
    shared by every surface."""
    pick = (lambda a: a) if s is None else (lambda a: a[s])
    coeffs, sse, r, count, cond, fb = solved
    d = int(spec.degree)
    req.coeffs = np.asarray(pick(coeffs))[:d + 1].copy()
    req.sse = float(pick(sse))
    req.r = float(pick(r))
    req.count = float(pick(count))
    req.condition = float(pick(cond))
    req.fallback_used = bool(pick(fb))
    req.degree = d
    req.done = True


def split_step_buffer(buf, width: int):
    """The layout of the ONE float32 buffer a bucket dispatch sends, shape
    (n_slots, 3·width + 4): x, y and the weights (n_slots, width) each,
    then the per-slot keep, rmask, loss_id and cval (n_slots,).  Loss ids
    are small integers, exact in float32; the compiled step casts them
    back to int32.  Static slices: views of a numpy buffer, which
    ``FitServeEngine._pack`` fills through them, and fixed-shape slices
    inside the compiled step."""
    w = width
    return (buf[:, :w], buf[:, w:2 * w], buf[:, 2 * w:3 * w], buf[:, 3 * w],
            buf[:, 3 * w + 1], buf[:, 3 * w + 2], buf[:, 3 * w + 3])


def pack_solved(solved):
    """A fixed-degree solve's six outputs as ONE (n_slots, (d+1) + 5)
    array in their common float dtype — columns coeffs, sse, r, count,
    cond, fallback (0/1) — so one copy brings them all to the host."""
    coeffs, sse, r, count, cond, fb = solved
    dt = jnp.result_type(coeffs, sse, r, count, cond)
    cols = [a.astype(dt)[:, None] for a in (sse, r, count, cond, fb)]
    return jnp.concatenate([coeffs.astype(dt)] + cols, axis=1)


def unpack_solved(packed: np.ndarray):
    """Host-side inverse of ``pack_solved``: the six-tuple
    ``fill_fixed_result`` takes."""
    coeffs, rest = packed[:, :-5], packed[:, -5:]
    sse, r, count, cond, fb = rest.T
    return coeffs, sse, r, count, cond, fb != 0


def auto_outputs(sw, r_ladder, count) -> dict:
    """Convert one ``make_spec_sweep`` output to host-side numpy once per
    solve (the per-request fill then just indexes)."""
    scores = {name: np.asarray(sw.scores.by_name(name))
              for name in select_lib.MOMENT_CRITERIA + ("sse", "r2")}
    return {"scores": scores, "ladder": np.asarray(sw.coeffs),
            "cond": np.asarray(sw.condition),
            "fb": np.asarray(sw.fallback_used),
            "r": np.asarray(r_ladder), "count": np.asarray(count)}


def fill_auto_result(req: FitRequest, spec, outs: dict, criterion: str,
                     s=None) -> None:
    """Populate one auto-degree request from ``auto_outputs``."""
    pick = (lambda a: a) if s is None else (lambda a: a[s])
    scores = outs["scores"]
    d = int(np.argmin(pick(scores[criterion])))
    req.degree = d
    req.coeffs = np.asarray(pick(outs["ladder"]))[d, :d + 1].copy()
    req.sse = float(pick(scores["sse"])[d])
    req.r = float(pick(outs["r"])[d])
    req.count = float(pick(outs["count"]))
    req.condition = float(pick(outs["cond"])[d])
    req.fallback_used = bool(pick(outs["fb"])[d])
    req.scores = {k: np.asarray(pick(v)).copy() for k, v in scores.items()}
    req.condition_ladder = np.asarray(pick(outs["cond"])).copy()
    req.done = True


class _Bucket:
    """One length bucket: a slot pool + its compiled fused
    ingest+default-solve step."""

    def __init__(self, width: int, n_slots: int, engine: "FitServeEngine"):
        cfg = engine.cfg
        pool = engine.spec
        self.width = width
        self.state = streaming.StreamState.create(
            pool.max_degree, (n_slots,), decay=pool.decay, dtype=cfg.dtype)
        self.slot_req: list[FitRequest | None] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int64)    # points ingested
        self.reset = np.zeros(n_slots, bool)           # zero slot next step
        self.queue: list[FitRequest] = []
        dom = pool.domain_or(None, dtype=cfg.dtype)
        rsolver = engine._pool_solver
        ridge = max(pool.ridge, 1e-9)   # the reweight solve must tolerate
        # idle/young slots even when the request asked for ridge=0
        degree = pool.max_degree

        sweeps = pool.irls.stream_sweeps

        @jax.jit
        def ingest(state, buf):
            x, y, w, keep, rmask, loss_id, cval = split_step_buffer(buf,
                                                                    width)
            loss_id = loss_id.astype(jnp.int32)
            # keep==0 wipes a slot's previous occupant inside the same
            # compiled step (count included: it restarts for the new series)
            m = state.moments
            k = keep.astype(m.gram.dtype)
            m = moments_lib.Moments(
                gram=m.gram * k[:, None, None], vty=m.vty * k[:, None],
                yty=m.yty * k, count=m.count * k, weight_sum=m.weight_sum * k)
            st = streaming.StreamState(m, state.decay)
            xt = dom.apply(x) if dom is not None else x

            def solve(mm):
                coeffs, _, _ = solve_lib.solve_with_fallback(
                    mm.regularized(ridge).gram, mm.regularized(ridge).vty,
                    method=rsolver, fallback="svd")
                return coeffs

            def rw_of(coeffs, w):
                # ψ-weights with the loss/tuning selected by RUNTIME
                # per-slot arrays — one executable serves any robust/plain
                # mix with zero recompiles
                r = y - basis_lib.evaluate(coeffs, xt, basis=pool.basis)
                sigma = robust_lib.chunk_scale(r, w, y)
                wr = robust_lib.robust_weights_by_id(
                    r / sigma, loss_id[:, None], cval[:, None])
                return jnp.where((rmask > 0)[:, None], wr, 1.0)

            def reweight(w):
                # per-slot single-pass IRLS: sweep 0 against the slot's
                # RUNNING fit (where determined), then stream_sweeps − 1
                # re-accumulations of the in-hand chunk against
                # (decayed slot state + chunk) — robust from the first
                # chunk.  Mirrors streaming._streaming_irls_weights,
                # including the decay bookkeeping: old mass ages by γⁿ and
                # the chunk carries its own γ age ladder, exactly as the
                # final streaming.update accumulation will weight it.
                determined = (st.moments.count > degree)[:, None]
                wr = jnp.where(determined, rw_of(solve(st.moments), w), 1.0)
                from repro import engine as engine_lib
                plan = engine_lib.plan_fit(
                    x.shape, degree, basis=pool.basis, dtype=x.dtype,
                    weighted=True, engine=pool.engine,
                    accum_dtype=st.moments.gram.dtype)
                n = x.shape[-1]
                g = st.decay ** jnp.asarray(n, st.decay.dtype)
                old = jax.tree.map(lambda a: a * g, st.moments)
                lad = moments_lib.decay_ladder(n, st.decay, x.dtype)
                for _ in range(sweeps - 1):
                    new = engine_lib.compute_moments(plan, xt, y,
                                                     lad * w * wr)
                    wr = rw_of(solve(old + new), w)
                return wr * w

            w = jax.lax.cond(jnp.any(rmask > 0), reweight, lambda w: w, w)
            return streaming.update(st, xt, y, weights=w, basis=pool.basis,
                                    engine=pool.engine)

        self.ingest = ingest

        # The fused hot path: accumulate the chunk AND solve the pool's
        # default fixed spec in ONE executable, so the updated Gram flows
        # from the moment matmul straight into the solve without a
        # round-trip through HBM (or a second host dispatch) between
        # ticks.  The solve half is the same ``_spec_solve_from_state``
        # the standalone executable traces — non-default request specs
        # still go through ``FitServeEngine._solve`` on the returned
        # state, unchanged.  Its six answers leave as one packed array.
        fixed_spec = engine.fixed_spec

        @jax.jit
        def ingest_solve(state, buf):
            st = ingest(state, buf)
            return st, pack_solved(
                _spec_solve_from_state(st, fixed_spec, degree))

        self.ingest_solve = ingest_solve


class FitServeEngine:
    """Host-side continuous batching around compiled moment-ingest steps."""

    def __init__(self, cfg: FitServeConfig | None = None,
                 obs: "obs_lib.Observability | None" = None):
        from repro.api import spec as spec_lib
        self.cfg = cfg = cfg or FitServeConfig()
        # observability is injected and OFF by default: the null bundle
        # makes every record below an empty method call (the perf gate's
        # ``obs_overhead`` row holds enabled-vs-null to <= 5%)
        self.obs = obs or obs_lib.NULL_OBS
        self._m_submitted = self.obs.metrics.counter("submitted")
        self._m_completed = self.obs.metrics.counter("completed")
        self._g_queue = self.obs.metrics.gauge("queue_depth")
        self._h_points = self.obs.metrics.histogram("points_per_fit")
        # host-clock latencies, recorded only when obs is enabled
        self._h_queue_wait = self.obs.metrics.histogram("queue_wait_ms")
        self._h_latency = self.obs.metrics.histogram("fit_latency_ms")
        self._submit_t: dict[int, float] = {}
        self._step_no = 0
        if tuple(sorted(cfg.buckets)) != tuple(cfg.buckets):
            raise ValueError(f"buckets must ascend: {cfg.buckets}")
        specs = self.pool_specs = derive_pool_specs(cfg)
        self.spec = specs.pool
        # default per-request specs for the legacy degree= spellings
        self.fixed_spec = specs.fixed
        self.auto_spec = specs.auto
        self.default_spec = specs.default
        # the reweight solve's static rung (pool degree/dtype/basis)
        self._pool_solver = (
            self.spec.numerics.solver if self.spec.numerics.solver
            not in ("auto",) + spec_lib.RAW_DATA_SOLVERS
            else solve_lib.select_solver(
                self.spec.max_degree, cfg.dtype, basis=self.spec.basis,
                normalized=self.spec.domain is not None))
        self.buckets = [_Bucket(w, cfg.n_slots, self) for w in cfg.buckets]
        self._uid = 0
        self.fits_done = 0
        # per dispatch of ingest/ingest_solve: live points, active slots,
        # slots sent, and lanes sent (slots × width)
        self.points_ingested = 0
        self.slots_active = 0
        self.slots_dispatched = 0
        self.lanes_dispatched = 0
        # host↔device copy calls the steps make: one each way per
        # dispatch on the default path
        self.h2d_copies = 0
        self.d2h_copies = 0
        self._solve = make_spec_solve(self.spec.max_degree)
        self._sweep = make_spec_sweep(self.spec.max_degree)

    # ------------------------------------------------------------- plumbing
    def _resolve_spec(self, degree, spec):
        """Map the (degree=, spec=) submit spellings onto one FitSpec."""
        return resolve_request_spec(self.pool_specs, degree, spec)

    def _validate_request_spec(self, spec) -> None:
        validate_request_spec(self.pool_specs, spec)

    def submit(self, x, y, *, degree: int | str | None = None,
               spec=None) -> FitRequest:
        """Queue one ragged series; routed to the smallest bucket that holds
        it in one chunk, else the largest (multi-chunk streaming ingest).

        ``spec=`` attaches a full ``FitSpec`` to the request: its method
        (LSE / IRLS chunk-reweighting / moment-space LSPIA), its solve
        policy (solver/fallback/cond_cap/ridge), a nested fixed degree
        <= cfg.degree, or a DegreeSearch over the nested ladder.  Each
        distinct spec compiles its solve once, then coexists with every
        other spec — no recompiles.  ``degree=`` is the legacy spelling:
        the pool degree, or "auto" for selection under the engine's
        default criterion."""
        rspec = self._resolve_spec(degree, spec)
        auto = rspec.is_search
        x, y = validate_series(x, y, rspec)
        req = FitRequest(self._uid, x, y, spec=rspec, auto=auto)
        self._uid += 1
        self._m_submitted.inc()
        if self.obs.enabled:
            self._submit_t[req.uid] = time.perf_counter()
        for b in self.buckets[:-1]:
            if req.n <= b.width:
                b.queue.append(req)
                return req
        self.buckets[-1].queue.append(req)
        return req

    def warmup(self) -> int:
        """Compile every executable up front — one full-width synthetic
        fixed-degree request AND one auto-degree request per bucket,
        plus one double-width request whose mid-series chunk compiles the
        widest bucket's plain (no-solve) ingest step — drained
        immediately, so steady-state serving provably never recompiles
        whatever mix of DEFAULT-spec request kinds arrives.  (A novel
        per-request spec compiles its own solve once on first use, then
        joins the invariant.)  Returns ``compiled_executables()`` (the
        baseline the no-recompile invariant is asserted against).
        Deterministic: does not depend on the live traffic's lengths."""
        if self.pending:
            raise RuntimeError("warmup() requires an idle engine")
        for b in self.buckets:
            n = max(b.width, self.spec.max_degree + 1)
            x = np.linspace(-1.0, 1.0, n, dtype=np.float32)
            self.submit(x, x, spec=self.fixed_spec)
            self.submit(x, x, spec=self.auto_spec)
        # only the LAST bucket ever ingests multi-chunk series (routing
        # sends every shorter request to a bucket wide enough to finish
        # it in one step), so one over-length request warms its
        # mid-series path — 3 chunks long, so at least one step is
        # mid-series-only even when it shares its first step with the
        # completing requests above
        n2 = 3 * self.buckets[-1].width
        x2 = np.linspace(-1.0, 1.0, n2, dtype=np.float32)
        self.submit(x2, x2, spec=self.fixed_spec)
        self.run()
        return self.compiled_executables()

    def compiled_executables(self) -> int:
        """Total compiled executables across the engine's jitted steps —
        constant after warmup (plus one per NOVEL request spec, compiled
        at first use) is the no-recompile serving invariant.  The fused
        ingest+solve is ONE executable per bucket; the plain ingest
        compiles only where mid-series (no-completion) steps can occur —
        the widest bucket."""
        return (self._solve._cache_size() + self._sweep._cache_size()
                + sum(b.ingest._cache_size() + b.ingest_solve._cache_size()
                      for b in self.buckets))

    @property
    def pending(self) -> int:
        return (sum(len(b.queue) for b in self.buckets)
                + sum(r is not None for b in self.buckets
                      for r in b.slot_req))

    # ----------------------------------------------------------------- run
    def _step_bucket(self, b: _Bucket) -> None:
        with _span("fit_engine.pack", bucket=b.width):
            packed = self._pack(b)
        if packed is None:
            return
        host_buf, ready = packed
        with _span("fit_engine.put", bucket=b.width):
            # the whole dispatch's input in one host-to-device copy
            buf = jnp.asarray(host_buf)
            self.h2d_copies += 1
        with _span("fit_engine.launch", bucket=b.width):
            if ready:
                b.state, fused = b.ingest_solve(b.state, buf)
            else:
                b.state = b.ingest(b.state, buf)
        if ready:
            with _span("fit_engine.collect", bucket=b.width):
                self._collect(b, ready, fused)

    def _pack(self, b: _Bucket):
        """Admit from the queue and fill the host buffer of one dispatch
        (``split_step_buffer`` gives its layout): ``(buffer, ready
        slots)``, or None when no slot is active.  The buffer is fresh
        each step: on the CPU backend a device array may alias the numpy
        memory it was made from."""
        for slot, req in enumerate(b.slot_req):
            if req is None and b.queue:
                req = b.slot_req[slot] = b.queue.pop(0)
                b.slot_pos[slot] = 0
                b.reset[slot] = True
                if self.obs.enabled:
                    self._h_queue_wait.observe(
                        (time.perf_counter() - self._submit_t[req.uid])
                        * 1e3)
        active = [s for s, r in enumerate(b.slot_req) if r is not None]
        if not active:
            return None

        n_slots, w = len(b.slot_req), b.width
        self.slots_active += len(active)
        self.slots_dispatched += n_slots
        self.lanes_dispatched += n_slots * w
        buf = np.zeros((n_slots, 3 * w + 4), np.float32)
        xh, yh, wh, keep, rmask, loss_id, cval = split_step_buffer(buf, w)
        cval[:] = 1.0
        for s in active:
            req = b.slot_req[s]
            lo = int(b.slot_pos[s])
            chunk = req.x[lo:lo + w]
            m = chunk.shape[0]
            xh[s, :m] = chunk
            yh[s, :m] = req.y[lo:lo + w]
            wh[s, :m] = 1.0
            b.slot_pos[s] = lo + m
            self.points_ingested += m
            if req.spec.method == "irls":
                rmask[s] = 1.0
                loss_id[s] = robust_lib.LOSS_IDS[req.spec.irls.loss]
                cval[s] = robust_lib.resolve_tuning(req.spec.irls.loss,
                                                    req.spec.irls.c)
        keep[:] = np.where(b.reset, 0.0, 1.0)
        b.reset[:] = False
        # readiness is host-known BEFORE dispatch (slot_pos already
        # advanced), so each step picks the cheapest executable: the
        # fused ingest+solve when ≥1 request completes this chunk — the
        # Gram never round-trips through HBM (or a second dispatch)
        # between accumulate and solve — and the plain ingest on
        # mid-series steps, where a solve would be wasted work
        ready = [s for s in active if b.slot_pos[s] >= b.slot_req[s].n]
        return buf, ready

    def _collect(self, b: _Bucket, ready: list[int], fused) -> None:
        """Bring the ready slots' answers to the host and free the slots."""
        # group ready slots by their request's spec: the default fixed
        # spec is already solved (fused above) and its answers come back
        # in ONE device-to-host copy of the packed array; every other
        # DISTINCT spec gets one compiled solve for its whole group, and
        # one copy per output
        fixed_groups: dict[Any, list[int]] = {}
        auto_groups: dict[Any, list[int]] = {}
        for s in ready:
            groups = (auto_groups if b.slot_req[s].auto else fixed_groups)
            groups.setdefault(b.slot_req[s].spec, []).append(s)
        for spec, slots in fixed_groups.items():
            if spec == self.fixed_spec:
                solved = unpack_solved(np.asarray(fused))
                self.d2h_copies += 1
            else:
                solved = tuple(np.asarray(a)
                               for a in self._solve(b.state, spec))
                self.d2h_copies += len(solved)
            for s in slots:
                req = b.slot_req[s]
                fill_fixed_result(req, spec, solved, s)
                b.slot_req[s] = None
                self._done(req)
        for spec, slots in auto_groups.items():
            outs = auto_outputs(*self._sweep(b.state, spec))
            # one copy per criterion row and per other output
            self.d2h_copies += len(outs["scores"]) + len(outs) - 1
            crit = spec.degree.criterion or self.cfg.select_criterion
            for s in slots:
                req = b.slot_req[s]
                fill_auto_result(req, spec, outs, crit, s)
                b.slot_req[s] = None
                self._done(req)

    def _done(self, req: FitRequest) -> None:
        self.fits_done += 1
        self._m_completed.inc()
        self._h_points.observe(req.n)
        if self.obs.enabled:
            self._h_latency.observe(
                (time.perf_counter() - self._submit_t.pop(req.uid)) * 1e3)

    def step(self) -> None:
        """One engine iteration: admit + one compiled fused ingest+solve
        per non-empty bucket (+ one compiled solve per distinct ready
        NON-default spec)."""
        self._step_no += 1
        with _span("fit_engine.step", step=self._step_no):
            for b in self.buckets:
                self._step_bucket(b)
            self._g_queue.set(sum(len(b.queue) for b in self.buckets))

    def run(self, max_steps: int = 1_000_000) -> None:
        """Drive until every queued request is served (or max_steps)."""
        for _ in range(max_steps):
            if not self.pending:
                return
            self.step()
        if self.pending:
            raise RuntimeError(f"{self.pending} requests still pending "
                               f"after {max_steps} steps")
