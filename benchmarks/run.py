"""Benchmark harness — one entry per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
quantity) and writes the same rows machine-readably to
``benchmarks/BENCH_<git-rev>.json`` so the perf trajectory is tracked across
PRs. Run: PYTHONPATH=src python -m benchmarks.run [--quick|--smoke] [--gate]

Every hot-path row carries the fields the roofline-anchored perf gate
(``repro.launch.perfgate``) consumes:

* ``us_per_call``   min-of-reps timing (the ``timing`` dict records the
  rep/iter/warmup counts — means hide bimodal host noise, minima don't);
* ``mpts_per_s`` / ``fits_per_s``   achieved throughput;
* ``roofline_frac``   achieved Mpts/s over the memory-bound ceiling from the
  measured-bandwidth STREAM triad (header field ``bandwidth_gbps``);
* ``backend`` / ``interpret``   provenance, so an interpret-mode Pallas
  number can never be mistaken for a hardware number.

``--smoke`` is the CI regression tripwire: tiny shapes, every bench still
exercised end to end, and every row is asserted to produce finite numbers.
``--gate`` additionally checks the run against the committed per-row
budgets in ``benchmarks/baseline.json`` and exits nonzero on any breach
(see README §Performance gate; ``--rebaseline`` rewrites the budgets after
an intentional change).  A bench that raises no longer aborts the run: it
lands as a ``"status": "failed"`` row so the trajectory shows holes instead
of pretending coverage.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import streaming
from repro.data import curve_dataset
from repro.kernels import moments as kernel
from repro.kernels import ops as kernel_ops
from repro.launch import perfgate


class Timed(float):
    """A µs-per-call float carrying its timing provenance."""

    meta: dict

    def __new__(cls, us: float, meta: dict | None = None):
        obj = super().__new__(cls, us)
        obj.meta = meta or {}
        return obj


def _time(fn, *args, iters=20, warmup=3, reps=5) -> Timed:
    """Min-of-reps µs/call: ``reps`` timed loops of ``iters`` calls each,
    keep the best loop's mean.  The minimum estimates the clean-machine
    cost; host-load noise only ever inflates a rep, never deflates it."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters * 1e6)
    return Timed(best, {"stat": "min_of_reps", "reps": reps, "iters": iters,
                        "warmup": warmup})


ROWS: list[dict] = []
SMOKE = False   # set by --smoke: tiny shapes + finite-number assertions
BW: perfgate.Bandwidth | None = None   # measured once per run (main())

# the rows the committed baseline budgets (benchmarks/baseline.json) gate —
# every hot path with a stable workload shape at a given mode
GATED_ROWS = ("moments_jnp", "moments_blocked", "moments_packed",
              "moments_packed_db", "fused_report", "streaming_update",
              "batched_fits", "select_sweep", "api_dispatch", "solve_ge",
              "serve_fit", "serve_fleet", "lspia_momentum", "lspia_async",
              "obs_overhead")


def _injected_slowdown(name: str) -> float | None:
    """PERFGATE_SLOW="row=factor,..." inflates named rows' measured time —
    the hook the gate's own failure test drives (never set in real runs)."""
    env = os.environ.get("PERFGATE_SLOW", "")
    for part in env.split(","):
        if "=" in part:
            k, v = part.split("=", 1)
            if k.strip() == name:
                return float(v)
    return None


def row(name, us, derived, *, n_points=None, n_fits=None, streams=2,
        interpret=False):
    """Record one bench row.

    ``n_points`` / ``n_fits`` are PER TIMED CALL, so ``n_points / us`` is
    Mpts/s directly.  ``streams`` is how many contiguous f32 arrays the
    pass reads per point (x, y [, w]) — the denominator of the memory-bound
    ceiling.  ``interpret=True`` tags emulated-Pallas rows so they are
    never read as hardware numbers (and are excluded from absolute
    roofline floors by the gate).
    """
    slow = _injected_slowdown(name)
    if slow is not None:
        us = Timed(float(us) * slow, getattr(us, "meta", {}))
    print(f"{name},{float(us):.1f},{derived}")
    if SMOKE:
        import math
        import re
        assert math.isfinite(float(us)), f"{name}: non-finite us={us}"
        bad = re.search(r"(?<![a-z])(nan|inf)(?![a-z])", str(derived),
                        re.IGNORECASE)
        assert not bad, f"{name}: non-finite derived: {derived}"
    r = {"name": name, "us_per_call": round(float(us), 1),
         "derived": derived, "status": "ok",
         "backend": jax.default_backend(), "interpret": bool(interpret)}
    if getattr(us, "meta", None):
        r["timing"] = us.meta
    if n_points is not None:
        mpts = n_points / float(us)          # n/µs == Mpts/s
        r["mpts_per_s"] = round(mpts, 3)
        if BW is not None:
            r["roofline_frac"] = round(perfgate.roofline_fraction(
                mpts, BW, streams=streams), 5)
            r["streams"] = streams
    if n_fits is not None:
        r["fits_per_s"] = round(n_fits / float(us) * 1e6, 1)
    if slow is not None:
        r["slowdown_injected"] = slow
    ROWS.append(r)


def _interp() -> bool:
    """Do Pallas rows run in interpret mode on this backend?"""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------- Table II-V
def bench_accuracy(quick: bool):
    """Paper Tables II-V: coefficients + Σe² vs the QR (polyfit) baseline on
    the paper's dataset. derived = max |coeff - polyfit coeff| at order 3."""
    x = jnp.asarray([39.206, 29.74, 21.31, 12.087, 1.812, 0.001])
    y = jnp.asarray([751.912, 567.121, 403.746, 221.738, 18.8418, 1.88672])
    for order in (1, 2, 3):
        us = _time(lambda: core.polyfit(x, y, order))
        gauss = core.polyfit(x, y, order)
        qr = core.polyfit(x, y, order, solver="qr_vandermonde")
        sse = float(core.fit_report(gauss, x, y).sse)
        gap = float(jnp.max(jnp.abs(gauss.coeffs - qr.coeffs)))
        row(f"table2-4_order{order}_fit", us,
            f"sse={sse:.4f};max_coeff_gap_vs_qr={gap:.2e}")


# ------------------------------------------------------------------ §IV perf
def bench_speedup(quick: bool):
    """Paper §IV: matricized parallel accumulation vs the sequential
    per-point scalar loop (the pre-matricization implementation the paper
    benchmarks against; their GPU port reached ~100x over it). derived =
    speedup of the matricized path on this host."""
    sizes = ([10_000] if SMOKE
             else [10_000, 100_000] if quick
             else [10_000, 100_000, 1_000_000])

    def sequential_power_sums(xs, ys, m=3):
        """Faithful scalar baseline: one point at a time, plain floats."""
        s = [0.0] * (2 * m + 1)
        t = [0.0] * (m + 1)
        for xi, yi in zip(xs, ys):
            p = 1.0
            for k in range(2 * m + 1):
                s[k] += p
                if k <= m:
                    t[k] += p * yi
                p *= xi
        return s, t

    for n in sizes:
        x, y, _ = curve_dataset(n, degree=3, seed=0)
        mat = jax.jit(lambda x, y: core.gram_moments(x, y, 3).gram)
        us_mat = _time(mat, x, y, iters=10)

        n_seq = min(n, 20_000)  # time a slice, extrapolate linearly
        xs = [float(v) for v in np.asarray(x[:n_seq])]
        ys = [float(v) for v in np.asarray(y[:n_seq])]
        t0 = time.perf_counter()
        sequential_power_sums(xs, ys)
        us_seq_full = (time.perf_counter() - t0) * 1e6 * (n / n_seq)
        row(f"speedup_n{n}", us_mat,
            f"seq_us={us_seq_full:.0f};speedup={us_seq_full / us_mat:.1f}x",
            n_points=n)


def bench_kernel(quick: bool):
    """Pallas moments kernel (interpret mode on CPU): correctness-equivalent
    throughput vs the jnp path; derived = Mpoints/s of the jnp path (the
    kernel's CPU interpret timing is NOT the TPU number — the row's
    interpret flag says so machine-readably)."""
    n = 1 << 14 if SMOKE else 1 << 18 if quick else 1 << 20
    x, y, _ = curve_dataset(n, degree=3, seed=1)
    jnp_path = jax.jit(lambda x, y: core.gram_moments(x, y, 3).gram)
    us = _time(jnp_path, x, y, iters=10)
    blocked = jax.jit(
        lambda x, y: core.gram_moments_blocked(x, y, 3, block=1 << 14).gram)
    us_b = _time(blocked, x, y, iters=10)
    k = jax.jit(lambda x, y: kernel_ops.moments(x, y, 3).gram)
    us_k = _time(k, x, y, iters=2, warmup=1, reps=3)
    row("moments_jnp", us, f"{n / us:.1f}Mpts/s", n_points=n)
    row("moments_blocked", us_b, f"{n / us_b:.1f}Mpts/s", n_points=n)
    row("moments_pallas_interpret", us_k,
        f"{n / us_k:.2f}Mpts/s(interpret)" if _interp()
        else f"{n / us_k:.2f}Mpts/s",
        n_points=n, streams=3, interpret=_interp())


def bench_kernel_packed(quick: bool):
    """Packed multi-series kernel on the batched degree-3 workload (the
    monitors/serving hot path). derived = MXU-FLOPs-per-fit ratio vs the
    plain one-series-per-tile layout (the hardware-independent speedup; 25×
    at degree 3), interpret-mode wall speedup, and max relative error of the
    packed Gram vs core.gram_moments.  ``moments_packed_db`` is the same
    workload through the manually double-buffered DMA pipeline
    (kernels.moments nbuf=2) with the autotuned block_n — parity asserted;
    its wall time only means something on real hardware."""
    from repro.kernels import tune

    deg = 3
    b = 8 if SMOKE else 32 if quick else 64
    n = 512 if SMOKE else 2048 if quick else 4096
    x, y, _ = curve_dataset(n, degree=deg, seed=4, batch=(b,))

    plain = jax.jit(lambda x, y: kernel_ops.moments(
        x, y, deg, packing="plain").gram)
    packed = jax.jit(lambda x, y: kernel_ops.moments(
        x, y, deg, packing="packed").gram)
    us_plain = _time(plain, x, y, iters=2, warmup=1, reps=3)
    us_packed = _time(packed, x, y, iters=2, warmup=1, reps=3)

    # MXU work is identical per (128, n)x(n, 128) tile product; the packed
    # layout amortizes each product over P fits instead of 1.
    pfac = kernel.packing_factor(deg)
    groups = -(-b // pfac)
    flops_per_fit_plain = 2 * kernel.K_PAD ** 2 * n            # b tiles / b
    flops_per_fit_packed = 2 * kernel.K_PAD ** 2 * n * groups / b
    ratio = flops_per_fit_plain / flops_per_fit_packed

    g_ref = core.gram_moments(x, y, deg, accum_dtype=jnp.float32).gram
    rel = float(jnp.max(jnp.abs(packed(x, y) - g_ref)
                        / jnp.maximum(jnp.abs(g_ref), 1e-9)))
    row("moments_packed", us_packed,
        f"flops_per_fit_ratio={ratio:.1f}x;interpret_speedup="
        f"{us_plain / us_packed:.1f}x;max_rel_err_vs_gram={rel:.2e}",
        n_points=b * n, streams=3, interpret=_interp())

    # double-buffered DMA pipeline at the autotuned block size
    bn = tune.autotune_block_n(deg, n, dtype=jnp.float32)
    packed_db = jax.jit(lambda x, y: kernel_ops.moments(
        x, y, deg, packing="packed", nbuf=2, block_n=bn).gram)
    us_db = _time(packed_db, x, y, iters=2, warmup=1, reps=3)
    rel_db = float(jnp.max(jnp.abs(packed_db(x, y) - g_ref)
                           / jnp.maximum(jnp.abs(g_ref), 1e-9)))
    row("moments_packed_db", us_db,
        f"nbuf=2;block_n={bn};max_rel_err_vs_gram={rel_db:.2e}",
        n_points=b * n, streams=3, interpret=_interp())
    if SMOKE:
        assert rel_db < 1e-5, f"double-buffered kernel diverged: {rel_db}"


def bench_fused_report(quick: bool):
    """Fused evaluate+residual+SSE/R pass vs the materializing fit_report.
    derived = Mpts/s of the fused pass and the HBM bytes it avoids writing
    (fitted + residuals arrays)."""
    b = 4 if SMOKE else 16 if quick else 32
    n = 1 << 12 if SMOKE else 1 << 14 if quick else 1 << 16
    x, y, _ = curve_dataset(n, degree=3, seed=5, batch=(b,))
    poly = core.polyfit(x, y, 3)

    base = jax.jit(lambda p, x, y: core.fit_report(p, x, y).sse)
    fused = jax.jit(lambda p, x, y: core.fit_report_streamed(p, x, y).sse)
    us_base = _time(base, poly, x, y, iters=3, warmup=1)
    us_fused = _time(fused, poly, x, y, iters=3, warmup=1)
    saved = 2 * b * n * 4  # fitted + residuals f32, never hit HBM
    row("fused_report", us_fused,
        f"{b * n / us_fused:.1f}Mpts/s;materializing_us={us_base:.1f};"
        f"hbm_bytes_avoided={saved}", n_points=b * n)


def bench_solver_stack(quick: bool):
    """Condition-aware solver stack (PR-3): the explicit ladder's hot rung
    (batched GE), the SVD rescue on an ill-conditioned degree-9 Gram, IRLS
    robust fitting under 20% contamination, and the matrix-free LSPIA
    iteration.  Every derived field is finite-asserted under --smoke, so a
    solver regression that starts shipping NaNs trips CI here."""
    rng = np.random.default_rng(9)

    # solve_ge: the paper's solver, batched over a slot-pool-sized stack
    deg = 3
    b = 64 if SMOKE else 1024
    a = rng.normal(0, 1, (b, deg + 1, deg + 1))
    a = a @ a.transpose(0, 2, 1) + (deg + 1) * np.eye(deg + 1)
    rhs = rng.normal(0, 1, (b, deg + 1))
    aj = jnp.asarray(a, jnp.float32)
    bj = jnp.asarray(rhs, jnp.float32)
    ge = jax.jit(core.gaussian_elimination)
    us = _time(ge, aj, bj)
    resid = float(jnp.max(jnp.abs(
        jnp.einsum("bij,bj->bi", aj, ge(aj, bj)) - bj)))
    row("solve_ge", us, f"{b / us * 1e6:.0f}solves/s;max_resid={resid:.2e}",
        n_fits=b)

    # solve_svd_fallback: degree-9 raw-monomial Gram on [0, 8] — κ far past
    # the f32 cap, GE alone degrades; the guard must swap in the SVD and
    # stay finite
    n = 1 << 10 if SMOKE else 1 << 14
    x9 = jnp.asarray(np.linspace(0.0, 8.0, n), jnp.float32)
    y9 = jnp.asarray(np.polyval(rng.normal(0, 1, 10)[::-1],
                                np.linspace(0.0, 8.0, n)), jnp.float32)
    m9 = core.gram_moments(x9, y9, 9)
    fb = jax.jit(lambda a, b: core.solve_with_fallback(a, b, method="gauss",
                                                       fallback="svd"))
    us = _time(fb, m9.gram, m9.vty, iters=10)
    coeffs, cond, used = fb(m9.gram, m9.vty)
    ok = bool(jnp.all(jnp.isfinite(coeffs)))
    row("solve_svd_fallback", us,
        f"fallback_used={bool(used)};finite_coeffs={ok};"
        f"cond_past_cap={float(cond) > core.cond_cap_for(jnp.float32)}")
    if SMOKE:
        assert bool(used) and ok, "SVD rescue failed to produce finite output"

    # irls: Tukey robust fit under 20% gross contamination
    n = 1 << 10 if SMOKE else 1 << 13
    xr = rng.uniform(-2, 2, n)
    true = np.array([1.0, -2.0, 0.5, 0.8])
    yr = np.polyval(true[::-1], xr) + rng.normal(0, 0.05, n)
    out = rng.choice(n, n // 5, replace=False)
    yr[out] += rng.choice([-1.0, 1.0], out.size) * 50.0
    xrj = jnp.asarray(xr, jnp.float32)
    yrj = jnp.asarray(yr, jnp.float32)
    irls = jax.jit(lambda x, y: core.robust_polyfit(x, y, 3,
                                                    loss="tukey").poly.coeffs)
    us = _time(irls, xrj, yrj, iters=5, warmup=1)
    rfit = core.robust_polyfit(xrj, yrj, 3, loss="tukey")
    rel = float(np.linalg.norm(np.asarray(rfit.poly.monomial_coeffs(),
                                          np.float64) - true)
                / np.linalg.norm(true))
    row("irls", us, f"rel_err_20pct_outliers={rel:.2e};"
        f"iters={int(rfit.iterations)};converged={bool(rfit.converged)}")
    if SMOKE:
        assert rel < 0.05, f"IRLS accuracy regression: {rel:.3f}"

    # lspia: the Gram-free iteration on its natural (Chebyshev) basis
    n = 1 << 10 if SMOKE else 1 << 14
    xl = jnp.asarray(rng.uniform(-3, 3, n), jnp.float32)
    yl = jnp.asarray(np.sin(np.asarray(xl)) + 0.02 * rng.normal(0, 1, n),
                     jnp.float32)
    lsp = jax.jit(lambda x, y: core.lspia_fit(x, y, 5,
                                              basis="chebyshev").poly.coeffs)
    us = _time(lsp, xl, yl, iters=5, warmup=1)
    lf = core.lspia_fit(xl, yl, 5, basis="chebyshev")
    ref = core.polyfit(xl, yl, 5, basis="chebyshev", normalize=True)
    gap = float(jnp.max(jnp.abs(lf.poly.coeffs - ref.coeffs)))
    row("lspia", us, f"iters={int(lf.iterations)};"
        f"converged={bool(lf.converged)};max_coeff_gap_vs_lse={gap:.2e}")
    if SMOKE:
        assert bool(lf.converged), "LSPIA failed to converge on smoke shapes"

    # lspia_momentum: heavy-ball PIA-with-memory (β = 0.5, the measured
    # optimum) — same fixed point, multiples fewer sweeps at one extra
    # axpy per sweep
    lspm = jax.jit(lambda x, y: core.lspia_fit(
        x, y, 5, basis="chebyshev", momentum=0.5).poly.coeffs)
    us_m = _time(lspm, xl, yl, iters=5, warmup=1)
    lfm = core.lspia_fit(xl, yl, 5, basis="chebyshev", momentum=0.5)
    gap_m = float(jnp.max(jnp.abs(lfm.poly.coeffs - ref.coeffs)))
    row("lspia_momentum", us_m,
        f"iters={int(lfm.iterations)};plain_iters={int(lf.iterations)};"
        f"converged={bool(lfm.converged)};max_coeff_gap_vs_lse={gap_m:.2e}")
    if SMOKE:
        assert bool(lfm.converged), "momentum LSPIA failed to converge"
        assert int(lfm.iterations) < int(lf.iterations), (
            f"momentum did not accelerate: {int(lfm.iterations)} vs "
            f"plain {int(lf.iterations)}")

    # lspia_async: barrier-free sharded LSPIA — a python coordinator over
    # jitted shard gradients on the virtual-tick mailbox substrate, so the
    # row times one whole fault-free fit (wall time), not a kernel call
    from repro.api.spec import FitSpec, LSPIAOptions
    from repro.core import distributed as dist_lib
    from repro.engine.plan import NumericsPolicy
    # normalize=True: LSPIA needs the [-1, 1] domain map for a contractive
    # iteration (the lspia_fit shim defaults it on, FitSpec defaults it off)
    aspec = FitSpec(degree=5, basis="chebyshev", method="lspia",
                    numerics=NumericsPolicy(solver="auto", normalize=True),
                    lspia=LSPIAOptions(momentum=0.5))
    n_sh = 4
    dist_lib.async_lspia_fit(xl, yl, aspec, n_shards=n_sh)  # warm the jits
    t0 = time.perf_counter()
    af = dist_lib.async_lspia_fit(xl, yl, aspec, n_shards=n_sh)
    us_a = Timed((time.perf_counter() - t0) * 1e6,
                 {"stat": "single_call", "reps": 1, "iters": 1, "warmup": 1})
    gap_a = float(jnp.max(jnp.abs(af.poly(xl) - ref(xl))))
    # no n_points: this row is wall time of a python tick coordinator, not
    # a memory-bound kernel — regression-gated only, no roofline floor
    row("lspia_async", us_a,
        f"versions={int(af.iterations)};ticks={int(af.ticks)};"
        f"shards={n_sh};converged={bool(af.converged)};"
        f"max_pred_gap_vs_lse={gap_a:.2e}")
    if SMOKE:
        assert bool(af.converged), "async LSPIA failed to converge"


def bench_streaming(quick: bool):
    """Streaming O(1)-state fitter: points/s through update() + solve cost.
    derived = Mpts/s and the (constant) state size."""
    chunk = 1 << 14
    x, y, _ = curve_dataset(chunk, degree=2, seed=2)
    state = streaming.StreamState.create(2)
    upd = jax.jit(streaming.update)
    us = _time(upd, state, x, y, iters=20)
    state_bytes = sum(np.asarray(l).nbytes
                      for l in jax.tree.leaves(state))
    us_solve = _time(jax.jit(lambda s: streaming.current_fit(s).coeffs),
                     upd(state, x, y))
    row("streaming_update", us, f"{chunk / us:.1f}Mpts/s", n_points=chunk)
    row("streaming_solve", us_solve, f"state_bytes={state_bytes}")


def bench_batched_fits(quick: bool):
    """Batched (vmapped-by-construction) fitting — the monitors' workload:
    fit 4096 independent series at once. derived = fits/s."""
    b = 128 if SMOKE else 512 if quick else 4096
    x, y, _ = curve_dataset(256, degree=1, seed=3, batch=(b,))
    fit = jax.jit(lambda x, y: core.polyfit(x, y, 1).coeffs)
    us = _time(fit, x, y, iters=10)
    row("batched_fits", us, f"{b / (us / 1e6):.0f}fits/s",
        n_points=b * 256, n_fits=b)


def bench_select(quick: bool):
    """Single-pass model selection (repro.select).  ``select_sweep``:
    the degree ladder from ONE degree-M accumulation vs the naive
    refit-per-degree loop (M+1 accumulations) — derived = wall speedup +
    the chosen degree.  ``select_cv``: the full k-fold moment-space CV
    path end to end (eager entry point, fold accumulation included)."""
    from repro import select as select_lib

    max_deg = 8
    n = 1 << 12 if SMOKE else 1 << 15 if quick else 1 << 18
    rng = np.random.default_rng(21)
    xs = rng.uniform(-1.0, 1.0, n)
    true = np.array([0.5, -1.0, 0.3, 0.9])          # planted cubic
    sig = np.polyval(true[::-1], xs)
    ys = sig + (sig.std() / 10.0) * rng.normal(0, 1, n)   # SNR 10
    x = jnp.asarray(xs, jnp.float32)
    y = jnp.asarray(ys, jnp.float32)

    sweep = jax.jit(lambda x, y: select_lib.sweep_from_moments(
        core.gram_moments(x, y, max_deg)).scores.aicc)

    def naive(x, y):
        # the pre-select workflow: one full accumulation per degree
        return tuple(core.gram_moments(x, y, d).gram for d in
                     range(max_deg + 1))

    naive_j = jax.jit(naive)
    us_sweep = _time(sweep, x, y, iters=10)
    us_naive = _time(naive_j, x, y, iters=10)
    aicc = np.asarray(sweep(x, y))
    best = int(np.argmin(aicc))
    row("select_sweep", us_sweep,
        f"best=deg{best};naive_refit_us={us_naive:.1f};"
        f"speedup_vs_refit={us_naive / us_sweep:.1f}x", n_points=n)
    if SMOKE:
        assert best == 3, f"sweep missed the planted cubic: {best}"
        assert np.all(np.isfinite(aicc)), "non-finite AICc in sweep"

    def cv_path():
        return select_lib.select_degree(x, y, max_degree=max_deg, folds=5)

    for _ in range(2):
        cv_path()                                     # compile both halves
    best_us = float("inf")
    reps, iters = 3, 5
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            sel = cv_path()
        best_us = min(best_us, (time.perf_counter() - t0) / iters * 1e6)
    us_cv = Timed(best_us, {"stat": "min_of_reps", "reps": reps,
                            "iters": iters, "warmup": 2})
    cv = np.asarray(sel.sweep.scores.cv)
    row("select_cv", us_cv,
        f"best=deg{sel.best_degree};folds=5;"
        f"cv_min={float(np.min(cv)):.4g}", n_points=n)
    if SMOKE:
        assert sel.best_degree == 3, f"CV missed the planted cubic: {sel}"
        assert np.all(np.isfinite(cv)), "non-finite CV scores"


def bench_api_dispatch(quick: bool):
    """The declarative-API tax: spec-based ``api.fit()`` vs the direct
    jitted ``_polyfit_fixed`` on the same n=1e6 fit.  The spec is the jit
    static arg, so both paths run ONE compiled executable — the measured
    gap is pure host-side dispatch (spec hash, cache lookup, FitResult
    wrap).  derived = overhead %; --smoke asserts it stays under 25%."""
    from repro import api
    from repro.core import fit as fit_lib

    n = 1_000_000
    x, y, _ = curve_dataset(n, degree=3, seed=7)
    spec = api.FitSpec(degree=3)

    def spec_fit():
        return api.fit(x, y, spec).poly.coeffs

    def direct():
        return fit_lib._polyfit_fixed(x, y, 3).coeffs

    # min-of-reps on both paths: they are compared on a ~12ms compute-bound
    # op, so host-load noise (±25% observed) would swamp the few-us
    # dispatch gap at any single rep
    iters = 5 if SMOKE or quick else 10
    us_direct = _time(direct, iters=iters, warmup=3, reps=5)
    us_spec = _time(spec_fit, iters=iters, warmup=3, reps=5)
    ratio = us_spec / us_direct
    row("api_dispatch", us_spec,
        f"direct_us={us_direct:.1f};overhead={(ratio - 1) * 100:+.2f}%;"
        f"n={n}", n_points=n)
    if SMOKE:
        # regression tripwire, not the headline claim: the row reports the
        # measured overhead; the assertion only catches a dispatch-path
        # BLOWUP (2x+).  The two sides are timed sequentially, so a host
        # load window during one phase skews the ratio ±20% even at
        # min-of-reps — budget accordingly
        assert ratio < 1.25, (
            f"spec dispatch overhead {ratio:.3f}x exceeds the 25% budget "
            f"({us_spec:.1f}us vs {us_direct:.1f}us)")


def bench_serve_fit(quick: bool):
    """Continuous-batching fit server on a ragged request trace (1k requests
    in the full run), served through the fused ingest+solve executable.
    derived = sustained fits/s and Mpts/s after warmup, min over full trace
    reps, with the no-recompile invariant asserted (zero new executables
    across every steady-state wave)."""
    from repro.serve import FitServeConfig, FitServeEngine

    n_req = 32 if SMOKE else 200 if quick else 1000
    lo, hi = (8, 512) if SMOKE else (16, 4096)
    engine = FitServeEngine(FitServeConfig(
        degree=3, n_slots=8, buckets=(256, 2048), ridge=1e-9))
    rng = np.random.default_rng(11)
    series = []
    for _ in range(n_req):
        n = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        x = rng.uniform(-2, 2, n).astype(np.float32)
        y = (0.3 * x**3 - 0.5 * x + 1.0
             + rng.normal(0, 0.1, n)).astype(np.float32)
        series.append((x, y))

    execs = engine.warmup()        # compiles every bucket + the solve
    reps = 3 if (SMOKE or quick) else 2
    best_dt = float("inf")
    for _ in range(reps):
        reqs = [engine.submit(x, y) for x, y in series]
        t0 = time.perf_counter()
        engine.run()
        best_dt = min(best_dt, time.perf_counter() - t0)
        assert all(r.done for r in reqs)
    recompiles = engine.compiled_executables() - execs
    assert recompiles == 0, f"{recompiles} recompiles in steady state"
    pts = sum(x.shape[0] for x, _ in series)
    dt = best_dt
    us = Timed(dt / n_req * 1e6, {"stat": "min_of_reps", "reps": reps,
                                  "iters": n_req, "warmup": 1})
    row("serve_fit", us,
        f"{n_req / dt:.1f}fits/s;{pts / dt / 1e6:.2f}Mpts/s;"
        f"executables={execs};recompiles_after_warmup={recompiles}",
        n_points=pts / n_req, n_fits=1, streams=3)


def bench_serve_fleet(quick: bool):
    """Fault-tolerant fleet (PR-6): the same ragged trace served by 4
    replicated workers, fault-free vs one worker crash-killed mid-run.
    derived = fits/s + p99 tick latency in both regimes, with zero lost
    requests asserted under the fault — the recovery machinery (journal
    replay, restart, hedging) must absorb the crash, not drop work."""
    from repro.runtime.chaos import ChaosSchedule, FaultEvent
    from repro.serve import FitServeConfig, FleetConfig, FitFleet

    n_req = 16 if SMOKE else 48 if quick else 200
    lo, hi = (64, 512) if SMOKE else (128, 4096)
    rng = np.random.default_rng(11)
    series = []
    for _ in range(n_req):
        n = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        xs = rng.uniform(-2, 2, n).astype(np.float32)
        ys = (0.3 * xs**3 - 0.5 * xs + 1.0
              + rng.normal(0, 0.1, n)).astype(np.float32)
        series.append((xs, ys))

    def run(chaos):
        fleet = FitFleet(FleetConfig(
            fit=FitServeConfig(degree=3), n_workers=4, chaos=chaos,
            straggler_threshold=2.0))
        fleet.warmup()
        reqs = [fleet.submit(xs, ys) for xs, ys in series]
        t0 = time.perf_counter()
        fleet.run(max_ticks=50_000)
        dt = time.perf_counter() - t0
        lost = sum(1 for r in reqs if not r.done or r.failed)
        return fleet, dt, lost

    base, dt0, lost0 = run(None)
    chaos = ChaosSchedule((FaultEvent(2, 1, "crash"),))
    faulted, dt1, lost1 = run(chaos)
    assert lost0 == 0 and lost1 == 0, f"lost requests: {lost0}/{lost1}"
    assert faulted.stats["worker_deaths"] == 1
    q0, q1 = base.latency_quantiles(), faulted.latency_quantiles()
    us = Timed(dt1 / n_req * 1e6, {"stat": "single_faulted_run", "reps": 1,
                                   "iters": n_req, "warmup": 1})
    row("serve_fleet", us,
        f"{n_req / dt1:.1f}fits/s_under_crash;"
        f"faultfree={n_req / dt0:.1f}fits/s;"
        f"p99_ticks={q1['p99']:.0f}(vs{q0['p99']:.0f});"
        f"replays={faulted.stats['replays']};lost=0", n_fits=1)


def bench_obs_overhead(quick: bool):
    """The observability tax (PR-9): the serve_fit ragged trace served
    twice by the same engine config — once with the default ``NULL_OBS``
    recorders, once with ``Observability.on()`` (live metric registry +
    host-clock queue-wait and latency histograms).  All instrumentation is host-side
    python outside the jitted executables, so the measured gap is pure
    recording cost.  derived = overhead %; --smoke asserts it stays
    under 5% (the "observability is free" invariant the README claims).
    The two paths are timed in interleaved reps (min-of-reps each) so a
    host-load window skews both sides, not one."""
    from repro import obs as obs_lib
    from repro.serve import FitServeConfig, FitServeEngine

    n_req = 32 if SMOKE else 100 if quick else 400
    # recording cost is fixed per request, so the denominator must be a
    # *representative* request — multi-step series like the full-run
    # serve trace, not the smoke-tier 8-point degenerate, where the
    # percentage would measure dispatch-bound pathology instead
    lo, hi = (1024, 8192) if SMOKE else (1024, 16384)
    rng = np.random.default_rng(11)
    series = []
    for _ in range(n_req):
        n = int(np.exp(rng.uniform(np.log(lo), np.log(hi))))
        x = rng.uniform(-2, 2, n).astype(np.float32)
        y = (0.3 * x**3 - 0.5 * x + 1.0
             + rng.normal(0, 0.1, n)).astype(np.float32)
        series.append((x, y))

    def build(obs):
        engine = FitServeEngine(FitServeConfig(
            degree=3, n_slots=8, buckets=(256, 2048), ridge=1e-9), obs=obs)
        engine.warmup()
        return engine

    def one_rep(engine):
        reqs = [engine.submit(x, y) for x, y in series]
        t0 = time.perf_counter()
        engine.run()
        dt = time.perf_counter() - t0
        assert all(r.done for r in reqs)
        return dt

    eng_null = build(None)
    obs = obs_lib.Observability.on()
    eng_on = build(obs)
    reps = 7 if SMOKE else 5
    dt_null = dt_on = float("inf")
    for _ in range(reps):
        dt_null = min(dt_null, one_rep(eng_null))
        dt_on = min(dt_on, one_rep(eng_on))
    # the enabled side really recorded: live metrics and latencies
    assert obs.metrics.counter("completed").value >= n_req * reps
    assert obs.metrics.histogram("queue_wait_ms").count >= n_req * reps
    assert obs.metrics.histogram("fit_latency_ms").count >= n_req * reps
    ratio = dt_on / dt_null
    us = Timed(dt_on / n_req * 1e6, {"stat": "min_of_reps", "reps": reps,
                                     "iters": n_req, "warmup": 1})
    row("obs_overhead", us,
        f"overhead={(ratio - 1) * 100:+.2f}%;"
        f"null_us={dt_null / n_req * 1e6:.1f};"
        f"n_req={n_req}", n_fits=1)
    if SMOKE:
        assert ratio < 1.05, (
            f"obs-enabled serving is {ratio:.3f}x the null path — the "
            f"<=5% observability budget is breached "
            f"({dt_on * 1e3:.1f}ms vs {dt_null * 1e3:.1f}ms)")


BENCHES = [bench_accuracy, bench_speedup, bench_kernel, bench_kernel_packed,
           bench_fused_report, bench_solver_stack, bench_select,
           bench_streaming, bench_batched_fits, bench_api_dispatch,
           bench_serve_fit, bench_serve_fleet, bench_obs_overhead]


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except Exception:  # noqa: BLE001 — no git / not a checkout
        return "norev"


def _bench_dir() -> str:
    return os.path.dirname(os.path.abspath(__file__))


def _write_json(quick: bool) -> str:
    rev = _git_rev()
    # quick/smoke runs get their own file so a smoke check at the same rev
    # never overwrites the full-run numbers the perf trajectory tracks
    suffix = "_smoke" if SMOKE else "_quick" if quick else ""
    path = os.path.join(_bench_dir(), f"BENCH_{rev}{suffix}.json")
    payload = {
        "rev": rev,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "backend": jax.default_backend(),
        "quick": quick,
        "smoke": SMOKE,
        "bandwidth_gbps": round(BW.gbps, 2) if BW else None,
        "bandwidth_source": BW.source if BW else None,
        "rows": ROWS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    return path


def _mode_name(quick: bool) -> str:
    return "smoke" if SMOKE else "quick" if quick else "full"


def _run_gate(quick: bool) -> int:
    """Check this run against benchmarks/baseline.json; write the report."""
    base_path = os.path.join(_bench_dir(), "baseline.json")
    report_path = os.path.join(_bench_dir(), "gate_report.json")
    if not os.path.exists(base_path):
        print(f"perf gate: no baseline at {base_path} — run "
              "--rebaseline first", file=sys.stderr)
        return 2
    with open(base_path) as f:
        baseline = json.load(f)
    mode = _mode_name(quick)
    if baseline.get("mode", mode) != mode:
        print(f"perf gate: baseline was captured in mode="
              f"{baseline.get('mode')!r} but this run is {mode!r}; "
              "budgets are shape-dependent — not comparable",
              file=sys.stderr)
        return 2
    report = perfgate.check_gate(ROWS, baseline)
    payload = report.summary()
    payload["mode"] = mode
    payload["rev"] = _git_rev()
    payload["bandwidth_gbps"] = round(BW.gbps, 2) if BW else None
    with open(report_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(report.render(), file=sys.stderr)
    print(f"wrote {report_path}", file=sys.stderr)
    return 0 if report.ok else 1


def _write_baseline(quick: bool) -> None:
    base_path = os.path.join(_bench_dir(), "baseline.json")
    baseline = perfgate.make_baseline(ROWS, gated=GATED_ROWS)
    baseline["mode"] = _mode_name(quick)
    baseline["rev"] = _git_rev()
    baseline["bandwidth_gbps"] = round(BW.gbps, 2) if BW else None
    baseline["note"] = ("per-row perf budgets; regenerate with "
                        "`python -m benchmarks.run --smoke --rebaseline` "
                        "after an INTENTIONAL perf change (see README "
                        "§Performance gate)")
    with open(base_path, "w") as f:
        json.dump(baseline, f, indent=2, sort_keys=True)
    print(f"wrote {base_path}", file=sys.stderr)


def main() -> None:
    global SMOKE, BW
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + finite-number assertions on every "
                         "row (CI kernel-regression tripwire)")
    ap.add_argument("--gate", action="store_true",
                    help="check this run against benchmarks/baseline.json "
                         "and exit nonzero on any budget breach")
    ap.add_argument("--rebaseline", action="store_true",
                    help="rewrite benchmarks/baseline.json from this run "
                         "(after an intentional perf change)")
    args = ap.parse_args()
    SMOKE = args.smoke
    quick = args.quick or args.smoke
    BW = perfgate.measure_bandwidth()
    print(f"# bandwidth: {BW.gbps:.1f} GB/s ({BW.source}, {BW.backend})",
          file=sys.stderr)
    print("name,us_per_call,derived")
    failed: list[str] = []
    # BENCH_<rev>.json is ALWAYS emitted, and a bench that raises records a
    # "failed" row and the run continues — the trajectory shows holes
    # instead of silently dropping every row after the first crash.
    try:
        for bench in BENCHES:
            try:
                bench(quick)
            except Exception as e:  # noqa: BLE001
                print(f"{bench.__name__},ERROR,{type(e).__name__}: {e}",
                      file=sys.stderr)
                ROWS.append({"name": bench.__name__, "status": "failed",
                             "error": f"{type(e).__name__}: {e}",
                             "backend": jax.default_backend()})
                failed.append(bench.__name__)
    finally:
        print(f"wrote {_write_json(quick)}", file=sys.stderr)
    if args.rebaseline:
        _write_baseline(quick)
    rc = 0
    if args.gate:
        rc = max(rc, _run_gate(quick))
    if failed:
        print(f"{len(failed)} bench(es) failed: {', '.join(failed)}",
              file=sys.stderr)
        rc = max(rc, 1)
    sys.exit(rc)


if __name__ == "__main__":
    main()
