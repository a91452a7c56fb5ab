#!/usr/bin/env python3
"""Run the fitting system once on a TPU, through its normal entry points.

    python chip_smoke.py [--seed N]            # one chip: phases a, b, c
    python chip_smoke.py [--seed N] --chips 4  # phase d only, on four chips

a. One large fit (the paper's regime): 2^27 f32 points at degree 3 through
   ``core.polyfit`` on the auto engine (which must plan ``kernel_plain`` and
   compile the Pallas kernel) and on the reference engine; an (8, 2^20)
   batch fit, then ``core.fit_report_streamed`` on it against
   ``core.fit_report``; lone series of 54 to 2^17 points on the auto engine,
   on both sides of the plan's switch to the kernel and of the kernel's
   switch to the default MXU precision.
b. Batched fits: a (4096, 2048) batch at degree 3 on the packed kernel.
c. The fit server: ``repro.launch.serve.main(["--requests", "1000", ...])``.
d. The sharded fit: ``FitSpec(degree=3).distributed`` over a 4-device mesh,
   against a one-device fit of the same data.

Fits are held to numpy f64 least squares on the host by their excess SSE,
SSE(chip coefficients) / SSE(f64 coefficients) - 1, evaluated in f64.  All
data comes from ``--seed``.  Each checked run prints one line; the last line
is ``{"ok": true, "device": {...}}``, printed only when every check passed.
Without a TPU the script exits non-zero before any work: there is no CPU
fallback.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import api, core, engine  # noqa: E402
from repro.core import streaming  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

DEGREE = 3
TRUE_COEFFS = (1.0, -2.0, 0.5, 0.25)   # y = 1 - 2x + x²/2 + x³/4 + N(0, 1)
NOISE = 1.0
EXCESS_SSE_MAX = 1e-4
KERNEL_OP = "tpu_custom_call"

LARGE_N = 1 << 27              # phase a: one series, 1.07 GB of (x, y)
REPORT_SHAPE = (8, 1 << 20)    # phase a: the streamed report's batch
LONE_NS = (54, 245, 1 << 15, 1 << 17)  # phase a: lone series, auto engine
LONE_DRAWS = 8
BATCH_SHAPE = (4096, 2048)     # phase b
SERVE_REQUESTS = 1000          # phase c
SHARD_N = 1 << 27              # phase d: points per device


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


# ---------------------------------------------------------------- data
def make_data(key, shape, sharding=None):
    """x ~ U[-10, 10], y = the cubic + unit noise, generated on the device."""
    def gen(key):
        kx, ke = jax.random.split(key)
        x = jax.random.uniform(kx, shape, jnp.float32, -10.0, 10.0)
        c0, c1, c2, c3 = TRUE_COEFFS
        y = (c0 + x * (c1 + x * (c2 + x * c3))
             + NOISE * jax.random.normal(ke, shape, jnp.float32))
        return x, y
    out = jax.jit(gen, out_shardings=sharding)(key)
    return jax.block_until_ready(out)


class F64Fit:
    """numpy f64 least squares of one series: the triangular factor R of
    [V | y], from a QR per chunk and one QR of the stacked factors.  Any
    coefficient vector c then has SSE(c) = ||R [c; -1]||², in f64."""

    CHUNK = 1 << 22

    def __init__(self, x: np.ndarray, y: np.ndarray, degree: int):
        k = degree + 2
        factors = []
        for lo in range(0, x.size, self.CHUNK):
            xc = x[lo:lo + self.CHUNK].astype(np.float64)
            a = np.empty((xc.size, k))
            a[:, 0] = 1.0
            for j in range(1, degree + 1):
                a[:, j] = a[:, j - 1] * xc
            a[:, -1] = y[lo:lo + self.CHUNK]
            factors.append(np.linalg.qr(a, mode="r"))
        self.r = np.linalg.qr(np.vstack(factors), mode="r")
        self.coeffs = np.linalg.solve(self.r[:-1, :-1], self.r[:-1, -1])

    def sse(self, coeffs) -> float:
        z = np.append(np.asarray(coeffs, np.float64), -1.0)
        return float(np.sum((self.r @ z) ** 2))

    def excess_sse(self, coeffs) -> float:
        return self.sse(coeffs) / self.sse(self.coeffs) - 1.0


# ------------------------------------------------------------ plumbing
def compile_timed(fn, *args):
    """AOT-compile ``fn`` for ``args``: (executable, seconds, has kernel)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    return compiled, secs, KERNEL_OP in compiled.as_text()


def run_warm(compiled, *args):
    """First call (discarded), then one timed warm call: (out, seconds)."""
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def report(phase: str, **fields) -> None:
    fields["peak_bytes"] = peak_bytes()
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def polyfit_fn(engine_name: str):
    return lambda x, y: core.polyfit(x, y, DEGREE, engine=engine_name)


def raw_coeffs(poly) -> np.ndarray:
    """A fit's coefficients in raw x: at degree 3 the plan keeps the
    identity domain, which this checks."""
    check(float(poly.domain_shift) == 0.0 and float(poly.domain_scale) == 1.0,
          "the fit ran on a normalized domain")
    return np.asarray(poly.coeffs, np.float64)


# -------------------------------------------------------------- phases
def phase_large_fit(key) -> None:
    n = LARGE_N
    x, y = make_data(key, (n,))
    ref = F64Fit(np.asarray(x), np.asarray(y), DEGREE)
    for eng, want in (("auto", engine.KERNEL_PLAIN),
                      ("reference", engine.REFERENCE)):
        plan = api.FitSpec(degree=DEGREE, engine=eng).plan(x.shape, x.dtype)
        compiled, secs, kernel = compile_timed(polyfit_fn(eng), x, y)
        poly, warm = run_warm(compiled, x, y)
        excess = ref.excess_sse(raw_coeffs(poly))
        report(f"a.polyfit.{eng}", n=n, degree=DEGREE, plan=plan.path,
               kernel=kernel, excess_sse=excess, compile_s=secs,
               warm_s=warm)
        check(plan.path == want, f"engine={eng} planned {plan.path}")
        check(kernel == (want != engine.REFERENCE),
              f"engine={eng}: kernel in program is {kernel}")
        check(excess <= EXCESS_SSE_MAX, f"engine={eng} excess SSE {excess}")
    del x, y

    b, n = REPORT_SHAPE
    x, y = make_data(jax.random.fold_in(key, 1), (b, n))
    plan = api.FitSpec(degree=DEGREE).plan(x.shape, x.dtype)
    compiled, secs, kernel = compile_timed(polyfit_fn("auto"), x, y)
    poly, warm = run_warm(compiled, x, y)
    coeffs, xh, yh = raw_coeffs(poly), np.asarray(x), np.asarray(y)
    excess = max(F64Fit(xh[i], yh[i], DEGREE).excess_sse(coeffs[i])
                 for i in range(b))
    report("a.polyfit.report_batch", batch=b, n=n, plan=plan.path,
           kernel=kernel, max_excess_sse=excess, compile_s=secs,
           warm_s=warm)
    check(plan.path == engine.KERNEL_PACKED and kernel,
          f"report batch planned {plan.path}, kernel {kernel}")
    check(excess <= EXCESS_SSE_MAX, f"report batch excess SSE {excess}")
    plan = engine.plan_fit(x.shape, DEGREE, dtype=x.dtype, workload="report")
    compiled, secs, kernel = compile_timed(
        lambda p, x, y: core.fit_report_streamed(p, x, y), poly, x, y)
    srep, warm = run_warm(compiled, poly, x, y)
    rep = core.fit_report(poly, x, y)
    sse_rel = float(np.max(np.abs(np.asarray(srep.sse, np.float64)
                                  / np.asarray(rep.sse, np.float64) - 1)))
    r_diff = float(np.max(np.abs(np.asarray(srep.r, np.float64)
                                 - np.asarray(rep.r, np.float64))))
    report("a.fit_report_streamed", batch=b, n=n, plan=plan.path,
           kernel=kernel, sse_rel_vs_fit_report=sse_rel,
           r_diff_vs_fit_report=r_diff, compile_s=secs, warm_s=warm)
    check(plan.path == engine.KERNEL_PLAIN and kernel,
          f"streamed report planned {plan.path}, kernel {kernel}")
    check(sse_rel <= 2e-4 and r_diff <= 1e-4,
          f"streamed report vs fit_report: sse {sse_rel}, r {r_diff}")
    del x, y

    for n in LONE_NS:
        want = (engine.KERNEL_PLAIN if n >= engine.KERNEL_MIN_POINTS
                else engine.REFERENCE)
        plan = api.FitSpec(degree=DEGREE).plan((n,), jnp.float32)
        arg = jax.ShapeDtypeStruct((n,), jnp.float32)
        compiled, secs, kernel = compile_timed(polyfit_fn("auto"), arg, arg)
        excess = 0.0
        for d in range(LONE_DRAWS):
            x, y = make_data(jax.random.fold_in(jax.random.fold_in(key, n),
                                                d), (n,))
            poly = compiled(x, y)
            excess = max(excess, F64Fit(np.asarray(x), np.asarray(y), DEGREE)
                         .excess_sse(raw_coeffs(poly)))
        report("a.polyfit.lone", n=n, draws=LONE_DRAWS, plan=plan.path,
               kernel=kernel, max_excess_sse=excess, compile_s=secs)
        check(plan.path == want and kernel == (want != engine.REFERENCE),
              f"lone n={n} planned {plan.path}, kernel {kernel}")
        check(excess <= EXCESS_SSE_MAX, f"lone n={n} excess SSE {excess}")


def phase_batched(key) -> None:
    b, n = BATCH_SHAPE
    x, y = make_data(key, (b, n))
    plan = api.FitSpec(degree=DEGREE).plan(x.shape, x.dtype)
    compiled, secs, kernel = compile_timed(polyfit_fn("auto"), x, y)
    poly, warm = run_warm(compiled, x, y)
    coeffs, xh, yh = raw_coeffs(poly), np.asarray(x), np.asarray(y)
    rows = np.linspace(0, b - 1, 16).astype(int)
    excess = max(F64Fit(xh[i], yh[i], DEGREE).excess_sse(coeffs[i])
                 for i in rows)
    report("b.polyfit.batched", batch=b, n=n, plan=plan.path, kernel=kernel,
           max_excess_sse_16_rows=excess, compile_s=secs, warm_s=warm)
    check(plan.path == engine.KERNEL_PACKED and kernel,
          f"batched fit planned {plan.path}, kernel {kernel}")
    check(excess <= EXCESS_SSE_MAX, f"batched excess SSE {excess}")


def phase_serve(seed: int) -> None:
    # the serve buckets ingest (8 slots, width) chunks through
    # streaming.update; compile the widest one to see which path it takes
    slots, width = 8, 2048
    state = streaming.StreamState.create(DEGREE, (slots,))
    chunk = jnp.zeros((slots, width), jnp.float32)
    plan = engine.plan_fit(chunk.shape, DEGREE, dtype=chunk.dtype,
                           weighted=True)
    _, _, kernel = compile_timed(
        lambda s, x, y, w: streaming.update(s, x, y, weights=w),
        state, chunk, chunk, chunk)
    t0 = time.perf_counter()
    # serve.main asserts that nothing recompiles after warmup
    reqs = serve.main(["--requests", str(SERVE_REQUESTS),
                       "--seed", str(seed)])
    wall = time.perf_counter() - t0
    done = sum(r.done for r in reqs)
    checked = reqs[:: len(reqs) // 32][:32]
    excess = max(F64Fit(r.x, r.y, DEGREE).excess_sse(r.coeffs)
                 for r in checked)
    report("c.serve", requests=len(reqs), done=done, plan=plan.path,
           kernel=kernel, max_excess_sse_32_requests=excess,
           wall_s_incl_warmup=wall)
    check(done == len(reqs) == SERVE_REQUESTS, f"served {done}/{len(reqs)}")
    check(plan.path == engine.KERNEL_PACKED and kernel,
          f"serve ingest planned {plan.path}, kernel {kernel}")
    check(excess <= EXCESS_SSE_MAX, f"served excess SSE {excess}")


def phase_sharded(key) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    mesh = mesh_lib.make_host_mesh()
    n = 4 * SHARD_N
    x, y = make_data(key, (n,), NamedSharding(mesh, P("data")))
    held = {s.device: s.data.shape[0] for s in x.addressable_shards}
    check(len(held) == 4 and set(held.values()) == {n // 4},
          f"shards per device: {held}")
    run = api.FitSpec(degree=DEGREE).distributed(mesh)
    compiled, secs, kernel = compile_timed(
        lambda x, y: run(x, y).poly, x, y)
    text = compiled.as_text()
    all_reduce = "all-reduce" in text
    c_dist, warm = run_warm(compiled, x, y)
    x1, y1 = jax.device_put((x, y), devices[0])
    one, secs1, kernel1 = compile_timed(polyfit_fn("auto"), x1, y1)
    c_one, warm1 = run_warm(one, x1, y1)
    ref = F64Fit(np.asarray(x), np.asarray(y), DEGREE)
    ex_dist = ref.excess_sse(raw_coeffs(c_dist))
    ex_one = ref.excess_sse(raw_coeffs(c_one))
    report("d.distributed", n=n, devices=len(held),
           points_per_device=n // 4, all_reduce=all_reduce, kernel=kernel,
           excess_sse=ex_dist, compile_s=secs, warm_s=warm)
    report("d.one_device", n=n, kernel=kernel1, excess_sse=ex_one,
           compile_s=secs1, warm_s=warm1)
    check(all_reduce and kernel, f"sharded program: all-reduce "
          f"{all_reduce}, kernel {kernel}")
    check(kernel1, "one-device fit has no kernel")
    check(max(ex_dist, ex_one) <= EXCESS_SSE_MAX,
          f"excess SSE: sharded {ex_dist}, one device {ex_one}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fit, on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing ran",
              file=sys.stderr)
        return 1
    use_compile_cache()
    key = jax.random.PRNGKey(args.seed)
    try:
        if args.chips == 4:
            phase_sharded(key)
        else:
            phase_large_fit(jax.random.fold_in(key, 0))
            phase_batched(jax.random.fold_in(key, 1))
            phase_serve(args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
