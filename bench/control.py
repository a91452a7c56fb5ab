#!/usr/bin/env python3
"""The control: the reference fit computed one precision below the
configuration's float32, put in the program's place.  It has to come out
not correct, or the comparison could not tell a fit that a later change
computed below float32 from a sound one.

Two steps below float32, both bfloat16 (``--sums``):

* ``bfloat16``: all of the arithmetic is bfloat16, the values, their
  powers, the products and every sum, within a 4096-point block and over
  blocks;
* ``float32``: each power and y rounded to bfloat16 as a matmul at the
  MXU's DEFAULT precision rounds its operands, the products and every
  sum in float32.  This is the step that tempts: the lone-series pass
  took it until the VPU pass replaced its matmul.  ``large_fit``'s limits
  were set on that program, which read far inside them, so they admit
  this step; its readings at 1e9 points are what a tighter
  ``excess_sse`` limit would be set from (PERF.md section 7).

    python bench/control.py --workload large_fit --seeds 11 --sums float32

For each seed it makes the cell's own inputs at the cell's own size (the
resident series, or the fit server's payloads and the sample its check
draws), answers them with ``bf16_fit`` and prints the numbers the cell
compares, each against its limit.  The benchmark's runs never run it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

CHUNK = 4096   # the kernels' block: a bf16 Gram summed block by block


@functools.partial(jax.jit, static_argnums=(3, 4))
def bf16_gram(x, y, n_live, degree: int, sums=jnp.bfloat16):
    """The Gram of the augmented design [V | y] of the first ``n_live``
    points from bfloat16 operands: the values, their powers, each chunk's
    products and sums, and the sum over chunks are all bfloat16; with
    ``sums=jnp.float32`` the powers are taken in float32 and rounded to
    bfloat16 (so is y), and the products and sums are float32."""
    bf = jnp.bfloat16
    n = x.shape[0]
    chunk = min(CHUNK, n)

    def body(i, g):
        # the last chunk ends at n and leaves out what an earlier one
        # summed: no padded copy of a series that fills half the chip
        lo = jnp.minimum(i * chunk, n - chunk)
        xc = jax.lax.dynamic_slice(x, (lo,), (chunk,)).astype(sums)
        yc = jax.lax.dynamic_slice(y, (lo,), (chunk,)).astype(sums)
        at = lo + jnp.arange(chunk)
        wc = ((at >= i * chunk) & (at < n_live)).astype(bf)
        rows = [jnp.ones_like(xc)]
        for _ in range(degree):
            rows.append(rows[-1] * xc)
        a = jnp.stack(rows + [yc]).astype(bf)
        return g + jnp.einsum("kn,jn->kj", a * wc, a,
                              preferred_element_type=sums)

    g0 = jnp.zeros((degree + 2, degree + 2), sums)
    return jax.lax.fori_loop(0, -(-n // chunk), body, g0)


def bf16_fit(x, y, degree: int, n_live: int | None = None,
             sums=jnp.bfloat16):
    """(coefficients, reported SSE, reported count) of the reference fit
    from ``bf16_gram`` of the first ``n_live`` points (all by default);
    the small solve, the SSE and the count are then taken from that Gram
    exactly."""
    n_live = x.shape[0] if n_live is None else n_live
    g = np.asarray(bf16_gram(x, y, n_live, degree, sums), np.float64)
    m1 = degree + 1
    coeffs = np.linalg.lstsq(g[:m1, :m1], g[:m1, m1], rcond=None)[0]
    z = np.append(coeffs, -1.0)
    return coeffs, float(z @ g @ z), g[0, 0]


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def control_readings(cell, seed: int, seconds: float, devices,
                     sums=jnp.bfloat16):
    """The cell's numbers compared, with the control (its sums in
    ``sums``) in the program's place, on the inputs of ``seed``."""
    from bench import harness
    ctx = harness.Context(cell.config, cell.traffic, seed, seconds, devices,
                          harness.span)
    system = harness.load_module(harness.system_path(cell.config)).build(ctx)
    degree = system.degree
    if cell.config["system"] == "resident_fit":
        coeffs, sse, _ = bf16_fit(system.x, system.y, degree, sums=sums)
        system.finish()
        return system.readings([(coeffs, 0.0, 1.0, sse)])
    answers = {}
    for i in system.sample(np.arange(len(system.payloads))):
        x, y = system.payloads[i]
        # padded to a power of two, so that a few shapes compile
        n = _pow2(x.size)
        answers[i] = bf16_fit(np.pad(x, (0, n - x.size)),
                              np.pad(y, (0, n - x.size)), degree, x.size,
                              sums)
    return system.readings(sorted(answers), lambda i: answers[i])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window the serving payloads are made for "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--sums", choices=["bfloat16", "float32"],
                    default="bfloat16",
                    help="the control's products and sums; its operands "
                         "are bfloat16 either way")
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.resolve(args.workload)
    seconds = args.seconds or harness.load_benchmark()["run_seconds"]
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print("control: needs the cell's TPU chips; nothing ran",
              file=sys.stderr)
        return 2
    for seed in args.seeds:
        checks = control_readings(cell, seed, seconds, devs[:cell.chips],
                                  jnp.dtype(args.sums))
        print(json.dumps({
            "workload": args.workload, "seed": seed, "sums": args.sums,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
