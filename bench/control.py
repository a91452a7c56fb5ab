#!/usr/bin/env python3
"""The control: the reference fit computed one precision below the
configuration's float32, in bfloat16, put in the program's place.  It has
to come out not correct, or the comparison could not tell a fit that a
later change computed in bfloat16 from a sound one.

All of the control's arithmetic is bfloat16: the values, their powers,
the products and every sum, within a 4096-point block and over blocks.
Bfloat16 operands with float32 sums are not below the program: its plain
kernel multiplies at the MXU's DEFAULT precision from 2^17 points a call,
which rounds the operands to bfloat16 already.

    python bench/control.py --workload large_fit --seeds 11 12 13

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


@functools.partial(jax.jit, static_argnums=3)
def bf16_gram(x, y, n_live, degree: int):
    """The Gram of the augmented design [V | y] of the first ``n_live``
    points, in bfloat16: the values, their powers, each chunk's products
    and sums, and the sum over chunks are all bfloat16."""
    bf = jnp.bfloat16
    n = x.shape[0]
    chunk = min(CHUNK, n)
    pad = (-n) % chunk
    w = (jnp.arange(n + pad) < n_live).astype(bf).reshape(-1, chunk)
    xb = jnp.pad(x.astype(bf), (0, pad)).reshape(-1, chunk)
    yb = jnp.pad(y.astype(bf), (0, pad)).reshape(-1, chunk)

    def body(g, xyw):
        xc, yc, wc = xyw
        rows = [jnp.ones_like(xc)]
        for _ in range(degree):
            rows.append(rows[-1] * xc)
        a = jnp.stack(rows + [yc])
        return g + jnp.einsum("kn,jn->kj", a * wc, a,
                              preferred_element_type=bf), None

    g0 = jnp.zeros((degree + 2, degree + 2), bf)
    return jax.lax.scan(body, g0, (xb, yb, w))[0]


def bf16_fit(x, y, degree: int, n_live: int | None = None):
    """(coefficients, reported SSE, reported count) of the reference fit
    from the bfloat16 Gram of the first ``n_live`` points (all by
    default); the small solve, the SSE and the count are then taken from
    that Gram exactly."""
    n_live = x.shape[0] if n_live is None else n_live
    g = np.asarray(bf16_gram(x, y, n_live, degree), np.float64)
    m1 = degree + 1
    coeffs = np.linalg.lstsq(g[:m1, :m1], g[:m1, m1], rcond=None)[0]
    z = np.append(coeffs, -1.0)
    return coeffs, float(z @ g @ z), g[0, 0]


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def control_readings(cell, seed: int, seconds: float, devices):
    """The cell's numbers compared, with the control in the program's
    place, on the inputs of ``seed``."""
    from bench import harness
    ctx = harness.Context(cell.config, cell.traffic, seed, seconds, devices,
                          harness.span)
    system = harness.load_module(harness.system_path(cell.config)).build(ctx)
    degree = system.degree
    if cell.config["system"] == "resident_fit":
        coeffs, sse, _ = bf16_fit(system.x, system.y, degree)
        system.finish()
        return system.readings([(coeffs, 0.0, 1.0, sse)])
    answers = {}
    for i in system.sample(np.arange(len(system.payloads))):
        x, y = system.payloads[i]
        # padded to a power of two, so that a few shapes compile
        n = _pow2(x.size)
        answers[i] = bf16_fit(np.pad(x, (0, n - x.size)),
                              np.pad(y, (0, n - x.size)), degree, x.size)
    return system.readings(sorted(answers), lambda i: answers[i])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window the serving payloads are made for "
                         "(default: BENCHMARK.json's run_seconds)")
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
        checks = control_readings(cell, seed, seconds, devs[:cell.chips])
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": all(c.ok for c in checks),
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
