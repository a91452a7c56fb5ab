#!/usr/bin/env python3
"""Find the fit server's knee: the highest offered rate at which its
backlog does not grow over a window.

    python bench/knee.py --workload serve_overload --seed 1 --seconds 10 \
        --rates 200 300 400 500 600

Runs the cell's system once per rate, in one process, with the cell's
traffic mix at that rate, and prints one line per rate: offered and
served requests per second, the backlog at the window's close and the
95th-percentile latency of the requests served in the window.  The rates
of the serving cells' mixes are set from its output, once, on the chip;
the benchmark's runs never search for a rate.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="serve_overload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("knee: no TPU; nothing ran", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    cell = harness.resolve(args.workload)
    sysmod = harness.load_module(harness.system_path(cell.config))
    for rate in args.rates:
        traffic = dict(cell.traffic, rate_per_s=rate)
        ctx = harness.Context(cell.config, traffic, args.seed, args.seconds,
                              jax.devices()[:1], harness.span)
        system = sysmod.build(ctx)
        system.warm()
        t0 = time.perf_counter()
        w = system.window(args.seconds)
        res = system.result
        lat = res.latency_s()
        served = np.isfinite(lat)
        print(json.dumps({
            "offered_per_s": w.attempted / w.window_s,
            "served_per_s": w.completed / w.window_s,
            "backlog": int(system.engine.pending),
            "p95_ms_served": (1e3 * float(np.percentile(lat[served], 95))
                              if served.any() else None),
            "steps": res.steps, "wall_s": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
