#!/usr/bin/env python3
"""The fitting system's chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets the cell up (data or payloads from the seed, executables from the
compile cache in ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another), measures for ``--seconds``,
checks what the timed path produced against the float64 reference, and
prints one JSON line last on standard output: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics from a profiler trace
of the window with ``--trace 1``.  The numbers compared, each with its
limit, are the last lines on standard error.  With no TPU, or fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from bench import harness
    cell = harness.resolve(args.workload)

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # cache every executable, however quick to compile, so that a cell's
    # second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return harness.run_cell(cell, args.seed, args.seconds,
                            bool(args.trace), process_start=PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
