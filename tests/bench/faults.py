#!/usr/bin/env python3
"""Faults planted in the timed path, each as the program could have it.
The benchmark's tests plant them on the CPU; on the chip, run one cell
with one fault planted, at the cell's own size:

    python tests/bench/faults.py --workload large_fit \
        --fault half_the_blocks --seeds 11 --seconds 5

prints one result line per seed; each has to read ``"correct": false``
(give ``large_fit`` one seed a process: three 1e9-point series and
their float64 checks outgrow a 40 GiB host).
With ``--small`` it runs the cell at the tests' size with the harness's
look for a chip skipped (``tests/bench/_cells.run_small_on`` runs it so
in a child process with several CPU devices); with no ``--fault`` the
run is sound.  Each line is the run's result line with the workload,
fault, seed and the count of devices JAX found (``host_devices``).
"""
import argparse
import dataclasses
import io
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]


def half_the_blocks(mp):
    """The moment pass sums the first half of the series and skips the
    rest, while the count it reports still comes from the shape."""
    import jax.numpy as jnp
    import repro.engine
    compute = repro.engine.compute_moments

    def first_half(plan, x, y, weights=None):
        h = x.shape[-1] // 2
        m = compute(plan, x[..., :h], y[..., :h],
                    None if weights is None else weights[..., :h])
        full = jnp.full_like(m.count, x.shape[-1])
        return dataclasses.replace(m, count=full, weight_sum=full)

    mp.setattr(repro.engine, "compute_moments", first_half)


def fit_answer_altered(mp):
    """Every fitted coefficient 10 % off, where the solve produces it."""
    from repro.core import fit as fit_lib
    solve = fit_lib.fit_from_moments

    def altered(*a, **k):
        poly = solve(*a, **k)
        return dataclasses.replace(poly, coeffs=poly.coeffs * 1.1)

    mp.setattr(fit_lib, "fit_from_moments", altered)


def state_unchanged(mp):
    """The fit server's ingest returns its running state unchanged."""
    from repro.core import streaming
    mp.setattr(streaming, "update", lambda state, *a, **k: state)


def half_the_points(mp):
    """The fit server's ingest gives every other point of a chunk zero
    weight."""
    import jax.numpy as jnp
    from repro.core import streaming
    update = streaming.update

    def every_other(state, x, y, weights=None, **k):
        keep = (jnp.arange(x.shape[-1]) % 2 == 0).astype(x.dtype)
        return update(state, x, y, weights=weights * keep, **k)

    mp.setattr(streaming, "update", every_other)


def served_answer_altered(mp):
    """Every served coefficient 10 % off, where the server's solve
    produces it."""
    from repro.serve import fit_engine
    solve = fit_engine._spec_solve_from_state

    def altered(state, spec, pool_degree):
        coeffs, *rest = solve(state, spec, pool_degree)
        return (coeffs * 1.1, *rest)

    mp.setattr(fit_engine, "_spec_solve_from_state", altered)


# the faults each cell kind can have
FAULTS = {
    "resident_fit": [half_the_blocks, fit_answer_altered],
    "fit_server": [state_unchanged, half_the_points, served_answer_altered],
}


def plant(mp, system: str, name: str) -> None:
    """Plant the fault ``name`` that a cell of kind ``system`` can
    have."""
    faults = {f.__name__: f for f in FAULTS[system]}
    if name not in faults:
        raise KeyError(f"no fault {name!r} for {system} (have "
                       f"{sorted(faults)})")
    faults[name](mp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--small", action="store_true",
                    help="the tests' size, on whatever devices JAX has")
    args = ap.parse_args(argv)

    import jax
    import pytest

    from _cells import SECONDS, small_cell
    from bench import harness
    if args.small:
        cell, seconds = small_cell(args.workload), SECONDS
    else:
        cell, seconds = harness.resolve(args.workload), args.seconds
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
    with pytest.MonkeyPatch.context() as mp:
        if args.fault is not None:
            plant(mp, cell.config["system"], args.fault)
        for seed in args.seeds:
            out = io.StringIO()
            rc = harness.run_cell(cell, seed, seconds, False,
                                  process_start=time.perf_counter(),
                                  require_chip=not args.small, out=out)
            if rc:
                return rc
            got = json.loads(out.getvalue().strip().splitlines()[-1])
            print(json.dumps(dict(got, workload=args.workload,
                                  fault=args.fault, seed=seed,
                                  host_devices=len(jax.devices()))),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
