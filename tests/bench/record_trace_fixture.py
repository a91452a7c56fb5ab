#!/usr/bin/env python3
"""Record the small TPU trace that tests/bench/test_bench_trace.py reads.

    python tests/bench/record_trace_fixture.py [--out DIR]   # on a TPU

Inside one ``bench.window`` span: three ``api.fit`` calls on a resident
2^22-point series (the plain Pallas kernel), each in a ``bench.fit``
span, with the profiler on as the benchmark runs it.  (The fit server is
left out: the trace would carry the metadata of every program its
warm-up loaded, some megabytes.)  Writes
``tpu_small.xplane.pb`` and ``tpu_small.json`` (what the recording saw:
fits, steps, plan) into ``--out``, by default ``fixtures/`` beside this
file.
"""
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "fixtures"))
    out = ap.parse_args(argv).out

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro import api

    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: no TPU", file=sys.stderr)
        return 2
    n = 1 << 22
    kx, ke = jax.random.split(jax.random.key(0))
    x = jax.random.uniform(kx, (n,), jnp.float32, -10.0, 10.0)
    y = 1.0 + 0.25 * x ** 3 + jax.random.normal(ke, (n,), jnp.float32)
    spec = api.FitSpec(degree=3)
    np.asarray(api.fit(x, y, spec).poly.coeffs)

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.fit"):
                np.asarray(api.fit(x, y, spec).poly.coeffs)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out, exist_ok=True)
    shutil.copy(src[0], os.path.join(out, "tpu_small.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out, "tpu_small.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "fits": 3, "points": n,
                   "plan": spec.plan(x.shape, x.dtype).path}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
