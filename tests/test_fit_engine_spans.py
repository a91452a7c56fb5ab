"""The fit server's profiler spans and batch counters, on the CPU: a small
fixed mix served under the JAX profiler, its host spans read back from
the trace file."""
import glob
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.serve import FitServeConfig, FitServeEngine

PHASES = ("fit_engine.pack", "fit_engine.put", "fit_engine.launch",
          "fit_engine.collect")
# the phase each phase may follow inside one step (None: the step's start)
FOLLOWS = {"fit_engine.pack": (None, "fit_engine.pack", "fit_engine.launch",
                               "fit_engine.collect"),
           "fit_engine.put": ("fit_engine.pack",),
           "fit_engine.launch": ("fit_engine.put",),
           "fit_engine.collect": ("fit_engine.launch",)}
# 2 slots in buckets of 8 and 32 points.  Step 1: the 8-bucket sends 5 + 8
# points and finishes both, the 32-bucket sends 20 + 32 and finishes the
# 20.  Step 2: the 8-bucket sends 6 and finishes it, the 32-bucket sends
# 32 more of the 70 (no answer, no collect).  Step 3: the 8-bucket is
# empty (pack only), the 32-bucket sends the last 6.
BUCKETS = (8, 32)
LENGTHS = (5, 8, 6, 20, 70)
STEPS = 3
DISPATCHES = 5                       # buckets with an active slot
SLOTS_ACTIVE = 2 + 2 + 1 + 1 + 1
LANES = 2 * (8 + 32 + 8 + 32 + 32)
COLLECTS = 4


def _serve():
    engine = FitServeEngine(FitServeConfig(degree=3, n_slots=2,
                                           buckets=BUCKETS))
    rng = np.random.default_rng(0)
    reqs = []
    for n in LENGTHS:
        x = np.sort(rng.uniform(-1, 1, n)).astype(np.float32)
        reqs.append(engine.submit(x, 1.0 + 0.5 * x ** 3))
    return engine, reqs


def _host_spans(path):
    """``(name, start_ns, end_ns, {arg: value})`` of every ``fit_engine.*``
    event on the profiler's host plane, in order of start."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fit_engine."):
                    spans.append((e.name, e.start_ns, e.end_ns,
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    engine, reqs = _serve()
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    return engine, reqs, _host_spans(path)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_one_step_span_per_engine_step(served):
    engine, reqs, spans = served
    assert all(r.done for r in reqs)
    assert len(_named(spans, "fit_engine.step")) == STEPS
    assert len(_named(spans, "fit_engine.pack")) == 2 * STEPS
    assert len(_named(spans, "fit_engine.put")) == DISPATCHES
    assert len(_named(spans, "fit_engine.launch")) == DISPATCHES
    assert len(_named(spans, "fit_engine.collect")) == COLLECTS


def test_phases_lie_in_their_step_in_order(served):
    _, _, spans = served
    steps = _named(spans, "fit_engine.step")
    phases = [s for s in spans if s[0] in PHASES]
    for _, t0, t1, _ in steps:
        inside = [s for s in phases if t0 <= s[1] and s[2] <= t1]
        prev, end = None, t0
        for name, s0, s1, _ in inside:
            assert prev in FOLLOWS[name], (prev, name)
            assert s0 >= end
            prev, end = name, s1
        assert sum(s[2] - s[1] for s in inside) <= t1 - t0
    # every phase span lies inside some step
    assert sum(len([s for s in phases if t0 <= s[1] and s[2] <= t1])
               for _, t0, t1, _ in steps) == len(phases)


def test_spans_carry_their_step_and_bucket(served):
    engine, _, spans = served
    steps = [a["step"] for *_, a in _named(spans, "fit_engine.step")]
    assert steps == list(range(engine._step_no - STEPS + 1,
                               engine._step_no + 1))
    packs = [a["bucket"] for *_, a in _named(spans, "fit_engine.pack")]
    assert packs == list(BUCKETS) * STEPS
    for name in PHASES:
        assert all(a["bucket"] in BUCKETS for *_, a in _named(spans, name))


def test_the_batch_counters_match_a_hand_count(served):
    engine, _, _ = served
    assert engine.points_ingested == sum(LENGTHS)
    assert engine.slots_active == SLOTS_ACTIVE
    assert engine.slots_dispatched == 2 * DISPATCHES
    assert engine.lanes_dispatched == LANES
    assert engine.slots_active <= engine.slots_dispatched
    assert engine.points_ingested <= engine.lanes_dispatched


def test_the_spans_leave_the_answers_alone(served):
    _, traced, _ = served
    engine, plain = _serve()
    engine.run()
    for a, b in zip(traced, plain):
        np.testing.assert_array_equal(a.coeffs, b.coeffs)


def test_the_benchmarks_traced_run_takes_the_engines_spans():
    # the benchmark's reduction reads its own bench.* spans; the engine's
    # spans in the same trace must leave its traced run whole
    sys.path.insert(0, str(pathlib.Path(__file__).parent / "bench"))
    from _cells import run_small
    got = run_small("serve_overload", trace=True)
    assert got["correct"], got["checks"]
    assert got["metrics"]["engine_step_ms.serve_overload"]["value"] > 0


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_one_copy_each_way_per_dispatch(x64):
    with jax.enable_x64(x64):
        engine, reqs = _serve()
        engine.run()
    assert all(r.done for r in reqs)
    assert engine.h2d_copies == DISPATCHES
    assert engine.d2h_copies == COLLECTS
