"""Observability layer: metric registry, trace spans, SLO monitors.

The committed invariants (ISSUE 9):

* deterministic telemetry — the same chaos seed yields a *bit-identical*
  JSONL event log and identical metric snapshots across two fleet runs
  (the observability analogue of the chaos-parity invariant);
* complete span chains — every admitted request reaches exactly one
  terminal annotation, and every replay/hedge the request surfaced is
  annotated in its chain;
* sketch quantiles — the DDSketch-style histogram answers quantiles to
  the configured relative error with NO sample retention, and merge is
  associative/commutative by construction (property-tested);
* self-fitting SLOs — a monitor built from the repo's own streaming
  moment fits forecasts an injected latency ramp's breach BEFORE the
  threshold is crossed;
* zero-cost off path — the null recorders record nothing and leave
  serving results identical.
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import api
from repro import obs as obs_lib
from repro.core import streaming
from repro.obs.metrics import HistogramSketch, MetricsRegistry, NULL_REGISTRY
from repro.obs.slo import SLOBoard, SLOMonitor, resolve_metric
from repro.obs.trace import Tracer, validate_events
from repro.runtime.chaos import ChaosSchedule, FaultEvent
from repro.serve import fit_engine as fe
from repro.serve.fleet import FitFleet, FleetConfig

CHUNK = 128


def _series(seed, n_lo=300, n_hi=900, k=4):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        n = int(rng.integers(n_lo, n_hi))
        x = np.sort(rng.uniform(-1, 1, n)).astype(np.float32)
        y = (0.3 - 1.2 * x + 0.5 * x ** 3
             + 0.02 * rng.normal(size=n)).astype(np.float32)
        out.append((x, y))
    return out


CHAOS = ChaosSchedule((FaultEvent(3, 1, "crash"),
                       FaultEvent(2, 2, "stall", 400),
                       FaultEvent(1, 3, "poison")))


def _run(seed=0, chaos=CHAOS, **kw):
    kw.setdefault("fit", fe.FitServeConfig(degree=5))
    kw.setdefault("n_workers", 4)
    kw.setdefault("chunk_width", CHUNK)
    kw.setdefault("trace", True)
    kw.setdefault("straggler_threshold", 2.0)
    fleet = FitFleet(FleetConfig(chaos=chaos, **kw))
    reqs = [fleet.submit(x, y, spec=api.FitSpec(degree=3))
            for x, y in _series(seed)]
    fleet.run(max_ticks=5000)
    return fleet, reqs


# -------------------------------------------------------- histogram sketch
def test_sketch_quantile_relative_error():
    rng = np.random.default_rng(0)
    data = np.exp(rng.normal(3.0, 1.5, 4000))     # heavy-tailed latencies
    h = HistogramSketch("lat", alpha=0.01)
    for v in data:
        h.observe(float(v))
    assert h.count == data.size
    for q in (0.1, 0.5, 0.9, 0.99):
        lo = float(np.quantile(data, q, method="lower"))
        hi = float(np.quantile(data, q, method="higher"))
        est = h.quantile(q)
        assert lo * (1 - 2 * h.alpha) <= est <= hi * (1 + 2 * h.alpha), \
            (q, lo, est, hi)


def test_sketch_no_sample_retention():
    h = HistogramSketch("lat", alpha=0.05)
    for v in np.linspace(1, 10_000, 100_000):
        h.observe(float(v))
    # 100k observations over 4 decades: O(log range / log gamma) buckets
    assert len(h.buckets) < 120
    assert h.count == 100_000


def test_sketch_zero_and_snapshot_roundtrip():
    h = HistogramSketch("lat", alpha=0.02)
    for v in (0.0, -1.0, 3.0, 900.0):
        h.observe(v)
    assert h.zero_count == 2
    assert h.quantile(0.0) == 0.0
    h2 = HistogramSketch.from_snapshot("lat", h.snapshot())
    for q in (0.0, 0.5, 0.99):
        assert h2.quantile(q) == h.quantile(q)
    assert h2.count == h.count and h2.zero_count == h.zero_count


@settings(max_examples=25)
@given(st.integers(0, 2 ** 16), st.integers(1, 60), st.integers(1, 60),
       st.integers(1, 60))
def test_sketch_merge_associative_commutative(seed, na, nb, nc):
    rng = np.random.default_rng(seed)
    parts = []
    for n in (na, nb, nc):
        h = HistogramSketch("m", alpha=0.01)
        for v in rng.exponential(50.0, n):
            h.observe(float(v))
        parts.append(h)
    a, b, c = parts

    def key(h):
        return (h.count, h.zero_count, h.min, h.max,
                tuple(sorted(h.buckets.items())))

    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    swapped = c.merge(a).merge(b)
    assert key(left) == key(right) == key(swapped)
    assert np.isclose(left.total, right.total) \
        and np.isclose(left.total, swapped.total)
    for q in (0.25, 0.5, 0.99):
        assert left.quantile(q) == right.quantile(q) == swapped.quantile(q)


@settings(max_examples=25)
@given(st.integers(0, 2 ** 16), st.floats(0.0, 1.0))
def test_sketch_merge_equals_union_stream(seed, q):
    """Merging two sketches answers quantiles exactly as one sketch fed
    the concatenated stream would (bucket counts are exact)."""
    rng = np.random.default_rng(seed)
    xs = rng.exponential(20.0, 40)
    ys = rng.exponential(200.0, 30)
    ha, hb, hu = (HistogramSketch("m", 0.01) for _ in range(3))
    for v in xs:
        ha.observe(float(v))
        hu.observe(float(v))
    for v in ys:
        hb.observe(float(v))
        hu.observe(float(v))
    assert ha.merge(hb).quantile(q) == hu.quantile(q)


def test_sketch_merge_alpha_mismatch_rejected():
    with pytest.raises(ValueError, match="alpha"):
        HistogramSketch("a", 0.01).merge(HistogramSketch("b", 0.05))


# --------------------------------------------------------------- registry
def test_registry_snapshot_deterministic_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("completed").inc(3)
    reg.gauge("queue_depth").set(7)
    reg.gauge("queue_depth").set(2)
    reg.histogram("latency_ticks").observe(10)
    snap = reg.snapshot()
    assert snap["counters"] == {"completed": 3}
    assert snap["gauges"]["queue_depth"] == {"value": 2.0, "hwm": 7.0}
    assert reg.snapshot_json() == json.dumps(snap, sort_keys=True)
    text = reg.render_prometheus()
    assert "# TYPE completed counter\ncompleted 3" in text
    assert "queue_depth_hwm 7" in text
    assert 'latency_ticks{quantile="0.99"}' in text
    assert "latency_ticks_count 1" in text


def test_null_registry_records_nothing():
    NULL_REGISTRY.counter("x").inc(5)
    NULL_REGISTRY.gauge("g").set(3)
    NULL_REGISTRY.histogram("h").observe(1.0)
    assert NULL_REGISTRY.counter("x").value == 0
    assert NULL_REGISTRY.snapshot() == {"counters": {}, "gauges": {},
                                        "histograms": {}}


# ----------------------------------------------------------------- tracer
def test_tracer_idempotent_spans_and_validation():
    t = Tracer()
    t.instant(0, "submit", 0)
    t.instant(0, "admit", 1)
    t.begin(0, "ingest", 1)
    t.begin(0, "ingest", 2)          # re-begin: ignored, span kept
    t.end(0, "ingest", 3)
    t.end(0, "ingest", 4)            # double-end: dropped
    t.end(0, "solve", 4)             # end without begin: dropped
    t.instant(0, "respond", 5)
    assert [e["ph"] for e in t.events] == ["i", "i", "B", "E", "i"]
    assert validate_events(t.events) == []


def test_tracer_detects_missing_terminal_and_dangling_span():
    t = Tracer()
    t.instant(0, "admit", 1)
    problems = validate_events(t.events)
    assert any("terminal" in p for p in problems)
    t2 = Tracer()
    t2.instant(1, "admit", 1)
    t2.begin(1, "solve", 2)
    t2.instant(1, "respond", 3)
    assert any("open spans" in p for p in validate_events(t2.events))


# ------------------------------------------------------ fleet determinism
def test_chaos_seed_determinism_bit_identical_telemetry():
    """Same chaos schedule, two runs: byte-identical JSONL event log AND
    identical metric snapshots — telemetry is replayable evidence."""
    fleet_a, _ = _run(seed=0)
    fleet_b, _ = _run(seed=0)
    assert fleet_a.tracer.to_jsonl() == fleet_b.tracer.to_jsonl()
    assert fleet_a.metrics.snapshot_json() == fleet_b.metrics.snapshot_json()
    assert len(fleet_a.tracer.events) > 0


def test_fleet_span_chains_complete_under_chaos():
    fleet, reqs = _run(seed=1)
    assert validate_events(fleet.tracer.events) == []
    for r in reqs:
        names = fleet.tracer.names_for(r.uid)
        assert "submit" in names and "admit" in names
        assert sum(n in ("respond", "failed") for n in names) == 1
        # every surfaced replay/hedge is annotated in the chain
        assert names.count("replay") == r.replays
        if r.hedged:
            assert "hedge" in names


def test_fleet_stats_coverage_and_registry():
    """The old ad-hoc dict keys survive, and the coverage gaps are
    closed: hedge wins/losses, per-cause retries, queue-depth hwm."""
    fleet, reqs = _run(seed=0)
    s = fleet.stats
    for k in ("completed", "shed", "degraded", "failed", "replays",
              "hedges", "hedge_wins", "hedge_losses", "resends",
              "retries_timeout", "retries_invalid", "poisoned",
              "worker_deaths", "revivals"):
        assert k in s, k
    assert s["completed"] == len(reqs)
    assert s["hedge_wins"] + s["hedge_losses"] == sum(
        1 for r in reqs if r.hedged and r.done and not r.failed)
    assert s["retries_timeout"] + s["retries_invalid"] \
        == sum(r.retries for r in reqs)
    assert fleet.metrics.gauge("queue_depth").hwm >= 1
    # the registry IS the stats backing store
    assert fleet.metrics.counter("completed").value == s["completed"]


def test_latency_quantiles_from_sketch_mid_run():
    """Quantiles are sketch-backed: identical at both call sites and
    available mid-run, not only at shutdown."""
    fleet, reqs = _run(seed=0, chaos=None)
    q = fleet.latency_quantiles()
    h = fleet.metrics.histogram("latency_ticks")
    assert q["p50"] == h.quantile(0.5) and q["p99"] == h.quantile(0.99)
    assert h.count == len(reqs)
    lats = [r.latency_ticks for r in reqs]
    lo = float(np.quantile(lats, 0.5, method="lower"))
    hi = float(np.quantile(lats, 0.5, method="higher"))
    assert lo * 0.98 <= q["p50"] <= hi * 1.02
    # empty sketch: defined zeros, no retained samples anywhere
    empty = FitFleet(FleetConfig(fit=fe.FitServeConfig(degree=5)))
    assert empty.latency_quantiles() == {"p50": 0.0, "p99": 0.0}
    assert not hasattr(fleet, "latencies")


def test_fleet_snapshot_surfaces_obs():
    fleet, _ = _run(seed=0, chaos=None, slo_p99=500.0)
    snap = fleet.snapshot()
    assert snap["tick"] == fleet.tick
    assert snap["metrics"]["counters"]["completed"] == len(_series(0))
    assert "latency_ticks:p99" in snap["slo"]
    rep = snap["slo"]["latency_ticks:p99"]
    assert rep["threshold"] == 500.0 and not rep["breached"]


def test_trace_off_by_default_zero_events():
    fleet, reqs = _run(seed=0, chaos=None, trace=False)
    assert fleet.tracer.events == [] and not fleet.tracer.enabled
    assert fleet.stats["completed"] == len(reqs)   # metrics still live


# ------------------------------------------------------------ SLO monitor
def test_slo_monitor_forecasts_injected_ramp_before_breach():
    """The acceptance invariant: feed the monitor a latency ramp and it
    must flag the coming p99 breach while the metric is still BELOW the
    threshold, with a sane crossing-time estimate."""
    mon = SLOMonitor(metric="latency_ticks:p99", threshold=100.0,
                     decay=0.995)
    slope = 0.5
    tick = 0
    for tick in range(8, 8 * 16 + 1, 8):          # ramp: 10 + 0.5·tick
        mon.observe(tick, 10.0 + slope * tick)
    assert mon.ready
    assert mon.last_value < mon.threshold          # not yet breached...
    eta = mon.breach_eta(tick)
    assert eta is not None and eta > 0             # ...but forecast fires
    true_eta = (mon.threshold - (10.0 + slope * tick)) / slope
    assert 0.5 * true_eta <= eta <= 1.5 * true_eta, (eta, true_eta)
    assert mon.slope(tick) == pytest.approx(slope, rel=0.35)


def test_slo_monitor_flat_metric_never_breaches():
    mon = SLOMonitor(metric="queue_depth", threshold=50.0, decay=0.99)
    for tick in range(8, 200, 8):
        mon.observe(tick, 5.0 + (tick % 16 == 0))
    assert mon.breach_eta(192) is None
    rep = mon.report(192)
    assert rep["breached"] is False and rep["breach_eta_ticks"] is None


def test_slo_board_resolves_live_registry_refs():
    reg = MetricsRegistry()
    board = SLOBoard(reg)
    board.watch("latency_ticks:p99", threshold=100.0, decay=0.995)
    board.watch("queue_depth", threshold=64.0)
    h = reg.histogram("latency_ticks")
    rng = np.random.default_rng(0)
    tick = 0
    for step in range(24):
        tick = 8 * (step + 1)
        base = 5.0 + 0.4 * tick                    # injected latency ramp
        for v in base + rng.exponential(2.0, 16):
            h.observe(float(v))
        reg.gauge("queue_depth").set(3)
        board.update(tick)
    rep = board.report(tick)
    p99 = rep["latency_ticks:p99"]
    assert p99["value"] < 100.0                    # below threshold now
    assert p99["breach_eta_ticks"] is not None     # breach forecast fires
    assert board.breaching(tick, within=p99["breach_eta_ticks"] + 1) \
        == ["latency_ticks:p99"]
    assert rep["queue_depth"]["breach_eta_ticks"] is None


def test_resolve_metric_forms():
    reg = MetricsRegistry()
    reg.counter("completed").inc(4)
    reg.gauge("queue_depth").set(9)
    assert resolve_metric(reg, "completed") == 4
    assert resolve_metric(reg, "queue_depth") == 9
    assert resolve_metric(reg, "queue_depth:hwm") == 9
    assert resolve_metric(reg, "latency_ticks:p99") is None   # empty sketch
    reg.histogram("latency_ticks").observe(10.0)
    assert resolve_metric(reg, "latency_ticks:p50") \
        == pytest.approx(10.0, rel=0.02)
    with pytest.raises(ValueError, match="stat"):
        resolve_metric(reg, "latency_ticks:median")


def test_fleet_slo_board_live_under_ramp():
    """End-to-end dogfood: a fleet whose SLO board watches the live
    latency sketch keeps a current forecast via step()."""
    fleet, _ = _run(seed=0, chaos=None, slo_p99=1000.0, slo_every=1)
    for _ in range(8):          # idle ticks: the board keeps observing
        fleet.step()
    mon = fleet.slo.monitors["latency_ticks:p99"]
    assert mon.ready and mon.last_value == fleet.latency_quantiles()["p99"]
    assert mon.report(fleet.tick)["breached"] is False


# ------------------------------------------------------------ serve engine
def test_engine_obs_enabled_vs_null_identical_results():
    series = _series(3, k=3)

    def serve(obs):
        eng = fe.FitServeEngine(fe.FitServeConfig(degree=4), obs=obs)
        reqs = [eng.submit(x, y) for x, y in series]
        eng.run()
        return eng, reqs

    on = obs_lib.Observability.on()
    eng_on, reqs_on = serve(on)
    eng_off, reqs_off = serve(None)
    for a, b in zip(reqs_on, reqs_off):
        np.testing.assert_array_equal(a.coeffs, b.coeffs)
        assert a.sse == b.sse
    assert on.metrics.counter("submitted").value == len(series)
    assert on.metrics.counter("completed").value == len(series)
    assert on.metrics.histogram("points_per_fit").count == len(series)
    wait = on.metrics.histogram("queue_wait_ms")
    lat = on.metrics.histogram("fit_latency_ms")
    assert wait.count == lat.count == len(series)
    assert 0 <= wait.quantile(0.5) <= lat.quantile(0.5)
    assert eng_on._submit_t == {}
    # the engine's spans go to the profiler; its obs tracer stays empty
    assert on.tracer.events == []
    # the default engine records nothing and keeps no submit bookkeeping
    assert eng_off.obs is obs_lib.NULL_OBS
    assert eng_off.obs.tracer.events == []
    assert eng_off._submit_t == {}


# ------------------------------------------------------- async ingest/LSPIA
def test_ingestor_lag_gauge_and_counter_mirror():
    reg = MetricsRegistry()
    st0 = streaming.StreamState.create(2, dtype=np.float32)
    ing = streaming.AsyncChunkIngestor(st0, n_sources=2, staleness=2,
                                       metrics=reg)
    x = np.linspace(-1, 1, 8, dtype=np.float32)
    y = x ** 2
    for seq in range(1, 5):
        ing.offer(0, seq, x, y)                   # source 0 races ahead
    ing.offer(0, 2, x, y)                         # duplicate
    ing.offer(1, 1, x, y)
    assert reg.counter("chunks_applied").value == 5
    assert reg.counter("chunks_duplicate").value == ing.duplicates == 1
    assert reg.gauge("source_lag").value == ing.lag() == 3
    assert reg.gauge("source_lag").hwm == 4.0     # worst lag seen
    assert ing.stale_sources() == [1]


def test_async_lspia_stats_registry_backed():
    from repro.core.distributed import async_lspia_fit
    rng = np.random.default_rng(0)
    x = np.sort(rng.uniform(-1, 1, 600)).astype(np.float32)
    y = (0.5 + 0.8 * x - 0.4 * x ** 2).astype(np.float32)
    reg = MetricsRegistry()
    spec = api.FitSpec(degree=2, method="lspia")
    out = async_lspia_fit(x, y, spec, n_shards=2, registry=reg)
    assert out.converged
    assert out.metrics is reg
    for k in ("updates", "updates_during_stall", "stale_rejected",
              "poisoned", "resends", "duplicates", "crashes", "freezes"):
        assert out.stats[k] == reg.counter(k).value
    assert out.stats["updates"] > 0
    assert "staleness_lag" in reg.snapshot()["gauges"]
    assert "updates" in reg.render_prometheus()
