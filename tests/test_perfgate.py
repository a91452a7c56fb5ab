"""Perf-gate units: measured-bandwidth ceilings, roofline fractions, and
``check_gate`` budget semantics — plus extra canned-HLO collective parsing
cases for ``launch.roofline`` (the static half the gate builds on)."""
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch import perfgate as pg
from repro.launch import roofline as roof

settings.register_profile("perfgate", deadline=None, max_examples=20)
settings.load_profile("perfgate")


# ------------------------------------------------------- HLO collective bytes
HLO_MIXED = """
ENTRY %main {
  %p = bf16[64,512]{1,0} parameter(0)
  %ag = bf16[128,512]{1,0} all-gather(%p), replica_groups={{0,1}}
  %rs = f32[32,512]{1,0} reduce-scatter(%q), replica_groups={{0,1}}
}
"""

HLO_NO_COLLECTIVES = """
ENTRY %main {
  %p = f32[128,128]{1,0} parameter(0)
  %d = f32[128,128]{1,0} dot(%p, %p)
}
"""


def test_collective_bytes_mixed_ops_and_dtypes():
    got = roof.collective_bytes(HLO_MIXED)
    # all-gather output is bf16 (2 bytes); reduce-scatter output is f32;
    # both carry wire factor 1.0 (only all-reduce moves the shape twice)
    assert got["all-gather"] == 128 * 512 * 2 * 1.0
    assert got["reduce-scatter"] == 32 * 512 * 4 * 1.0


def test_collective_bytes_empty_when_no_collectives():
    assert roof.collective_bytes(HLO_NO_COLLECTIVES) == {}


# ------------------------------------------------------------------- ceilings
def test_stream_bytes_counts_streams():
    assert pg.stream_bytes(1000) == 1000 * 2 * 4
    assert pg.stream_bytes(1000, streams=3) == 1000 * 3 * 4
    with pytest.raises(ValueError):
        pg.stream_bytes(-1)
    with pytest.raises(ValueError):
        pg.stream_bytes(10, streams=0)


@given(st.integers(0, 10**12), st.integers(1, 10**12))
def test_memory_s_monotone_in_bytes(extra, base):
    """More bytes can never take less time at fixed bandwidth."""
    bw = pg.Bandwidth(gbps=50.0, source="model", backend="cpu")
    assert pg.memory_s(base + extra, bw) >= pg.memory_s(base, bw)


def test_memory_s_validates_inputs():
    bw = pg.Bandwidth(gbps=10.0, source="model", backend="cpu")
    assert pg.memory_s(10e9, bw) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        pg.memory_s(-1.0, bw)
    with pytest.raises(ValueError):
        pg.memory_s(1.0, 0.0)


def test_ceiling_and_fraction_roundtrip():
    bw = pg.Bandwidth(gbps=80.0, source="measured", backend="cpu")
    # 2 f32 streams/point at 80 GB/s -> 10,000 Mpts/s ceiling
    assert pg.ceiling_mpts(bw) == pytest.approx(10_000.0)
    assert pg.roofline_fraction(1_000.0, bw) == pytest.approx(0.1)
    # 3 streams lowers the ceiling, raising the achieved fraction
    assert (pg.roofline_fraction(1_000.0, bw, streams=3)
            > pg.roofline_fraction(1_000.0, bw, streams=2))


def test_measure_bandwidth_sane_and_cached():
    bw = pg.measure_bandwidth(n_mb=4, reps=2, iters=2, force=True)
    assert bw.backend == jax.default_backend()
    assert bw.source == "measured"
    assert 0.1 < bw.gbps < 1e5
    assert pg.measure_bandwidth() is bw          # cache hit


# ----------------------------------------------------------------------- gate
def _row(name, us, *, frac=None, interpret=False, status="ok", **kw):
    r = {"name": name, "us_per_call": us, "interpret": interpret,
         "status": status}
    if frac is not None:
        r["roofline_frac"] = frac
    r.update(kw)
    return r


BASELINE = {
    "default_max_slowdown": 3.0,
    "rows": {
        "moments_jnp": {"ref_us": 100.0},
        "serve_fit": {"ref_us": 200.0, "max_slowdown": 2.0},
        "moments_packed": {"ref_us": 50.0, "min_roofline_frac": 0.05},
    },
}


def test_gate_passes_within_budget():
    rows = [_row("moments_jnp", 250.0, frac=0.5),
            _row("serve_fit", 399.0),
            _row("moments_packed", 60.0, frac=0.10)]
    rep = pg.check_gate(rows, BASELINE)
    assert rep.ok and len(rep.checked) == 3
    assert "PASS" in rep.render()


def test_gate_regression_breach_names_row_and_budget():
    rows = [_row("moments_jnp", 100.0),
            _row("serve_fit", 401.0),                    # > 200 x 2.0
            _row("moments_packed", 50.0, frac=0.10)]
    rep = pg.check_gate(rows, BASELINE)
    assert not rep.ok
    (b,) = rep.breaches
    assert b.row == "serve_fit" and b.kind == "regression"
    assert b.budget == pytest.approx(400.0)
    assert b.measured == pytest.approx(401.0)
    assert "serve_fit" in rep.render() and "400.0" in b.detail


def test_gate_roofline_floor_binds_on_hardware_rows():
    rows = [_row("moments_jnp", 100.0),
            _row("serve_fit", 200.0),
            _row("moments_packed", 50.0, frac=0.01)]     # below 0.05 floor
    rep = pg.check_gate(rows, BASELINE)
    (b,) = rep.breaches
    assert b.row == "moments_packed" and b.kind == "roofline"
    assert b.budget == pytest.approx(0.05)


def test_gate_roofline_floor_excluded_for_interpret_rows():
    rows = [_row("moments_jnp", 100.0),
            _row("serve_fit", 200.0),
            _row("moments_packed", 50.0, frac=0.0001, interpret=True)]
    rep = pg.check_gate(rows, BASELINE)
    assert rep.ok
    assert any("interpret" in s for s in rep.skipped)


def test_gate_missing_and_failed_rows_breach():
    rows = [_row("moments_jnp", 100.0, status="failed", error="boom"),
            _row("moments_packed", 50.0, frac=0.10)]
    rep = pg.check_gate(rows, BASELINE)
    kinds = {b.row: b.kind for b in rep.breaches}
    assert kinds == {"moments_jnp": "failed", "serve_fit": "missing"}
    assert "boom" in next(b.detail for b in rep.breaches
                          if b.kind == "failed")


def test_make_baseline_sets_floors_only_on_hardware_rows():
    rows = [_row("a", 100.0, frac=0.2, interpret=False),
            _row("b", 50.0, frac=0.3, interpret=True),
            _row("c", 10.0, status="failed"),
            _row("d", 10.0)]
    base = pg.make_baseline(rows, roofline_margin=0.5, gated=("a", "b", "c"))
    assert base["rows"]["a"] == {"ref_us": 100.0, "min_roofline_frac": 0.1}
    assert base["rows"]["b"] == {"ref_us": 50.0}         # interpret: no floor
    assert "c" not in base["rows"]                       # failed: no budget
    assert "d" not in base["rows"]                       # not gated
    # and the derived baseline gates its own run clean
    assert pg.check_gate(rows, base).ok
