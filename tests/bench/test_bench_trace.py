"""The trace reduction, on a small trace recorded on a TPU v5e
(tests/bench/record_trace_fixture.py) and on made-up traces."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import trace_reduce as tr  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "fixtures"


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((FIXTURE / "tpu_small.json").read_text())
    trace = tr.read(str(FIXTURE / "tpu_small.xplane.pb"))
    return meta, trace, tr.reduce(trace, {0})


def test_the_recorded_trace_has_one_chip_and_the_benchmark_spans(recorded):
    meta, trace, summary = recorded
    assert list(trace.devices) == [0] and trace.devices[0]
    names = [s.name for s in summary.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.fit") == meta["fits"] == len(names) - 1


def test_busy_and_idle_partition_the_window(recorded):
    _, _, summary = recorded
    dev = summary.devices[0]
    window_ns = summary.window[1] - summary.window[0]
    gaps = sum(e - s for s, e in dev.gaps)
    assert dev.busy_ns + gaps == pytest.approx(window_ns, abs=1.0)
    assert 0 < summary.busy_s < summary.window_s
    assert summary.idle_share() == pytest.approx(gaps / window_ns)
    assert summary.collective_share() == 0.0


def test_the_recorded_readings_stay_as_they_were(recorded):
    # the numbers the reduction gave this trace before it was scoped to a
    # cell's chips: the trace has one chip, so nothing may move
    _, _, summary = recorded
    assert summary.busy_s == 0.0022603610000000002
    assert summary.window_s == 0.008494400000000001
    assert summary.idle_share() == 0.7338998634394425
    assert summary.collective_share() == 0.0
    assert summary.top_ops()[:3] == [
        ["moments_extended (custom-call)", 0.002135764],
        ["while (while)", 5.2897000000000005e-05],
        ["broadcast_in_dim (broadcast)", 4.9179000000000005e-05]]
    assert summary.idle_gaps()[:3] == [
        ["bench.fit", 0.00220649], ["bench.fit", 0.002130491],
        ["bench.fit", 0.0018772980000000001]]


def test_the_kernel_leads_the_breakdown(recorded):
    meta, _, summary = recorded
    assert meta["plan"] == "kernel_plain"
    out = summary.breakdown()
    assert out["device_ops"][0][0] == "moments_extended (custom-call)"
    assert len(out["device_ops"]) <= tr.TOP
    assert 0 < len(out["idle_gaps"]) <= tr.TOP
    assert {g[0] for g in out["idle_gaps"]} <= {"bench.fit", "bench.window"}
    lengths = [g[1] for g in out["idle_gaps"]]
    assert lengths == sorted(lengths, reverse=True)
    assert summary.span_mean_s("bench.fit") > 0


@pytest.mark.parametrize("event,label,collective", [
    ("%moments_extended.1 = f32[1,128,128]{2,1,0:T(8,128)S(1)} "
     "custom-call(f32[1,400003072]{1,0:T(1,128)} %pad.6)",
     "moments_extended (custom-call)", False),
    ("%pad.8.clone = f32[400003072]{0:T(1024)} pad(f32[400000000]{0} %b)",
     "pad (pad)", False),
    ("%fusion.160 = (bf16[]{:T(256)}, s32[]{:T(128)}) fusion(f32[4]{0} %a)",
     "fusion (fusion)", False),
    ("%all-reduce.3 = f32[6,6]{1,0} all-reduce(f32[6,6]{1,0} %m)",
     "all-reduce (all-reduce)", True),
    ("%all-reduce-start.2 = f32[6]{0} all-reduce-start(f32[6]{0} %m)",
     "all-reduce-start (all-reduce-start)", True),
    ("%fusion.9 = f32[8]{0} collective-permute-done(f32[8]{0} %p)",
     "fusion (collective-permute-done)", True),
    ("some runtime event", "some runtime event", False),
])
def test_op_labels(event, label, collective):
    assert tr.op_label(event) == (label, collective)


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 6), (0, 2), (1, 3), (5, 5.5), (7, 9)]) == [
        (0, 3), (5, 6), (7, 9)]


def test_the_idlest_chip_sets_the_idle_share():
    op = tr.Op
    trace = tr.Trace(
        devices={0: [op("fit (custom-call)", False, 0, 80),
                     op("psum (all-reduce)", True, 80, 90)],
                 1: [op("fit (custom-call)", False, 0, 50),
                     op("psum (all-reduce)", True, 50, 90)]},
        spans=[tr.Span("bench.window", 0, 100),
               tr.Span("bench.fit", 0, 95)])
    s = tr.reduce(trace, {0, 1})
    assert s.busy_s == pytest.approx(90e-9)
    assert s.idle_share() == pytest.approx(0.1)
    assert s.collective_share() == pytest.approx(50 / 180)
    assert s.top_ops()[0] == ["fit (custom-call)", pytest.approx(65e-9)]
    assert s.idle_gaps() == [["bench.fit", pytest.approx(10e-9)]]


def test_a_trace_without_its_window_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(tr.Trace(devices={}, spans=[]), ())


def _host_of(planes):
    """A made-up trace of a host with chips ``planes``, where only chip 0
    ran operations; the window is 100 ns."""
    op = tr.Op
    ran = [op("fit (custom-call)", False, 10, 40),
           op("solve (while)", False, 40, 50),
           op("fit (custom-call)", False, 70, 90)]
    return tr.Trace(devices={p: list(ran) if p == 0 else [] for p in planes},
                    spans=[tr.Span("bench.window", 0, 100),
                           tr.Span("bench.fit", 5, 55),
                           tr.Span("bench.fit", 60, 95)])


def test_a_one_chip_cell_on_a_host_of_four_reads_as_on_a_host_of_one():
    alone = tr.reduce(_host_of([0]), {0})
    among = tr.reduce(_host_of([0, 1, 2, 3]), {0})
    assert list(among.devices) == [0]
    assert among.busy_s == alone.busy_s == pytest.approx(60e-9)
    assert among.idle_share() == alone.idle_share() == pytest.approx(0.4)
    assert among.top_ops() == alone.top_ops()
    assert among.idle_gaps() == alone.idle_gaps()
    assert among.devices[0].gaps == alone.devices[0].gaps == [
        (0, 10), (50, 70), (90, 100)]


def test_a_cell_chip_that_ran_nothing_is_wholly_idle():
    s = tr.reduce(_host_of([0, 1, 2, 3]), {0, 1, 2, 3})
    assert s.busy_s == pytest.approx(60e-9 / 4)
    assert s.idle_share() == 1.0
    assert s.top_ops()[0] == ["fit (custom-call)", pytest.approx(50e-9 / 4)]
    # the idle chip's one gap is the whole window, named by the span that
    # overlaps it most
    assert s.idle_gaps() == [["bench.fit", pytest.approx(100e-9)]]


def test_a_cell_whose_chips_all_ran_nothing_while_another_ran_is_refused():
    # the device-to-plane map named the wrong planes
    with pytest.raises(ValueError, match="/device:TPU:0 did"):
        tr.reduce(_host_of([0, 1, 2, 3]), {1})
    with pytest.raises(ValueError, match="/device:TPU:0 did"):
        tr.reduce(_host_of([0, 1, 2, 3]), {2, 3})


def test_a_cell_chip_with_no_plane_is_refused():
    with pytest.raises(ValueError, match="/device:TPU:4"):
        tr.reduce(_host_of([0, 1, 2, 3]), {0, 4})
    # off the TPU the cell has no chip planes to read
    assert tr.reduce(_host_of([]), ()).idle_share() is None
