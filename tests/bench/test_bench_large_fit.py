"""The ``large_fit`` cell as ``BENCHMARK.json`` resolves it, run on the CPU
at small point counts, one ragged and one a whole number of blocks: a
sound run comes out correct and reports its end-to-end metrics, and each
fault the resident fit can have comes out not correct under the
configuration's limits, which were set from chip readings at 1e9
points."""
import io
import json
import time

import jax
import pytest

from _cells import SECONDS, SMALL_POINTS  # noqa: E402  (sets sys.path)
from faults import FAULTS  # noqa: E402

from bench import harness  # noqa: E402

RAGGED = 65_539   # no multiple of 128: the kernel's last block is ragged


def _run(seed: int, points: int) -> dict:
    jax.clear_caches()   # nothing traced before a planted fault survives
    cell = harness.resolve("large_fit")
    cell.config = dict(cell.config, points=points)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(cell, seed, SECONDS, False,
                          process_start=time.perf_counter(),
                          require_chip=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_cell_is_the_resident_1e9_series():
    cell = harness.resolve("large_fit")
    assert cell.chips == 1 and cell.traffic == {"loop": "closed",
                                                "callers": 1}
    cfg = cell.config
    assert cfg["name"] == "paper-large-series-1e9"
    assert (cfg["system"], cfg["points"], cfg["degree"], cfg["engine"]) == (
        "resident_fit", 1_000_000_000, 3, "auto")
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "fits_per_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle.large_fit", "fit_roofline.large_fit"}


@pytest.mark.parametrize("seed,points", [
    pytest.param(7, RAGGED, id="7"),
    pytest.param(2**31 + 5, RAGGED, id="2147483653"),
    pytest.param(7, SMALL_POINTS, id="7-whole")])
def test_a_sound_run_is_correct(seed, points):
    got = _run(seed, points)
    assert got["correct"], got["checks"]
    assert got["failed"] == 0 and got["attempted"] > 0
    assert set(got["metrics"]) == {"setup_s", "fits_per_s"}
    assert set(got["checks"]) == {"excess_sse", "sse_gap"}


@pytest.mark.parametrize("fault,points", [
    pytest.param(f, points, id=f.__name__ + suffix)
    for points, suffix in ((RAGGED, ""), (SMALL_POINTS, "-whole"))
    for f in FAULTS["resident_fit"]])
def test_a_broken_fit_is_not_correct(monkeypatch, fault, points):
    fault(monkeypatch)
    got = _run(7, points)
    assert not got["correct"], got["checks"]
