"""A cell on a host with more devices than it asks for: it runs on its
own, and its trace is read on its own chips alone."""
import types

import pytest

from _cells import run_small_on  # noqa: E402  (sets sys.path)

from bench import harness  # noqa: E402


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           ("half_the_blocks", False)])
def test_a_one_chip_cell_runs_on_a_host_of_four(fault, correct):
    got = run_small_on("large_fit", 4, fault=fault)
    assert got["host_devices"] == 4
    assert got["correct"] is correct, got["checks"]
    assert got["device"]["count"] == 1
    assert set(got["metrics"]) == {"setup_s", "fits_per_s"}


def test_the_trace_planes_are_those_of_the_cells_chips():
    tpu = [types.SimpleNamespace(platform="tpu", id=i) for i in range(4)]
    assert harness.plane_ordinals(tpu[:1]) == {0}
    assert harness.plane_ordinals(tpu[2:]) == {2, 3}
    cpu = [types.SimpleNamespace(platform="cpu", id=0)]
    assert harness.plane_ordinals(cpu) == set()
