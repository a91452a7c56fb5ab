"""Work counted from the problem's shape, and the table of peaks."""
import pathlib
import sys

import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import peaks, work  # noqa: E402
from repro import engine  # noqa: E402


@pytest.mark.parametrize("shape", [(1 << 20,), (8, 4096), (3, 5, 2048)])
def test_work_does_not_depend_on_the_path(shape):
    counted = set()
    for eng, path in (("reference", engine.REFERENCE),
                      ("kernel_plain", engine.KERNEL_PLAIN),
                      ("kernel_packed", engine.KERNEL_PACKED)):
        plan = engine.plan_fit(shape, 3, dtype=jnp.float32, engine=eng,
                               backend="tpu")
        assert plan.path == path
        counted.add(work.plan_work(plan))
    assert len(counted) == 1
    points = 1
    for s in shape:
        points *= s
    assert counted == {work.fit_work(points, 3)}


def test_the_large_fit_is_memory_bound_at_3_9_ms():
    v5e = peaks.peaks_for("TPU v5 lite")
    w = work.fit_work(400_000_000, 3)
    assert w.bytes == 3.2e9 and w.bound(v5e) == "memory"
    assert w.least_time_s(v5e) == pytest.approx(3.2e9 / 819e9)
    assert (w * 2).bytes == (w + w).bytes == 6.4e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_an_unknown_chip_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for(kind)
