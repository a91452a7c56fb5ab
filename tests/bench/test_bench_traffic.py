"""The traffic generator and the open-loop driver, on a fake clock."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.traffic import generator  # noqa: E402
from bench.traffic.open_loop import OpenLoop  # noqa: E402

MIX = {"loop": "open", "rate_per_s": 500,
       "lengths": {"dist": "log_uniform", "min": 16, "max": 8192},
       "x_range": [-2.0, 2.0], "noise": 0.1}


class FakeClock:
    """Time moves by sleeps and steps, and by a microsecond a reading, as
    the driver's spin between sleeps needs."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-6
        return self.t

    def sleep(self, s):
        self.t += s


def _driver(due, step_s, stall_at=None, stall_s=0.0):
    """A system that finishes every request one step after it arrives;
    each step takes ``step_s``, the step at ``stall_at`` s ``stall_s``."""
    clock = FakeClock()
    queued = []

    def step():
        took = step_s
        if stall_at is not None and stall_at <= clock.t < stall_at + step_s:
            took += stall_s
        clock.t += took
        done = list(queued)
        queued.clear()
        return done

    loop = OpenLoop(due, queued.append, step, lambda: bool(queued),
                    clock=clock, sleep=clock.sleep)
    return loop.run(1.0)


def test_a_stall_inflates_the_latency_of_requests_due_after_it():
    due = np.arange(0.0, 1.0, 0.01)
    calm = _driver(due, 0.001)
    stalled = _driver(due, 0.001, stall_at=0.5, stall_s=0.2)
    assert np.all(np.isfinite(calm.done_s)) and np.all(
        np.isfinite(stalled.done_s))
    before = due < 0.5
    inside = (due > 0.5) & (due < 0.7)
    np.testing.assert_allclose(stalled.latency_s()[before],
                               calm.latency_s()[before])
    assert calm.latency_s().max() < 0.003
    # every request that fell due during the stall waited for it
    assert np.all(stalled.latency_s()[inside] > 0.7 - due[inside] - 1e-9)
    assert np.all(stalled.lateness_s()[inside] > 0)
    assert stalled.lateness_s()[before].max() < 0.002


def test_every_seed_gets_the_same_work_in_another_order():
    a = generator.schedule(MIX, 1, 10.0)
    b = generator.schedule(MIX, 2**31 + 5, 10.0)
    assert a.lengths.size == b.lengths.size == 5000
    np.testing.assert_array_equal(np.sort(a.lengths), np.sort(b.lengths))
    assert not np.array_equal(a.lengths, b.lengths)
    for s in (a, b):
        assert np.all(np.diff(s.due_s) > 0)
        assert 0 < s.due_s[0] and s.due_s[-1] < 10.0
    np.testing.assert_allclose(np.sort(np.diff(a.due_s)),
                               np.sort(np.diff(b.due_s)), rtol=0.05,
                               atol=1e-3)
    assert a.lengths.min() >= 16 and a.lengths.max() <= 8192


def test_payloads_come_from_the_seed():
    s = generator.schedule(MIX, 7, 0.1)
    p1 = generator.payloads(MIX, 7, s.lengths, 3)
    p2 = generator.payloads(MIX, 7, s.lengths, 3)
    p3 = generator.payloads(MIX, 8, s.lengths, 3)
    assert [x.size for x, _ in p1] == s.lengths.tolist()
    for (x1, y1), (x2, y2), (x3, _) in zip(p1, p2, p3):
        np.testing.assert_array_equal(x1, x2)
        np.testing.assert_array_equal(y1, y2)
        assert x1.dtype == y1.dtype == np.float32
        assert not np.array_equal(x1, x3)


def test_an_unknown_mix_is_refused(tmp_path):
    (tmp_path / "bad.json").write_text('{"loop": "sideways"}')
    with pytest.raises(ValueError, match="loop"):
        generator.load("bad", str(tmp_path))
    with pytest.raises(ValueError, match="distribution"):
        generator.length_set({"dist": "zipf"}, 4)
