"""An open-loop driver: requests are sent when they are due, whatever the
system under test is doing, and each is timed from its due time.

The driver and the system share one thread.  Between submissions it runs
one step of the system; a step that stalls makes every request that fell
due during it late, and that lateness counts in their latency.  The
driver reports how late it sent each request, so that a starved driver is
not read as a fast server.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable

import numpy as np

# idle waits shorter than this are spun, longer ones sleep until this
# close: on a loaded host a sleep can overrun by a tenth of a second, and
# every request due meanwhile would be sent that late
SPIN_S = 0.05


@dataclasses.dataclass
class OpenLoopResult:
    due_s: np.ndarray       # when each request was due, from window open
    sent_s: np.ndarray      # when it was submitted (nan: never)
    done_s: np.ndarray      # when its result was on the host (nan: never)
    window_s: float         # window open to the end of its last step
    steps: int              # system steps inside the window
    longest_step_s: float   # the slowest step inside the window
    oversleep_s: float      # the most a sleep overran what it asked for

    def latency_s(self) -> np.ndarray:
        return self.done_s - self.due_s

    def lateness_s(self) -> np.ndarray:
        return self.sent_s - self.due_s


class OpenLoop:
    """Drive ``submit(i)`` at ``due_s[i]`` and ``step()`` in between.

    ``step()`` runs one iteration of the system and returns the indices of
    the requests whose results it brought to the host; ``pending()`` says
    whether the system holds unfinished work.  ``clock`` and ``sleep`` are
    injectable so that a test can stall the system on a fake clock."""

    def __init__(self, due_s: np.ndarray, submit: Callable[[int], None],
                 step: Callable[[], Iterable[int]],
                 pending: Callable[[], bool], *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 span=None):
        self.due_s = np.asarray(due_s, np.float64)
        self.submit, self.step, self.pending = submit, step, pending
        self.clock, self.sleep = clock, sleep
        self.span = span
        k = self.due_s.size
        self.sent_s = np.full(k, np.nan)
        self.done_s = np.full(k, np.nan)
        self.t0 = None
        self.longest_step_s = 0.0
        self.oversleep_s = 0.0

    def _now(self) -> float:
        return self.clock() - self.t0

    def _step(self) -> None:
        start = self._now()
        if self.span is None:
            done = self.step()
        else:
            with self.span("bench.engine_step"):
                done = self.step()
        now = self._now()
        self.longest_step_s = max(self.longest_step_s, now - start)
        for i in done:
            self.done_s[i] = now

    def _sleep(self, s: float) -> None:
        start = self._now()
        self.sleep(s)
        self.oversleep_s = max(self.oversleep_s, self._now() - start - s)

    def run(self, seconds: float) -> OpenLoopResult:
        """The measured window: send on schedule for ``seconds``."""
        self.t0 = self.clock()
        k, i, steps = self.due_s.size, 0, 0
        while True:
            now = self._now()
            while i < k and self.due_s[i] <= now:
                self.submit(i)
                self.sent_s[i] = self._now()
                i += 1
            if now >= seconds and i >= k:
                break
            if self.pending():
                self._step()
                steps += 1
                continue
            wait = (self.due_s[i] if i < k else seconds) - now
            if wait > SPIN_S:
                self._sleep(wait - SPIN_S)
        return OpenLoopResult(self.due_s, self.sent_s, self.done_s,
                              self._now(), steps, self.longest_step_s,
                              self.oversleep_s)
