"""The fit server (``repro.serve.FitServeEngine``) under an open loop.

Payloads are made in set-up from the seed; in the window the driver only
submits them on schedule and steps the engine, in one thread.  A request
is done when its coefficients are on the host, which the engine's step
does for every request it completes.

The window stops at its close, and what is still queued then is
backlog, not failure: the mix offers more than the server sustains.  A
sample of the served requests, drawn from the seed with the longest
among them, is checked against the float64 reference.
"""
from __future__ import annotations

import numpy as np

from bench import reference
from bench.harness import Check, Window
from bench.traffic import generator
from bench.traffic.open_loop import OpenLoop

SAMPLE = 256      # served requests checked, drawn from the seed
LONGEST = 8       # plus this many of the longest served


class FitServer:
    def __init__(self, ctx):
        from repro.serve import FitServeConfig, FitServeEngine

        cfg = self.cfg = ctx.config
        traffic = self.traffic = ctx.traffic
        if traffic["loop"] != "open":
            raise ValueError("fit_server runs under an open loop")
        self.seed, self.span = ctx.seed, ctx.span
        self.degree = int(cfg["degree"])
        self.engine = FitServeEngine(FitServeConfig(
            degree=self.degree, n_slots=int(cfg["n_slots"]),
            buckets=tuple(cfg["buckets"]), ridge=float(cfg["ridge"])))
        self.schedule = generator.schedule(traffic, ctx.seed, ctx.seconds)
        self.payloads = generator.payloads(traffic, ctx.seed,
                                           self.schedule.lengths,
                                           self.degree)
        self.reqs: list = [None] * len(self.payloads)
        self.index: dict[int, int] = {}
        self.loop = None
        self.result = None
        self.work_per_fit = None

    def warm(self) -> None:
        self.engine.warmup()

    def _submit(self, i: int) -> None:
        with self.span("bench.submit"):
            x, y = self.payloads[i]
            req = self.reqs[i] = self.engine.submit(x, y)
            self.index[req.uid] = i

    def _step(self) -> list[int]:
        # the only requests a step can finish: those in slots, and those
        # it admits from the head of each bucket's queue
        cands = [r for b in self.engine.buckets for r in b.slot_req
                 if r is not None]
        cands += [r for b in self.engine.buckets
                  for r in b.queue[:len(b.slot_req)]]
        self.engine.step()
        return [self.index[r.uid] for r in cands if r.done]

    def window(self, seconds: float) -> Window:
        self.loop = OpenLoop(self.schedule.due_s, self._submit, self._step,
                             lambda: self.engine.pending > 0,
                             span=self.span)
        points0 = self.engine.points_ingested
        res = self.result = self.loop.run(seconds)
        return Window(
            window_s=res.window_s,
            completed=int(np.isfinite(res.done_s).sum()),
            attempted=len(self.reqs), latencies_s=res.latency_s(),
            counters={"points_ingested":
                      self.engine.points_ingested - points0,
                      "steps": res.steps})

    def finish(self) -> None:
        pass

    def _served(self) -> np.ndarray:
        return np.flatnonzero([r is not None and r.done for r in self.reqs])

    def sample(self, indices: np.ndarray) -> list[int]:
        """The requests checked: SAMPLE drawn from the seed, and the
        LONGEST longest."""
        rng = np.random.default_rng([self.seed, 3])
        pick = rng.choice(indices, min(SAMPLE, indices.size), replace=False)
        longest = indices[np.argsort(self.schedule.lengths[indices],
                                     kind="stable")][-LONGEST:]
        return sorted(set(pick.tolist()) | set(longest.tolist()))

    def readings(self, indices, answer) -> list[Check]:
        """The numbers compared over requests ``indices``, whose answers
        ``answer(i) -> (coeffs, reported SSE, reported count)`` gives: the
        worst excess SSE, the worst gap of a reported SSE from the true SSE
        of its coefficients, and the worst gap between the points a fit
        says it used and the request's length, which must be exact."""
        excess, gap, count = [0.0], [0.0], [0.0]
        for i in indices:
            coeffs, sse, n = answer(i)
            x, y = self.payloads[i]
            ref = reference.F64Fit(x, y, self.degree)
            excess.append(ref.excess_sse(coeffs))
            gap.append(ref.sse_gap(coeffs, sse))
            count.append(abs(float(n) / x.size - 1.0))
        limits = self.cfg["limits"]
        return [Check("excess_sse", max(excess), limits["excess_sse"]),
                Check("sse_gap", max(gap), limits["sse_gap"]),
                Check("count_gap", max(count), 0.0)]

    def check(self) -> list[Check]:
        return self.readings(self.sample(self._served()),
                             lambda i: (self.reqs[i].coeffs,
                                        self.reqs[i].sse,
                                        self.reqs[i].count))

    def notes(self) -> list[str]:
        res = self.result
        late = res.lateness_s()
        lat = res.latency_s()
        in_window = np.isfinite(res.done_s) & (res.done_s <= res.window_s)
        lines = [
            f"offered {len(self.reqs)} requests in {res.window_s:.3f} s "
            f"({len(self.reqs) / res.window_s:.1f}/s), "
            f"{int(in_window.sum())} served in the window, "
            f"{res.steps} engine steps, the slowest "
            f"{res.longest_step_s * 1e3:.3f} ms, sleeps overran by at most "
            f"{res.oversleep_s * 1e3:.3f} ms",
            "generator lateness ms p50/p95/max "
            + "/".join(f"{v * 1e3:.3f}" for v in
                       (np.median(late), np.percentile(late, 95),
                        late.max())),
        ]
        if in_window.any():
            lines.append("latency ms of requests served in the window "
                         "p50/p95/max " + "/".join(
                             f"{v * 1e3:.3f}" for v in
                             (np.median(lat[in_window]),
                              np.percentile(lat[in_window], 95),
                              lat[in_window].max())))
        lines.append(f"backlog at the close: {self.engine.pending} requests")
        return lines


def build(ctx) -> FitServer:
    return FitServer(ctx)
