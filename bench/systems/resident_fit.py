"""One large series resident in device memory, fitted again and again by
one caller in a closed loop through ``api.fit``: the paper's regime.

Each fit's coefficients and reported SSE are brought to the host, which
ends the fit; all of them are checked against the float64 reference once
the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.harness import Check, Window
from bench.work import fit_work


def make_series(key, n: int, cfg: dict, sharding):
    """x uniform on ``x_range``, y the configuration's polynomial plus
    N(0, noise²), made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp
    lo, hi = cfg["x_range"]
    coeffs = [float(c) for c in cfg["true_coeffs"]]

    def gen(key):
        kx, ke = jax.random.split(key)
        x = jax.random.uniform(kx, (n,), jnp.float32, lo, hi)
        y = jnp.zeros_like(x)
        for c in coeffs[::-1]:
            y = y * x + c
        return x, y + cfg["noise"] * jax.random.normal(ke, (n,), jnp.float32)

    return jax.block_until_ready(jax.jit(gen, out_shardings=sharding)(key))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, however large."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class ResidentFit:
    def __init__(self, ctx):
        import jax
        from repro import api

        cfg = self.cfg = ctx.config
        traffic = ctx.traffic
        if traffic["loop"] != "closed" or traffic.get("callers", 1) != 1:
            raise ValueError("resident_fit runs one closed-loop caller")
        self.span = ctx.span
        self.degree = int(cfg["degree"])
        self.n = int(cfg["points"])
        if len(ctx.devices) != 1:
            raise ValueError("resident_fit runs api.fit on one chip")
        spec = api.FitSpec(degree=self.degree, engine=cfg["engine"])
        self.fit = lambda x, y: api.fit(x, y, spec)
        self.work_per_fit = fit_work(self.n, self.degree)
        self.x, self.y = make_series(
            seed_key(ctx.seed), self.n, cfg,
            jax.sharding.SingleDeviceSharding(ctx.devices[0]))
        self.answers: dict[bytes, list] = {}
        self.host = None
        self.host_s = 0.0   # in api.fit until it has dispatched the fit

    def _one(self):
        import jax
        t0 = time.perf_counter()
        r = self.fit(self.x, self.y)
        t1 = time.perf_counter()
        got = jax.device_get((r.poly.coeffs, r.poly.domain_shift,
                              r.poly.domain_scale, r.report.sse))
        self.host_s += t1 - t0
        return tuple(np.asarray(a, np.float64) for a in got)

    def warm(self) -> None:
        for _ in range(2):
            self._one()

    def window(self, seconds: float) -> Window:
        fits, self.host_s = 0, 0.0
        t0 = time.perf_counter()
        while True:
            with self.span("bench.fit"):
                got = self._one()
            fits += 1
            key = b"".join(a.tobytes() for a in got)
            if key in self.answers:
                self.answers[key][1] += 1
            else:
                self.answers[key] = [got, 1]
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                return Window(window_s=elapsed, completed=fits,
                              attempted=fits)

    def finish(self) -> None:
        """Bring the series to the host and free the device."""
        self.host = (np.asarray(self.x), np.asarray(self.y))
        self.x = self.y = None

    def check(self) -> list[Check]:
        return self.readings([a for a, _ in self.answers.values()])

    def readings(self, answers) -> list[Check]:
        """The numbers compared, over answers (coeffs, domain shift,
        domain scale, reported SSE) for the series on the host: the worst
        excess SSE of the coefficients, and the worst gap of a reported
        SSE from the true SSE of its coefficients.  The gap is what sees
        points the moment pass left out: the fit of half of an i.i.d.
        series is a sound fit of the whole, but its SSE is half."""
        ref = reference.F64Fit(*self.host, self.degree)
        excess, gap = [], []
        for coeffs, shift, scale, sse in answers:
            raw = reference.raw_monomial(coeffs, shift, scale)
            excess.append(ref.excess_sse(raw))
            gap.append(ref.sse_gap(raw, sse))
        limits = self.cfg["limits"]
        return [Check("excess_sse", max(excess), limits["excess_sse"]),
                Check("sse_gap", max(gap), limits["sse_gap"])]

    def notes(self) -> list[str]:
        fits = sum(c for _, c in self.answers.values())
        return [f"distinct answers {len(self.answers)} over {fits} fits; "
                f"api.fit took {1e3 * self.host_s / fits:.3f} ms a fit to "
                f"dispatch"]


def build(ctx) -> ResidentFit:
    return ResidentFit(ctx)
