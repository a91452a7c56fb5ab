"""Helpers for the benchmark's tests: run a cell on the CPU at a test
size, with the harness's look for a chip skipped."""
import io
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402
from bench.traffic import generator  # noqa: E402

SMALL_POINTS = 1 << 16   # resident series: 2^16 points
SMALL_RATE = 30          # serving: requests a second
SECONDS = 1.0

# cells whose files are in bench/ but which BENCHMARK.json does not run
# until their limits come from chip readings (PERF.md, open questions):
# configuration, traffic and end-to-end metrics
FILE_CELLS = {
    "large_fit": ("paper-large-series", "closed_loop", ["setup_s", "fit_ms"]),
}


def cell(workload: str) -> harness.Cell:
    if workload not in FILE_CELLS:
        return harness.resolve(workload)
    config, traffic, metrics = FILE_CELLS[workload]
    path = ROOT / "bench" / "configs" / f"{config}.json"
    return harness.Cell(workload, 1, json.loads(path.read_text()),
                        generator.load(traffic),
                        [{"name": m, "unit": "-"} for m in metrics], [])


def small_cell(workload: str) -> harness.Cell:
    c = cell(workload)
    if "points" in c.config:
        c.config = dict(c.config, points=SMALL_POINTS)
    if c.traffic.get("rate_per_s"):
        c.traffic = dict(c.traffic, rate_per_s=SMALL_RATE)
    return c


def run_small(workload: str, seed: int = 7, trace: bool = False) -> dict:
    """The result line of one CPU run of ``workload`` at a test size."""
    import jax
    jax.clear_caches()   # nothing traced before a planted fault survives
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(small_cell(workload), seed, SECONDS, trace,
                          process_start=time.perf_counter(),
                          require_chip=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])
