"""Helpers for the benchmark's tests: run a cell on the CPU at a test
size, with the harness's look for a chip skipped, in this process
(``run_small``) or in a child process whose CPU backend has several
devices (``run_small_on``, which runs ``tests/bench/faults.py
--small``)."""
import io
import json
import os
import pathlib
import re
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

SMALL_POINTS = 1 << 16   # resident series: 2^16 points
SMALL_RATE = 30          # serving: requests a second
SECONDS = 1.0
CHILD_TIMEOUT_S = 600    # a child run, start-up and compiles included
_DEVICE_COUNT = re.compile(r"--xla_force_host_platform_device_count=\S*")


def small_cell(workload: str) -> harness.Cell:
    c = harness.resolve(workload)
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


def run_small_on(workload: str, host_devices: int, fault: str | None = None,
                 seed: int = 7) -> dict:
    """The result line of one CPU run of ``workload`` at a test size, in a
    child process whose CPU backend has ``host_devices`` devices, with the
    fault of ``tests/bench/faults.py`` named ``fault`` planted first (a
    monkeypatch of this process would not reach the child), and the
    child's count of devices as ``host_devices``."""
    flags = _DEVICE_COUNT.sub("", os.environ.get("XLA_FLAGS", "")).strip()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"{flags} --xla_force_host_platform_device_count={host_devices}"
    ).strip())
    cmd = [sys.executable, str(HERE / "faults.py"), "--small",
           "--workload", workload, "--seeds", str(seed)]
    if fault is not None:
        cmd += ["--fault", fault]
    got = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=CHILD_TIMEOUT_S)
    assert got.returncode == 0, got.stderr[-4000:]
    return json.loads(got.stdout.strip().splitlines()[-1])
