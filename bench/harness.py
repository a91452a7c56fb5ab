"""One run of one cell: find its files by name, set it up, measure it,
check what it produced, and print one result line.

Everything that belongs to one configuration, traffic mix or metric lives
in files of its own, found by the names in ``BENCHMARK.json``:

* ``configs[].file``: the configuration's sizes; its ``system`` key names
  ``bench/systems/<system>.py``, which builds the system under test;
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
* ``bench/metrics/<metric>.py``: one reader per metric, end-to-end or
  per-layer, with ``read(run) -> float | None``.  A metric split by the
  end-to-end metric it moves, ``<quantity>.<part>``, is read by
  ``<quantity>.py`` unless it has a file of its own.

A new cell or metric is new files plus entries in ``BENCHMARK.json``; no
file here changes.

A system module has ``build(ctx) -> system``, and the system has
``warm()``, ``window(seconds) -> Window``, ``finish()``, ``check() ->
list[Check]`` and ``notes() -> list[str]``, plus ``work_per_fit``, the
work of one fit on one chip (a ``bench.work.Work``) or None.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Callable

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")

_SAFE = re.compile(r"[^A-Za-z0-9_]")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Check:
    """One number the run compares with its limit; ``value <= limit``
    passes."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a system's measured window did."""
    window_s: float
    completed: int                  # fits whose results reached the host
    attempted: int                  # requests due, or fits begun
    failed: int = 0
    latencies_s: Any = None         # per request due (serving), else None
    counters: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    span: Callable[[str], Any]


@dataclasses.dataclass
class RunRecord:
    """What a metric reader reads."""
    cell: Cell
    setup_s: float
    window: Window
    work_per_fit: Any = None        # bench.work.Work per chip, or None
    peaks: Any = None               # bench.peaks.Peaks, or None
    trace: Any = None               # bench.trace_reduce.Summary, or None


def load_module(path: str):
    name = "bench_" + _SAFE.sub("_", os.path.relpath(path, BENCH))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def system_path(config: dict) -> str:
    return os.path.join(BENCH, "systems", f"{config['system']}.py")


def metric_path(name: str) -> str:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if os.path.isfile(path) or "." not in name:
        return path
    return metric_path(name.rsplit(".", 1)[0])


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, traffic and
    metrics read from their files."""
    from bench.traffic import generator
    spec = load_benchmark(root)
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    traffic = generator.load(cell["traffic"])
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, int(cell["chips"]), config, traffic, e2e,
                per_layer)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _metric(m: dict, run: RunRecord) -> dict | None:
    value = load_module(metric_path(m["name"])).read(run)
    if value is None or not math.isfinite(value):
        return None
    return {"value": float(value), "unit": m["unit"]}


class _GcPauses:
    """The interpreter's garbage collections while installed."""

    def __init__(self):
        self.count, self.longest_s, self._start = 0, 0.0, None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.count += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._start)


def _start_trace() -> str:
    import jax
    os.makedirs(OUT, exist_ok=True)
    tmp = tempfile.mkdtemp(prefix="trace-", dir=OUT)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # a span on every Python call would
    opts.host_tracer_level = 1     # slow the host loops under test
    opts.enable_hlo_proto = False  # the programs' HLO would fill the file
    jax.profiler.start_trace(tmp, profiler_options=opts)
    return tmp


def plane_ordinals(devices) -> set[int]:
    """The ``/device:TPU:<n>`` planes of ``devices`` in a profiler trace:
    a TPU's ``n`` is its ``Device.id``; a CPU has no such plane."""
    return {d.id for d in devices if d.platform == "tpu"}


def _read_trace(tmp: str, devices):
    """The trace under ``tmp``, reduced over ``devices`` alone."""
    from bench import trace_reduce
    try:
        paths = [os.path.join(d, f) for d, _, files in os.walk(tmp)
                 for f in files if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {tmp}, "
                               f"found {len(paths)}")
        return trace_reduce.reduce(trace_reduce.read(paths[0]),
                                   plane_ordinals(devices))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _number(v: float):
    return v if math.isfinite(v) else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             process_start: float, require_chip: bool = True,
             out=None, err=None) -> int:
    """Set up, measure, check and print; returns the exit code.

    With ``require_chip`` (every real run) a host with no TPU, or with
    fewer chips than the cell asks for, ends the run before any work and
    without a result line."""
    out = out or sys.stdout
    err = err or sys.stderr
    import jax

    from bench import peaks as peaks_lib

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform}); nothing ran",
              file=err)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devs)}; nothing ran", file=err)
        return 2
    devices = devs[:cell.chips]
    kind = devices[0].device_kind
    peaks = (peaks_lib.peaks_for(kind) if require_chip
             else peaks_lib.PEAKS.get(kind))

    ctx = Context(cell.config, cell.traffic, seed, seconds, devices, span)
    t_build = time.perf_counter()
    system = load_module(system_path(cell.config)).build(ctx)
    t_warm = time.perf_counter()
    system.warm()
    # what set-up made stays out of the collections the window triggers
    gc.collect()
    gc.freeze()
    t_end = time.perf_counter()
    setup_s = t_end - process_start

    from repro.analysis.sanitizers import CompileCounter
    pauses = _GcPauses()
    tmp = _start_trace() if trace else None
    gc.callbacks.append(pauses)
    try:
        with CompileCounter() as compiles, span("bench.window"):
            window = system.window(seconds)
    finally:
        gc.callbacks.remove(pauses)
        gc.unfreeze()
        if tmp is not None:
            jax.profiler.stop_trace()
    memory_peak = _memory_peak(devices)
    stats = devices[0].memory_stats() or {}
    print("bench: device memory: " + ", ".join(
        f"{k} {stats[k]}" for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_reserved", "peak_bytes_reserved",
                                    "bytes_limit") if k in stats), file=err)
    summary = _read_trace(tmp, devices) if tmp is not None else None
    system.finish()
    checks = system.check()
    notes = system.notes()

    run = RunRecord(cell, setup_s, window, system.work_per_fit, peaks,
                    summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        got = _metric(m, run)
        if got is not None:
            metrics[m["name"]] = got
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": all(c.ok for c in checks) and window.failed == 0,
              "attempted": int(window.attempted),
              "failed": int(window.failed), "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": _number(c.value),
                                 "limit": c.limit} for c in checks}
    print(f"bench: set-up s: start {t_build - process_start!r}, build "
          f"{t_warm - t_build!r}, warm {t_end - t_warm!r}", file=err)
    print(f"bench: in the window: {compiles.count} compiles "
          f"{compiles.names[:5]}, {pauses.count} garbage collections, the "
          f"longest {pauses.longest_s * 1e3:.3f} ms", file=err)
    for line in notes:
        print(f"bench: {line}", file=err)
    print(f"bench: setup_s={setup_s} window_s={window.window_s} "
          f"completed={window.completed} attempted={window.attempted} "
          f"failed={window.failed}", file=err)
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=err)
    err.flush()
    print(json.dumps(result, allow_nan=False), file=out)
    out.flush()
    return 0
