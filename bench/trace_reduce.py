"""Reduce one JAX profiler trace (``.xplane.pb``) to the benchmark's
device and host numbers.

What a TPU trace holds, as read by ``jax.profiler.ProfileData``:

* one plane per chip the profiler saw, ``/device:TPU:<n>``, which on a
  host of several chips may include chips the cell does not use; its
  line ``XLA Ops`` has one event per HLO operation, named by the
  instruction's text (``%moments_extended.1 = f32[...]
  custom-call(...)``), which may nest (a ``while`` holds its body's
  operations);
* the plane ``/host:CPU``, whose lines are host threads; the benchmark's
  own ``TraceAnnotation`` spans, all named ``bench.*``, are on the
  thread that ran the window.

Host and device events share one clock, in ns from the trace's start.
The measured window is the host span ``bench.window``; every number is
taken inside it, over the cell's own chips: a one-chip cell on a host of
four reads like one on a host of one.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=.*?\s([a-z][a-z0-9_\-]*)\(")
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Op:
    label: str          # instruction name without its number, and opcode
    collective: bool
    start: float        # ns
    end: float


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Op]]
    spans: list[Span]


def op_label(event_name: str) -> tuple[str, bool]:
    """(label, collective) of an ``XLA Ops`` event: ``moments_extended
    (custom-call)`` for ``%moments_extended.1 = ... custom-call(...)``."""
    m = _INSTR.match(event_name)
    if m is None:
        return event_name[:80], bool(COLLECTIVE.search(event_name[:80]))
    name, opcode = _SUFFIX.sub("", m.group(1)), m.group(2)
    return f"{name} ({opcode})", bool(COLLECTIVE.search(name + " " + opcode))


def read(path: str) -> Trace:
    """The device operations of every TPU plane and the host's
    ``bench.*`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[int, list[Op]] = {}
    spans: list[Span] = []
    labels: dict[str, tuple[str, bool]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    got = labels.get(e.name)
                    if got is None:
                        got = labels[e.name] = op_label(e.name)
                    ops.append(Op(got[0], got[1], e.start_ns, e.end_ns))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns, e.end_ns))
    return Trace(devices, spans)


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class DeviceSummary:
    busy_ns: float
    collective_ns: float
    op_ns: dict[str, float]
    gaps: list[tuple[float, float]]


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]          # ns
    devices: dict[int, DeviceSummary]
    spans: list[Span]                    # inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the cell's
        chips."""
        if not self.devices:
            return 0.0
        return (sum(d.busy_ns for d in self.devices.values())
                / len(self.devices) * 1e-9)

    def idle_share(self) -> float | None:
        """1 − busy / window on the cell's chip that was idle longest."""
        if not self.devices:
            return None
        busy = min(d.busy_ns for d in self.devices.values())
        return 1.0 - busy * 1e-9 / self.window_s

    def collective_share(self) -> float | None:
        """Device time inside collective operations over busy time, on
        the cell's chips."""
        busy = sum(d.busy_ns for d in self.devices.values())
        if not busy:
            return None
        return sum(d.collective_ns for d in self.devices.values()) / busy

    def span_mean_s(self, name: str) -> float | None:
        d = [s.end - s.start for s in self.spans if s.name == name]
        return sum(d) / len(d) * 1e-9 if d else None

    def top_ops(self) -> list[list]:
        """The device operations that took most time, in seconds per
        chip of the cell."""
        total: dict[str, float] = {}
        for d in self.devices.values():
            for k, v in d.op_ns.items():
                total[k] = total.get(k, 0.0) + v
        n = max(len(self.devices), 1)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n * 1e-9] for k, v in ranked]

    def idle_gaps(self) -> list[list]:
        """The longest idle gaps of the cell's idlest chip, each named by
        the host span that overlaps it most (the innermost ``bench.*``
        span other than the window; ``bench.window`` where none does)."""
        if not self.devices:
            return []
        idlest = min(self.devices.values(), key=lambda d: d.busy_ns)
        gaps = sorted(idlest.gaps, key=lambda g: g[0] - g[1])[:TOP]
        inner = sorted((s for s in self.spans if s.name != WINDOW_SPAN),
                       key=lambda s: s.start)
        starts = [s.start for s in inner]
        out = []
        for g0, g1 in gaps:
            best, label = 0.0, WINDOW_SPAN
            # spans are short next to the window; look back far enough to
            # catch one that began before the gap and is still open
            lo = bisect.bisect_left(starts, g0 - 60e9)
            for s in inner[lo:bisect.bisect_right(starts, g1)]:
                overlap = min(s.end, g1) - max(s.start, g0)
                if overlap > best:
                    best, label = overlap, s.name
            out.append([label, (g1 - g0) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def reduce(trace: Trace, chips) -> Summary:
    """Everything inside the ``bench.window`` span, on the chips whose
    plane ordinals are ``chips``: the cell's own, none off the TPU.  The
    host's other chips are left out; a cell's chip whose plane ran
    nothing in the window is wholly idle, and one with no plane at all is
    an error, since its busy time is unknown.  So is a trace in which no
    chip of the cell ran anything in the window while another chip did:
    the planes named are then not the cell's."""
    windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    chips = sorted(set(chips))
    missing = [c for c in chips if c not in trace.devices]
    if missing:
        raise ValueError(
            "the trace has no plane for the cell's chip(s) "
            + ", ".join(f"/device:TPU:{c}" for c in missing)
            + f" (it has TPU planes {sorted(trace.devices)})")
    w0, w1 = windows[0].start, windows[0].end
    devices = {}
    for dev in chips:
        ops = trace.devices[dev]
        clipped = [(max(o.start, w0), min(o.end, w1), o) for o in ops
                   if o.end > w0 and o.start < w1]
        busy = union((s, e) for s, e, _ in clipped)
        coll = union((s, e) for s, e, o in clipped if o.collective)
        op_ns: dict[str, float] = {}
        for s, e, o in clipped:
            op_ns[o.label] = op_ns.get(o.label, 0.0) + (e - s)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices[dev] = DeviceSummary(_length(busy), _length(coll), op_ns,
                                     gaps)
    if devices and not any(d.busy_ns for d in devices.values()):
        ran = [p for p, ops in sorted(trace.devices.items())
               if p not in devices
               and any(o.end > w0 and o.start < w1 for o in ops)]
        if ran:
            raise ValueError(
                "no chip of the cell ran anything in the window, but "
                + ", ".join(f"/device:TPU:{p}" for p in ran)
                + f" did: the cell's chips are not planes {chips}")
    spans = [s for s in trace.spans if s.end > w0 and s.start < w1]
    return Summary((w0, w1), devices, spans)
