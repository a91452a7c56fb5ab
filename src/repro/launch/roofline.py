"""Roofline-term extraction from compiled (partitioned) executables.

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (DESIGN.md §6).

  compute_s    = per-device HLO FLOPs / 197e12
  memory_s     = per-device HLO bytes accessed / 819e9
  collective_s = per-device collective wire bytes / 50e9

``cost_analysis()`` on a compiled partitioned executable reports per-device
FLOPs/bytes (verified empirically in tests). Collective bytes are parsed from
the partitioned HLO text; wire-byte model per op (ring algorithm):
  all-reduce        2·(n-1)/n · bytes  ≈ 2·bytes
  all-gather        (n-1)/n · out_bytes ≈ out_bytes
  reduce-scatter    (n-1)/n · in_bytes  ≈ in_bytes
  all-to-all        (n-1)/n · bytes     ≈ bytes
  collective-permute  bytes
(n is not recovered per-op from text; the ≈ forms are used and noted.)
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# e.g. "f32[128,256]{1,0}" or "bf16[2,16]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_WIRE_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device wire bytes by collective kind from partitioned HLO text.
    Skips the '-done' halves of async pairs (shape appears on both)."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done.1" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_txt, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_txt)
        out[kind] = out.get(kind, 0.0) + b * _WIRE_FACTOR[kind]
    return out


@dataclasses.dataclass(frozen=True)
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    coll_bytes: float            # per device (wire model)
    coll_breakdown: dict
    peak_memory: int             # per device, bytes (from memory_analysis)

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def summary(self) -> dict:
        return {
            "flops_per_dev": self.flops,
            "bytes_per_dev": self.bytes_accessed,
            "coll_bytes_per_dev": self.coll_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
            "peak_memory_gb": self.peak_memory / 1e9,
            "coll_breakdown": self.coll_breakdown,
        }


def analyze(compiled) -> Roofline:
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return Roofline(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=sum(coll.values()),
        coll_breakdown=coll,
        peak_memory=int(peak),
    )

