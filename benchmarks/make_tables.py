"""Render the committed BENCH_*.json files into the perf-trajectory table
of EXPERIMENTS.md (row x rev):

    PYTHONPATH=src python -m benchmarks.make_tables [--mode smoke]

The trajectory table is the history the perf gate's budgets are anchored
to: one column per benchmarked revision (git order), us/call per cell,
with the newest revision's achieved Mpts/s and roofline fraction broken
out in their own columns.  Interpret-mode Pallas rows are tagged ``*`` —
their absolute numbers are CPU-emulation artifacts (correctness tools,
excluded from the gate's roofline floors).
"""
import argparse
import glob
import json
import os
import re
import subprocess

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


# -------------------------------------------------------- trajectory tables
def _git_rev_order():
    """Map short-rev -> position in first-parent history (oldest first)."""
    try:
        out = subprocess.run(
            ["git", "log", "--format=%h", "--reverse"],
            cwd=BENCH_DIR, capture_output=True, text=True, check=True)
        return {h: i for i, h in enumerate(out.stdout.split())}
    except Exception:  # noqa: BLE001 — outside a checkout: timestamp order
        return {}


# BENCH_<rev>.json (full run) / BENCH_<rev>_smoke.json / BENCH_<rev>_quick.json.
# The mode suffix is matched against the known set, so revs containing
# underscores (or the "norev" fallback) parse correctly.
_BENCH_RE = re.compile(r"^BENCH_(?P<rev>.+?)(?:_(?P<mode>smoke|quick))?\.json$")


def _rev_position(rev, order):
    """Position of ``rev`` in first-parent history.  Matches by hash prefix
    in either direction — ``git log --format=%h`` and the bench writer may
    abbreviate the same commit to different lengths.  Unknown revs sort
    after all known history (then by timestamp) instead of crashing."""
    if rev in order:
        return order[rev]
    for h, i in order.items():
        if h.startswith(rev) or rev.startswith(h):
            return i
    return len(order)


def load_trajectory(mode="smoke", bench_dir=BENCH_DIR):
    """Committed BENCH files for ``mode`` ("smoke"/"quick"/"full"), oldest
    rev first.  One run per (rev, mode): when several files claim the same
    rev (re-runs, embedded rev overriding the filename) the newest
    timestamp wins.  Unparseable filenames and corrupt JSON are skipped."""
    by_rev = {}
    for p in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
        m = _BENCH_RE.match(os.path.basename(p))
        if not m:
            continue
        fmode = m.group("mode") or "full"
        if fmode != mode:
            continue
        try:
            with open(p) as f:
                d = json.load(f)
        except (json.JSONDecodeError, OSError):
            continue  # half-written bench drop: skip, don't kill the table
        d.setdefault("rev", m.group("rev"))
        prev = by_rev.get(d["rev"])
        if prev is None or d.get("timestamp", "") > prev.get("timestamp", ""):
            by_rev[d["rev"]] = d
    order = _git_rev_order()
    runs = list(by_rev.values())
    runs.sort(key=lambda d: (_rev_position(d["rev"], order),
                             d.get("timestamp", "")))
    return runs


def _cell(row):
    if row is None:
        return "—"
    if row.get("status", "ok") != "ok":
        return "FAIL"
    tag = "\\*" if row.get("interpret") else ""
    return f"{row['us_per_call']:.1f}{tag}"


def trajectory_table(runs):
    if not runs:
        return "(no BENCH files found)"
    revs = [d["rev"] for d in runs]
    by_rev = {d["rev"]: {r["name"]: r for r in d["rows"]} for d in runs}
    names = []                                     # first-appearance order
    for d in runs:
        for r in d["rows"]:
            if r["name"] not in names:
                names.append(r["name"])
    latest = revs[-1]

    head = ("| row | " + " | ".join(f"{r} us" for r in revs)
            + f" | {latest} Mpts/s | {latest} roofline |")
    sep = "|---|" + "---|" * (len(revs) + 2)
    lines = [head, sep]
    for name in names:
        cells = [_cell(by_rev[rev].get(name)) for rev in revs]
        last = by_rev[latest].get(name) or {}
        mpts = last.get("mpts_per_s")
        frac = last.get("roofline_frac")
        tag = "\\*" if last.get("interpret") else ""
        mp = f"{mpts:.2f}{tag}" if mpts is not None else "—"
        fr = f"{frac:.2%}{tag}" if frac is not None else "—"
        lines.append(f"| {name} | " + " | ".join(cells)
                     + f" | {mp} | {fr} |")
    bw = runs[-1].get("bandwidth_gbps")
    src = runs[-1].get("bandwidth_source", "model")
    lines.append("")
    lines.append(f"us/call are min-of-reps; \\* = interpret-mode Pallas "
                 f"(CPU emulation — correctness row, absolute numbers not "
                 f"meaningful, excluded from gate roofline floors). "
                 f"Latest ceilings vs {bw} GB/s ({src}).")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="smoke",
                    help="BENCH file suffix to aggregate (default: smoke)")
    args = ap.parse_args()
    print(f"### Perf trajectory ({args.mode})")
    print()
    print(trajectory_table(load_trajectory(args.mode)))


if __name__ == "__main__":
    main()
