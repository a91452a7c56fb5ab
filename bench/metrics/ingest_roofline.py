"""ingest_roofline: the live request points the fit server ingested in
the traced window (its own counter), at 8 bytes each over the peak
bandwidth, over the chip's busy time.  Padding and zero-weight lanes are
not work."""
from bench.work import BYTES_PER_POINT


def read(run):
    t, peaks = run.trace, run.peaks
    points = run.window.counters.get("points_ingested")
    if t is None or peaks is None or points is None or t.busy_s <= 0:
        return None
    return (100.0 * points * BYTES_PER_POINT / peaks.hbm_bytes_per_s
            / t.busy_s)
