"""fit_roofline: the least time of the traced window's fits on one chip
(bench.work: bytes over peak bandwidth, or FLOP over peak, the larger),
over the chip's busy time in the window, whatever ran.  The work is
counted from the problem's shape, so it is the same on every path the
plan may pick."""


def read(run):
    t, work, peaks = run.trace, run.work_per_fit, run.peaks
    if t is None or work is None or peaks is None or t.busy_s <= 0:
        return None
    return 100.0 * work.least_time_s(peaks) * run.window.completed / t.busy_s
