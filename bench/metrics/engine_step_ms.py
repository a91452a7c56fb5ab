"""The mean duration of the benchmark's own span around each call of the
fit server's step, in the traced window."""


def read(run):
    t = run.trace
    mean = None if t is None else t.span_mean_s("bench.engine_step")
    return None if mean is None else 1e3 * mean
