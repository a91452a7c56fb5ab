"""fit_ms: the window's length over the fits completed in it; each fit
ends with its coefficients on the host."""


def read(run):
    w = run.window
    return 1e3 * w.window_s / w.completed if w.completed else None
