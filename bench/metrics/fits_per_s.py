"""fits_per_s: fits whose results reached the host inside the window,
over the window's length."""


def read(run):
    w = run.window
    return w.completed / w.window_s if w.window_s > 0 else None
