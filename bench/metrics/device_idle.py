"""1 − (union of the device's operation intervals) / window, in the traced
window, on the chip that was idle longest."""


def read(run):
    t = run.trace
    idle = None if t is None else t.idle_share()
    return None if idle is None else 100.0 * idle
