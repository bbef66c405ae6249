"""device_idle: share of the window in which no operation ran on the chip,
averaged over the cell's chips: 1 - union of the op intervals in the
profiler trace over the window's length."""


def read(w):
    return None if w.trace is None else 100.0 * w.trace["idle_share"]
