"""pad_eff: share of the rows the worker batchers dispatched that were
request rows and not padding, from the program's rows_valid and
rows_dispatched counters over the window."""


def read(w):
    dispatched = w.counter("rows_dispatched")
    return 100.0 * w.counter("rows_valid") / dispatched if dispatched else None
