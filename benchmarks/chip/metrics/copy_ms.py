"""copy_ms: mean milliseconds of one device-to-host copy of answers (a
device partial when the combiner posts it), from the program's copy stage
timer over the window."""


def read(w):
    total, count = w.stage("copy")
    return 1e3 * total / count if count else None
