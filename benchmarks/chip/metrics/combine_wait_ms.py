"""combine_wait_ms: mean milliseconds the combiner waited for one device
partial's fold program to finish before copying it to the host (the chip
runs it behind whatever was queued before it), from the program's
combine_wait stage timer over the window."""


def read(w):
    total, count = w.stage("combine_wait")
    return 1e3 * total / count if count else None
