"""device_wait_ms: mean milliseconds a worker's sender waited for one
dispatched chunk's output to be ready on the chip, from the program's
device_wait stage timer over the window."""


def read(w):
    total, count = w.stage("device_wait")
    return 1e3 * total / count if count else None
