"""dispatch_wait_ms: mean milliseconds a normal-priority chunk waited in a
worker's dispatch queue, from the program's dispatch_wait.normal stage
timer over the window."""


def read(w):
    total, count = w.stage("dispatch_wait.normal")
    return 1e3 * total / count if count else None
