"""inflight_wait_ms: mean milliseconds a request waited for a slot of the
in-flight window inside predict_async, from the program's inflight_wait
stage timer over the window."""


def read(w):
    total, count = w.stage("inflight_wait")
    return 1e3 * total / count if count else None
