"""step_ms: mean device milliseconds of one call of the member step program
(the configuration's ``step`` kernel, ``jit_predict`` for danube2) over the
window, from the profiler trace: the step's own time, without the queueing
that device_wait_ms includes.  Its calls run every row bucket the traffic
uses, so the mean follows the mix of buckets."""

KERNEL = "step"


def read(w):
    if w.trace is None:
        return None
    secs = w.trace["kernel_s"].get(KERNEL, 0.0)
    calls = w.trace["kernel_calls"].get(KERNEL, 0)
    return 1e3 * secs / calls if secs and calls else None
