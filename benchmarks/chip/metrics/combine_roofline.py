"""combine_roofline: the device combine kernel's share of its roofline over
the window -- the least time the bytes its calls must move take at the
chip's HBM bandwidth, over the kernel's time in the profiler trace, which
covers the whole window.

Each call folds one member's predictions for one segment into the device
partial: it reads the float32 partial and the member's float32 rows and
writes the partial back (``flops.combine_bytes``).  The rows per call are
the rows answered in the window, times the members, over the calls the
program's ``combine`` stage timer counted in the same window."""
from chipbench import flops

KERNEL = "combine"


def read(w):
    if w.trace is None:
        return None
    secs = w.trace["kernel_s"].get(KERNEL, 0.0)
    calls = w.trace["kernel_calls"].get(KERNEL, 0)
    _total, adds = w.stage("combine")
    rows = sum(r.rows for r in w.completed_in_window()) * w.members
    if not secs or not calls or not adds or not rows:
        return None
    per_call = flops.combine_bytes(rows / adds, w.cfg["vocab_size"])
    return 100.0 * calls * per_call / w.peak["hbm_bytes_per_s"] / secs
