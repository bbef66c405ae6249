"""slot_wait_ms: mean milliseconds a worker's batcher waited for a free ring
slot when opening a normal-priority batch (a slot frees once every chunk
of its batch is materialized), from the program's slot_wait stage timer
over the window."""


def read(w):
    total, count = w.stage("slot_wait")
    return 1e3 * total / count if count else None
