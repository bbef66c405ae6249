"""send_wait_ms: mean milliseconds a dispatched group of chunks waited in a
worker's sender queue, from the predictor's hand-off to the sender's
pick-up, from the program's send_wait stage timer over the window."""


def read(w):
    total, count = w.stage("send_wait")
    return 1e3 * total / count if count else None
