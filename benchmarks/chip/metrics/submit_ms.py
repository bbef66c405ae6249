"""submit_ms: mean milliseconds EnsembleClient.predict_async blocked the
sender, harness clock around each call in the window (admission, buffer
take, striping, and the wait for a slot of the in-flight window)."""


def read(w):
    times = [r.submitted - r.sent for r in w.records if r.submitted]
    return 1e3 * sum(times) / len(times) if times else None
