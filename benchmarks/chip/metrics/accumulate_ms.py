"""accumulate_ms: mean milliseconds the prediction accumulator spent per
message it folded, from the program's accumulate stage timer over the
window."""


def read(w):
    total, count = w.stage("accumulate")
    return 1e3 * total / count if count else None
