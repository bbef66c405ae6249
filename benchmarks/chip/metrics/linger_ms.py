"""linger_ms: mean milliseconds from a worker batch's opening to its flush,
from the program's linger stage timer over the window."""


def read(w):
    total, count = w.stage("linger")
    return 1e3 * total / count if count else None
