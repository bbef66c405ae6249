"""p95_ms: 95th percentile of the same latencies as p50_ms."""
import numpy as np


def read(w):
    lat = w.latencies_ms()
    return float(np.percentile(lat, 95)) if lat else None
