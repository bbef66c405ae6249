"""p50_ms: median request latency, from when the schedule made each request
due to when its answer arrived, over every request due in the window that
was answered."""
import numpy as np


def read(w):
    lat = w.latencies_ms()
    return float(np.percentile(lat, 50)) if lat else None
