"""p50_ms: median latency (ms) of every query sent in the window, from
its due time to its answer on the host (host clock)."""

import numpy as np


def read(run):
    lat = run.summary()["latency_s"]
    return float(np.percentile(lat, 50) * 1e3) if lat else None
