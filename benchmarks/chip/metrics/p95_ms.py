"""p95_ms: 95th percentile latency (ms) of every query sent in the
window, from its due time to its answer on the host (host clock).  An
answer that came after the close counts with its whole wait."""

import numpy as np


def read(run):
    lat = run.summary()["latency_s"]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
