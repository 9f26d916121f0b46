"""95th percentile of query latency over every request due in the window,
from when it was due to when its last node was answered (host clock)."""

import numpy as np


def read(run):
    if not run.latencies:
        return None
    return float(np.percentile(np.asarray(run.latencies), 95)) * 1e3
