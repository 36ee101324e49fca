"""Median decision latency, due to verified schedule, in ms (host clock)."""

import numpy as np


def read(run):
    lat = run.latencies_s()
    return 1e3 * float(np.median(lat)) if lat else None
