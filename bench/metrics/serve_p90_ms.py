"""90th percentile latency over every request scheduled in the window,
from its scheduled arrival to the moment its label is in the collector's
hand. A request that was rejected, raised or never finished counts as
infinitely late. No interpolation: the value is one request's latency."""

import numpy as np

Q = 0.9


def read(run):
    lat = run.window.latency_s()
    if lat.size == 0:
        return None
    return float(np.quantile(lat, Q, method="inverted_cdf")) * 1e3
