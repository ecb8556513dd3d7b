"""Requests admitted per service cycle in the traced slice, from the
service's own ``admitted`` and ``cycles`` counters."""


def read(run):
    c = run.slice_counters
    if not c or c["cycles"] <= 0:
        return None
    return c["admitted"] / c["cycles"]
