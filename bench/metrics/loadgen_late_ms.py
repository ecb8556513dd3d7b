"""How late the generator ran: 99th percentile of submit-return time
minus scheduled arrival, over the requests scheduled in the traced slice.
Large values mean a starved generator, not a fast server."""

import numpy as np


def read(run):
    if run.window.slice_s is None:
        return None
    s0, s1 = run.window.slice_s
    w = run.window
    sel = (w.t_sched >= s0) & (w.t_sched < s1) & np.isfinite(w.t_submit)
    if not sel.any():
        return None
    late = w.t_submit[sel] - w.t_sched[sel]
    return float(np.quantile(late, 0.99, method="inverted_cdf")) * 1e3
