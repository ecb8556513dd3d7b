"""Model FLOP utilization of the serving step in the traced slice.

Model operations of the requests finished in the slice, 2 F D (encode)
+ 2 D n (activations) + 3 C n (profile distances) each, over the device
time in which the encode or predict executable ran times the chip's peak.
It bounds a claimed gain in either kernel: a kernel taken off the path
goes silent in its roofline, not here."""

import numpy as np

MODULES = ("jit_encode", "jit_run")


def flops_per_request(s: dict) -> float:
    f, d, n, c = s["in_features"], s["dim"], s["n_bundles"], s["n_classes"]
    return 2.0 * f * d + 2.0 * d * n + 3.0 * c * n


def read(run):
    t, w = run.trace, run.window
    if t is None or w.slice_s is None:
        return None
    busy = t.module_union(MODULES)
    s0, s1 = w.slice_s
    done = int(np.sum((w.t_done >= s0) & (w.t_done < s1)))
    if busy <= 0 or done == 0:
        return None
    return 100.0 * done * flops_per_request(run.config) / (
        busy * run.peaks["flops_per_s"])
