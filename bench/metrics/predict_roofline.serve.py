"""Predict's share of its roofline in the traced slice.

The predict executable (``api/dispatch.py``: ``bundle_sim`` kernel,
``profile_decode`` kernel, argmax) does the decode from h to labels. For
R unpadded rows that needs 2 R D n operations for the activations and
3 R C n for the distances to every profile, and moves 4 (R D + n D + C n
+ R) bytes: hypervectors, bundles, profiles, labels. Padding and a scores
matrix are not counted, so a change that removes them does not make the
count less true. Least time max(ops / peak, bytes / bandwidth), summed
over calls, over the executable's summed device time; totals stand in for
the per-call sum because the work is bytes bound for every R up to 64 at
both configurations' sizes."""

MODULES = ("jit_run",)


def flops(rows: float, s: dict) -> float:
    d, n, c = s["dim"], s["n_bundles"], s["n_classes"]
    return 2.0 * rows * d * n + 3.0 * rows * c * n


def bytes_moved(rows: float, calls: int, s: dict) -> float:
    d, n, c = s["dim"], s["n_bundles"], s["n_classes"]
    return 4.0 * (rows * d + calls * (n * d + c * n) + rows)


def least_time(rows, calls, s, peak) -> float:
    return max(flops(rows, s) / peak["flops_per_s"],
               bytes_moved(rows, calls, s) / peak["bytes_per_s"])


def read(run):
    t = run.trace
    if t is None:
        return None
    calls = t.module_calls(MODULES)
    busy = t.module_time(MODULES)
    rows = run.rows_per_call()
    if not calls or busy <= 0 or rows is None:
        return None
    return 100.0 * least_time(rows * calls, calls, run.config,
                              run.peaks) / busy
