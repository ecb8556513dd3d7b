"""Encode's share of its roofline in the traced slice.

The encode executable (``jit(encode)``: z = xW, cos(z + b) sin(z), two
normalizations) does, for R unpadded rows, 2 R F D operations and moves
4 (R F + F D + R D) bytes: rows in, the projection, hypervectors out. The
least time is max(ops / peak, bytes / bandwidth), summed over calls, over
the executable's summed device time. Totals stand in for the per-call sum:
the intensity is at most R / 2 operations per byte, bytes bound for every
R up to 64, so one bound holds for every call."""

MODULES = ("jit_encode",)


def flops(rows: float, s: dict) -> float:
    return 2.0 * rows * s["in_features"] * s["dim"]


def bytes_moved(rows: float, calls: int, s: dict) -> float:
    f, d = s["in_features"], s["dim"]
    return 4.0 * (rows * f + calls * f * d + rows * d)


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
