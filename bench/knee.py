"""Find a serving cell's knee: one process, one set-up, a window at each
offered rate in turn.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2000,4000,8000

For each rate it prints one JSON line: offered and completed rate,
p50/p99 latency, generator lateness, rows per step and the queue's
backlog at the window's close. The knee is the highest rate the service
keeps up with (completed = offered, no backlog growing through the
window); a cell's traffic mix offers a fixed share of it. Not run by the
benchmark itself.
"""

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if sys.path and pathlib.Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def sweep(cell, seed: int, seconds: float, rates, require) -> list:
    import numpy as np

    from bench.modes.serve_open_loop import DONE, delta
    from bench.loadgen import schedule

    require(cell.chips)
    built = cell.maker.build(cell.config, seed)
    rig = cell.mode.prepare(cell, built, seed, seconds)
    out = []
    for rate in rates:
        traffic = {**cell.traffic, "rate_rps": float(rate)}
        rig.plan = schedule(traffic, seconds, len(built.pool), seed)
        w = rig.measure()
        lat = w.latency_s()
        done = w.status == DONE
        c = delta(w.counters["start"], w.counters["end"])
        # requests not done when the window closed: the backlog
        backlog = int(np.sum(w.t_sched < seconds)
                      - np.sum(done & (w.t_done <= seconds)))
        out.append({
            "rate_rps": float(rate),
            "completed_rps": w.done_in_window() / seconds,
            "p50_ms": float(np.quantile(lat, 0.5, method="inverted_cdf"))
            * 1e3,
            "p99_ms": float(np.quantile(lat, 0.99, method="inverted_cdf"))
            * 1e3,
            "late_p99_ms": float(np.quantile(w.t_submit - w.t_sched, 0.99,
                                             method="inverted_cdf")) * 1e3,
            "rows_per_step": c["admitted"] / max(c["cycles"], 1),
            "backlog_at_close": backlog,
            "failed": int(np.sum(~done)),
        })
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import spec
    from bench.run import NoAccelerator, require_device
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.cell(args.workload)
    try:
        sweep(cell, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], require_device)
    except NoAccelerator as exc:
        print(f"knee: {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
