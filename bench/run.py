"""Run one cell of BENCHMARK.json on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's model and request pool from the seed, starts the
program's service and warms every shape the window uses. Then the cell's
mode measures for ``--seconds``. With ``--trace 1`` a slice of the
window is traced and the cell's per-layer metrics are reported in place of
its end-to-end ones. Once the window has closed, the device's memory peak
is read, the program's state dropped, and every finished answer checked
against the configuration's plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.
Without an accelerator, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if sys.path and pathlib.Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)        # bench's module names must not shadow others
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_START, TRACE_MAX_S = 0.25, 4.0


class NoAccelerator(RuntimeError):
    pass


class RunData:
    """What the metric readers see of one run. The traced slice's counters
    are the cell's mode's own: ``mode.delta`` of the counters at the
    slice's start and end, where the mode defines one."""

    def __init__(self, *, setup_s, window, config, peaks, trace, mode):
        self.setup_s = setup_s
        self.window = window
        self.config = config
        self.peaks = peaks
        self.trace = trace
        c = window.counters
        self.slice_counters = None
        delta = getattr(mode, "delta", None)
        if delta is not None and "slice_start" in c and "slice_end" in c:
            self.slice_counters = delta(c["slice_start"], c["slice_end"])

    def rows_per_call(self):
        c = self.slice_counters
        if not c or c["cycles"] <= 0:
            return None
        return c["admitted"] / c["cycles"]


def require_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoAccelerator(f"no accelerator: JAX's first device is "
                            f"{dev.platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(n_chips: int) -> int | None:
    import jax
    peaks = []
    for dev in jax.devices()[:n_chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def trace_slice(seconds: float) -> tuple:
    start = TRACE_START * seconds
    return (start, start + min(TRACE_MAX_S, 0.5 * seconds))


class Tracer:
    """Starts the profiler at the slice's start and stops it at its end,
    with a ``bench.window`` span around exactly that slice."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    def __call__(self, what: str) -> None:
        import jax
        if what == "start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        else:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def measure(cell, seed: int, seconds: float, trace: bool, *,
            require=require_device) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import numpy as np

    from bench import spec
    from bench.tracefile import find_xplane, read

    device = require(cell.chips)
    peaks = spec.peaks(device["kind"])
    built = cell.maker.build(cell.config, seed)
    rig = cell.mode.prepare(cell, built, seed, seconds)

    compiles = []
    listener = (lambda event, duration, **kw: compiles.append(duration)
                if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        kw = {}
        if trace:
            kw = {"trace_slice": trace_slice(seconds),
                  "on_slice": Tracer(log_dir),
                  "span": jax.profiler.TraceAnnotation}
        window = rig.measure(**kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    setup_s = window.started_at - _T_START
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    rig.free()
    verdict = cell.mode.check(cell, built, window, seed)

    reduced = None
    if trace:
        try:
            reduced = read(find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    run = RunData(setup_s=setup_s, window=window, config=cell.config,
                  peaks=peaks, trace=reduced, mode=cell.mode)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_module(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = int((window.status != 0).sum())
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(window.status.size), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_gaps()}
    result["compiles_in_window"] = len(compiles)
    lat = window.latency_s()
    result["info"] = {**verdict["info"], "errors": window.errors,
                      "latency_ms": {f"p{q}": float(np.quantile(
                          lat, q / 100, method="inverted_cdf")) * 1e3
                          for q in (50, 90, 95, 99, 100)} if lat.size
                      else {}}
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = measure(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
