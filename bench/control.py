"""Readings that set a cell's correctness limits, over many seeds in one
process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

For each seed it makes the cell's model and serves a window at the cell's
own load twice: as configured (the lower reading) and with the program's
own lower-precision path, int8 residency, switched on (the control, the
upper reading). Every finished answer is judged against the reference at
the configuration's stated precision, as a run judges it, and the
reference computed in bfloat16 is read beside them as a witness.
``--precisions`` adds readings against references that state other
matmul precisions (``projection/similarity/decode``, comma-separated).
One JSON line per seed. Not run by the benchmark itself.
"""

import argparse
import dataclasses
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if sys.path and pathlib.Path(sys.path[0]).resolve() == _ROOT / "bench":
    sys.path.pop(0)
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def readings_for_seed(cell, seed: int, seconds: float,
                      precisions=()) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from bench.correct import control_labels, readings
    from bench.modes.serve_open_loop import DONE
    from bench.reference import stated

    built = cell.maker.build(cell.config, seed)
    windows = {}
    for name, bits in (("program", None), ("control_int8", 8)):
        service = {**cell.config["service"], "quantize_bits": bits}
        run = dataclasses.replace(cell, config={**cell.config,
                                                "service": service})
        rig = cell.mode.prepare(run, built, seed, seconds)
        windows[name] = rig.measure()
        rig.free()
    w = windows["program"]
    done = w.status == DONE
    ref = cell.maker.reference_scores
    out = {"seed": seed, "answers": int(done.sum()),
           "distinct_rows": int(np.unique(w.row[done]).size),
           "failed": {k: int(np.sum(v.status != DONE))
                      for k, v in windows.items()}}
    for prec in (stated(cell.config, cell.maker.STAGES), *precisions):
        got = {k: readings(ref, prec, built.params, built.pool,
                           v.row[v.status == DONE], v.label[v.status == DONE])
               for k, v in windows.items()}
        got["witness_bf16"] = readings(
            ref, prec, built.params, built.pool, w.row[done],
            control_labels(ref, prec, built.params, built.pool,
                           w.row[done], jnp.bfloat16))
        out["/".join(prec)] = got
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--precisions", default="",
                    help="more reference statements, e.g. "
                         "highest/highest/highest,default/default/default")
    args = ap.parse_args(argv)

    from bench import spec
    from bench.run import NoAccelerator, require_device
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = spec.cell(args.workload)
    try:
        require_device(cell.chips)
    except NoAccelerator as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 3
    precs = [tuple(p.split("/")) for p in args.precisions.split(",") if p]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings_for_seed(cell, seed, args.seconds, precs)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
