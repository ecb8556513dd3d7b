"""Benchmark driver: one module per paper table/figure.

  fig3_bitflip       — Fig. 3: accuracy vs flip prob at matched budgets
  fig4_dim_quant     — Fig. 4: D x precision sensitivity (UCIHAR)
  fig5_alphabet      — Fig. 5: alphabet size k sweep
  fig6_hybrid        — Fig. 6: hybrid n x sparsity heatmap
  table2_efficiency  — Table II: modeled ASIC/CPU/GPU efficiency ratios
  kernels_bench      — Pallas kernel spot checks + derived numbers
  fault_sweep_bench  — fused sweep engine vs frozen legacy per-trial loop;
                       appends a perf-trajectory record to
                       BENCH_fault_sweep.json at the repo root
  breakpoint_surface — max sustained severity per (method, budget, fault
                       model) across the repro.faults zoo; appends to
                       BENCH_breakpoints.json, gated on LogHD >= SparseHD
                       under iid and zero post-warmup recompiles
  serve_bench        — continuous-batched classifier service vs naive
                       one-request-per-call (conventional vs LogHD at
                       matched memory); appends p50/p99 latency and
                       requests/sec to BENCH_serve.json
  fit_bench          — fused single-jit training engine vs the frozen
                       eager epoch loops per method; appends to
                       BENCH_fit.json, gated >=5x with accuracy z-tests
                       and zero post-warmup retraces
  extreme_bench      — class-sharded LogHD at C in {2^16, 2^20} on the
                       forced-8-device mesh; appends fit/predict throughput
                       and resident bytes-per-device to BENCH_extreme.json,
                       gated <= 1.2x the ideal C/n_shards split and zero
                       post-warmup recompiles (skips below 2 devices)

`python -m benchmarks.run` (or `--quick`) runs the QUICK suite (the 1-core
CPU container cannot finish the full grids in reasonable time); `--full`
runs everything.  Full CSVs land on stdout; EXPERIMENTS.md records a
curated full run.  CI runs `--quick --only fault_sweep` as a smoke stage
and uploads the JSON artifact so the perf trend is recorded per PR.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="explicit quick suite (the default; --full wins)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (breakpoint_surface, extreme_bench,
                            fault_sweep_bench, fig3_bitflip, fig4_dim_quant,
                            fig5_alphabet, fig6_hybrid, fit_bench,
                            kernels_bench, serve_bench, table2_efficiency)
    suites = {
        "table2": table2_efficiency,
        "kernels": kernels_bench,
        "fault_sweep": fault_sweep_bench,
        "breakpoint_surface": breakpoint_surface,
        "serve": serve_bench,
        "fit": fit_bench,
        "extreme": extreme_bench,
        "fig5": fig5_alphabet,
        "fig4": fig4_dim_quant,
        "fig6": fig6_hybrid,
        "fig3": fig3_bitflip,
    }
    for name, mod in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"# ==== {name} ({mod.__name__}) ====", flush=True)
        if name == "fig3":
            # run once, print the grid AND the derived break-point table
            rows = mod.run(quick=quick)
            print("dataset,budget,bits,scope,method,p,accuracy")
            for r in rows:
                print(",".join(str(x) for x in r))
            from benchmarks.breakpoints import breakpoints, ratios
            bps = breakpoints([tuple(r) for r in rows])
            print("# ---- break points (p* at clean-10pts; C2 ratio) ----")
            print("dataset,budget,bits,scope,pstar_loghd,pstar_sparsehd,ratio")
            for row in ratios(bps):
                print(",".join(str(x) for x in row))
        else:
            mod.main(quick=quick)
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
