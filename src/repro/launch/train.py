"""Training launcher CLI.

Local debug run (this container):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 20

Production posture: on a real pod the same entrypoint runs under the TPU
runtime (no XLA_FLAGS override; jax.distributed.initialize() picks up the
pod topology), with --mesh production selecting make_production_mesh().
The loop resumes from the newest committed checkpoint automatically, so the
cluster scheduler can kill/reschedule the job freely (straggler aborts exit
with a distinct status for the scheduler to act on).
"""

from __future__ import annotations

import argparse
import logging
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "debug", "production"],
                    default="none")
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    from repro.runtime.train_loop import (StragglerAbort, TrainLoopConfig,
                                          run_training)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = None
    if args.mesh == "debug":
        mesh = make_debug_mesh()
    elif args.mesh == "production":
        mesh = make_production_mesh()

    loop = TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           peak_lr=args.peak_lr,
                           microbatches=args.microbatches)
    try:
        out = run_training(cfg, mesh=mesh, loop=loop,
                           global_batch=args.global_batch,
                           seq_len=args.seq_len)
    except StragglerAbort as e:
        logging.error("straggler abort: %s", e)
        sys.exit(75)  # EX_TEMPFAIL: scheduler should reschedule elsewhere
    logging.info("done: resumed=%s loss %.4f -> %.4f", out["resumed"],
                 out["losses"][0], out["losses"][-1])


if __name__ == "__main__":
    main()
