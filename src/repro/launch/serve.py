"""Serving launcher CLI: batched decode over a synthetic request stream.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --requests 6 --max-new 16
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import init_params
    from repro.runtime.serve_loop import Request, ServeLoopConfig, run_serving

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, size=3 + i % 5)
                    .astype(np.int32))
            for i in range(args.requests)]
    t0 = time.time()
    out = run_serving(cfg, params, reqs,
                      ServeLoopConfig(batch_slots=args.slots,
                                      max_new_tokens=args.max_new,
                                      max_len=256))
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(f"served {len(out)} requests, {total} tokens in {dt:.1f}s "
          f"({total/dt:.1f} tok/s)")
    for uid in sorted(out):
        print(f"  req {uid}: {out[uid][:10].tolist()}...")


if __name__ == "__main__":
    main()
