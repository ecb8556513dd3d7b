"""Smoke run of the LogHD classifier path on a TPU, through the entry points
a user calls, at the paper's width (D = 10,000).

    python chip_smoke.py                # one chip: fit, serve, sweep
    python chip_smoke.py --four-chips   # class-sharded extreme C on 4 chips

One chip:
  fit    ISOLET-shaped data (F = 617, C = 26, seeded); LogHD (k = 2, five
         extra bundles) and a conventional model at matched memory, through
         ``HDClassifier.fit``.  The Pallas kernels must be on the path.
  serve  both models behind ``ClassifierService``; every test row is
         submitted to each, every future is read, nothing may compile after
         warmup, and the served labels are held to a float32 reference
         (the family's own jnp predict at "highest" matmul precision).
  sweep  ``sweep_under_flips`` on the kernel path (p = 0 must equal clean
         quantized accuracy), plus direct ``flip_corrupt`` flip-rate checks
         of the chip's hardware PRNG.
Four chips (only this phase): LogHD at C = 2^20 over a (data=1, class=4)
mesh against the same model gathered onto chip 0.

Exits non-zero, printing no result line, when JAX finds no TPU or any check
fails.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

D = 10_000                       # the paper's hypervector width
SEED = 0
SWEEP_P = [0.0, 0.05, 0.1, 0.2]
SWEEP_TRIALS = 4
MIN_AGREEMENT = 0.99             # served vs float32 reference labels
MAX_ACC_GAP = 0.01               # served vs reference accuracy
SHARDED_MIN_AGREEMENT = 0.999    # class-sharded vs gathered labels
RATIO_CEILING = 1.2              # resident bytes vs the ideal C/S split
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check(ok: bool, what: str) -> None:
    """Stop the run, exit code 1 and no result line, unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) while
    registered as a JAX monitoring listener."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


def require_tpu(n_chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX's first device is "
          f"{dev.platform!r}")
    check(len(devices) >= n_chips, f"need {n_chips} chips, JAX sees "
          f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ------------------------------------------------------------------ fit --

def phase_fit(x_tr, y_tr, *, dim: int = D):
    import jax
    import jax.numpy as jnp

    from repro.api import dispatch, fit_engine, make_classifier

    check(dispatch.kernels_qualify(), "kernels_qualify() is false")
    n_classes, n_feat = int(y_tr.max()) + 1, x_tr.shape[1]
    t0 = time.perf_counter()
    log = make_classifier("loghd", n_classes=n_classes, in_features=n_feat,
                          dim=dim, k=2, extra_bundles=5).fit(x_tr, y_tr)
    jax.block_until_ready(log.model.bundles)
    t_log = time.perf_counter() - t0
    # matched memory: C * D' words = LogHD's n * D + C * n stored words
    n, d = log.model.bundles.shape
    d_conv = (n * d + n_classes * n) // n_classes
    t0 = time.perf_counter()
    conv = make_classifier("conventional", n_classes=n_classes,
                           in_features=n_feat, dim=d_conv).fit(x_tr, y_tr)
    jax.block_until_ready(conv.model.protos)
    t_conv = time.perf_counter() - t0
    check(any(k[0] == "refine" and k[-1] is True
              for k in fit_engine._FIT_JIT_CACHE),
          "the LogHD refine did not run on the bundle_update kernel")
    for clf in (log, conv):
        h = jnp.zeros((64, clf.model.enc["proj"].shape[1]), jnp.float32)
        text = dispatch.predict_fn(clf.model).lower(clf.model, h).as_text()
        check("tpu_custom_call" in text,
              f"{clf.method} predict executable holds no Pallas kernel")
    print(f"[fit] loghd n={n} D={d} in {t_log:.2f} s wall (compile "
          f"included); conventional D'={d_conv} in {t_conv:.2f} s; "
          f"kernels on the predict and refine paths", flush=True)
    return log, conv


# ---------------------------------------------------------------- serve --

def phase_serve(models: dict, x_te, y_te) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import ClassifierService

    svc = ClassifierService(models, max_batch=64)
    svc.warmup()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        t0 = time.perf_counter()
        futs = {name: [svc.submit(name, row) for row in x_te]
                for name in models}
        svc.run_until_drained()
        served = {name: np.array([f.result(timeout=120) for f in fs])
                  for name, fs in futs.items()}
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    n_req = sum(len(v) for v in served.values())
    print(f"[serve] {n_req} requests over {len(models)} models in "
          f"{wall:.3f} s wall; errors={svc.errors}; compiles after "
          f"warmup={counter.n}", flush=True)
    check(svc.errors == 0, f"{svc.errors} service cycles failed")
    check(counter.n == 0, f"{counter.n} compilations after warmup")

    x = jnp.asarray(x_te)
    for name, model in models.items():
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(type(model).predict)(model, x))
        agree = float(np.mean(served[name] == ref))
        acc_s = float(np.mean(served[name] == y_te))
        acc_r = float(np.mean(ref == y_te))
        print(f"[serve] {name}: served accuracy {acc_s:.4f}, float32 "
              f"reference {acc_r:.4f}, {int(np.sum(served[name] != ref))} "
              f"of {len(ref)} rows disagree", flush=True)
        check(agree >= MIN_AGREEMENT, f"{name}: served labels agree with the "
              f"reference on {agree:.4f} < {MIN_AGREEMENT}")
        check(abs(acc_s - acc_r) <= MAX_ACC_GAP, f"{name}: served accuracy "
              f"{acc_s:.4f} vs reference {acc_r:.4f}")


# ---------------------------------------------------------------- sweep --

def flipped_share(out, codes, scale, bits: int):
    """Share of stored bits that differ between corrupted output and codes."""
    import numpy as np
    mask = (1 << bits) - 1
    got = np.round(np.asarray(out) / float(scale)).astype(np.int64) & mask
    diff = (got ^ (np.asarray(codes, np.int64) & mask)).astype(np.uint8)
    return np.unpackbits(diff).sum() / (diff.size * bits), diff


def phase_sweep(log, x_te, y_te, *, dim: int = D) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.evaluate import accuracy
    from repro.core.quantize import quantize
    from repro.hdc.encoders import encode_batched
    from repro.kernels.flip_corrupt.ops import flip_corrupt

    h = encode_batched(log.model.enc, jnp.asarray(x_te), log.enc_cfg.kind)
    y = jnp.asarray(y_te)
    t0 = time.perf_counter()
    accs = log.sweep_under_flips(4, SWEEP_P, h, y,
                                 jax.random.PRNGKey(SEED + 1),
                                 n_trials=SWEEP_TRIALS)
    wall = time.perf_counter() - t0
    clean = accuracy(log.model.quantized(4).materialized(), h, y)
    rows = ", ".join(f"p={p}: {a:.4f}" for p, a in
                     zip(SWEEP_P, accs.mean(axis=1)))
    print(f"[sweep] 4-bit loghd, {SWEEP_TRIALS} trials, mean accuracy "
          f"{rows}; clean quantized {clean:.4f}; {wall:.2f} s wall "
          f"(compile included)", flush=True)
    check(accs.shape == (len(SWEEP_P), SWEEP_TRIALS), f"shape {accs.shape}")
    check(bool(np.all(accs[0] == np.float32(clean))),
          f"p=0 row {accs[0].tolist()} != clean quantized accuracy {clean}")

    bits, p = 4, 0.1
    q = quantize(jax.random.normal(jax.random.PRNGKey(SEED + 2), (26, dim)),
                 bits)
    share, mask_a = flipped_share(flip_corrupt(q.codes, q.scale, bits, p, 7),
                                  q.codes, q.scale, bits)
    sigma = (p * (1 - p) / (q.codes.size * bits)) ** 0.5
    all_share, _ = flipped_share(flip_corrupt(q.codes, q.scale, bits, 1.0, 7),
                                 q.codes, q.scale, bits)
    _, mask_b = flipped_share(flip_corrupt(q.codes, q.scale, bits, p, 8),
                              q.codes, q.scale, bits)
    print(f"[sweep] flip_corrupt (26, {dim}) 4-bit: share flipped at p=0.1 "
          f"{share:.6f} ({(share - p) / sigma:+.2f} sigma); at p=1 "
          f"{all_share:.6f}; two seeds differ on "
          f"{float(np.mean(mask_a != mask_b)):.4f} of words", flush=True)
    check(abs(share - p) <= 5 * sigma, f"flip share {share} not within 5 "
          f"sigma of {p}")
    check(all_share == 1.0, f"p=1 flipped {all_share} of the bits")
    check(bool(np.any(mask_a != mask_b)), "two seeds gave the same mask")


# ------------------------------------------------------------ four chips --

def phase_four_chips(n_queries: int = 1024, dim: int = D) -> None:
    import jax
    import numpy as np

    from benchmarks.extreme_bench import CASES, FEATURES, _fixture
    from repro.api import make_classifier

    n_classes, n_train = CASES[-1]                   # C = 2^20
    x, y, _ = _fixture(n_classes, n_train)
    t0 = time.perf_counter()
    clf = make_classifier("loghd", n_classes=n_classes, in_features=FEATURES,
                          dim=dim, class_sharding=4).fit(x, y)
    model = clf.model
    jax.block_until_ready(model.profiles)
    t_fit = time.perf_counter() - t0
    mesh = model.profiles.sharding.mesh
    check(dict(mesh.shape) == {"data": 1, "class": 4},
          f"mesh {dict(mesh.shape)}")
    xq = np.random.default_rng(SEED + 3).normal(
        size=(n_queries, FEATURES)).astype(np.float32)
    sharded = np.asarray(clf.predict(xq))
    gathered = jax.device_put(model.gathered(), jax.devices()[0])
    single = np.asarray(clf.with_model(gathered).predict(xq))
    agree = float(np.mean(sharded == single))
    info = model.resident_bytes_per_device()
    print(f"[four-chips] C={n_classes} n={model.n_bundles} D={dim} fit in "
          f"{t_fit:.2f} s wall (compile included); sharded vs gathered on "
          f"chip 0: {int(np.sum(sharded != single))} of {n_queries} labels "
          f"differ (agreement {agree:.4f})", flush=True)
    print(f"[four-chips] resident_bytes_per_device: {info}", flush=True)
    check(agree >= SHARDED_MIN_AGREEMENT, f"sharded labels agree with the "
          f"gathered model on {agree:.4f} < {SHARDED_MIN_AGREEMENT}")
    check(info["ratio_to_ideal"] <= RATIO_CEILING, f"resident bytes "
          f"{info['ratio_to_ideal']:.3f}x the ideal split")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the class-sharded phase, on 4 chips")
    args = ap.parse_args(argv)

    device = require_tpu(4 if args.four_chips else 1)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        phase_four_chips()
    else:
        from repro.data.synth import load_dataset
        x_tr, y_tr, x_te, y_te, _ = load_dataset("isolet", seed=SEED)
        log, conv = phase_fit(x_tr, y_tr)
        phase_serve({"loghd": log.model, "conventional": conv.model},
                    x_te, y_te)
        phase_sweep(log, x_te, y_te)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
