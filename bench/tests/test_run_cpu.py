"""The entry point refuses the CPU, and a run with the timed path broken
underneath comes out not correct."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench import spec

ROOT = spec.ROOT


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


@pytest.mark.parametrize("argv", [
    [sys.executable, "bench/run.py"],
    [sys.executable, "-m", "bench.run"],
])
def test_cpu_is_refused_without_a_result(argv):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        argv + ["--workload", "isolet.serve.poisson", "--seed", "1",
                "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    _no_result(proc)


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "isolet.serve.poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    _no_result(proc)


def tiny_cell(name="lfat1.3m.serve.poisson"):
    cell = spec.cell(name)
    cfg = {**cell.config, "dim": 256, "n_classes": 2000, "n_bundles": 11,
           "in_features": 32, "pool": 64,
           }
    return dataclasses.replace(cell, config=cfg,
                               traffic={**cell.traffic, "rate_rps": 200})


def cpu_as_chip(chips):
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": "TPU v5 lite", "count": 1}


def test_sound_run_is_correct():
    res = bench_run.measure(tiny_cell(), 2**31 + 11, 0.5, False,
                            require=cpu_as_chip)
    assert res["correct"] is True
    assert res["attempted"] == 100 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"serve_p90_ms", "serve_p50_ms",
                                   "serve_rps", "setup_s"}


@pytest.mark.parametrize("name", ["isolet.serve.poisson",
                                  "lfat1.3m.serve.poisson"])
def test_altered_answer_is_not_correct(name, monkeypatch):
    """Each label moved to the next class where the program produces it."""
    from repro.serving.buckets import BucketedPredict
    cell = tiny_cell(name) if name.startswith("lfat") else dataclasses.replace(
        spec.cell(name), config={**spec.cell(name).config, "dim": 256,
                                 "pool": 512},
        traffic={**spec.cell(name).traffic, "rate_rps": 200})
    n_classes = cell.config["n_classes"]
    real = BucketedPredict.predict

    def altered(self, model, h, use_kernels=None):
        return (real(self, model, h, use_kernels) + 1) % n_classes

    monkeypatch.setattr(BucketedPredict, "predict", altered)
    res = bench_run.measure(cell, 5, 0.5, False, require=cpu_as_chip)
    assert res["correct"] is False
    assert res["checks"]["mismatch_share"]["value"] == 1.0


def test_int8_residency_control_is_not_correct():
    """The program's own lower-precision path, int8 residency
    (``quantize_bits=8``), switched on: the 1.3M-class cell's limits,
    which sound runs meet, fail it."""
    cell = tiny_cell()
    sound = bench_run.measure(cell, 9, 0.5, False, require=cpu_as_chip)
    assert sound["correct"] is True
    service = {**cell.config["service"], "quantize_bits": 8}
    control = dataclasses.replace(
        cell, config={**cell.config, "service": service})
    res = bench_run.measure(control, 9, 0.5, False, require=cpu_as_chip)
    assert res["correct"] is False
    limit = res["checks"]["mismatch_share"]["limit"]
    assert res["checks"]["mismatch_share"]["value"] > limit


def test_bf16_reference_reads_above_float32():
    """The reference computed in bfloat16 puts other labels first than in
    float32; in float32 it agrees with itself exactly."""
    import jax.numpy as jnp

    from bench.correct import control_labels, readings
    from bench.reference import stated
    cell = tiny_cell()
    built = cell.maker.build(cell.config, 9)
    rows = np.random.default_rng(0).integers(0, len(built.pool), 512)
    ref = cell.maker.reference_scores
    prec = stated(cell.config, cell.maker.STAGES)
    lab = control_labels(ref, prec, built.params, built.pool, rows,
                         jnp.bfloat16)
    low = readings(ref, prec, built.params, built.pool, rows, lab)
    assert low["mismatch_share"] > 0 and low["gap_max"] > 0
    exact = control_labels(ref, prec, built.params, built.pool, rows,
                           jnp.float32)
    assert readings(ref, prec, built.params, built.pool, rows, exact) == {
        "gap_max": 0.0, "mismatch_share": 0.0}


def test_request_never_collected_is_missing():
    """An answer that never came fails the run, whatever the others say."""
    from bench.correct import check_labels
    from bench.modes import serve_open_loop as drv
    cell = tiny_cell()
    built = cell.maker.build(cell.config, 3)
    rows = np.arange(4)
    from repro.hdc.encoders import encode
    model = built.model()
    labels = np.asarray(model.predict_encoded(encode(
        model.enc, jax.numpy.asarray(built.pool[rows]), "cos")))
    status = np.array([drv.DONE, drv.DONE, drv.PENDING, drv.REJECTED],
                      np.int8)
    w = drv.Window(seconds=1.0, t_sched=np.zeros(4), t_submit=np.zeros(4),
                   t_done=np.zeros(4), label=labels, status=status,
                   row=rows, counters={}, started_at=0.0)
    res = check_labels(cell.config, cell.maker, built, w, 3)
    assert res["checks"]["missing"]["value"] == 1
    assert res["checks"]["mismatch_share"]["value"] == 0.0
    assert res["correct"] is False
