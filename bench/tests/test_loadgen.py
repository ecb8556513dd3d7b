"""The generator's schedule, and the serving window at a tiny size on the
CPU."""

import dataclasses

import jax
import numpy as np

from bench import loadgen, spec
from bench.modes import serve_open_loop as drv


def test_steady_schedule_fixed_count_per_seed():
    a = loadgen.schedule({"rate_rps": 1000}, 2.0, 50, seed=2**33 + 1)
    b = loadgen.schedule({"rate_rps": 1000}, 2.0, 50, seed=2**33 + 1)
    c = loadgen.schedule({"rate_rps": 1000}, 2.0, 50, seed=7)
    assert len(a) == len(c) == 2000
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.row, b.row)
    assert not np.array_equal(a.t, c.t)
    assert np.all(np.diff(a.t) >= 0) and 0 <= a.t[0] and a.t[-1] < 2.0
    assert a.row.min() >= 0 and a.row.max() < 50


def test_window_accounts_for_every_request():
    cell = spec.cell("lfat1.3m.serve.poisson")
    cfg = {**cell.config, "dim": 256, "n_classes": 300, "n_bundles": 9,
           "in_features": 16, "pool": 32}
    cell = dataclasses.replace(cell, config=cfg,
                               traffic={**cell.traffic, "rate_rps": 400})
    built = cell.maker.build(cfg, 5)
    rig = cell.mode.prepare(cell, built, 5, 0.5)
    w = rig.measure(trace_slice=(0.1, 0.3), on_slice=lambda what: None)
    assert w.status.size == 200
    assert np.all(w.status == drv.DONE)
    assert np.all(w.t_done >= w.t_sched)
    assert np.all(w.t_submit >= w.t_sched - 1e-9)
    assert set(w.counters) == {"start", "end", "slice_start", "slice_end"}
    d = drv.delta(w.counters["start"], w.counters["end"])
    assert d["admitted"] == 200 and d["cycles"] >= 200 / cfg["service"][
        "max_batch"]
    # the served labels are the program's own predict on the same rows
    from repro.hdc.encoders import encode
    model = built.model()
    ref = np.asarray(model.predict_encoded(encode(
        model.enc, jax.numpy.asarray(built.pool[w.row]), "cos")))
    np.testing.assert_array_equal(w.label, ref)
