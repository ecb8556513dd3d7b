"""The knee sweep and the control readings at a tiny size on the CPU."""

import dataclasses

from bench import control, knee
from bench.tests.test_run_cpu import cpu_as_chip, tiny_cell


def test_knee_sweep_reports_each_rate():
    out = knee.sweep(tiny_cell(), 3, 0.5, [100, 300], cpu_as_chip)
    assert [r["rate_rps"] for r in out] == [100.0, 300.0]
    for r in out:
        assert r["failed"] == 0
        assert 0 < r["p50_ms"] <= r["p99_ms"]
        assert r["rows_per_step"] >= 1.0


def test_control_readings_order_program_control_fault():
    cell = tiny_cell()
    cell = dataclasses.replace(cell, config={**cell.config,
                                             "n_classes": 20000,
                                             "n_bundles": 15})
    r = control.readings_for_seed(cell, 4, 0.5,
                                  [("highest", "highest", "highest")])
    assert r["failed"] == {"program": 0, "control_int8": 0}
    assert r["answers"] == 100
    stated = r["default/default/default"]
    # on the CPU every precision is float32: the program is the reference
    assert stated["program"] == {"gap_max": 0.0, "mismatch_share": 0.0}
    assert stated["control_int8"]["mismatch_share"] > 0
    assert stated["witness_bf16"]["mismatch_share"] > 0
    assert r["highest/highest/highest"] == stated
