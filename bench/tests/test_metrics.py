"""Each metric's operation and byte counts, and its reduction, on made-up
runs."""

import math
import types

import numpy as np
import pytest

from bench import spec
from bench.modes.serve_open_loop import DONE, RAISED, Window

ISOLET = {"in_features": 617, "dim": 10000, "n_bundles": 10,
          "n_classes": 26}
LFAT = {"in_features": 768, "dim": 10000, "n_bundles": 21,
        "n_classes": 1305265}
PEAK = spec.peaks("TPU v5 lite")


def m(name):
    return spec.metric_module(name)


def test_encode_counts():
    enc = m("encode_roofline.serve")
    assert enc.flops(64, ISOLET) == 2 * 64 * 617 * 10000
    # the projection read alone is 24.68 MB
    assert enc.bytes_moved(0, 1, ISOLET) == 4 * 617 * 10000
    assert enc.bytes_moved(64, 1, ISOLET) == 4 * (64 * 617 + 617 * 10000
                                                  + 64 * 10000)
    # bytes bound at every batch up to 64
    for r in (1, 8, 64):
        assert (enc.flops(r, ISOLET) / PEAK["flops_per_s"]
                < enc.bytes_moved(r, 1, ISOLET) / PEAK["bytes_per_s"])


def test_predict_counts():
    pr = m("predict_roofline.serve")
    assert pr.flops(64, LFAT) == 2 * 64 * 10000 * 21 + 3 * 64 * 1305265 * 21
    needed = pr.bytes_moved(64, 1, LFAT)
    assert needed == 4 * (64 * 10000 + 21 * 10000 + 1305265 * 21 + 64)
    assert 112e6 < needed < 114e6          # about 113 MB per batch
    for s in (ISOLET, LFAT):
        for r in (1, 64):
            assert (pr.flops(r, s) / PEAK["flops_per_s"]
                    < pr.bytes_moved(r, 1, s) / PEAK["bytes_per_s"])


def test_mfu_counts():
    mfu = m("serve_mfu")
    assert mfu.flops_per_request(LFAT) == (2 * 768 * 10000 + 2 * 10000 * 21
                                           + 3 * 1305265 * 21)


def test_padded_rows():
    pad = m("serve_padded_row_share")
    # 3 calls at bucket 4 and one at 64 holding 10 + 50 admitted rows
    assert pad.padded_rows({4: 3, 64: 1}, 60) == 12 + 64 - 60


class FakeTrace:
    def __init__(self, calls, busy, union=None, window=1.0, dev=0.25):
        self._calls, self._busy = calls, busy
        self._union = busy if union is None else union
        self.window_s, self.busy_s = window, dev

    def module_calls(self, prefixes):
        return self._calls[prefixes[0]]

    def module_time(self, prefixes):
        return self._busy[prefixes[0]]

    def module_union(self, prefixes):
        return self._union


def fake_run(trace=None, counters=None, **window):
    n = 4
    w = Window(seconds=2.0, t_sched=np.array([0.1, 0.5, 1.0, 1.5]),
               t_submit=np.array([0.1005, 0.501, 1.0, 1.52]),
               t_done=np.array([0.102, 0.51, np.nan, 1.53]),
               label=np.zeros(n, np.int64),
               status=np.array([DONE, DONE, RAISED, DONE], np.int8),
               row=np.zeros(n, np.int64), counters={}, started_at=0.0,
               slice_s=(0.0, 2.0))
    for k, v in window.items():
        setattr(w, k, v)
    run = types.SimpleNamespace(window=w, trace=trace, config=LFAT,
                                peaks=PEAK, setup_s=12.5,
                                slice_counters=counters)
    run.rows_per_call = lambda: (counters["admitted"] / counters["cycles"]
                                 if counters else None)
    return run


def test_latency_percentiles_count_failures():
    run = fake_run()
    # sorted latencies: 1.5 ms, 10 ms, 30 ms, inf
    assert m("serve_p50_ms").read(run) == pytest.approx(10.0)
    assert math.isinf(m("serve_p90_ms").read(run))
    assert m("serve_rps").read(run) == 1.5
    assert m("setup_s").read(run) == 12.5
    assert m("loadgen_late_ms").read(run) == pytest.approx(20.0)


def test_counter_metrics():
    c = {"admitted": 60, "cycles": 4, "bucket_calls": {4: 3, 64: 1}}
    run = fake_run(counters=c)
    assert m("serve_rows_per_step").read(run) == 15.0
    assert m("serve_padded_row_share").read(run) == pytest.approx(
        100 * 16 / 76)
    assert m("serve_rows_per_step").read(fake_run()) is None


def test_roofline_and_mfu_readers():
    c = {"admitted": 640, "cycles": 10, "bucket_calls": {64: 10}}
    pr = m("predict_roofline.serve")
    need = pr.least_time(640, 10, LFAT, PEAK)
    tr = FakeTrace({"jit_run": 10, "jit_encode": 10},
                   {"jit_run": 4 * need, "jit_encode": 1e-3},
                   union=0.01)
    run = fake_run(trace=tr, counters=c)
    assert pr.read(run) == pytest.approx(25.0)
    enc = m("encode_roofline.serve")
    assert enc.read(run) == pytest.approx(
        100 * enc.least_time(640, 10, LFAT, PEAK) / 1e-3)
    done_in_slice = 3
    assert m("serve_mfu").read(run) == pytest.approx(
        100 * done_in_slice * m("serve_mfu").flops_per_request(LFAT)
        / (0.01 * PEAK["flops_per_s"]))
    assert m("device_idle_share.serve").read(run) == pytest.approx(75.0)


def test_readers_return_nothing_without_a_trace():
    run = fake_run(counters={"admitted": 1, "cycles": 1,
                             "bucket_calls": {1: 1}})
    for name in ("encode_roofline.serve", "predict_roofline.serve",
                 "serve_mfu", "device_idle_share.serve"):
        assert m(name).read(run) is None
    empty = FakeTrace({"jit_run": 0, "jit_encode": 0},
                      {"jit_run": 0.0, "jit_encode": 0.0}, union=0.0)
    run.trace = empty
    for name in ("encode_roofline.serve", "predict_roofline.serve",
                 "serve_mfu"):
        assert m(name).read(run) is None
