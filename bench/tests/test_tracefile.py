"""The trace reducer: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on the chip."""

import pathlib

import pytest

from bench import tracefile as tf

DATA = pathlib.Path(__file__).parent / "data"


def test_union_and_merge():
    iv = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 41)]
    assert tf.union_ns(iv) == 15 + 11 + 1
    assert tf.merged(iv) == [[0, 15], [20, 31], [40, 41]]
    assert tf.union_ns([]) == 0


def test_module_name():
    assert tf.module_name("jit_encode(1234)") == "jit_encode"
    assert tf.module_name("jit_run") == "jit_run"


def made_up():
    ops = [[("fusion.1", 100, 200), ("convolution", 200, 260),
            ("fusion.1", 500, 600), ("copy", 900, 950)]]
    modules = [[("jit_encode(7)", 100, 260), ("jit_run(9)", 500, 600),
                ("jit_slice(3)", 900, 950)]]
    spans = [("bench.result", 260, 480), ("bench.submit", 610, 640),
             ("bench.result", 700, 890)]
    return tf.Trace(window_ns=(0, 1000), ops=ops, modules=modules,
                    spans=spans)


def test_trace_sums():
    t = made_up()
    assert t.window_s == pytest.approx(1e-6)
    assert t.busy_s == pytest.approx(310e-9)
    assert t.module_calls(("jit_encode",)) == 1
    assert t.module_time(("jit_encode", "jit_run")) == pytest.approx(260e-9)
    assert t.module_union(("jit_encode", "jit_run")) == pytest.approx(
        260e-9)
    assert t.top_ops(2) == [["fusion.1", pytest.approx(200e-9)],
                            ["convolution", pytest.approx(60e-9)]]


def test_idle_gaps_named_by_host_spans():
    gaps = made_up().idle_gaps()
    # gaps: 0-100, 260-500, 600-900, 950-1000
    assert [g[0] for g in gaps] == ["bench.result", "bench.result",
                                    "unattributed", "unattributed"]
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 240e-9, 100e-9,
                                                  50e-9])


def test_clip_to_window():
    assert tf._clip([("a", -5, 5), ("b", 8, 20), ("c", 30, 40)], 0, 10) == [
        ("a", 0, 5), ("b", 8, 10)]


def test_recorded_chip_trace():
    """A 0.45 s slice of ``lfat1.3m.serve.poisson`` recorded on a TPU v5e
    (``--seconds 1 --trace 1``): the device never idles, predict is most
    of its time, and the per-call profile pad is the top operation."""
    t = tf.read(str(DATA / "lfat1.3m_serve_slice.xplane.pb"))
    assert t.window_s == pytest.approx(0.448842611)
    assert t.busy_s == pytest.approx(0.447023109)
    assert t.module_calls(("jit_run",)) == 90
    assert t.module_calls(("jit_encode",)) == 89
    assert t.module_time(("jit_run",)) == pytest.approx(0.438025278)
    assert t.module_time(("jit_encode",)) == pytest.approx(0.00900281)
    assert t.module_union(("jit_encode", "jit_run")) == pytest.approx(
        0.447028088)
    top = t.top_ops(3)
    assert top[0][0].startswith("%pad.19 = f32[1305600,128]")
    assert top[0][1] == pytest.approx(0.168822815)
    assert {n for n, _, _ in t.spans} == {"bench.submit", "bench.result"}
    gaps = t.idle_gaps()
    assert len(gaps) <= 10 and gaps[0] == ["bench.submit",
                                           pytest.approx(0.001111257)]


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        tf.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert tf.find_xplane(str(tmp_path)).endswith("host.xplane.pb")
