"""BENCHMARK.json against the contract's shapes, and every name in it
resolving to its files."""

import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"][1] == "bench/run.py"
    assert 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in
                                            bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    for c in bench["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25, m
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_per_layer_moves_an_end_to_end_metric(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] == "serve_p50_ms"
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and m["layer"]


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.benchmark()["workloads"]])
def test_every_workload_resolves(workload):
    cell = spec.cell(workload)
    assert cell.chips == 1
    assert callable(cell.maker.build)
    assert callable(cell.maker.reference_scores)
    assert callable(cell.mode.prepare) and callable(cell.mode.check)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "serve_p50_ms"}
    # the tail is bounded only where host stalls leave it steady
    assert ("serve_p90_ms" in {m["name"] for m in cell.end_to_end}) == (
        workload == "lfat1.3m.serve.poisson")
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_module(m["name"]).read)
    limits = cell.config["correct"]["limits"]
    assert all(isinstance(v, (int, float)) for v in limits.values())


def test_config_files_are_distinct_and_named(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg["reduced"])


@pytest.mark.parametrize("config", [c["file"] for c in
                                    spec.benchmark()["configs"]])
def test_config_states_each_matmul_precision(config):
    """The reference runs at what the configuration states, stage by
    stage, and nothing is left to a default."""
    from bench.reference import PRECISIONS, STAGES, stated
    cfg = spec.load_json(spec.ROOT / config)
    assert len(stated(cfg)) == len(STAGES)
    assert set(stated(cfg)) <= set(PRECISIONS)
    assert cfg["residency"] == "float32"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in"):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_new_cell_is_found_by_name(tmp_path):
    """A new configuration, traffic mix, metric and cell are new files plus
    entries: no existing file changes."""
    root = tmp_path
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = spec.benchmark()
    (root / "bench" / "configs" / "tiny-new.json").write_text(json.dumps(
        {"name": "tiny-new", "service": {"max_batch": 4},
         "correct": {"sample": 8, "limits": {}}}))
    (root / "bench" / "configs" / "tiny-new.py").write_text(
        "def build(cfg, seed):\n    return ('built', seed)\n"
        "def reference_scores(params, x, dtype=None):\n    return x\n")
    (root / "bench" / "traffic" / "burst-new.json").write_text(json.dumps(
        {"mode": "serve_open_loop", "rate_rps": 100}))
    (root / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "tiny-new", "source": "x",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.burst", "config": "tiny-new",
                               "traffic": "burst-new", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "serving", "moves": "serve_p50_ms",
                               "workloads": ["tiny.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.cell("tiny.burst", root=root)
    assert cell.maker.build({}, 3) == ("built", 3)
    assert cell.traffic["rate_rps"] == 100
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert spec.metric_module("new_metric", root=root).read(None) == 42.0
    # the metric lists its cell, so the other cells do not report it
    other = spec.cell("isolet.serve.poisson", root=root)
    assert "new_metric" not in [m["name"] for m in other.per_layer]
