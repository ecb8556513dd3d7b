"""BENCHMARK.json against the contract's shapes, and every name in it
resolving to its files."""

import json
import re
import shutil
import types

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RESIDENCIES = ("float32", "bfloat16")
CLASSIFIER_PER_LAYER = ("loadgen_late_ms", "serve_rows_per_step",
                        "serve_padded_row_share", "encode_roofline.serve",
                        "predict_roofline.serve", "serve_mfu",
                        "device_idle_share.serve")


def config_faults(config: dict, maker) -> list:
    """What a configuration (its ``.json`` and its module) breaks of the
    benchmark's contract with it; empty where it keeps it.

    Every configuration states a precision for exactly the ``STAGES`` its
    module exports, each one the reference knows, and a residency. One
    that the LogHD reference judges keeps the classifier's contract: the
    three LogHD stages and float32 residency."""
    from bench.reference import PRECISIONS, STAGES, loghd_scores
    stages = tuple(getattr(maker, "STAGES", ()))
    if not stages:
        return ["its module exports no STAGES"]
    faults = []
    precision = {k: v for k, v in config["precision"].items()
                 if k != "meaning"}
    if set(precision) != set(stages) or len(set(stages)) != len(stages):
        faults.append(f"precision states {sorted(precision)}, the module's "
                      f"STAGES are {list(stages)}")
    faults += [f"{k}: precision {v!r} is not in {sorted(PRECISIONS)}"
               for k, v in precision.items() if v not in PRECISIONS]
    if config.get("residency") not in RESIDENCIES:
        faults.append(f"residency {config.get('residency')!r} is not one "
                      f"of {RESIDENCIES}")
    if maker.reference_scores is loghd_scores:
        if stages != STAGES:
            faults.append(f"a LogHD configuration's STAGES are {STAGES}")
        if config.get("residency") != "float32":
            faults.append("a LogHD configuration is resident in float32")
    return faults


def four_chip_cells_allowed(workloads: list) -> bool:
    """At most half of the cells, rounded down, ask for four chips; one
    may always."""
    four = sum(int(w["chips"]) == 4 for w in workloads)
    return four <= max(len(workloads) // 2, 1)


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
    """Each per-layer metric lists its cells, and each of them reports the
    end-to-end metric it moves: a cell added later reports only the
    per-layer metrics that name it."""
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and m["layer"]
        assert m["workloads"] and set(m["workloads"]) <= cells, m
        for w in m["workloads"]:
            assert m["moves"] in {e["name"] for e in
                                  spec.cell(w, bench).end_to_end}, (m, w)


def assert_cell_resolves(cell, bench: dict, root=spec.ROOT) -> None:
    """A cell's files load, and it reports what the contract asks: set-up
    time, another end-to-end metric, a per-layer metric, and exactly the
    metrics that list it."""
    assert cell.chips in (1, 4)
    assert callable(cell.maker.build)
    assert callable(cell.maker.reference_scores)
    assert callable(cell.mode.prepare) and callable(cell.mode.check)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            assert ((m["name"] in e2e | {p["name"] for p in cell.per_layer})
                    == (cell.name in m["workloads"])), (m["name"], cell.name)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_module(m["name"], root).read)
    limits = cell.config["correct"]["limits"]
    assert all(isinstance(v, (int, float)) for v in limits.values())


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.benchmark()["workloads"]])
def test_every_workload_resolves(workload, bench):
    cell = spec.cell(workload)
    assert_cell_resolves(cell, bench)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "serve_p50_ms"}
    # the tail is bounded only where host stalls leave it steady
    p90 = {m["name"]: m for m in bench["end_to_end"]}["serve_p90_ms"]
    assert ("serve_p90_ms" in {m["name"] for m in cell.end_to_end}) == (
        workload in p90["workloads"])


def test_four_chip_cells_within_the_contract(bench):
    assert four_chip_cells_allowed(bench["workloads"])
    assert not four_chip_cells_allowed([{"chips": 4}, {"chips": 4}])


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
    from bench.reference import PRECISIONS, stated
    path = spec.ROOT / config
    cfg = spec.load_json(path)
    maker = spec.load_module(path.with_suffix(".py"))
    assert len(stated(cfg, maker.STAGES)) == len(maker.STAGES)
    assert set(stated(cfg, maker.STAGES)) <= set(PRECISIONS)
    assert config_faults(cfg, maker) == []


@pytest.mark.parametrize("residency,stages,value,maker_stages,fault", [
    ("bfloat16", None, "default", None, "resident in float32"),
    ("float32", ("attention", "head"), "default", ("attention", "head"),
     "LogHD configuration's STAGES"),
    ("float32", ("projection", "similarity"), "default", None,
     "precision states"),
    ("float32", None, "fast", None, "is not in"),
    ("int8", None, "default", None, "residency 'int8'"),
], ids=["loghd_in_bfloat16", "loghd_with_own_stages", "stage_not_stated",
        "unknown_precision", "unknown_residency"])
def test_config_contract_refuses(residency, stages, value, maker_stages,
                                 fault):
    """A LogHD configuration keeps the classifier's contract, and any
    configuration states what its stages need."""
    from bench.reference import STAGES, loghd_scores
    cfg = spec.load_json(spec.ROOT / "bench/configs/loghd-lfat1.3m.json")
    cfg = {**cfg, "residency": residency,
           "precision": {**{s: value for s in stages or STAGES},
                         "meaning": "test"}}
    maker = types.SimpleNamespace(STAGES=maker_stages or STAGES,
                                  reference_scores=loghd_scores)
    faults = config_faults(cfg, maker)
    assert any(fault in f for f in faults), faults


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in"):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def _checkout(tmp_path):
    """A copy of the benchmark's files, to add new ones to."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path, spec.benchmark()


def test_new_cell_is_found_by_name(tmp_path):
    """A new configuration, traffic mix, metric and cell are new files plus
    entries: no existing file changes."""
    root, bench = _checkout(tmp_path)
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


LM_COUNTERS = {"slice_start": {"prefill_tokens": 100, "decode_steps": 7},
               "slice_end": {"prefill_tokens": 4196, "decode_steps": 519}}


def test_foreign_configuration_enters_as_new_files(tmp_path):
    """A bfloat16 configuration with stages of its own, a mode with
    counters and a ``delta`` of its own, and a four-chip cell enter as new
    files plus entries, and the cell reports none of the classifier's
    per-layer metrics."""
    from bench.modes import serve_open_loop
    from bench.run import RunData
    root, bench = _checkout(tmp_path)
    cfg_dir = root / "bench" / "configs"
    (cfg_dir / "lm-tiny.json").write_text(json.dumps(
        {"name": "lm-tiny", "reduced": [], "residency": "bfloat16",
         "precision": {"attention": "default", "experts": "default",
                       "head": "highest", "meaning": "test"},
         "correct": {"limits": {"logit_gap_max": 0.5}}}))
    (cfg_dir / "lm-tiny.py").write_text(
        "STAGES = ('attention', 'experts', 'head')\n"
        "def build(cfg, seed):\n    return ('built', seed)\n"
        "def reference_scores(params, x, precision, dtype=None):\n"
        "    return x\n")
    (root / "bench" / "modes" / "lm-decode.py").write_text(
        "def prepare(cell, built, seed, seconds):\n    return None\n"
        "def check(cell, built, window, seed):\n    return None\n"
        "def delta(a, b):\n"
        "    return {'tokens': b['prefill_tokens'] - a['prefill_tokens'],\n"
        "            'steps': b['decode_steps'] - a['decode_steps']}\n")
    (root / "bench" / "traffic" / "lm-short.json").write_text(json.dumps(
        {"mode": "lm-decode", "rate_rps": 4}))
    (root / "bench" / "metrics" / "decode_tokens_per_step.py").write_text(
        "def read(run):\n    c = run.slice_counters\n"
        "    return c['tokens'] / c['steps'] if c else None\n")
    bench["configs"].append({"name": "lm-tiny", "source": "x",
                             "file": "bench/configs/lm-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "lm-tiny.short", "config": "lm-tiny",
                               "traffic": "lm-short", "chips": 4,
                               "why": "test"})
    bench["per_layer"].append({"name": "decode_tokens_per_step",
                               "unit": "tokens", "better": "higher",
                               "source": "program_counter",
                               "layer": "decode", "moves": "serve_p50_ms",
                               "workloads": ["lm-tiny.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.cell("lm-tiny.short", root=root)
    assert cell.chips == 4 and four_chip_cells_allowed(bench["workloads"])
    assert config_faults(cell.config, cell.maker) == []
    assert_cell_resolves(cell, bench, root)
    names = [m["name"] for m in cell.per_layer]
    assert names == ["decode_tokens_per_step"]
    assert not set(names) & set(CLASSIFIER_PER_LAYER)
    # the slice counters are the new mode's delta; the classifier's would
    # not know these counters
    window = types.SimpleNamespace(counters=LM_COUNTERS)
    run = RunData(setup_s=1.0, window=window, config=cell.config,
                  peaks=spec.peaks("TPU v5 lite"), trace=None,
                  mode=cell.mode)
    assert run.slice_counters == {"tokens": 4096, "steps": 512}
    assert spec.metric_module("decode_tokens_per_step",
                              root).read(run) == 8.0
    with pytest.raises(KeyError):
        serve_open_loop.delta(*LM_COUNTERS.values())


CLASSIFIER_COUNTERS = {
    "slice_start": {"admitted": 10, "cycles": 2, "rejected": 0,
                    "errors": 0, "bucket_calls": {8: 1, 16: 1}},
    "slice_end": {"admitted": 70, "cycles": 6, "rejected": 1, "errors": 0,
                  "bucket_calls": {8: 2, 16: 3, 32: 1}}}


@pytest.mark.parametrize("mode,counters,expected", [
    ("serve_open_loop", CLASSIFIER_COUNTERS,
     {"admitted": 60, "cycles": 4, "rejected": 1, "errors": 0,
      "bucket_calls": {8: 1, 16: 2, 32: 1}}),
    ("no_delta", CLASSIFIER_COUNTERS, None),
    ("serve_open_loop", {"start": {}, "end": {}}, None),
])
def test_slice_counters_are_the_modes(mode, counters, expected):
    """The classifier's mode reads its slice as before; a mode with no
    ``delta``, or a run with no traced slice, has no slice counters."""
    from bench.run import RunData
    mod = (spec.load_module(spec.BENCH_DIR / "modes" / f"{mode}.py")
           if mode != "no_delta" else types.SimpleNamespace())
    run = RunData(setup_s=1.0, window=types.SimpleNamespace(
        counters=counters), config={}, peaks={}, trace=None, mode=mod)
    assert run.slice_counters == expected
