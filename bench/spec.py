"""Resolve a cell of ``BENCHMARK.json`` to its files by name.

Nothing here knows a particular cell: a new configuration, traffic mix,
window mode or metric is a new file plus an entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a benchmark file by path. File names may hold '.' and '-'.
    A file of this checkout whose path is a dotted module name is imported
    as such, so it is one module object wherever it is imported."""
    path = pathlib.Path(path).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    if path.is_relative_to(ROOT):
        parts = path.relative_to(ROOT).with_suffix("").parts
        if all(p.isidentifier() for p in parts):
            return importlib.import_module(".".join(parts))
    name = f"bench._by_path.{path}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry with everything it names, loaded."""
    name: str
    chips: int
    config: dict          # configs/<config>.json
    maker: ModuleType     # configs/<config>.py
    traffic: dict         # traffic/<mix>.json
    mode: ModuleType      # modes/<traffic["mode"]>.py
    end_to_end: tuple     # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """Whether ``cell`` reports ``metric``: every cell listed under its
    ``workloads`` key, or, without that key, every cell that reports the
    end-to-end metric it ``moves`` (end-to-end metrics: every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def cell(name: str, bench: dict | None = None,
         root: pathlib.Path = ROOT) -> Cell:
    """Load workload ``name`` and the files it names under ``root``."""
    bench = bench if bench is not None else benchmark(root)
    bench_dir = root / "bench"
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(entries)}")
    w = entries[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = root / cfg_entry["file"]
    config = load_json(cfg_path)
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, name, set()))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if reports(m, name, e2e_names))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                maker=load_module(cfg_path.with_suffix(".py")),
                traffic=traffic,
                mode=load_module(bench_dir / "modes"
                                   / f"{traffic['mode']}.py"),
                end_to_end=e2e, per_layer=per_layer)


def metric_module(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    return load_module(root / "bench" / "metrics" / f"{name}.py")


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
