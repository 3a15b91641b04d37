"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

A cell names a configuration and a traffic mix; each metric has a
reader module of its own. All of them are found by name, so a later
change adds a cell, a mix or a metric by adding files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic mix file's contents
    end_to_end: List[dict]  # BENCHMARK.json entries that apply to the cell
    per_layer: List[dict]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_mix(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    with open(bench_dir / "mixes" / f"{name}.json") as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, workload: str,
            root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(root / entry["file"]) as f:
        config = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        mix=load_mix(w["traffic"], root / "perfbench"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)])


_READERS: Dict[str, object] = {}


def reader(metric: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The reader module ``metrics/<metric>.py``: its ``read(obs)``
    returns the metric's value, or None where it finds nothing to
    read."""
    if metric not in _READERS:
        path = bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "perfbench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _READERS[metric] = mod
    return _READERS[metric]


def read_metrics(entries: List[dict], obs) -> Dict[str, dict]:
    """Each entry's value from its reader, with its unit; a reader that
    returns None leaves its metric out."""
    out: Dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = reader(m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
