"""A cell as ``BENCHMARK.json`` names it, resolved to its files: the
configuration (its ``file``), the traffic mix (``bench/traffic/<traffic>.json``),
the entry the traffic drives (``bench/entries/<entry>.py``) and the metrics
it reports (``bench/metrics/<metric>.py``).  A new cell, entry or metric is
new files and ``BENCHMARK.json`` entries only."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    reader: ModuleType


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    entry: type
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(path: Path, kind: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(root: Path, name: str) -> type:
    """The ``ENTRY`` class of ``bench/entries/<name>.py``."""
    return _module(root / "bench" / "entries" / f"{name}.py", "entry").ENTRY


def load_metric(root: Path, entry: Dict) -> Metric:
    path = root / "bench" / "metrics" / f"{entry['name']}.py"
    mod = _module(path, "metric")
    for key in ("unit", "source", "better", "layer", "moves"):
        if key in entry and getattr(mod, key.upper()) != entry[key]:
            raise ValueError(f"{path.name}: {key} {getattr(mod, key.upper())!r}"
                             f" but BENCHMARK.json says {entry[key]!r}")
    return Metric(entry["name"], entry["unit"], mod)


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        entry=load_entry(root, traffic["entry"]),
        end_to_end=[load_metric(root, m) for m in bench["end_to_end"]
                    if _reports(m, name)],
        per_layer=[load_metric(root, m) for m in bench["per_layer"]
                   if _reports(m, name)])
