"""The benchmark's plan, read as data.

``BENCHMARK.json`` names every cell, configuration and metric. Whatever
belongs to one of them sits in a file of its own, found by that name:

  configs/<config>.json   the deployment: leaf shapes, dtype, algorithm,
                          the limits of the correctness check
  mixes/<traffic>.json    the traffic mix, read by ``traffic.make``
  metrics/<metric>.py     a per-layer metric: ``read(window)`` returns a
                          number, or None where the trace has nothing for it

So a cell, a configuration or a metric is added by adding files and
entries, never by editing one that exists.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

#: the checkout's root: chipbench/plan.py -> repo
REPO = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None  # per-layer metrics only


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(path: Path) -> Callable:
    """The ``read`` function of one per-layer metric's file."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, repo: Path = REPO) -> Cell:
    """Resolve one cell of ``<repo>/BENCHMARK.json`` to its files."""
    bench = json.loads((repo / "BENCHMARK.json").read_text())
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; "
                       f"cells: {', '.join(sorted(cells))}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((repo / configs[cell["config"]]["file"]).read_text())
    base = repo / bench["paths"][0]
    mix = json.loads((base / "mixes" / f"{cell['traffic']}.json").read_text())
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [Metric(m["name"], m["unit"],
                        load_reader(base / "metrics" / f"{m['name']}.py"))
                 for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, int(cell["chips"]), config, mix, e2e, per_layer)
