"""Find a cell's parts by name: the workload entry of ``BENCHMARK.json``,
its configuration and traffic files, the family module that serves the
configuration, and the reader of each metric the cell reports.

Nothing here lists a configuration, a mix or a metric by name: a later
change adds a cell with new files and new ``BENCHMARK.json`` entries.

  configs/<config>.json     sizes as run; ``family`` names the module
  families/<family>.py      builds the served objects, runs the check
  traffic/<traffic>.json    parameters of the one generator (loadgen.py)
  metrics/<metric>.py       ``read(run) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import a file by path (metric names carry dots, so they cannot
    be imported as package modules)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    rel = "/".join(path.parts[-2:])
    mod_name = name or "benchmarks_chip_" + "".join(
        ch if ch.isalnum() else "_" for ch in rel)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    family: object
    end_to_end: list      # metric entries this cell reports
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _reports(metric: dict, workload: str, e2e_here: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_here


def load_cell(name: str, bench: Optional[dict] = None,
              base: pathlib.Path = HERE) -> Cell:
    """The cell ``name`` of ``bench`` (default: the repo's
    ``BENCHMARK.json``), with files looked up under ``base``."""
    if bench is None:
        bench = load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    wl = by_name[name]
    config = load_json(base / "configs" / f"{wl['config']}.json")
    config.setdefault("name", wl["config"])
    traffic = load_json(base / "traffic" / f"{wl['traffic']}.json")
    family = load_module(base / "families" / f"{config['family']}.py")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    here = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, here)]
    return Cell(workload=wl, config=config, traffic=traffic, family=family,
                end_to_end=e2e, per_layer=per_layer)


def metric_module(name: str, base: pathlib.Path = HERE):
    """The reader of metric ``name``: ``read(run)``, and optionally the
    ``MODULE`` name pattern of the XLA programs it needs from the trace."""
    return load_module(base / "metrics" / f"{name}.py")
