"""Finds a cell's files by name: everything the harness runs is data beside it.

    BENCHMARK.json                 cells, configurations, metrics
    bench/configs/<config>.json    a configuration (the file BENCHMARK.json names)
    bench/workloads/<cell>.json    the traffic of one cell
    bench/metrics/<metric>.py      a reader: ``read(run) -> float | None``

Adding a configuration, a cell or a metric means adding such a file and an
entry in BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

__all__ = ["Cell", "load_cell", "load_metric"]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    #: the metrics this cell reports, as BENCHMARK.json lists them:
    #: ``end_to_end`` for a run with ``--trace 0``, ``per_layer`` with 1.
    end_to_end: list
    per_layer: list
    root: Path


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise LookupError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root``/BENCHMARK.json, with its files read."""
    root = Path(root)
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise LookupError(f"unknown cell {name!r}; have {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise LookupError(f"cell {name!r} names unknown config {entry['config']!r}")
    config = _read_json(root / configs[entry["config"]]["file"])
    workload = _read_json(root / "bench" / "workloads" / f"{name}.json")
    if workload.get("config") != entry["config"]:
        raise ValueError(
            f"bench/workloads/{name}.json is for config "
            f"{workload.get('config')!r}, BENCHMARK.json says {entry['config']!r}"
        )
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root,
    )


def load_metric(root: Path, name: str) -> ModuleType:
    """The reader module ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise TypeError(f"{path} defines no read(run)")
    return module
