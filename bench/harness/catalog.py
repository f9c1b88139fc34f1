"""Finds a cell's files by name: everything the harness runs is data beside it.

    BENCHMARK.json                 cells, configurations, metrics
    bench/configs/<config>.json    a configuration (the file BENCHMARK.json names)
    bench/networks/<network>.py    the plain reference of a network (the file a
                                   configuration names under "network"): its
                                   ``init_params(key, cfg)``, ``loss(params, x,
                                   y, mask, cfg, step)``, ``last_layer(cfg)``
                                   and ``train_flops_per_sample(cfg)``
    bench/workloads/<cell>.json    the traffic of one cell
    bench/metrics/<metric>.py      a reader: ``read(run) -> float | None``

Adding a network, a configuration, a cell or a metric means adding such a
file and an entry in BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType

__all__ = ["Cell", "load_cell", "load_metric", "load_network"]

#: what the harness calls of a network module.
NETWORK_API = ("init_params", "loss", "last_layer", "train_flops_per_sample")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    #: the configuration's network module (``load_network``).
    network: ModuleType
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
    config_file = root / configs[entry["config"]]["file"]
    config = _read_json(config_file)
    if "network" not in config:
        raise LookupError(f"{config_file} names no network module")
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
        network=load_network(root, config["network"]),
        workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root,
    )


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_metric(root: Path, name: str) -> ModuleType:
    """The reader module ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no reader for metric {name!r} at {path}")
    module = _load_module(path, f"bench_metric_{name}")
    if not callable(getattr(module, "read", None)):
        raise TypeError(f"{path} defines no read(run)")
    return module


def load_network(root: Path, relpath: str) -> ModuleType:
    """The network module at ``relpath`` under ``root``, as a configuration's
    ``"network"`` names it."""
    root = Path(root).resolve()
    path = (root / relpath).resolve()
    if not path.is_relative_to(root) or path.suffix != ".py" or not path.is_file():
        raise LookupError(f"no network module {relpath!r} under {root}")
    module = _load_module(path, f"bench_network_{path.stem}")
    missing = [f for f in NETWORK_API if not callable(getattr(module, f, None))]
    if missing:
        raise TypeError(f"{path} defines no {', '.join(missing)}")
    return module
