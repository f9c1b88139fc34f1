"""Tests of the benchmark's own code, on the CPU at small sizes.

    python -m pytest bench/tests
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

#: the benchmark's configurations, name -> file, as BENCHMARK.json lists them.
with open(ROOT / "BENCHMARK.json") as _f:
    CONFIG_FILES = {c["name"]: ROOT / c["file"] for c in json.load(_f)["configs"]}


def config(name: str) -> dict:
    with open(CONFIG_FILES[name]) as f:
        return json.load(f)


def tiny_config(name: str) -> dict:
    """The configuration's small stand-in for the CPU: its ``tiny`` block
    over the file, the same network and layout at small sizes."""
    cfg = config(name)
    cfg.update(cfg.pop("tiny"))
    cfg.pop("parameters", None)
    return cfg


def per_layer_off_device() -> set:
    """The per-layer metrics of BENCHMARK.json that a run without a device
    trace (on the CPU) reads: all but those taken from the device trace."""
    with open(ROOT / "BENCHMARK.json") as f:
        return {m["name"] for m in json.load(f)["per_layer"] if m["source"] != "device_trace"}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-like root with BENCHMARK.json and bench/ holding two small
    cells, tiny_ptychonn.pfs and tiny_cosmoflow.cached, for the CPU."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    shutil.copytree(ROOT / "bench" / "networks", bench / "networks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir()
    (bench / "workloads").mkdir()
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"], spec["workloads"] = [], []
    for base, traffic, extra in (
        ("ptychonn_repo", "pfs", dict(buffer_size=32, num_epochs=40, warmup_steps=4)),
        ("cosmoflow_repo", "cached", dict(buffer_size=12, num_epochs=200, warmup_steps=3)),
    ):
        cfg = tiny_config(base)
        name = cfg["name"]
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        with open(ROOT / "bench" / "workloads" / f"{base}.{traffic}.json") as f:
            wl = json.load(f)
        wl.update(extra, config=name)
        (bench / "workloads" / f"{name}.{traffic}.json").write_text(json.dumps(wl))
        spec["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                                "file": f"bench/configs/{name}.json"})
        spec["workloads"].append({"name": f"{name}.{traffic}", "config": name,
                                  "traffic": traffic, "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    import jax

    kind = jax.devices()[0].device_kind
    (bench / "peaks.json").write_text(json.dumps(
        {"devices": {kind: {"bf16_flops_per_s": 1e12}}}))
    return tmp_path
