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

#: small stand-ins of the two configurations: same kinds and layouts.
TINY = {
    "ptychonn_repo": dict(input_shape=[16, 16, 1], output_shape=[16, 16, 2], base_channels=8,
                     depth=2, record_shape=[16, 16, 3], local_batch=8, num_samples=512),
    "cosmoflow_repo": dict(input_shape=[16, 16, 16, 4], output_shape=[4], base_channels=8,
                      depth=2, record_shape=[16, 16, 16, 4], local_batch=2, num_samples=48),
}


def tiny_config(name: str) -> dict:
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(TINY[name], name=f"tiny_{cfg['kind']}")
    cfg.pop("parameters", None)
    return cfg


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout-like root with BENCHMARK.json and bench/ holding two small
    cells, tiny_ptychonn.pfs and tiny_cosmoflow.cached, for the CPU."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
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
