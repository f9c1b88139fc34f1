import json
import shutil
import time

import pytest

from bench.harness import catalog
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_of_the_benchmark_is_found_with_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in spec["workloads"]:
        cell = catalog.load_cell(ROOT, entry["name"])
        assert cell.config["name"] == entry["config"]
        assert cell.workload["config"] == entry["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"samples_per_s", "setup_s"}
        assert cell.per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(catalog.load_metric(ROOT, m["name"]).read)


def test_unknown_names_fail():
    with pytest.raises(LookupError):
        catalog.load_cell(ROOT, "no_such.cell")
    with pytest.raises(LookupError):
        catalog.load_metric(ROOT, "no_such_metric")


def test_a_cell_and_a_metric_added_as_files_are_found(tiny_root):
    """Adding a cell or a metric takes new files and BENCHMARK.json entries only."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    base = json.loads((tiny_root / "bench/workloads/tiny_ptychonn.pfs.json").read_text())
    base["buffer_size"] = 64
    (tiny_root / "bench/workloads/tiny_ptychonn.bigbuf.json").write_text(json.dumps(base))
    (tiny_root / "bench/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return len(run.steps)\n")
    spec["workloads"].append({"name": "tiny_ptychonn.bigbuf", "config": "tiny_ptychonn",
                              "traffic": "bigbuf", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_in_window", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "loader",
                              "moves": "samples_per_s", "workloads": ["tiny_ptychonn.bigbuf"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = catalog.load_cell(tiny_root, "tiny_ptychonn.bigbuf")
    assert cell.workload["buffer_size"] == 64
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    assert "steps_in_window" not in [
        m["name"] for m in catalog.load_cell(tiny_root, "tiny_ptychonn.pfs").per_layer]
    assert catalog.load_metric(tiny_root, "steps_in_window").read(
        type("R", (), {"steps": [1, 2]})) == 2


def test_a_cell_whose_file_names_another_config_is_refused(tiny_root):
    path = tiny_root / "bench/workloads/tiny_ptychonn.pfs.json"
    wl = json.loads(path.read_text())
    wl["config"] = "tiny_cosmoflow"
    path.write_text(json.dumps(wl))
    with pytest.raises(ValueError):
        catalog.load_cell(tiny_root, "tiny_ptychonn.pfs")


def test_a_network_added_as_a_file_plugs_in(tiny_root):
    """A configuration that names a network module found only in this root
    (a copy of cnn_repo.py under a new name) runs as a cell with no edit to
    the harness: correct, with the losses of the cell whose configuration
    names the benchmark's own module."""
    from bench.harness.cell import run_cell

    networks = tiny_root / "bench/networks"
    shutil.copy(networks / "cnn_repo.py", networks / "cnn_plugged.py")
    assert not (ROOT / "bench/networks/cnn_plugged.py").exists()
    cfg = json.loads((tiny_root / "bench/configs/tiny_ptychonn.json").read_text())
    cfg |= {"name": "tiny_plugged", "network": "bench/networks/cnn_plugged.py"}
    (tiny_root / "bench/configs/tiny_plugged.json").write_text(json.dumps(cfg))
    wl = json.loads((tiny_root / "bench/workloads/tiny_ptychonn.pfs.json").read_text())
    (tiny_root / "bench/workloads/tiny_plugged.pfs.json").write_text(
        json.dumps(wl | {"config": "tiny_plugged"}))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_plugged", "source": "test", "reduced": [],
                            "why": "test", "file": "bench/configs/tiny_plugged.json"})
    spec["workloads"].append({"name": "tiny_plugged.pfs", "config": "tiny_plugged",
                              "traffic": "pfs", "chips": 1, "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = catalog.load_cell(tiny_root, "tiny_plugged.pfs")
    assert Path(cell.network.__file__) == networks / "cnn_plugged.py"
    runs = {name: run_cell(tiny_root, name, seed=2**33 + 11, seconds=1.0, trace=False,
                           t_start=time.perf_counter(), require_tpu=False)[0]
            for name in ("tiny_plugged.pfs", "tiny_ptychonn.pfs")}
    for r in runs.values():
        assert r["correct"], r["checks"]
    plugged, base = (runs[n]["detail"]["losses"] for n in ("tiny_plugged.pfs",
                                                           "tiny_ptychonn.pfs"))
    assert plugged == base


def test_a_network_outside_the_root_or_without_its_functions_is_refused(tiny_root):
    shutil.copy(tiny_root / "bench/networks/cnn_repo.py", tiny_root.parent / "outside.py")
    with pytest.raises(LookupError):
        catalog.load_network(tiny_root, "../outside.py")
    with pytest.raises(LookupError):
        catalog.load_network(tiny_root, "bench/networks/no_such.py")
    (tiny_root / "bench/networks/half.py").write_text("def init_params(key, cfg):\n    pass\n")
    with pytest.raises(TypeError, match="loss, last_layer, train_flops_per_sample"):
        catalog.load_network(tiny_root, "bench/networks/half.py")
