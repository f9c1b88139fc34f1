"""The profiler-trace reduction."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import devtrace

DATA = Path(__file__).resolve().parent / "data"


def test_reduction_of_a_known_trace():
    ms = 1_000_000
    events = {
        "host": [["bench.window", 0, 100 * ms],
                 ["bench.step", 0, 30 * ms],
                 ["bench.next_batch", 30 * ms, 70 * ms],
                 ["bench.make_batch", 70 * ms, 90 * ms],
                 ["bench.step", 90 * ms, 110 * ms]],
        "devices": {"/device:TPU:0": [
            ["fusion.1", -5 * ms, 10 * ms],      # clipped at the window's start
            ["conv.2", 5 * ms, 20 * ms],         # overlaps fusion.1
            ["conv.2", 95 * ms, 130 * ms],       # clipped at its end
        ]},
    }
    r = devtrace.reduce(events)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["device_ops"] == [["conv.2", pytest.approx(0.02)],
                               ["fusion.1", pytest.approx(0.01)]]
    # the gap 20-95 ms, split by the annotations it overlaps
    assert r["idle_gaps"] == [["bench.next_batch", pytest.approx(0.04)],
                              ["bench.make_batch", pytest.approx(0.02)],
                              ["bench.step", pytest.approx(0.015)]]


def test_averages_over_devices_and_names_unattributed_gaps():
    events = {"host": [["bench.window", 0, 100]],
              "devices": {"/device:TPU:0": [["a", 0, 50]], "/device:TPU:1": [["a", 0, 30]]}}
    r = devtrace.reduce(events)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_gaps"] == [["other", pytest.approx(60e-9)]]


def test_nothing_to_read_gives_nothing():
    assert devtrace.reduce({"host": [], "devices": {}}) is None
    assert devtrace.reduce({"host": [["bench.window", 0, 10]],
                            "devices": {"/device:TPU:0": [["a", 20, 30]]}}) is None


def test_a_recorded_chip_trace():
    """Events extracted from a profiler trace of a TPU v5e (bench_events_v5e.json)."""
    events = json.loads((DATA / "bench_events_v5e.json").read_text())
    r = devtrace.reduce(events)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    assert {n for n, _ in r["idle_gaps"]} <= {"bench.step", "bench.make_batch",
                                              "bench.next_batch", "other"}


def test_extract_finds_the_annotations_of_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = devtrace.extract(devtrace.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in events["host"]]
    assert devtrace.WINDOW in names and "bench.step" in names
    assert events["devices"] == {}
