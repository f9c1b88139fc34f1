"""The benchmark's ``read_tasks_per_step`` reader, loaded by path as the
benchmark's catalog loads it, on synthetic runs."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

READER = Path(__file__).resolve().parents[1] / "bench" / "metrics" / "read_tasks_per_step.py"


@pytest.fixture(scope="module")
def read():
    spec = importlib.util.spec_from_file_location("bench_metric_read_tasks_per_step", READER)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _run(spans, steps=4):
    # a window of (10, 20]; its steps complete at 11, 12, ...
    return SimpleNamespace(t_open=10.0, t_close=20.0, spans=spans,
                           steps=[(11.0 + i, 64, 0) for i in range(steps)])


def test_no_value_without_read_task_spans(read):
    assert read(_run(None)) is None, "untraced"
    assert read(_run([("prefetch.read_wait", 11.0, 12.0)])) is None, "an older program"
    assert read(_run([("prefetch.read_task", 11.0, 12.0)], steps=0)) is None


@pytest.mark.parametrize("steps", [1, 4, 8])
def test_counts_window_read_tasks_per_step(read, steps):
    kind = "prefetch.read_task"
    spans = [
        (kind, 9.0, 9.5),          # ends before the window opens: ignored
        (kind, 9.9, 10.1),         # starts before, ends inside: counted
        (kind, 12.0, 12.003),
        (kind, 12.0, 12.004),      # concurrent tasks each count
        (kind, 19.999, 20.0),      # ends at the close: counted
        (kind, 19.9, 20.5),        # ends after the close: ignored
        ("chunk.read", 11.0, 12.0),
    ]
    assert read(_run(spans, steps)) == pytest.approx(4 / steps)
    # the program records the kind, none in the window: zero
    assert read(_run([(kind, 1.0, 2.0)], steps)) == 0.0
