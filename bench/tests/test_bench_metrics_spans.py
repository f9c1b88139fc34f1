"""The readers of the program's training-loop, assembly and read-wait spans,
on a synthetic run."""
from pathlib import Path

import pytest

import conftest
from bench.harness import catalog
from bench.harness.cell import Run

ROOT = Path(__file__).resolve().parents[2]


def _run(spans, steps=4):
    # a window of (10, 20]; its steps complete at 11, 12, ...
    return Run(setup_s=1.0, t_open=10.0, t_close=20.0,
               steps=[(11.0 + i, 64, 0) for i in range(steps)], pfs_reads=[],
               flops_per_sample=1, peak_flops=1.0, spans=spans)


@pytest.mark.parametrize("metric, kind", [("metrics_fetch_ms", "train.metrics"),
                                          ("to_global_ms", "batch.to_global"),
                                          ("read_wait_ms", "prefetch.read_wait")])
def test_reader_sums_window_spans_per_step(metric, kind):
    read = catalog.load_metric(ROOT, metric).read
    spans = [
        (kind, 9.0, 9.5),        # ends before the window opens: ignored
        (kind, 9.9, 10.1),       # starts before, ends inside: counted
        (kind, 12.0, 12.003),
        (kind, 19.999, 20.0),    # ends at the close: counted
        (kind, 19.9, 20.5),      # ends after the close: ignored
        ("train.compute", 11.0, 12.0),
    ]
    assert read(_run(spans)) == pytest.approx(1e3 * (0.2 + 0.003 + 0.001) / 4)
    # per window step: twice the steps, half the value
    assert read(_run(spans, steps=8)) == pytest.approx(1e3 * 0.204 / 8)
    # none in the window, but the program records the kind: zero
    assert read(_run([(kind, 1.0, 2.0)])) == 0.0
    # untraced, or a program without the span (an older checkout): no value
    assert read(_run(None)) is None
    assert read(_run([("train.compute", 11.0, 12.0)])) is None
    assert read(_run(spans, steps=0)) is None


def test_traced_run_reads_the_new_spans(tiny_root):
    import time

    from bench.harness.cell import run_cell

    result, _ = run_cell(tiny_root, "tiny_ptychonn.pfs", seed=2**33 + 5, seconds=1.0,
                         trace=True, t_start=time.perf_counter(), require_tpu=False)
    assert result["correct"]
    # the CPU has no device plane: no idle share, no busy time
    assert set(result["metrics"]) == conftest.per_layer_off_device()
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 < m["to_global_ms"] < m["assemble_ms"], "to_global is a part of make_batch"
    assert m["metrics_fetch_ms"] > 0 and m["read_wait_ms"] >= 0
