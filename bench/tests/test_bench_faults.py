"""A run on the CPU with the timed path broken underneath reads not correct.

Skips only the harness's look for a chip; everything else is a whole run
of a small cell. One case per fault the cells can have (they run on one
chip, so there is no exchange between chips to leave out).
"""
import time

import numpy as np
import pytest

import conftest
from bench.harness.cell import run_cell
from repro.data.backends.hdf5 import Hdf5Backend
from repro.data.loaders import StepBatch
from repro.train import step as train_step

pytest.importorskip("h5py")


def _frozen_state(monkeypatch):
    make = train_step.make_train_step

    def frozen(*args, **kwargs):
        inner = make(*args, **kwargs)

        def step(state, batch):
            return state, inner(state, batch)[1]
        return step
    monkeypatch.setattr(train_step, "make_train_step", frozen)


def _half_batch(monkeypatch):
    to_global = StepBatch.to_global

    def half(self, capacity):
        rows, weights = to_global(self, capacity)
        real = len(self.node_ids[0])
        weights[real // 2:] = 0.0
        return rows, weights
    monkeypatch.setattr(StepBatch, "to_global", half)


def _altered_record(monkeypatch):
    read = Hdf5Backend._read_span

    def altered(self, start, stop):
        rows = np.array(read(self, start, stop))
        rows[0].flat[0] += 1.0
        return rows
    monkeypatch.setattr(Hdf5Backend, "_read_span", altered)


def _run(root, cell):
    result, lines = run_cell(root, cell, seed=2**33 + 3, seconds=1.0, trace=False,
                             t_start=time.perf_counter(), require_tpu=False)
    assert [ln.split()[1] for ln in lines] == list(result["checks"])
    return result


@pytest.mark.parametrize("cell", ["tiny_ptychonn.pfs", "tiny_cosmoflow.cached"])
def test_sound_run_is_correct(tiny_root, cell):
    r = _run(tiny_root, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"samples_per_s", "step_ms_p95", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault,fails", [
    (_frozen_state, "update_gap_steps_1_3_median_leaf"),
    (_half_batch, "loss_gap_steps_1_3"),
    (_altered_record, "sampled_rows_differing_from_data_set"),
])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault, fails):
    fault(monkeypatch)
    r = _run(tiny_root, "tiny_ptychonn.pfs")
    assert not r["correct"]
    c = r["checks"][fails]
    assert c["value"] > c["limit"]


def test_traced_run_reports_per_layer_metrics(tiny_root):
    result, _ = run_cell(tiny_root, "tiny_ptychonn.pfs", seed=9, seconds=1.0, trace=True,
                         t_start=time.perf_counter(), require_tpu=False)
    assert result["correct"]
    # the CPU has no device plane: no idle share, no busy time
    assert set(result["metrics"]) == conftest.per_layer_off_device()
