"""The control (the reference at the precision below the configuration's,
in the program's place) and a planted fault fail a limit of the check."""
from pathlib import Path

import jax
import pytest

from bench.harness import catalog, control

ROOT = Path(__file__).resolve().parents[2]
SEEDS = (5, 2**33 + 5)


def _fails(reading, limits) -> bool:
    return any(reading[k] > limits[k] for k in limits)


@pytest.mark.parametrize("cell", ["tiny_ptychonn.pfs", "tiny_cosmoflow.cached"])
def test_half_batch_fails_a_limit(tiny_root, cell):
    c = catalog.load_cell(tiny_root, cell)
    for seed in SEEDS:
        r = control.readings(c, seed)
        assert _fails(r["half_batch"], c.config["limits"]), r


@pytest.mark.parametrize("cell", ["ptychonn_repo.pfs", "cosmoflow_repo.pfs",
                                  "ptychonn_repo.cached", "cosmoflow_repo.cached"])
def test_control_fails_a_limit_at_the_cells_size(cell):
    """At a small size the control's error shrinks below the limits, so this
    reads the benchmark's own cells, at their size, on the chip."""
    if jax.devices()[0].platform != "tpu":
        pytest.skip("matmul precision 'high' differs from 'highest' only on a TPU")
    c = catalog.load_cell(ROOT, cell)
    for seed in SEEDS:
        r = control.readings(c, seed)
        assert _fails(r["control"], c.config["limits"]), r
