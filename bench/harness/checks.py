"""The numbers that decide ``correct``, each held to its limit.

Loader and batch assembly are compared exactly (limit 0): the ids trained at
every step against rank 0's plan slice, each epoch of the plan against the
data set, and the rows of sampled steps against records made again from the
seed. The device step is compared with the reference over the first three
steps: each step's loss, the first gradient as AdamW took it, and each
parameter's change over the three steps. A leaf's gap is the gap between
the program's norm of the leaf and the reference's, over the larger of the
reference's norm of that leaf and of the median leaf.

The configuration's ``limits`` name how the leaves are held: a limit
``grad_gap_worst_leaf`` holds the gradient by its worst leaf, one
``update_gap_median_leaf`` the change by its median leaf. The change is held
by the median leaf, not the worst: where a pre-activation lies within
rounding of a leaky ReLU's kink, the two computations take different slopes
there, and Adam's sign-like first steps carry that into the changes of
elements whose gradient is near zero (PERF.md gives the readings).

A limit with ``_last_layer_`` in its name holds only the leaves of the layer
next to the loss (the network module's ``last_layer``). No leaky ReLU lies
between them and the loss, so their gradient has no kink to fall on either
side of, and in a sound run it differs from the reference's by rounding
alone; deeper leaves differ by as much as a lower matmul precision moves
them wherever a pre-activation lies within rounding of a kink.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Checks", "held_gap", "leaf_gaps", "loss_gap", "steady_leaves"]

#: leaves whose reference gradient norm is under this share of the median
#: leaf's move by round-off alone under Adam; their change is not compared.
FLAT_LEAF = 1e-3


def _norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(a, np.float64))) for a in leaves])


def leaf_gaps(program, reference) -> np.ndarray:
    """Each leaf's gap of norms, over max(reference leaf, median leaf)."""
    p, r = _norms(program), _norms(reference)
    return np.abs(p - r) / np.maximum(r, np.median(r))


#: how a limit's name holds the leaves' gaps, by the name's ending
_STATISTICS = {"_worst_leaf": np.max, "_median_leaf": np.median}


def held_gap(limit_name: str, gaps, keep=None, last_layer=None) -> float:
    """The gaps of the leaves (those ``keep`` marks, and with ``_last_layer_``
    in the name only those ``last_layer`` marks), held as the limit's name
    says: ``..._worst_leaf`` or ``..._median_leaf``."""
    gaps = np.asarray(gaps, np.float64)
    mask = np.ones(gaps.shape, bool)
    if keep is not None:
        mask &= np.asarray(keep, bool)
    if "_last_layer_" in limit_name:
        if last_layer is None:
            raise ValueError(f"limit {limit_name!r} needs the last layer's leaves")
        mask &= np.asarray(last_layer, bool)
    gaps = gaps[mask]
    for ending, statistic in _STATISTICS.items():
        if limit_name.endswith(ending):
            return float(statistic(gaps))
    raise ValueError(f"limit {limit_name!r} names no statistic of the leaves")


def loss_gap(program, reference) -> float:
    """Largest relative gap of the per-step losses."""
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), np.finfo(np.float32).tiny)))


def steady_leaves(first_grad) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding."""
    n = _norms(first_grad)
    return n >= FLAT_LEAF * np.median(n)


class Checks:
    """Named numbers with their limits, in the order they were added."""

    def __init__(self):
        self.items: dict[str, tuple[float, float]] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = (float(value), float(limit))

    @property
    def correct(self) -> bool:
        return all(np.isfinite(v) and v <= lim for v, lim in self.items.values())

    def as_dict(self) -> dict:
        return {k: {"value": v, "limit": lim} for k, (v, lim) in self.items.items()}

    def lines(self) -> list[str]:
        return [f"check {k} {v!r} limit {lim!r} {'ok' if np.isfinite(v) and v <= lim else 'FAIL'}"
                for k, (v, lim) in self.items.items()]
