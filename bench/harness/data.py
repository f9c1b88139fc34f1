"""The data set of a run, made from ``--seed``.

Records are drawn in blocks: block ``b`` comes from a Philox stream keyed by
``(seed, b)``, so any record can be made again without the others (the check
reads records back this way, with no loader), and blocks are drawn in
parallel threads. Every seed gives the same sizes; only the values differ.

A configuration's optional ``record_dtype`` sets what a record holds:
``"float32"`` (the default) standard-normal values; ``"int16"`` counts
``k >= 0`` with probability ``2**-(k+1)`` (mean 1), the floor of an
exponential draw over ln 2, as a data set of particle counts stores them.
"""
from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["block_rows", "generate", "record_dtype", "records", "targets"]

#: about this many bytes per block; a record larger than that is one block.
_BLOCK_BYTES = 8 << 20
#: Philox key word of the target table, far above any block index.
_TARGET_STREAM = 1 << 63


def _normal(rng, shape) -> np.ndarray:
    return rng.standard_normal(shape, dtype=np.float32)


def _counts(rng, shape) -> np.ndarray:
    e = rng.standard_exponential(shape, dtype=np.float32)
    return np.floor(e / np.float32(math.log(2))).astype(np.int16)


_DRAW = {"float32": _normal, "int16": _counts}


def record_dtype(cfg) -> str:
    """The configuration's ``record_dtype``: ``"float32"`` or ``"int16"``."""
    name = cfg.get("record_dtype", "float32")
    if name not in _DRAW:
        raise ValueError(f"record_dtype {name!r} is not one of {sorted(_DRAW)}")
    return name


def block_rows(record_shape, dtype: str = "float32") -> int:
    nbytes = np.dtype(dtype).itemsize * int(np.prod(record_shape))
    return max(1, _BLOCK_BYTES // nbytes)


def _block(seed: int, b: int, n: int, record_shape, dtype: str) -> np.ndarray:
    rng = np.random.Generator(np.random.Philox(key=[seed, b]))
    return _DRAW[dtype](rng, (n,) + tuple(record_shape))


def generate(seed: int, num_samples: int, record_shape, threads: int = 8,
             dtype: str = "float32") -> np.ndarray:
    """All ``num_samples`` records, ``[num_samples, *record_shape]``."""
    rows = block_rows(record_shape, dtype)
    out = np.empty((num_samples,) + tuple(record_shape), dtype)

    def fill(b: int) -> None:
        lo = b * rows
        hi = min(lo + rows, num_samples)
        out[lo:hi] = _block(seed, b, hi - lo, record_shape, dtype)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(-(-num_samples // rows))))
    return out


def records(seed: int, ids, record_shape, dtype: str = "float32") -> np.ndarray:
    """Records ``ids`` (in that order), made again from the seed."""
    ids = np.asarray(ids, np.int64)
    rows = block_rows(record_shape, dtype)
    out = np.empty((ids.size,) + tuple(record_shape), dtype)
    for b in np.unique(ids // rows):
        lo = int(b) * rows
        sel = np.flatnonzero(ids // rows == b)
        n = int(ids[sel].max()) - lo + 1
        out[sel] = _block(seed, int(b), n, record_shape, dtype)[ids[sel] - lo]
    return out


def targets(seed: int, num_samples: int, size: int) -> np.ndarray:
    """A regression target of ``size`` floats per sample id, from the seed."""
    rng = np.random.Generator(np.random.Philox(key=[seed, _TARGET_STREAM]))
    return rng.standard_normal((num_samples, size), dtype=np.float32)
