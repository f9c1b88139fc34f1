"""The data set of a run, made from ``--seed``.

Records are standard-normal float32, drawn in blocks: block ``b`` comes from
a Philox stream keyed by ``(seed, b)``, so any record can be made again
without the others (the check reads records back this way, with no loader),
and blocks are drawn in parallel threads. Every seed gives the same sizes;
only the values differ.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

__all__ = ["block_rows", "generate", "records", "targets"]

#: about this many bytes per block; a record larger than that is one block.
_BLOCK_BYTES = 8 << 20
#: Philox key word of the target table, far above any block index.
_TARGET_STREAM = 1 << 63


def block_rows(record_shape) -> int:
    nbytes = 4 * int(np.prod(record_shape))
    return max(1, _BLOCK_BYTES // nbytes)


def _block(seed: int, b: int, n: int, record_shape) -> np.ndarray:
    rng = np.random.Generator(np.random.Philox(key=[seed, b]))
    return rng.standard_normal((n,) + tuple(record_shape), dtype=np.float32)


def generate(seed: int, num_samples: int, record_shape, threads: int = 8) -> np.ndarray:
    """All ``num_samples`` records, ``[num_samples, *record_shape]`` float32."""
    rows = block_rows(record_shape)
    out = np.empty((num_samples,) + tuple(record_shape), np.float32)

    def fill(b: int) -> None:
        lo = b * rows
        hi = min(lo + rows, num_samples)
        out[lo:hi] = _block(seed, b, hi - lo, record_shape)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(-(-num_samples // rows))))
    return out


def records(seed: int, ids, record_shape) -> np.ndarray:
    """Records ``ids`` (in that order), made again from the seed."""
    ids = np.asarray(ids, np.int64)
    rows = block_rows(record_shape)
    out = np.empty((ids.size,) + tuple(record_shape), np.float32)
    for b in np.unique(ids // rows):
        lo = int(b) * rows
        sel = np.flatnonzero(ids // rows == b)
        n = int(ids[sel].max()) - lo + 1
        out[sel] = _block(seed, int(b), n, record_shape)[ids[sel] - lo]
    return out


def targets(seed: int, num_samples: int, size: int) -> np.ndarray:
    """A regression target of ``size`` floats per sample id, from the seed."""
    rng = np.random.Generator(np.random.Philox(key=[seed, _TARGET_STREAM]))
    return rng.standard_normal((num_samples, size), dtype=np.float32)
