import threading
import time

import numpy as np
import pytest

from bench.harness import pfs
from repro.data import DatasetSpec, create_store

pytest.importorskip("h5py")


@pytest.fixture()
def store_path(tmp_path):
    path = str(tmp_path / "d.h5")
    create_store(path, "hdf5", spec=DatasetSpec(64, (8, 8), "<f4"), fill="arange",
                 chunk_samples=1).close()
    return path


def test_every_physical_read_is_charged(store_path):
    link = pfs.PfsLink(0.0, 1e12)
    store = pfs.open_store(store_path, "hdf5", link)
    try:
        rows = store.read_scattered(np.array([3, 4, 5, 9, 40, 41]))
        store.read_ranges([(0, 2), (2, 4), (10, 12)])
        store.read_range(60, 64)
        assert [int(r[0, 0]) for r in rows] == [3, 4, 5, 9, 40, 41]
        assert len(link.reads) == store.read_calls == 6
        assert sum(b for _, _, b in link.reads) == store.bytes_read == (6 + 6 + 4) * 256
    finally:
        store.close()


def test_latency_per_read_and_one_bandwidth_shared_by_threads(store_path):
    latency, bandwidth = 0.005, 1e6
    link = pfs.PfsLink(latency, bandwidth)
    store = pfs.open_store(store_path, "hdf5", link)
    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=store.read_range, args=(16 * i, 16 * i + 16))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        elapsed = time.perf_counter() - t0
        assert not any(t.is_alive() for t in threads)
        nbytes = 4 * 16 * 256
        # one link: the four transfers queue, and the latencies overlap
        assert elapsed >= latency + nbytes / bandwidth
        assert elapsed < 4 * latency + nbytes / bandwidth + 0.05
        assert all(t1 - t0_ >= latency + 16 * 256 / bandwidth for t0_, t1, _ in link.reads)
    finally:
        store.close()


def test_fake_clock_charges_latency_then_queued_transfer():
    now = [0.0]
    link = pfs.PfsLink(0.001, 1000.0, clock=lambda: now[0],
                       sleep=lambda s: now.__setitem__(0, now[0] + s))
    assert link.read(500, lambda: "a") == "a"
    assert link.reads == [(0.0, pytest.approx(0.501), 500)]
