"""Prefetch pipeline correctness: the async executor must be invisible.

Bit-identical batches, identical accounting, clean shutdown (no leaked
threads), and the lock-free parallel/coalescing read paths of ChunkStore.
"""
import threading
import time

import numpy as np
import pytest

from repro.data import (
    ChunkStore,
    LoaderSpec,
    PrefetchExecutor,
    build_pipeline,
    create_synthetic_store,
)
from repro.data.prefetch import read_tasks

ALL = ["naive", "lru", "nopfs", "deepio", "solar"]


def _ld(name, store, num_nodes, local_batch, num_epochs, buffer_size, seed=0, **kw):
    return build_pipeline(LoaderSpec(
        loader=name, store=store, num_nodes=num_nodes, local_batch=local_batch,
        num_epochs=num_epochs, buffer_size=buffer_size, seed=seed, **kw,
    ))


@pytest.fixture(scope="module")
def store_path(tmp_path_factory):
    p = tmp_path_factory.mktemp("pf") / "ds.bin"
    create_synthetic_store(
        str(p), num_samples=512, sample_shape=(8,), dtype=np.float32, kind="arange"
    )
    return str(p)


def _alive_extra(before):
    return [t for t in threading.enumerate() if t not in before and t.is_alive()]


# ---------------------------------------------------------------------------
# Executor output == synchronous iteration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL)
def test_async_bit_identical(store_path, name):
    s1, s2 = ChunkStore(store_path), ChunkStore(store_path)
    ld_sync = _ld(name, s1, 4, 8, 3, 64, 0, collect_data=True)
    ld_async = _ld(name, s2, 4, 8, 3, 64, 0, collect_data=True)
    with PrefetchExecutor(ld_async, depth=3, num_workers=4) as ex:
        batches = list(zip(list(ld_sync), list(ex)))
    assert batches, name
    for a, b in batches:
        assert a.epoch == b.epoch and a.step == b.step
        for ia, ib, da, db, ma, mb in zip(
            a.node_ids, b.node_ids, a.node_data, b.node_data,
            a.hit_masks, b.hit_masks,
        ):
            assert np.array_equal(ia, ib)
            assert np.array_equal(ma, mb)
            assert np.array_equal(da, db)
    ra, rb = ld_sync.report, ld_async.report
    assert ra.pfs_counts == rb.pfs_counts        # numPFS accounting
    assert ra.miss_counts == rb.miss_counts
    assert ra.batch_sizes == rb.batch_sizes
    assert ra.remote_counts == rb.remote_counts
    assert ra.total_hits == rb.total_hits
    assert ra.total_samples == rb.total_samples
    assert ra.modeled_time_s == pytest.approx(rb.modeled_time_s)
    # identical physical read pattern too (both coalesce the same way)
    assert s1.read_calls == s2.read_calls
    assert s1.bytes_read == s2.bytes_read


def test_async_counting_only(store_path):
    """collect_data=False: executor still yields plans + accounting."""
    ld = _ld("solar", ChunkStore(store_path), 4, 8, 2, 64, 0)
    with PrefetchExecutor(ld, depth=2) as ex:
        n = sum(1 for sb in ex if sb.node_data is None)
    assert n == 2 * (512 // 32)
    assert ld.report.total_samples == n * 32


def test_all_strategies_use_schedule_mode(store_path):
    """Plan-first: every strategy executes a Schedule, so every pipeline
    gets schedule-mode parallel chunk reads; iterator mode remains for
    plain iterables without a plan."""
    for name in ALL:
        ld = _ld(name, ChunkStore(store_path), 4, 8, 1, 64, 0)
        assert PrefetchExecutor(ld).mode == "schedule", name

    class _PlanlessLoader:
        collect_data = False

        def __iter__(self):
            return iter(())

    assert PrefetchExecutor(_PlanlessLoader()).mode == "iterator"


def test_pipeline_prefetch_knobs(store_path):
    ex = _ld(
        "solar", ChunkStore(store_path), 4, 8, 1, 64, 0,
        collect_data=True, prefetch_depth=2, num_workers=2,
    )
    assert isinstance(ex, PrefetchExecutor)
    assert ex.capacity == ex.loader.capacity  # attribute proxying
    with ex:
        steps = sum(1 for _ in ex)
    assert steps == 512 // 32


# ---------------------------------------------------------------------------
# Shutdown / cancellation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["solar", "naive"])
def test_cancel_mid_epoch_leaks_no_threads(store_path, name):
    before = set(threading.enumerate())
    ld = _ld(name, ChunkStore(store_path), 4, 8, 3, 64, 0, collect_data=True)
    ex = PrefetchExecutor(ld, depth=2, num_workers=4)
    it = iter(ex)
    for _ in range(3):
        next(it)
    ex.close()
    assert _alive_extra(before) == []
    # closing again is a no-op; a fresh iteration still works after close
    ex.close()
    first = next(iter(ex))
    assert first is not None
    ex.close()
    assert _alive_extra(before) == []


def test_stale_iterator_finalization_does_not_cancel_new_run(store_path):
    """Rebinding `it = iter(ex)` finalizes the old generator *after* the new
    run started; that cleanup must only tear down its own run."""
    ld = _ld("solar", ChunkStore(store_path), 4, 8, 2, 64, 0, collect_data=True)
    with PrefetchExecutor(ld, depth=2) as ex:
        it = iter(ex)
        next(it)
        it = iter(ex)  # old generator GC'd here, new run must survive
        steps = sum(1 for _ in it)
    assert steps == 2 * (512 // 32)


def test_abandoned_iterator_cleans_up(store_path):
    before = set(threading.enumerate())
    ld = _ld("solar", ChunkStore(store_path), 4, 8, 2, 64, 0, collect_data=True)
    with PrefetchExecutor(ld, depth=2) as ex:
        for i, _ in enumerate(ex):
            if i == 2:
                break  # generator finalization must close the pipeline
    assert _alive_extra(before) == []


def test_producer_exception_propagates(store_path):
    class _Boom(Exception):
        pass

    class _BadLoader:
        collect_data = False

        def __iter__(self):
            yield "one"
            raise _Boom("loader died")

    ex = PrefetchExecutor(_BadLoader(), depth=2)
    it = iter(ex)
    assert next(it) == "one"
    with pytest.raises(_Boom):
        for _ in it:
            pass
    ex.close()


# ---------------------------------------------------------------------------
# ChunkStore parallel + coalescing read paths
# ---------------------------------------------------------------------------


def test_read_ranges_coalesces_adjacent(store_path):
    s = ChunkStore(store_path)
    s.reset_counters()
    out = s.read_ranges([(0, 4), (4, 8), (10, 12)])
    assert s.read_calls == 2                       # [0,8) merged, [10,12) alone
    assert [a.shape[0] for a in out] == [4, 4, 2]
    assert np.array_equal(out[1][:, 0].astype(np.int64), np.arange(4, 8))
    assert np.array_equal(out[2][:, 0].astype(np.int64), np.arange(10, 12))


def test_read_scattered_coalesces_runs(store_path):
    s = ChunkStore(store_path)
    s.reset_counters()
    ids = [5, 1, 2, 3, 9, 9]
    out = s.read_scattered(ids)
    assert s.read_calls == 3                       # runs [1,4), [5,6), [9,10)
    assert np.array_equal(out[:, 0].astype(np.int64), np.asarray(ids))


def test_parallel_reads_are_correct_and_counted(store_path):
    s = ChunkStore(store_path)
    s.reset_counters()
    errors = []

    def hammer(seed):
        rng = np.random.default_rng(seed)
        for _ in range(50):
            i = int(rng.integers(0, 500))
            arr = s.read_range(i, i + 8)
            if not np.array_equal(
                arr[:, 0].astype(np.int64), np.arange(i, i + 8)
            ):
                errors.append(i)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert s.read_calls == 8 * 50
    assert s.bytes_read == 8 * 50 * 8 * s.sample_bytes
    s.close()
    with pytest.raises(ValueError):
        s.read_range(0, 1)  # reads after close must fail loudly


# ---------------------------------------------------------------------------
# One node-step's reads as several read tasks
# ---------------------------------------------------------------------------


def _samples(part):
    return sum(b - a for a, b in part)


@pytest.mark.parametrize("k", [2, 3, 4, 5, 37])
def test_read_tasks_cut_disjoint_spans_into_balanced_runs(store_path, k):
    s = ChunkStore(store_path)
    ranges = [(10 * i, 10 * i + 1 + i % 3) for i in range(k)]  # k disjoint spans
    tasks = read_tasks(s, ranges, 4)
    assert len(tasks) == min(k, 4)
    assert [r for part in tasks for r in part] == ranges, "runs of the list, in plan order"
    sizes = [_samples(part) for part in tasks]
    # balanced: no run is further from the mean than the largest read
    assert max(abs(x - _samples(ranges) / len(tasks)) for x in sizes) <= 3
    if k == 37:
        assert max(sizes) - min(sizes) <= 3


@pytest.mark.parametrize("ranges", [[], [(5, 9)], [(0, 3), (3, 4), (4, 8)]])
def test_read_tasks_keep_one_task_for_one_read(store_path, ranges):
    assert read_tasks(ChunkStore(store_path), ranges, 4) == [ranges]


def _separately(make, ranges, max_tasks):
    """The tasks' reads one after another on a fresh store, against one
    ``read_ranges`` of the whole list on another."""
    whole, parts = make(), make()
    for s in (whole, parts):
        s.reset_counters()
    expect = whole.read_ranges(ranges)
    tasks = read_tasks(parts, ranges, max_tasks)
    got = [arr for part in tasks for arr in parts.read_ranges(part)]
    assert len(got) == len(expect)
    for a, b in zip(got, expect):
        assert np.array_equal(a, b)
    assert parts.read_calls == whole.read_calls
    assert parts.bytes_read == whole.bytes_read
    assert parts.trace == whole.trace
    return tasks


def test_read_tasks_never_cut_touching_ranges(store_path):
    ranges = [(0, 4), (4, 8), (8, 9), (20, 21), (21, 22), (40, 41)]
    tasks = _separately(lambda: ChunkStore(store_path), ranges, 4)
    assert tasks == [ranges[:3], ranges[3:5], ranges[5:]]


def test_read_tasks_never_cut_a_shared_hdf5_chunk_window(tmp_path):
    pytest.importorskip("h5py")
    from repro.data.backends import create_store
    from repro.data.backends.base import DatasetSpec

    path = str(tmp_path / "c4.h5")
    create_store(path, "hdf5", spec=DatasetSpec(64, (3,), "<f4", chunk_samples=4),
                 fill="arange").close()

    def make():
        from repro.data.backends import open_store
        return open_store(path, "hdf5")

    # windows [0,4) shared by the first two; [8,12); [16,20) shared; [28,32)
    ranges = [(0, 1), (2, 3), (9, 10), (17, 18), (18, 19), (30, 31)]
    tasks = _separately(make, ranges, 4)
    assert tasks == [ranges[:2], ranges[2:3], ranges[3:5], ranges[5:]]
    # aligned windows that touch are one read: never cut
    assert read_tasks(make(), [(1, 2), (5, 6), (9, 10)], 4) == [[(1, 2), (5, 6), (9, 10)]]


@pytest.mark.parametrize("backend, chunk", [("binary", 0), ("hdf5", 1), ("hdf5", 3), ("hdf5", 4)])
def test_read_tasks_make_the_reads_of_one_task(tmp_path, backend, chunk):
    """Random sorted step read lists: the tasks read exactly what one task did."""
    if backend == "hdf5":
        pytest.importorskip("h5py")
    from repro.data.backends import create_store, open_store
    from repro.data.backends.base import DatasetSpec

    path = str(tmp_path / f"ds.{backend}")
    create_store(path, backend, spec=DatasetSpec(256, (2,), "<f4", chunk_samples=chunk),
                 fill="arange").close()
    rng = np.random.default_rng(chunk)
    for _ in range(30):
        ids = np.sort(rng.choice(256, size=int(rng.integers(0, 40)), replace=False))
        # runs of consecutive ids, some split again so that ranges touch
        ranges, start = [], None
        for i, x in enumerate(ids.tolist()):
            if start is None:
                start = x
            last = i + 1 == ids.size
            if last or ids[i + 1] != x + 1 or rng.random() < 0.3:
                ranges.append((start, x + 1))
                start = None
        tasks = _separately(lambda: open_store(path, backend), ranges,
                            int(rng.integers(1, 7)))
        assert [r for part in tasks for r in part] == ranges


class _ReverseStore(ChunkStore):
    """Reads that finish later the earlier their first sample: a step's
    read tasks complete in reverse order of submission."""

    def __init__(self, path):
        super().__init__(path)
        self.done: list[int] = []
        self._lock = threading.Lock()

    def read_ranges(self, ranges):
        ranges = list(ranges)
        if ranges:
            time.sleep(2e-5 * (self.num_samples - ranges[0][0]))
        out = super().read_ranges(ranges)
        with self._lock:
            self.done.append(ranges[0][0] if ranges else -1)
        return out


def test_split_reads_hand_execute_step_the_one_task_arrays(store_path):
    store = _ReverseStore(store_path)
    ld = _ld("solar", store, 2, 8, 2, 32, 0, collect_data=True)
    seen = []
    real = ld.execute_step

    def spy(ep, sp, chunk_arrays=None, **kw):
        seen.append((sp, chunk_arrays))
        return real(ep, sp, chunk_arrays=chunk_arrays, **kw)

    ld.execute_step = spy
    with PrefetchExecutor(ld, depth=2, num_workers=4) as ex:
        n = sum(1 for _ in ex)
    assert n == len(seen) == 2 * (512 // 16)
    plain = ChunkStore(store_path)
    split = 0
    for sp, chunk_arrays in seen:
        assert len(chunk_arrays) == len(sp.nodes)
        for npn, arrays in zip(sp.nodes, chunk_arrays):
            ranges = [(c.start, c.stop) for c in npn.chunks]
            split += len(read_tasks(store, ranges, 4)) > 1
            expect = plain.read_ranges(ranges)
            assert len(arrays) == len(expect)
            for a, b in zip(arrays, expect):
                assert a.dtype == b.dtype and np.array_equal(a, b)
    assert split, "some node-step was cut into several tasks"
    # the premise: some step's tasks finished out of submission order
    assert any(x > y for x, y in zip(store.done, store.done[1:]))


def test_each_read_task_records_one_span(store_path):
    from repro.obs import trace as obs_trace

    ld = _ld("solar", ChunkStore(store_path), 2, 8, 1, 32, 0, collect_data=True)
    expect = sorted(
        (len(part), sp.step)
        for _, sp in _ld("solar", ChunkStore(store_path), 2, 8, 1, 32, 0).plan_steps()
        for npn in sp.nodes
        for part in read_tasks(ld.store, [(c.start, c.stop) for c in npn.chunks], 4)
    )
    live = obs_trace.enable()
    try:
        with PrefetchExecutor(ld, depth=2, num_workers=4) as ex:
            for _ in ex:
                pass
    finally:
        obs_trace.disable()
    recs, tids, _ = live.records()
    names = [obs_trace.kind_name(int(r["kind"])) for r in recs]
    tasks = [(r, t) for r, t, k in zip(recs, tids, names) if k == "prefetch.read_task"]
    assert sorted((int(r["a"]), int(r["b"])) for r, _ in tasks) == expect
    assert len(expect) > len(list(ld.plan_steps())) * 2, "steps were cut"
    assert {t.rsplit("_", 1)[0] for _, t in tasks} == {"solar-io"}
    waits = [r for r, k in zip(recs, names) if k == "prefetch.read_wait"]
    assert sum(int(r["a"]) for r in waits) == len(tasks)
