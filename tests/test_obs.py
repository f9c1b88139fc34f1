"""Flight-recorder tests (DESIGN.md §13): tracer, metrics, report, parity.

Covers the tentpole's correctness contract:

  * span mechanics — matched begin/end (dur >= 0), per-thread monotonic
    timestamps, deterministic ring-buffer wraparound with dropped-row
    accounting, thread-merged export ordering;
  * export schemas — the JSONL dump (meta line + records) and the Chrome
    trace-event file (``ph="X"``, µs timestamps, pid=rank) both parse and
    carry every span;
  * disabled-tracer no-op — the default singleton records nothing, costs
    ``t() == 0.0``, and a traced distributed run's digests are bit-identical
    to an untraced one (the digest-parity invariant);
  * deterministic histogram bucketing — fixed log2 buckets, order-invariant
    quantiles, exact cross-rank merges;
  * the report CLI — analyze/check over a real traced run's dumps.
"""
from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _reset_tracer():
    """Every test starts and ends with the no-op singleton installed."""
    obs_trace.disable()
    yield
    obs_trace.disable()


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------


def test_spans_are_complete_and_ordered():
    tr = Tracer(capacity=128)
    for i in range(5):
        t0 = tr.t()
        tr.rec(obs_trace.CHUNK_READ, t0, a=i)
    recs, tids, dropped = tr.records()
    assert len(recs) == 5 and dropped == 0
    assert (recs["t1"] >= recs["t0"]).all(), "a span must not end before it begins"
    assert (np.diff(recs["t0"]) >= 0).all(), "export must be sorted by t0"
    assert recs["a"].tolist() == [0, 1, 2, 3, 4]
    assert all(t == threading.current_thread().name for t in tids)


def test_span_context_manager_and_instant():
    tr = Tracer(capacity=16)
    with tr.span(obs_trace.PEER_FETCH, a=3):
        pass
    tr.instant(obs_trace.PEER_RETRY, a=3, b=1)
    recs, _, _ = tr.records()
    assert len(recs) == 2
    fetch = recs[recs["kind"] == obs_trace.PEER_FETCH][0]
    retry = recs[recs["kind"] == obs_trace.PEER_RETRY][0]
    assert fetch["t1"] >= fetch["t0"]
    assert retry["t0"] == retry["t1"], "an instant is a zero-width span"


def test_step_stamp_rides_every_record():
    tr = Tracer(capacity=16)
    tr.set_step(7)
    tr.instant(obs_trace.SERVE_SHED)
    tr.set_step(8)
    tr.instant(obs_trace.SERVE_SHED)
    recs, _, _ = tr.records()
    assert recs["step"].tolist() == [7, 8]


def test_ring_wraparound_keeps_newest_and_counts_drops():
    tr = Tracer(capacity=8)
    for i in range(20):
        t0 = tr.t()
        tr.rec(obs_trace.STEP, t0, a=i)
    recs, _, dropped = tr.records()
    assert len(recs) == 8, "a full ring holds exactly capacity rows"
    assert dropped == 12, "overwritten rows must be accounted"
    assert recs["a"].tolist() == list(range(12, 20)), (
        "wraparound must keep the newest records in order"
    )


def test_per_thread_rings_merge_sorted():
    tr = Tracer(capacity=64)

    def worker():
        for _ in range(10):
            tr.instant(obs_trace.PREFETCH_QWAIT)

    threads = [threading.Thread(target=worker, name=f"w{i}") for i in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    tr.instant(obs_trace.STEP)
    recs, tids, dropped = tr.records()
    assert len(recs) == 31 and dropped == 0
    assert (np.diff(recs["t0"]) >= 0).all()
    assert {t for t in tids} >= {"w0", "w1", "w2"}


def test_kind_interning_is_stable():
    assert obs_trace.kind_id("chunk.read") == obs_trace.CHUNK_READ
    kid = obs_trace.kind_id("fault.crash:3")
    assert obs_trace.kind_id("fault.crash:3") == kid
    assert obs_trace.kind_name(kid) == "fault.crash:3"


# ---------------------------------------------------------------------------
# Export schemas
# ---------------------------------------------------------------------------


def _traced_dump(tmp_path, n=6):
    tr = Tracer(capacity=32)
    tr.set_step(2)
    for i in range(n):
        t0 = tr.t()
        tr.rec(obs_trace.CHUNK_READ, t0, a=i, b=i * 100)
    return tr.dump(str(tmp_path), rank=1)


def test_jsonl_export_schema(tmp_path):
    out = _traced_dump(tmp_path)
    lines = [
        json.loads(s) for s in open(out["jsonl"]) if s.strip()
    ]
    meta, records = lines[0], lines[1:]
    assert meta["meta"] and meta["rank"] == 1 and meta["clock"] == "perf_counter"
    assert meta["records"] == len(records) == 6
    assert meta["dropped"] == 0
    for r in records:
        assert set(r) == {"name", "ts", "dur", "step", "a", "b", "tid"}
        assert r["name"] == "chunk.read" and r["dur"] >= 0 and r["step"] == 2


def test_chrome_export_schema(tmp_path):
    out = _traced_dump(tmp_path)
    doc = json.load(open(out["chrome"]))
    events = doc["traceEvents"]
    assert len(events) == 6
    for ev in events:
        assert ev["ph"] == "X", "complete events only"
        assert ev["pid"] == 1, "pid is the rank"
        assert ev["dur"] >= 0 and isinstance(ev["ts"], float)
        assert set(ev["args"]) == {"step", "a", "b"}
    assert doc["otherData"]["rank"] == 1


# ---------------------------------------------------------------------------
# Disabled tracer: the no-op contract
# ---------------------------------------------------------------------------


def test_null_tracer_records_nothing():
    tr = obs_trace.get()
    assert not tr.enabled
    assert tr.t() == 0.0, "disabled timestamping must not touch the clock"
    tr.rec(obs_trace.STEP, 0.0)
    tr.instant(obs_trace.STEP)
    tr.set_step(5)
    with tr.span(obs_trace.STEP):
        pass
    live = obs_trace.enable(capacity=8)
    recs, _, _ = live.records()
    assert len(recs) == 0, "the null tracer must have dropped everything"


def test_enable_disable_roundtrip():
    assert obs_trace.disable() is None, "no live tracer yet"
    live = obs_trace.enable(capacity=8)
    assert obs_trace.get() is live
    live.instant(obs_trace.STEP)
    back = obs_trace.disable()
    assert back is live
    assert not obs_trace.get().enabled


# ---------------------------------------------------------------------------
# Metrics: deterministic histograms + registry folding
# ---------------------------------------------------------------------------


def test_bucket_index_is_log2_and_clamped():
    assert obs_metrics.bucket_index(0) == 0
    assert obs_metrics.bucket_index(-3.0) == 0
    assert obs_metrics.bucket_index(1) == 1      # [1, 2) us
    assert obs_metrics.bucket_index(2) == 2      # [2, 4) us
    assert obs_metrics.bucket_index(3) == 2
    assert obs_metrics.bucket_index(1024) == 11
    assert obs_metrics.bucket_index(2**80) == obs_metrics.NBUCKETS - 1


def test_histogram_quantiles_are_order_invariant():
    values = [3, 900, 17, 120000, 64, 64, 5000, 2, 31, 7]
    a, b = obs_metrics.Histogram(), obs_metrics.Histogram()
    for v in values:
        a.record(v)
    for v in reversed(values):
        b.record(v)
    for q in (0.5, 0.95, 0.99):
        assert a.quantile_us(q) == b.quantile_us(q)
    # 5th smallest of the 10 values is 31 -> bucket [16, 32) -> upper bound
    assert a.quantile_us(0.5) == 32.0


def test_histogram_merge_is_exact():
    xs, ys = [10, 200, 3000], [7, 7, 450000]
    h1, h2, ref = (obs_metrics.Histogram() for _ in range(3))
    for v in xs:
        h1.record(v)
    for v in ys:
        h2.record(v)
    for v in xs + ys:
        ref.record(v)
    merged = obs_metrics.merge_histograms([h1.bucket_dict(), h2.bucket_dict()])
    assert merged.count == ref.count
    assert merged.counts == ref.counts
    for q in (0.5, 0.95, 0.99):
        assert merged.quantile_us(q) == ref.quantile_us(q)


def test_empty_histogram_quantile_is_zero():
    h = obs_metrics.Histogram()
    assert h.quantile_us(0.5) == 0.0
    assert h.bucket_dict() == {}


def test_registry_fold_never_mutates_source():
    reg = obs_metrics.MetricsRegistry()
    legacy = {"numPFS": 12, "misses": 3, "ratio": 0.25,
              "nested": {"x": 1}, "name": "solar"}
    before = dict(legacy)
    reg.fold("loader", legacy)
    assert legacy == before, "folding must read, never rewrite"
    snap = reg.snapshot()
    assert snap["counters"]["loader.numPFS"] == 12
    assert snap["counters"]["loader.misses"] == 3
    assert snap["gauges"]["loader.ratio"] == 0.25
    assert "loader.nested" not in snap["counters"]
    assert "loader.name" not in snap["counters"]


def test_latency_summary_keys():
    s, f = obs_metrics.Histogram(), obs_metrics.Histogram()
    s.record(1500)
    f.record(300)
    out = obs_metrics.latency_summary(s, f)
    assert set(out) == {
        "step_ms_p50", "step_ms_p95", "step_ms_p99", "step_count",
        "fetch_ms_p50", "fetch_ms_p95", "fetch_ms_p99", "fetch_count",
    }
    assert out["step_count"] == 1 and out["fetch_count"] == 1
    assert out["step_ms_p50"] == 2.048  # bucket [1024, 2048) us -> upper bound


# ---------------------------------------------------------------------------
# Logging satellite
# ---------------------------------------------------------------------------


def test_log_configure_levels_and_rank_tag(capsys):
    import io

    buf = io.StringIO()
    obs_log.configure(1, rank=3, stream=buf)
    lg = obs_log.get_logger("test.mod")
    lg.info("hello %d", 42)
    lg.debug("invisible at -v")
    out = buf.getvalue()
    assert "[info r3 test.mod] hello 42" in out
    assert "invisible" not in out
    obs_log.configure(0, stream=io.StringIO())  # restore default level


def test_verbosity_args_roundtrip():
    import argparse

    ap = argparse.ArgumentParser()
    obs_log.add_verbosity_args(ap)
    assert obs_log.verbosity_from(ap.parse_args([])) == 0
    assert obs_log.verbosity_from(ap.parse_args(["-v"])) == 1
    assert obs_log.verbosity_from(ap.parse_args(["-vv"])) == 2
    assert obs_log.verbosity_from(ap.parse_args(["-q"])) == -1


# ---------------------------------------------------------------------------
# Report: analyze/check over synthetic + real dumps
# ---------------------------------------------------------------------------


def _synthetic_rank_dump(tmp_path, rank=0, steps=4):
    """A hand-built minimal trace a single rank's loop would produce."""
    tr = Tracer(capacity=256)
    now = 0.0
    for s in range(steps):
        tr.set_step(s)
        t0 = now
        tr.rec(obs_trace.BARRIER_WAIT, t0, t0 + 0.002, a=s)
        tr.rec(obs_trace.CHUNK_READ, t0 + 0.002, t0 + 0.003, a=8)
        tr.rec(obs_trace.STEP_PEER, t0 + 0.003, t0 + 0.004)
        tr.rec(obs_trace.STEP_EXECUTE, t0 + 0.004, t0 + 0.009)
        tr.rec(obs_trace.STEP, t0, t0 + 0.011)
        now += 0.011
    tr.dump(str(tmp_path), rank=rank)


def test_report_analyze_attribution(tmp_path):
    _synthetic_rank_dump(tmp_path, rank=0, steps=4)
    rep = obs_report.analyze(str(tmp_path))
    r0 = rep["ranks"]["0"]
    assert r0["steps"] == 4
    assert r0["step_ms_total"] == pytest.approx(44.0, abs=0.01)
    assert r0["stage_ms_per_step"]["barrier"] == pytest.approx(2.0, abs=0.01)
    assert r0["stage_ms_per_step"]["execute"] == pytest.approx(5.0, abs=0.01)
    assert r0["detail_ms_total"]["disk_pfs"] == pytest.approx(4.0, abs=0.01)
    assert rep["cluster"]["barrier_ms_per_step"] == pytest.approx(2.0, abs=0.01)
    # 2 + 1 + 5 of 11 ms accounted by the tiling sections
    assert rep["cluster"]["coverage"] == pytest.approx(8.0 / 11.0, abs=0.01)


def test_report_check_flags_problems(tmp_path):
    # empty dir
    assert obs_report.check(str(tmp_path))
    _synthetic_rank_dump(tmp_path, rank=0)
    # healthy single-rank dump passes at a coverage bar it meets
    assert obs_report.check(str(tmp_path), min_coverage=0.5) == []
    # and fails when the bar is above what the spans account for
    fails = obs_report.check(str(tmp_path), min_coverage=0.99)
    assert any("coverage" in f for f in fails)


def test_report_check_catches_missing_chunk_reads(tmp_path):
    tr = Tracer(capacity=16)
    tr.rec(obs_trace.STEP, 0.0, 0.01)
    tr.dump(str(tmp_path), rank=0)
    fails = obs_report.check(str(tmp_path), min_coverage=0.0)
    assert any("chunk.read" in f for f in fails)


def test_report_main_check_cli(tmp_path, capsys):
    _synthetic_rank_dump(tmp_path, rank=0)
    rc = obs_report.main([str(tmp_path), "--check", "--min-coverage", "0.5"])
    assert rc == 0
    assert "trace OK" in capsys.readouterr().out
    rc = obs_report.main([str(tmp_path), "--check", "--min-coverage", "0.99"])
    assert rc == 1


def _steps_dump(tmp_path, steps=3):
    """A ``Trainer.run`` trace: ``train.step`` spans, ms apart, each tiled by
    qwait / make_batch (with ``batch.to_global``) / compute / metrics."""
    tr = Tracer(capacity=256)
    for s in range(steps):
        tr.set_step(s)
        t = 1.0 + 0.010 * s
        tr.rec(obs_trace.PREFETCH_QWAIT, t, t + 0.001)
        tr.rec(obs_trace.BATCH_TO_GLOBAL, t + 0.0015, t + 0.002, a=8, b=64)
        tr.rec(obs_trace.TRAIN_MAKE_BATCH, t + 0.001, t + 0.003)
        tr.rec(obs_trace.TRAIN_COMPUTE, t + 0.003, t + 0.007)
        tr.rec(obs_trace.TRAIN_METRICS, t + 0.007, t + 0.008)
        tr.rec(obs_trace.TRAIN_STEP, t, t + 0.009)
    tr.rec(obs_trace.CHUNK_READ, 1.0, 1.001, a=8)
    return tr.records()[0], tr.dump(str(tmp_path), rank=0)


def test_report_tiles_trainer_loop(tmp_path):
    _steps_dump(tmp_path)
    rep = obs_report.analyze(str(tmp_path))
    t = rep["ranks"]["0"]["train"]
    assert t["steps"] == 3
    assert t["step_ms_mean"] == pytest.approx(9.0, abs=0.01)
    assert t["stage_ms_per_step"]["compute"] == pytest.approx(4.0, abs=0.01)
    assert t["coverage"] == pytest.approx(8.0 / 9.0, abs=0.001)
    # a Trainer-only trace is checked against its own loop's coverage
    assert obs_report.check(str(tmp_path), min_coverage=0.85) == []
    fails = obs_report.check(str(tmp_path), min_coverage=0.95)
    assert any("train.step coverage" in f for f in fails)


def _xplane(steps, offset_ns, ops):
    """A profiler trace as :func:`repro.obs.report.load_xplane` returns it."""
    return {"path": "synthetic", "steps": steps,
            "devices": {"/device:TPU:0": ops} if ops else {}}


def test_clock_split_pairs_steps_and_splits_idle(tmp_path):
    recs, _ = _steps_dump(tmp_path)
    records = [
        {"name": obs_trace.kind_name(int(r["kind"])), "ts": float(r["t0"]),
         "dur": float(r["t1"] - r["t0"]), "step": int(r["step"]), "tid": "main"}
        for r in recs
    ]
    off = 5_000_000   # profiler ns = perf_counter ns + off (+ jitter)
    jitter = [0, 3_000, -1_000]
    steps = {s: (round((1.0 + 0.010 * s) * 1e9) + off + jitter[s],
                 round((1.009 + 0.010 * s) * 1e9) + off + jitter[s])
             for s in range(3)}
    steps[99] = (0, 1)  # a profiler step with no span: not paired
    # the device is busy exactly during each step's compute section
    ops = [(round((1.003 + 0.010 * s) * 1e9) + off,
            round((1.007 + 0.010 * s) * 1e9) + off) for s in range(3)]
    out = obs_report.clock_split(records, _xplane(steps, off, ops))
    assert out["pairs"] == 3
    assert out["offset_ns"] == pytest.approx(off, abs=1)
    assert out["spread_ns"] == pytest.approx(4_000, abs=2)
    assert out["window_s"] == pytest.approx(0.027, abs=1e-6)
    assert out["busy_s"] == pytest.approx(0.012, abs=1e-5)
    idle = out["idle_s"]
    # per step: qwait 1 ms, make_batch 1.5 ms of its 2 (to_global inside it
    # takes 0.5), metrics 1 ms, unspanned 1 ms; compute was all busy
    assert idle["prefetch.qwait"] == pytest.approx(0.003, abs=1e-5)
    assert idle["train.make_batch"] == pytest.approx(0.0045, abs=1e-5)
    assert idle["batch.to_global"] == pytest.approx(0.0015, abs=1e-5)
    assert idle["train.metrics"] == pytest.approx(0.003, abs=1e-5)
    assert idle["train.step"] == pytest.approx(0.003, abs=1e-5)
    assert "train.compute" not in idle
    assert sum(idle.values()) == pytest.approx(0.027 - 0.012, abs=1e-5)
    # no device plane (a CPU trace): anchors only, an empty split
    cpu = obs_report.clock_split(records, _xplane(steps, off, []))
    assert cpu["pairs"] == 3 and cpu["idle_s"] == {} and cpu["busy_s"] == 0


def test_innermost_and_subtract_intervals():
    segs = obs_report._innermost([(0, 10, "outer"), (2, 4, "inner"),
                                  (4, 6, "next"), (7, 7, "empty")])
    assert segs == [(0, 2, "outer"), (2, 4, "inner"), (4, 6, "next"),
                    (6, 10, "outer")]
    assert obs_report._subtract([(0, 10), (20, 30)],
                                [[-5, 2], [4, 5], [9, 22], [25, 40]]) == [
        (2, 4), (5, 9), (22, 25)]


# ---------------------------------------------------------------------------
# Trainer.run: spans that tile each step, and the profiler's clock
# ---------------------------------------------------------------------------


def _train(tmp_path, *, prefetch_depth=2, steps=6):
    """A short SOLAR-fed ``Trainer.run`` over a file store; returns the
    trainer and the SHA-256 of the batches it trained."""
    import hashlib

    import jax

    from repro.core.scheduler import SolarConfig
    from repro.data import LoaderSpec, build_pipeline, create_synthetic_store
    from repro.data.loaders import update_batch_digest
    from repro.train.trainer import Trainer

    path = tmp_path / "store.bin"
    if not path.exists():
        create_synthetic_store(str(path), num_samples=96, sample_shape=(4, 4),
                               kind="random").close()
    solar = SolarConfig(num_nodes=2, local_batch=4, buffer_size=16, seed=0)
    ld = build_pipeline(LoaderSpec(
        loader="solar", backend="binary", path=str(path), num_nodes=2,
        local_batch=4, num_epochs=2, buffer_size=16, seed=0,
        collect_data=True, solar=solar, prefetch_depth=prefetch_depth,
    ))
    digest = hashlib.sha256()

    def make_batch(sb):
        update_batch_digest(digest, sb)
        x, w = sb.to_global(ld.capacity)
        return {"x": x, "w": w}

    step = jax.jit(lambda s, b: (s + 1, {"loss": (b["x"].sum(axis=(1, 2)) * b["w"]).sum(),
                                         "rows": b["w"].sum()}))
    t = Trainer(loader=ld, step_fn=step, state=np.int32(0), make_batch=make_batch,
                prefetch_depth=prefetch_depth)
    t.run(max_steps=steps)
    return t, digest.hexdigest()


def _by_kind(recs):
    out: dict[str, list] = {}
    for r in recs:
        out.setdefault(obs_trace.kind_name(int(r["kind"])), []).append(r)
    return out


def test_trainer_spans_tile_each_step(tmp_path):
    live = obs_trace.enable()
    trainer, _ = _train(tmp_path)
    recs, tids, _ = live.records()
    main = threading.current_thread().name
    on_main = _by_kind(r for r, tid in zip(recs, tids) if tid == main)
    steps = on_main["train.step"]
    assert [int(r["step"]) for r in steps] == list(range(6))
    assert len(on_main["train.metrics"]) == 6, "one metric conversion a step"
    for st in steps:
        inside = [r for k in obs_report.TRAIN_STAGES.values() for r in on_main.get(k[0], [])
                  if r["t0"] >= st["t0"] and r["t1"] <= st["t1"]]
        covered = sum(r["t1"] - r["t0"] for r in inside)
        assert covered >= 0.9 * (st["t1"] - st["t0"]), "children must tile train.step"
    # breakdown's load_s is the wait for the batch plus make_batch, read off
    # the same clock reads as the spans
    load = sum(r["t1"] - r["t0"] for k in ("prefetch.qwait", "train.make_batch")
               for r in on_main[k])
    assert load <= trainer.load_time_s <= sum(st["t1"] - st["t0"] for st in steps)
    compute = sum(r["t1"] - r["t0"] for r in on_main["train.compute"])
    assert trainer.compute_time_s == pytest.approx(compute, rel=1e-9)
    assert trainer.breakdown()["load_s"] > 0


def test_to_global_and_read_wait_payloads(tmp_path):
    live = obs_trace.enable()
    _train(tmp_path, steps=4)
    recs, tids, _ = live.records()
    kinds = _by_kind(recs)
    tg = kinds["batch.to_global"]
    assert len(tg) == 4
    # 2 nodes padded to the SPMD capacity; float32 rows of 4 x 4
    rows = int(tg[0]["a"])
    assert rows % 2 == 0 and rows >= 8
    assert all(int(r["b"]) == int(r["a"]) * 16 * 4 for r in tg)
    waits = [(r, tid) for r, tid in zip(recs, tids)
             if obs_trace.kind_name(int(r["kind"])) == "prefetch.read_wait"]
    assert len(waits) >= 4, "one wait per produced step"
    # 2 nodes, each step's reads cut into 1..num_workers (4) tasks a node
    assert all(2 <= int(r["a"]) <= 2 * 4 for r, _ in waits)
    assert {tid for _, tid in waits} == {"solar-pipeline"}
    tasks = [(r, tid) for r, tid in zip(recs, tids)
             if obs_trace.kind_name(int(r["kind"])) == "prefetch.read_task"]
    assert sum(int(r["a"]) for r, _ in waits) <= len(tasks), "each task waited on once"
    assert {tid.rsplit("_", 1)[0] for _, tid in tasks} == {"solar-io"}


def test_read_task_spans_leave_the_train_block_unchanged(tmp_path):
    """``prefetch.read_task`` is recorded on the I/O pool's threads: the
    report's ``train`` block and ``coverage`` tile the loop's thread only."""
    from repro.obs import report

    live = obs_trace.enable()
    _train(tmp_path, steps=4)
    obs_trace.disable()
    with_dir, without_dir = tmp_path / "with", tmp_path / "without"
    live.dump(str(with_dir))
    without_dir.mkdir()
    lines = (with_dir / "trace-rank0.jsonl").read_text().splitlines()
    kept = [ln for ln in lines if '"prefetch.read_task"' not in ln]
    assert len(kept) < len(lines), "the run recorded read tasks"
    (without_dir / "trace-rank0.jsonl").write_text("\n".join(kept) + "\n")
    a = report.analyze(str(with_dir))["ranks"]["0"]
    b = report.analyze(str(without_dir))["ranks"]["0"]
    assert a["train"] == b["train"] and a["train"]["steps"] == 4
    assert a["coverage"] == b["coverage"]


def test_untraced_trainer_records_nothing_and_trains_the_same(tmp_path, monkeypatch):
    import jax

    entered = []
    real = jax.profiler.StepTraceAnnotation

    def spy(name, **kw):
        entered.append(kw["step_num"])
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", spy)
    off, off_digest = _train(tmp_path)
    assert entered == [], "an untraced run enters no profiler annotation"
    assert not obs_trace.get().enabled
    live = obs_trace.enable()
    on, on_digest = _train(tmp_path)
    assert entered == list(range(6))
    assert len(live.records()[0]) > 0
    assert off_digest == on_digest
    assert off.metrics_history == on.metrics_history


def test_trainer_steps_anchor_to_the_profiler_clock(tmp_path):
    import jax

    trace_dir = str(tmp_path / "trace")
    live = obs_trace.enable()
    jax.profiler.start_trace(trace_dir)
    try:
        _train(tmp_path, steps=8)
    finally:
        jax.profiler.stop_trace()
    obs_trace.disable()
    live.dump(trace_dir, rank=0)
    rep = obs_report.analyze(trace_dir, trace_dir)
    xp = rep["xplane"]
    assert xp["pairs"] == 8
    assert xp["spread_ns"] < 2_000_000
    assert xp["idle_s"] == {}, "a CPU trace has no device plane"
    assert obs_report.main([trace_dir, "--xplane", trace_dir, "--check"]) == 0


# ---------------------------------------------------------------------------
# The invariant that matters: traced == untraced, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.dist
def test_traced_run_digest_parity_and_valid_trace(tmp_path):
    """A traced 2-rank run trains the same bytes as the untraced reference,
    dumps a trace that passes ``repro.obs.report --check``, and carries
    latency quantiles + a metrics snapshot on every RankResult."""
    from repro.core.scheduler import SolarConfig
    from repro.data import DatasetSpec, LoaderSpec, create_store
    from repro.runtime import in_process_digests, run_distributed

    path = str(tmp_path / "tokens")
    create_store(
        path, "binary", spec=DatasetSpec(512, (8,), "<f4"), fill="arange",
    ).close()
    solar = SolarConfig(
        num_nodes=2, local_batch=8, buffer_size=64, seed=0,
        capacity_factor=1.0, enable_peer=True,
    )
    spec = LoaderSpec(
        loader="solar", backend="binary", path=path, num_nodes=2,
        local_batch=8, num_epochs=2, buffer_size=64, collect_data=True,
        peer_fetch=True, solar=solar, transport="socket", prefetch_depth=2,
    )
    ref = in_process_digests(spec)
    trace_dir = str(tmp_path / "traces")
    metrics_out = str(tmp_path / "metrics.json")
    traced = run_distributed(
        spec, timeout_s=120.0, trace_dir=trace_dir, metrics_out=metrics_out,
    )
    assert traced.ok and traced.digests() == ref, (
        "tracing perturbed the trained bytes"
    )
    assert obs_report.check(trace_dir) == []
    rep = obs_report.analyze(trace_dir)
    assert rep["num_ranks"] == 2
    assert rep["cluster"]["coverage"] >= 0.9
    assert rep["cluster"]["barrier_ms_per_step"] > 0
    for r in traced.ranks:
        assert r.latency["step_count"] == r.steps
        assert r.latency["step_ms_p50"] > 0
        assert r.metrics["counters"], "metrics snapshot missing"
    # cluster quantiles come from exact bucket merges of per-rank histograms
    summ = traced.summary()
    assert summ["latency"]["step_count"] == sum(r.steps for r in traced.ranks)
    # the telemetry artifact: heartbeat-borne snapshots + the final summary
    m = json.load(open(metrics_out))
    assert m["telemetry"], "no telemetry rows rode the heartbeat path"
    row = m["telemetry"][0]
    assert {"t", "rank", "steps"} <= set(row)
    assert m["summary"]["latency"] == summ["latency"]
