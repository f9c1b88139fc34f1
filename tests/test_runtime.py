"""Distributed runtime (DESIGN.md §8): wire framing, the buffer server's
guards, transport failure modes, and real multi-process launcher runs.

The correctness bar for every failure path is the same as the in-process
peer tier's: degrade to PFS reads, never serve wrong bytes, never hang.
Multi-process tests carry the ``dist`` marker so constrained runners can
deselect them (``-m "not dist"``).
"""
import socket
import threading

import numpy as np
import pytest

from repro.core.scheduler import SolarConfig
from repro.data import DatasetSpec, LoaderSpec, SocketTransport, create_store
from repro.runtime import wire
from repro.runtime.launcher import in_process_digests, run_distributed
from repro.runtime.server import BufferServer


# ---------------------------------------------------------------------------
# Wire protocol framing
# ---------------------------------------------------------------------------


def _pipe():
    a, b = socket.socketpair()
    a.settimeout(2.0)
    b.settimeout(2.0)
    return a, b


def test_wire_roundtrip_fetch_and_rows():
    a, b = _pipe()
    ids = np.asarray([3, 1, 4, 1, 5], np.int64)
    wire.send_frame(a, wire.MSG_FETCH, wire.pack_fetch(7, ids))
    msg_type, payload = wire.recv_frame(b)
    assert msg_type == wire.MSG_FETCH
    step, got = wire.unpack_fetch(payload)
    assert step == 7 and np.array_equal(got, ids)

    ok = np.asarray([True, False, True, False, True])
    rows = np.arange(12, dtype="<f4").reshape(3, 4)
    wire.send_frame(b, wire.MSG_ROWS, wire.pack_rows(ok, rows))
    msg_type, payload = wire.recv_frame(a)
    ok2, rows2 = wire.unpack_rows(payload, 5, (4,), "<f4")
    assert np.array_equal(ok, ok2) and np.array_equal(rows, rows2)
    a.close(), b.close()


def test_wire_truncated_frame_detected():
    a, b = _pipe()
    header = wire._HEADER.pack(wire.MAGIC, wire.WIRE_VERSION, wire.MSG_CTRL, 100)
    a.sendall(header + b"x" * 10)  # promises 100 payload bytes, sends 10
    a.close()
    with pytest.raises(wire.TruncatedFrame):
        wire.recv_frame(b)
    b.close()


def test_wire_clean_eof_vs_truncation():
    a, b = _pipe()
    a.close()  # no bytes at all: clean close at a frame boundary
    assert wire.recv_frame(b, eof_ok=True) is None
    b.close()
    a, b = _pipe()
    a.close()
    with pytest.raises(wire.TruncatedFrame):  # without eof_ok it is an error
        wire.recv_frame(b)
    b.close()


def test_wire_checksum_mismatch_detected():
    a, b = _pipe()
    payload = wire.pack_json({"kind": "x"})
    header = wire._HEADER.pack(
        wire.MAGIC, wire.WIRE_VERSION, wire.MSG_CTRL, len(payload)
    )
    good = header + payload + wire._frame_digest(header, payload)
    corrupt = bytearray(good)
    corrupt[len(header) + 2] ^= 0xFF  # flip one payload bit
    a.sendall(bytes(corrupt))
    with pytest.raises(wire.ChecksumMismatch):
        wire.recv_frame(b)
    a.close(), b.close()


def test_wire_protocol_errors():
    a, b = _pipe()
    a.sendall(b"NOPE" + bytes(wire._HEADER.size - 4 + 32))
    with pytest.raises(wire.ProtocolError, match="magic"):
        wire.recv_frame(b)
    a.close(), b.close()
    a, b = _pipe()
    header = wire._HEADER.pack(wire.MAGIC, 99, wire.MSG_CTRL, 0)
    a.sendall(header + wire._frame_digest(header, b""))
    with pytest.raises(wire.ProtocolError, match="version"):
        wire.recv_frame(b)
    a.close(), b.close()


def test_wire_rows_payload_length_is_validated():
    ok = np.asarray([True, True, False])
    rows = np.zeros((2, 4), "<f4")
    payload = wire.pack_rows(ok, rows)
    with pytest.raises(wire.ProtocolError):  # geometry says 8-float rows
        wire.unpack_rows(payload, 3, (8,), "<f4")


# ---------------------------------------------------------------------------
# BufferServer + SocketTransport against a live mirror
# ---------------------------------------------------------------------------


class _Arena:
    """Minimal stand-in for _DataMirror: samples value == id."""

    def __init__(self, ids, width=4):
        self.ids = np.asarray(ids, np.int64)
        self.width = width

    def lookup(self, want):
        want = np.asarray(want, np.int64)
        return np.where(np.isin(want, self.ids), want, -1)

    def rows(self, slots):
        return np.repeat(
            slots.astype("<f4")[:, None], self.width, axis=1
        )


@pytest.fixture()
def served_arena():
    arena = _Arena([5, 6, 7, 20])
    server = BufferServer(0, (4,), "<f4", port=0).start()
    server.attach(lambda n: arena)
    transport = SocketTransport(
        {0: (server.host, server.port)}, timeout_s=2.0,
        sample_shape=(4,), dtype="<f4",
    )
    yield arena, server, transport
    transport.close()
    server.close()


def test_buffer_server_serves_resident_rows(served_arena):
    _arena, server, transport = served_arena
    server.at_step(3)
    transport.at_step(3)
    rows, ok = transport.fetch(0, np.asarray([5, 9, 20]))
    assert ok.tolist() == [True, False, True]
    assert np.array_equal(rows[:, 0].astype(np.int64), [5, 20])
    assert server.stale_refusals == 0


def test_buffer_server_step_guard_refuses_stale_fetches(served_arena):
    """The fetch-vs-eviction race across processes: a fetch stamped with a
    step the server has moved past is answered all-False (PFS fallback),
    never with bytes from a possibly-recycled arena slot."""
    _arena, server, transport = served_arena
    server.at_step(4)
    transport.at_step(3)  # requester believes it is step 3: too late
    rows, ok = transport.fetch(0, np.asarray([5, 6]))
    assert not ok.any() and rows.shape == (0, 4)
    assert server.stale_refusals == 1
    # while the executor mutates (deltas applying), serving is paused too
    server.at_step(5)
    transport.at_step(5)
    with server.mutating():
        pass  # exiting leaves the guard paused until the next at_step
    rows, ok = transport.fetch(0, np.asarray([5]))
    assert not ok.any()
    # and once the server republishes the right step, serving resumes
    server.at_step(6)
    transport.at_step(6)
    _, ok = transport.fetch(0, np.asarray([5]))
    assert ok.all()


def test_buffer_server_refuses_fetch_before_hello(served_arena):
    """Geometry negotiation is enforced server-side: a FETCH on a
    connection that never completed HELLO is refused with ERROR — a client
    with a same-byte-size but different layout must not get rows."""
    _arena, server, _ = served_arena
    server.at_step(0)
    conn = socket.create_connection((server.host, server.port), timeout=2.0)
    conn.settimeout(2.0)
    wire.send_frame(conn, wire.MSG_FETCH, wire.pack_fetch(0, np.asarray([5])))
    msg_type, payload = wire.recv_frame(conn)
    assert msg_type == wire.MSG_ERROR
    assert b"HELLO" in payload
    conn.close()


def test_buffer_server_refuses_mismatched_geometry(served_arena):
    """Geometry disagreement is a deployment bug: HandshakeError, loud."""
    _arena, server, _ = served_arena
    bad = SocketTransport(
        {0: (server.host, server.port)}, timeout_s=2.0,
        sample_shape=(16,), dtype="<f8",
    )
    with pytest.raises(wire.HandshakeError, match="geometry mismatch"):
        bad.fetch(0, np.asarray([5]))
    bad.close()


def test_transport_survives_peer_dying_mid_step(served_arena):
    """A peer vanishing between two fetches degrades to fallback and a
    reconnect attempt — no exception reaches batch assembly."""
    _arena, server, transport = served_arena
    server.at_step(1)
    transport.at_step(1)
    _, ok = transport.fetch(0, np.asarray([5]))
    assert ok.all()
    server.close()  # the peer dies with a connection pooled
    rows, ok = transport.fetch(0, np.asarray([6]))
    assert not ok.any() and rows.shape == (0, 4)
    rows, ok = transport.fetch(0, np.asarray([7]))  # stays down: still clean
    assert not ok.any()


def _misbehaving_server(respond):
    """One-shot TCP server: HELLO is answered correctly, then ``respond``
    gets the raw connection to abuse after the first FETCH arrives."""
    listener = socket.create_server(("127.0.0.1", 0))
    listener.settimeout(5.0)

    def serve():
        conn, _ = listener.accept()
        with conn:
            conn.settimeout(5.0)
            _t, payload = wire.recv_frame(conn)
            wire.send_frame(conn, wire.MSG_HELLO_OK, payload)  # echo geometry
            wire.recv_frame(conn)  # the FETCH
            respond(conn)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return listener, t


def test_transport_truncated_response_falls_back():
    def respond(conn):
        header = wire._HEADER.pack(
            wire.MAGIC, wire.WIRE_VERSION, wire.MSG_ROWS, 1000
        )
        conn.sendall(header + b"q" * 8)  # then hang up mid-frame

    listener, t = _misbehaving_server(respond)
    transport = SocketTransport(
        {0: ("127.0.0.1", listener.getsockname()[1])}, timeout_s=2.0,
        sample_shape=(4,), dtype="<f4",
    )
    rows, ok = transport.fetch(0, np.asarray([1, 2]))
    assert not ok.any() and rows.shape == (0, 4)
    t.join(timeout=5.0)
    listener.close()
    transport.close()


def test_transport_checksum_mismatch_falls_back():
    def respond(conn):
        ok = np.asarray([True, True])
        rows = np.zeros((2, 4), "<f4")
        payload = wire.pack_rows(ok, rows)
        header = wire._HEADER.pack(
            wire.MAGIC, wire.WIRE_VERSION, wire.MSG_ROWS, len(payload)
        )
        digest = bytearray(wire._frame_digest(header, payload))
        digest[0] ^= 0xFF  # corrupt the checksum
        conn.sendall(header + payload + bytes(digest))

    listener, t = _misbehaving_server(respond)
    transport = SocketTransport(
        {0: ("127.0.0.1", listener.getsockname()[1])}, timeout_s=2.0,
        sample_shape=(4,), dtype="<f4",
    )
    rows, ok = transport.fetch(0, np.asarray([1, 2]))
    assert not ok.any(), "corrupt rows must never enter a batch"
    t.join(timeout=5.0)
    listener.close()
    transport.close()


def test_transport_self_source_serves_from_local_mirror():
    arena = _Arena([11, 12])
    transport = SocketTransport(
        {}, self_node=3, mirror_of=lambda n: arena,
        sample_shape=(4,), dtype="<f4",
    )
    rows, ok = transport.fetch(3, np.asarray([11, 99]))
    assert ok.tolist() == [True, False]
    assert np.array_equal(rows[:, 0].astype(np.int64), [11])
    transport.close()


# ---------------------------------------------------------------------------
# The launcher: real multi-process runs
# ---------------------------------------------------------------------------


def _dist_spec(tmp_path, nodes, *, num_samples=1024, local_batch=16,
               buffer=256, epochs=3, peer=True):
    path = str(tmp_path / f"dist_{nodes}")
    create_store(
        path, "binary", spec=DatasetSpec(num_samples, (8,), "<f4"),
        fill="arange",
    ).close()
    solar = None
    if peer:
        solar = SolarConfig(
            num_nodes=nodes, local_batch=local_batch, buffer_size=buffer,
            seed=0, capacity_factor=1.0, enable_peer=True,
        )
    return LoaderSpec(
        loader="solar", backend="binary", path=path, num_nodes=nodes,
        local_batch=local_batch, num_epochs=epochs, buffer_size=buffer,
        collect_data=True, peer_fetch=peer, solar=solar, transport="socket",
    )


@pytest.mark.dist
@pytest.mark.parametrize("nodes", [2, 4])
def test_launcher_digests_match_in_process_run(tmp_path, nodes):
    """The acceptance bar: N real processes over SocketTransport produce
    per-rank stream digests bit-identical to the same plan executed
    in-process over SharedViewTransport — and the socket tier actually
    served (zero fallbacks on a healthy run)."""
    spec = _dist_spec(tmp_path, nodes)
    report = run_distributed(spec, timeout_s=240.0)
    assert report.ok, f"dead ranks: {report.dead}"
    assert report.digests() == in_process_digests(spec)
    assert sum(r.peer_fallbacks for r in report.ranks) == 0
    assert sum(r.stale_refusals for r in report.ranks) == 0
    assert sum(r.peer_served for r in report.ranks) > 0
    # aggregated run report: serving-load accounting survives aggregation
    summ = report.summary()
    assert summ["peer_served"] == sum(
        summ["served_by_source"].values()
    ) > 0
    assert [r["status"] for r in summ["ranks"]] == ["ok"] * nodes


@pytest.mark.dist
def test_launcher_survives_peer_death_mid_run(tmp_path):
    """Killing one rank mid-step degrades its peers to PFS fallback and the
    run completes with a correct report — no hang, no corrupt batches."""
    spec = _dist_spec(tmp_path, 4, epochs=2)
    report = run_distributed(
        spec, timeout_s=240.0, die_at_step={2: 5}
    )
    assert report.dead == [2]
    assert [r.status for r in report.ranks] == ["ok", "ok", "dead", "ok"]
    ref = in_process_digests(spec)
    steps = {r.steps for r in report.ranks if r.status == "ok"}
    assert len(steps) == 1 and steps.pop() > 5
    for r in report.ranks:
        if r.status == "ok":
            # survivors train exactly the planned bytes, fallback or not
            assert r.digest == ref[r.rank], f"rank {r.rank} corrupted"
    assert report.summary()["dead_ranks"] == [2]


@pytest.mark.dist
def test_launcher_distributes_plan_by_hash(tmp_path, monkeypatch):
    """A rank must refuse a plan artifact whose content digest does not
    match what the launcher announced: every rank exits, nobody hangs."""
    from repro.data import plan as plan_fn

    spec = _dist_spec(tmp_path, 2, epochs=1, num_samples=256, buffer=64)
    schedule = plan_fn(spec)
    # lie about the digest in the parent only; spawned ranks recompute the
    # real one from the artifact and must refuse to execute
    monkeypatch.setattr(
        type(schedule), "artifact_digest", lambda self: "0" * 64
    )
    report = run_distributed(spec, schedule=schedule, timeout_s=120.0)
    assert report.dead == [0, 1]
    assert not report.ok


# ---------------------------------------------------------------------------
# Elastic recovery (DESIGN.md §9): re-slicing, false suspects, rejoins
# ---------------------------------------------------------------------------


def test_launcher_rejects_invalid_configuration():
    from repro.runtime import LauncherConfigError

    spec = LoaderSpec(
        loader="solar", backend="binary", path="/nonexistent", num_nodes=2,
        local_batch=4, num_epochs=1, buffer_size=16, transport="socket",
    )
    with pytest.raises(LauncherConfigError, match="barrier_timeout_s"):
        run_distributed(spec, barrier_timeout_s=0.0)
    with pytest.raises(LauncherConfigError, match="barrier_timeout_s"):
        run_distributed(spec, barrier_timeout_s=-5.0)
    with pytest.raises(LauncherConfigError, match="suspect_timeout_s"):
        run_distributed(spec, suspect_timeout_s=0)
    with pytest.raises(LauncherConfigError, match="recovery"):
        run_distributed(spec, recovery="pray")


def test_coordinator_pending_detail_names_silent_ranks():
    """The who-is-missing for run timeouts: unfinished ranks with their
    last-contact ages (None for ranks that never spoke)."""
    from repro.runtime.launcher import _Coordinator

    coord = _Coordinator(3, barrier_timeout_s=5.0).start()
    try:
        detail = coord.pending_detail()
        assert sorted(detail) == [0, 1, 2]
        assert all(age is None for age in detail.values())
    finally:
        coord.close()


@pytest.mark.dist
def test_launcher_reslices_dead_ranks_plan_onto_survivors(tmp_path):
    """The elastic headline: a rank killed mid-run is re-sliced away — a
    survivor adopts its remaining plan at the next step boundary, the run
    completes, and the XOR-aggregate digest (dead rank's heartbeat prefix
    ⊕ survivor finals) is bit-identical to the in-process reference."""
    from repro.runtime import in_process_aggregate

    spec = _dist_spec(tmp_path, 4, epochs=2)
    report = run_distributed(
        spec, timeout_s=240.0, die_at_step={2: 5}, recovery="reslice"
    )
    assert report.dead == [2]
    assert report.resliced_samples > 0, "nobody adopted the orphaned plan"
    assert report.resliced_nodes == 1
    assert report.aggregate_digest() == in_process_aggregate(spec), (
        "the global per-step sample set was not preserved across the death"
    )
    # survivors' own-node stream digests are untouched by adoption
    ref = in_process_digests(spec)
    for r in report.ranks:
        if r.status == "ok":
            assert r.digest == ref[r.rank]
    # exactly one survivor reports the adopted node
    adopters = [r for r in report.ranks if r.adopted_nodes]
    assert len(adopters) == 1 and adopters[0].adopted_nodes == [2]
    summ = report.summary()
    assert summ["resliced_samples"] == report.resliced_samples
    assert summ["recovery"] == "reslice"


@pytest.mark.dist
def test_launcher_degrade_mode_keeps_legacy_behavior(tmp_path):
    """recovery='degrade' must not re-slice: survivors eat PFS fallbacks
    (the PR 5 path, kept as the chaos benchmark's comparison baseline)."""
    spec = _dist_spec(tmp_path, 4, epochs=2)
    report = run_distributed(
        spec, timeout_s=240.0, die_at_step={2: 5}, recovery="degrade"
    )
    assert report.dead == [2]
    assert report.resliced_samples == 0
    assert all(not r.adopted_nodes for r in report.ranks)
    ref = in_process_digests(spec)
    for r in report.ranks:
        if r.status == "ok":
            assert r.digest == ref[r.rank]


@pytest.mark.dist
def test_launcher_readmits_false_suspect_without_divergence(tmp_path):
    """Regression: a rank that merely goes silent (heartbeat loss + stalled
    step loop, process alive) must be suspected, probed, and re-admitted —
    never killed, never re-sliced — and every digest stays bit-identical."""
    from repro.runtime import Fault, FaultPlan, in_process_aggregate

    spec = _dist_spec(tmp_path, 2, epochs=2)
    faults = FaultPlan(
        seed=0, faults=(Fault("hb_loss", 1, step=4, delay_s=1.0),)
    )
    report = run_distributed(
        spec, timeout_s=240.0, faults=faults,
        heartbeat_interval_s=0.1, suspect_timeout_s=0.3, probe_grace_s=10.0,
    )
    assert report.ok, f"a stall must not kill the rank: {report.dead}"
    assert report.false_suspects >= 1, "the stall was never suspected"
    assert report.resliced_samples == 0, "re-admission must not re-slice"
    assert report.rejoins == 0
    assert report.digests() == in_process_digests(spec)
    assert report.aggregate_digest() == in_process_aggregate(spec)
    fired = report.ranks[1].faults_fired
    assert fired.get("hb_loss:4") == 1, fired


@pytest.mark.dist
def test_launcher_restarted_rank_rejoins_and_reclaims_its_slice(tmp_path):
    """A restarted rank re-registers, resumes at the current boundary, and
    reclaims its slice from the interim adopter — aggregate parity across
    death, adoption, and handback."""
    from repro.runtime import in_process_aggregate

    # 4 epochs (64 steps): the respawned process must re-register before
    # the survivors finish; under a loaded host that takes ~30 steps.
    spec = _dist_spec(tmp_path, 4, epochs=4)
    report = run_distributed(
        spec, timeout_s=240.0, die_at_step={1: 3}, restart_ranks={1},
    )
    assert report.rejoins == 1
    r1 = report.ranks[1]
    assert r1.status == "ok" and r1.rejoined
    assert 0 < r1.steps, "the rejoiner never executed a step"
    assert report.resliced_samples > 0, (
        "someone must cover the gap between death and rejoin"
    )
    assert report.aggregate_digest() == in_process_aggregate(spec)


@pytest.mark.dist
def test_launcher_survives_mixed_chaos_with_digest_parity(tmp_path):
    """Frame corruption, truncation, dial resets, slow serving — all armed
    at once from one seed: the retry/breaker ladder masks everything, no
    rank dies, counters move, and both digest forms stay bit-identical."""
    from repro.runtime import FaultPlan, in_process_aggregate

    spec = _dist_spec(tmp_path, 4, epochs=2)
    faults = FaultPlan.compile(
        11, 4, num_steps=8, corrupt=2, truncate=1, resets=2, slow=2
    )
    report = run_distributed(spec, timeout_s=240.0, faults=faults)
    assert report.ok, f"flaky faults must never kill ranks: {report.dead}"
    assert report.digests() == in_process_digests(spec)
    assert report.aggregate_digest() == in_process_aggregate(spec)
    summ = report.summary()
    assert summ["retries"] > 0, "injected faults never exercised the ladder"
    fired: dict = {}
    for r in report.ranks:
        for k, v in r.faults_fired.items():
            fired[k] = fired.get(k, 0) + v
    assert fired, "the armed plan never fired"
