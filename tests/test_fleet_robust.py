"""Robustness contracts of the event-loop ingest server + RemoteSink.

Deadline honoring (wait_idle/flush return False instead of hanging on a
dead peer), heartbeat keepalives and idle-host watermark release, ghost
hosts (handshake, no data) neither pinning the merge nor leaking empty
journals, full-jitter backoff bounds, and overload shedding with offline
recovery.
"""
import os
import time

import numpy as np

from repro.core import ProfileSession, detect_offline
from repro.fleet import (FleetSource, IngestServer, RemoteSink,
                         attach_remote)
from tests.test_tracer import FakeClock


def _wait(cond, timeout=5.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.01)
    assert cond()


def _stream_spans(s, w, clk, n, tag="x"):
    for _ in range(n):
        s.begin(w, tag)
        clk.advance(1000)
        s.end(w)
        clk.advance(500)


# ---------------------------------------------------------------------------
# deadline honoring: never hang on a dead/hung peer
# ---------------------------------------------------------------------------

def test_wait_idle_returns_false_on_deadline_not_hangs():
    server = IngestServer()
    server.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=None)
    try:
        _stream_spans(s, w, clk, 5)
        s.snapshot()
        assert sink.flush(5.0)
        # the host never says BYE: wait_idle must give up AT the deadline
        t0 = time.monotonic()
        assert server.wait_idle(0.3) is False
        assert time.monotonic() - t0 < 2.0
    finally:
        s.close()
        sink.close()
        server.close()


def test_flush_returns_false_against_unreachable_server():
    # nothing listens on this address: the sender retries forever, the
    # chunk stays pending — flush must return False at its deadline
    probe = IngestServer()                 # grab a port, never start it
    addr = probe.address
    probe.close()
    sink = RemoteSink(addr, "h", num_workers=1, worker_names=["w"],
                      clock_offset_ns=0, reconnect_delay=0.01,
                      backoff_max=0.05, max_reconnects=1 << 30,
                      heartbeat_interval=None)
    sink.start()
    try:
        sink.append_columns(np.array([1], np.int64), np.zeros(1, np.int32),
                            np.ones(1, np.int8), np.zeros(1, np.int32),
                            np.full(1, -1, np.int32))
        t0 = time.monotonic()
        assert sink.flush(0.5) is False
        assert time.monotonic() - t0 < 3.0
    finally:
        sink.abort()


def test_read_deadline_reclaims_silent_connection():
    """A peer that handshakes and then goes SILENT — no FIN, no frames,
    no heartbeats (a partitioned or frozen producer) — used to hold its
    connection open forever; the read deadline must reclaim it, and
    wait_idle must still honor its own deadline meanwhile."""
    import socket as socketlib
    from repro.fleet import wire
    server = IngestServer(read_deadline=0.2, idle_release=None)
    server.start()
    raw = socketlib.create_connection(server.address)
    try:
        f = raw.makefile("rwb")
        f.write(wire.encode_hello("frozen", 1, ["w"], t_client_ns=0,
                                  clock_offset_ns=0))
        f.flush()
        assert wire.read_frame(f)[0] == wire.WELCOME
        t0 = time.monotonic()
        assert server.wait_idle(0.5) is False       # host never says BYE
        assert time.monotonic() - t0 < 2.0
        _wait(lambda: server.stats()["deadline_closed"] >= 1)
        _wait(lambda: server.stats()["open_connections"] == 0)
    finally:
        raw.close()
        server.close()


# ---------------------------------------------------------------------------
# heartbeats & idle hosts
# ---------------------------------------------------------------------------

def test_heartbeat_keeps_idle_connection_alive():
    server = IngestServer(read_deadline=0.3, idle_release=None)
    server.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=0.05)
    try:
        _stream_spans(s, w, clk, 3)
        s.snapshot()
        assert sink.flush(5.0)
        time.sleep(1.0)                 # >3x the read deadline, zero data
        st = server.stats()
        assert st["open_connections"] == 1, st      # beacons kept it alive
        assert st["deadline_closed"] == 0, st
        assert st["heartbeats"] >= 3, st
        assert sink.heartbeats_sent >= 3
    finally:
        s.close()
        sink.close()
        server.close()


def test_silent_host_released_from_watermark_and_leaves_no_journal(tmp_path):
    """A host that handshakes and then never sends a CHUNK: idle_release
    un-gates the merge so healthy hosts emit, and closing the server
    removes the ghost's empty journal + meta (from_fleet_dir must not
    replay a ghost)."""
    fleet_dir = str(tmp_path / "fleet")
    server = IngestServer(fleet_dir=fleet_dir, read_deadline=None,
                          idle_release=0.15)
    server.start()
    fleet_sess = ProfileSession(server.source, n_min=1.0)
    fleet_sess.start()
    ghost = RemoteSink(server.address, "ghost", num_workers=1,
                       worker_names=["g0"], clock_offset_ns=0,
                       heartbeat_interval=None)
    ghost.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=None)
    try:
        _wait(lambda: server.stats()["hosts"] == 2)
        _stream_spans(s, w, clk, 10)
        s.snapshot()
        assert sink.flush(5.0)
        # the ghost pins nothing: its idle_release exemption lets the
        # healthy host's rows reach the fold while the ghost stays open
        _wait(lambda: server.stats()["idle_hosts"] >= 1)
        _wait(lambda: fleet_sess.stats()["events_folded"] >= 20)
        s.result()
        sink.close()
        rep = fleet_sess.result()
        assert rep.total_slices == 10
    finally:
        fleet_sess.stop()
        ghost.abort()
        server.close()
    st = server.stats()
    assert st["idle_released"] >= 1, st
    # no ghost journal/meta leaked; from_fleet_dir sees only the real host
    names = os.listdir(fleet_dir)
    assert not any(n.startswith("ghost") for n in names), names
    src = FleetSource.from_fleet_dir(fleet_dir)
    assert [h.host_id for h in src.hosts] == ["h"]
    assert len(src.full_log()) == 20


def test_dataless_heartbeat_does_not_pin_watermark():
    """An alive-but-dataless producer (heartbeats, no rows) must not gate
    the merge: its null-watermark beacons mark it exempt."""
    server = IngestServer(read_deadline=None, idle_release=None)
    server.start()
    fleet_sess = ProfileSession(server.source, n_min=1.0)
    fleet_sess.start()
    idle = RemoteSink(server.address, "idle", num_workers=1,
                      worker_names=["i0"], clock_offset_ns=0,
                      heartbeat_interval=0.05)
    idle.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=None)
    try:
        _wait(lambda: server.stats()["hosts"] == 2)
        _stream_spans(s, w, clk, 10)
        s.snapshot()
        assert sink.flush(5.0)
        _wait(lambda: server.stats()["idle_hosts"] >= 1)
        # rows flow despite the dataless host (the watermark holds back
        # only the newest in-flight row of the live gating host)
        _wait(lambda: fleet_sess.stats()["events_folded"] >= 10)
    finally:
        s.close()
        sink.close()
        idle.abort()
        fleet_sess.stop()
        server.close()


# ---------------------------------------------------------------------------
# finish_host idempotence
# ---------------------------------------------------------------------------

def test_finish_host_idempotent_and_unknown_false():
    server = IngestServer()
    server.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=None)
    try:
        _stream_spans(s, w, clk, 5)
        s.snapshot()
        assert sink.flush(5.0)
        # flush() returns once the rows are written to the socket; finish
        # the host only after the server has read them, or the merge can
        # end before they arrive
        _wait(lambda: server.stats()["rows_in"] == 10)
        assert server.finish_host("h") is True
        assert server.finish_host("h") is True      # idempotent
        assert server.finish_host("nope") is False
        rep = ProfileSession(server.source, n_min=1.0).result()
        assert rep.total_slices == 5                # finished, data intact
    finally:
        s.close()
        sink.abort()
        server.close()


# ---------------------------------------------------------------------------
# reconnect backoff: full jitter, bounded, seeded
# ---------------------------------------------------------------------------

def test_backoff_full_jitter_bounded_and_seeded(monkeypatch):
    import repro.fleet.transport as T
    slept = []
    monkeypatch.setattr(T.time, "sleep", lambda s: slept.append(s))
    sink = RemoteSink(("127.0.0.1", 1), "h", reconnect_delay=0.05,
                      backoff_max=0.4, backoff_seed=42)
    for a in range(12):
        sink._backoff(a)
    assert len(slept) == 12
    for a, d in enumerate(slept):
        cap = min(0.4, 0.05 * (1 << min(a, 16)))
        assert 0.0 <= d <= cap          # full jitter: uniform(0, cap)
    assert max(slept) <= 0.4            # capped despite attempt growth
    assert len(set(round(d, 12) for d in slept)) > 1    # actually jittered
    # the same seed replays the same schedule (chaos reproducibility)
    sink2 = RemoteSink(("127.0.0.1", 1), "h", reconnect_delay=0.05,
                       backoff_max=0.4, backoff_seed=42)
    slept2 = []
    monkeypatch.setattr(T.time, "sleep", lambda s: slept2.append(s))
    for a in range(12):
        sink2._backoff(a)
    assert slept2 == slept


# ---------------------------------------------------------------------------
# overload shedding: live report degrades, journals stay complete
# ---------------------------------------------------------------------------

def test_overload_sheds_oldest_but_journals_recover(tmp_path):
    fleet_dir = str(tmp_path / "fleet")
    server = IngestServer(fleet_dir=fleet_dir, max_pending_rows=20,
                          read_deadline=None, idle_release=None)
    server.start()                      # NOTE: no session draining
    journal = str(tmp_path / "h.journal")
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         journal=journal, heartbeat_interval=None)
    try:
        for _ in range(10):             # 10 chunks x 10 rows >> budget
            _stream_spans(s, w, clk, 5)
            s.snapshot()
        s.result()
        sink.close()
        assert server.wait_idle(10), server.stats()
        st = server.stats()
    finally:
        server.close()
    assert st["shed_chunks"] > 0, st
    assert st["shed_rows"] >= st["shed_chunks"], st
    assert st["lost_chunks"] == 0, st
    assert st["rows_in"] == 100         # every row was ACCEPTED (then shed)
    assert st["buffered_rows"] <= 20 + 10   # budget + one in-flight chunk
    # the journals kept what the live merge shed: offline replay is whole,
    # and the server journal agrees with the producer journal
    fleet = FleetSource.from_fleet_dir(fleet_dir)
    flog = fleet.full_log()
    assert len(flog) == 100
    prod = FleetSource.from_producer_journals([journal])
    plog = prod.full_log()
    np.testing.assert_array_equal(flog.times, plog.times)
    ra = detect_offline(flog, fleet.tags, fleet.stacks, n_min=1.0)
    rb = detect_offline(plog, prod.tags, prod.stacks, n_min=1.0)
    np.testing.assert_array_equal(ra.per_worker, rb.per_worker)
    assert ra.total_slices == rb.total_slices == 50


def test_non_journaled_overload_pauses_reads_lossless():
    """Without fleet_dir there is nothing to recover shed rows from, so
    overload must PAUSE reads (TCP backpressure), not shed."""
    server = IngestServer(max_pending_rows=20, read_deadline=None,
                          idle_release=None)
    server.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, server.address, host_id="h", clock_offset_ns=0,
                         heartbeat_interval=None)
    try:
        for _ in range(8):
            _stream_spans(s, w, clk, 5)
            s.snapshot()
        _wait(lambda: server.stats()["buffered_rows"] >= 20)
        time.sleep(0.2)                 # reads paused: no shedding ever
        st = server.stats()
        assert st["shed_chunks"] == 0, st
        assert st["buffered_rows"] <= 30, st
        # draining the merge resumes the reads and the rest arrives
        fleet_sess = ProfileSession(server.source, n_min=1.0)
        fleet_sess.start()
        s.result()
        sink.close()
        assert server.wait_idle(10), server.stats()
        rep = fleet_sess.result()
        fleet_sess.stop()
        assert server.stats()["rows_in"] == 80
        assert rep.total_slices == 40
    finally:
        s.close()
        server.close()


# ---------------------------------------------------------------------------
# close() is a delivery barrier: a flush into a dead socket's kernel
# buffers must never pass as delivery
# ---------------------------------------------------------------------------

def test_close_delivery_barrier_replays_into_restarted_server(tmp_path):
    """The silent-loss failure mode from the chaos gate: the server dies
    while the producer's tail (chunks + BYE) sits unread in socket
    buffers.  Every flush() succeeded, so without a barrier the sink
    would exit "clean" and the rows would vanish.  The dying server RSTs
    abandoned connections and a live server only closes a connection
    AFTER consuming its BYE, so close() discovers the loss, reconnects,
    and replays the journal tail into the restarted server."""
    fleet_dir = str(tmp_path / "fleet")
    server = IngestServer(fleet_dir=fleet_dir)
    addr = server.address
    server.start()
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
    w = s.register_worker("w")
    sink = attach_remote(s, addr, host_id="h", clock_offset_ns=0,
                         journal=str(tmp_path / "h.journal"),
                         reconnect_delay=0.01, backoff_max=0.05,
                         max_reconnects=1 << 30, heartbeat_interval=None)
    server2 = None
    try:
        _stream_spans(s, w, clk, 5)
        s.snapshot()
        assert sink.flush(5.0)
        # hard server loss with the producer mid-capture; the remaining
        # chunks and the BYE are written into a connection nobody will
        # ever read again
        server.close()
        _stream_spans(s, w, clk, 5)
        s.snapshot()
        s.result()
        # resurrect the aggregator on the same port + fleet_dir, THEN
        # close: the barrier must surface the dead-socket loss and the
        # replay must land everything in the restarted server
        server2 = IngestServer(addr, fleet_dir=fleet_dir)
        server2.start()
        sink.close(timeout=10.0)
        assert not sink.failed, sink.last_error
        assert sink.stats()["pending"] == 0
        assert server2.wait_idle(10.0), server2.stats()
        st = server2.stats()
        assert st["lost_chunks"] == 0, st
        src = FleetSource.from_fleet_dir(fleet_dir)
        oracle = detect_offline(src.full_log(), src.tags, src.stacks,
                                n_min=1.0)
        assert oracle.total_slices == 10     # nothing silently eaten
    finally:
        s.close()
        sink.close()
        server.close()
        if server2 is not None:
            server2.close()
