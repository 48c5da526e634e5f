"""Streaming ProfileSession: snapshot-during-capture == offline oracle,
spill-bounded memory, pluggable sources, exporter registry, live watch,
call-site interning and the offline-session shape."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core import (ProfileSession, SpillSource, SpillStore,
                        available_exporters, compute_numpy, detect_offline,
                        export, register_exporter, synthetic_log)
from repro.core.exporters import unregister_exporter
from repro.core.tracer import StackRegistry, TagRegistry
from tests.test_tracer import FakeClock


def _ranked(rep):
    return [(rep.path_str(p), p.cmetric, p.slices) for p in rep.paths]


# ---------------------------------------------------------------------------
# acceptance: live snapshot mid-capture, quiesce, result == offline oracle
# ---------------------------------------------------------------------------

def test_live_snapshot_then_result_bit_equal_to_offline_oracle():
    """snapshot() during live multi-threaded capture, then quiesce +
    result(): the final report must be bit-equal (numpy backend) to the
    one-shot detect_offline oracle on the same frozen log."""
    nt, iters = 4, 1500
    s = ProfileSession(n_min=2.0, capacity=1 << 14, drain_interval=0.001)
    wids = [s.register_worker(f"t{i}") for i in range(nt)]
    mid_reports = []

    def hammer(wid):
        h = s.handle(wid)
        for i in range(iters):
            with h.span(("step", "io", "net")[i % 3]):
                pass

    threads = [threading.Thread(target=hammer, args=(w,)) for w in wids]
    with s.running():
        for t in threads:
            t.start()
        # incremental snapshots while producers are mid-flight
        for _ in range(5):
            mid_reports.append(s.snapshot())
            time.sleep(0.002)
        for t in threads:
            t.join()
    rep = s.result()

    # the mid-capture snapshots were real incremental reports
    assert all(r.total_slices <= rep.total_slices for r in mid_reports)
    assert rep.total_slices == nt * iters
    assert s.tracer.ring.dropped == 0

    log = s.freeze()
    log.validate()
    oracle = detect_offline(log, s.tags, s.stacks, 2.0,
                            samples=s.probe.buffer
                            if len(s.probe.buffer) else None,
                            worker_names=s.tracer.worker_names())
    np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
    assert rep.total_critical == oracle.total_critical
    assert rep.total_slices == oracle.total_slices
    assert rep.idle_time == oracle.idle_time
    assert rep.total_time == oracle.total_time
    assert _ranked(rep) == _ranked(oracle)
    # per-slice agreement, bit-for-bit
    np.testing.assert_array_equal(rep.critical_table.cm,
                                  oracle.critical_table.cm)
    np.testing.assert_array_equal(rep.critical_table.threads_av,
                                  oracle.critical_table.threads_av)


# ---------------------------------------------------------------------------
# acceptance: disk spill bounds resident event memory at O(chunk_events)
# ---------------------------------------------------------------------------

def test_spill_session_bounds_resident_memory(tmp_path):
    """A spill-enabled session streams >=10x chunk_events events while the
    store's resident buffer never exceeds one chunk; the spilled file
    freezes back to the exact log and the final report matches it."""
    chunk = 512
    clk = FakeClock()
    s = ProfileSession(n_min=1.5, clock=clk, capacity=1024,
                       spill_path=str(tmp_path / "events.spill"),
                       chunk_events=chunk)
    w = [s.register_worker(f"w{i}") for i in range(2)]
    pairs = 10 * chunk  # 4 events per iteration => 40x chunk_events total
    for _ in range(pairs):
        s.begin(w[0], "a")
        clk.advance(1_000)
        s.begin(w[1], "b")
        clk.advance(1_000)
        s.end(w[1])
        clk.advance(500)
        s.end(w[0])
        clk.advance(500)
    rep = s.result()
    store = s.tracer.store
    assert isinstance(store, SpillStore)
    assert len(store) == 4 * pairs >= 10 * chunk
    # the memory bound: the RAM buffer never held more than one chunk
    assert store.max_resident_rows <= chunk
    assert store.rows_on_disk == 4 * pairs
    assert store.resident_nbytes < 64 * chunk   # 21B/row buffer, no growth
    # read-back equals what an unbounded store would have accumulated
    log = s.freeze()
    log.validate()
    assert len(log) == 4 * pairs
    res = compute_numpy(log)
    np.testing.assert_array_equal(res.per_worker, rep.per_worker)
    assert rep.total_slices == 2 * pairs
    # streaming re-analysis of the spilled file, block by block, agrees too
    replay = ProfileSession(
        SpillSource(store, log.num_workers, tags=s.tags, stacks=s.stacks),
        n_min=1.5)
    rep2 = replay.result()
    np.testing.assert_array_equal(rep2.per_worker, rep.per_worker)
    assert rep2.total_critical == rep.total_critical


# ---------------------------------------------------------------------------
# offline sources
# ---------------------------------------------------------------------------

def test_offline_session_matches_detect_offline():
    rng = np.random.default_rng(7)
    log = synthetic_log(rng, 6, 150)
    oracle = detect_offline(log, TagRegistry(), StackRegistry(), n_min=3.0,
                            sample_dt_ns=500_000)
    for chunk_events in (None, 101, 4096):
        s = ProfileSession.offline(log, n_min=3.0,
                                   chunk_events=chunk_events,
                                   sample_dt_ns=500_000)
        rep = s.result()
        np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
        assert rep.total_slices == oracle.total_slices
        assert rep.total_critical == oracle.total_critical
        assert _ranked(rep) == _ranked(oracle)


def test_offline_session_background_worker():
    """start() folds chunks on the worker thread; result() joins it."""
    rng = np.random.default_rng(3)
    log = synthetic_log(rng, 4, 400)
    oracle = detect_offline(log, TagRegistry(), StackRegistry(), n_min=2.0)
    s = ProfileSession.offline(log, n_min=2.0, chunk_events=64)
    s.start()
    # incremental snapshots while the worker folds
    partial = s.snapshot()
    assert partial.total_slices <= oracle.total_slices
    rep = s.result()
    np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
    assert rep.total_slices == oracle.total_slices
    assert s.stats()["done"]


def test_offline_session_sanitizes_dirty_streams():
    rng = np.random.default_rng(11)
    log = synthetic_log(rng, 4, 60)
    # corrupt: duplicate ACTIVATEs (spurious wakeups)
    dirty_idx = np.where(log.deltas == 1)[0][::3]
    times = np.insert(log.times, dirty_idx, log.times[dirty_idx])
    workers = np.insert(log.workers, dirty_idx, log.workers[dirty_idx])
    deltas = np.insert(log.deltas, dirty_idx, 1)
    tags = np.insert(log.tags, dirty_idx, -1)
    stacks = np.insert(log.stacks, dirty_idx, -1)
    from repro.core import EventLog
    dirty = EventLog(times, workers, deltas, tags, stacks, log.num_workers)
    oracle = detect_offline(dirty, TagRegistry(), StackRegistry(), n_min=2.0)
    s = ProfileSession.offline(dirty, n_min=2.0, chunk_events=97)
    rep = s.result()
    np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
    assert s.stats()["sanitize_dropped"] == len(dirty_idx)


def test_offline_session_has_no_live_api():
    s = ProfileSession.offline(synthetic_log(np.random.default_rng(0), 2, 5),
                               n_min=1.0)
    with pytest.raises(RuntimeError):
        s.register_worker("x")
    with pytest.raises(RuntimeError):
        s.begin(0, "t")


# ---------------------------------------------------------------------------
# exporter registry
# ---------------------------------------------------------------------------

def _tiny_live_session():
    clk = FakeClock()
    s = ProfileSession(n_min=1.9, clock=clk)
    w = [s.register_worker(f"w{i}") for i in range(2)]
    for _ in range(4):
        s.begin(w[0], "par")
        s.begin(w[1], "par")
        clk.advance(1_000_000)
        s.end(w[0])
        s.end(w[1])
        s.begin(w[0], "serial")
        clk.advance(2_000_000)
        s.end(w[0])
    return s


def test_exporter_registry_builtins():
    assert {"text", "json", "chrome", "callback", "watch"} <= \
        set(available_exporters())
    s = _tiny_live_session()
    text = s.export("text", max_paths=2)
    assert "GAPP bottleneck profile" in text and "serial" in text
    d = json.loads(s.export("json"))
    assert d["schema_version"] >= 2
    trace = json.loads(s.export("chrome"))
    assert any(e.get("name") == "serial" for e in trace["traceEvents"])
    got = []
    s.export("callback", callback=got.append)
    assert len(got) == 1 and got[0].total_slices == 12
    with pytest.raises(KeyError):
        s.export("no-such-format")


def test_exporter_chrome_needs_log_or_session():
    s = _tiny_live_session()
    rep = s.snapshot()
    with pytest.raises(ValueError):
        export(rep, "chrome")
    out = export(rep, "chrome", session=s)
    assert json.loads(out)["traceEvents"]


def test_register_custom_exporter():
    def _csv(rep, *, session=None, **kw):
        return "\n".join(f"{rep.path_str(p)},{p.cmetric}" for p in rep.paths)
    register_exporter("csv", _csv, capabilities={"machine"})
    try:
        s = _tiny_live_session()
        out = s.export("csv")
        assert out.splitlines()[0].startswith("serial,")
    finally:
        unregister_exporter("csv")


def test_chrome_export_to_path(tmp_path):
    s = _tiny_live_session()
    p = tmp_path / "trace.json"
    s.export("chrome", path=str(p))
    assert json.loads(p.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# live watch
# ---------------------------------------------------------------------------

def test_watch_pushes_live_and_final_reports():
    s = ProfileSession(n_min=1.0, drain_interval=0.002)
    w = s.register_worker("w")
    seen = []
    unsubscribe = s.watch(seen.append, every=0.0)
    with s.running():
        for _ in range(20):
            with s.span(w, "work"):
                time.sleep(0.001)
    assert seen, "no live updates during the run"
    n_live = len(seen)
    s.close()                    # final push fires even after unsubscribe #2
    assert len(seen) == n_live + 1
    final = seen[-1]
    assert final.total_slices == 20
    unsubscribe()
    assert s.watch_errors == []


def test_watch_via_exporter_and_errors_recorded():
    s = ProfileSession(n_min=1.0)
    w = s.register_worker("w")
    calls = []
    unsubscribe = s.export("watch", callback=calls.append, every=0.0)
    assert callable(unsubscribe)

    def boom(rep):
        raise RuntimeError("watcher bug")
    s.watch(boom, every=0.0)
    with s.span(w, "x"):
        pass
    s.close()                    # fires both watchers; boom must not raise
    assert calls and len(s.watch_errors) == 1


# ---------------------------------------------------------------------------
# call-site interning and the offline-session shape
# ---------------------------------------------------------------------------

def test_gapp_begin_callsite_resolved_once_and_loc_override():
    """begin() does not walk sys._getframe per call — the callsite is
    interned once per distinct tag and points at the *user* module (not
    the session), and loc= overrides it explicitly."""
    g = ProfileSession(n_min=1.0, clock=FakeClock())
    w = g.register_worker("w")
    g.begin(w, "hot_tag")
    g.end(w)
    tid = g.tracer.tags._ids["hot_tag"]
    loc = g.tracer.tags.locations[tid]
    assert loc.split(":")[0].endswith("test_session"), loc
    # explicit location: no frame walk at all
    g.begin(w, "explicit_tag", loc="my_module:42")
    g.end(w)
    tid2 = g.tracer.tags._ids["explicit_tag"]
    assert g.tracer.tags.locations[tid2] == "my_module:42"
    # repeated begins of a known tag never re-intern (location is stable)
    g.begin(w, "hot_tag")
    g.end(w)
    assert g.tracer.tags.locations[tid] == loc


def test_offline_session_with_registries_matches_detect_offline():
    rng = np.random.default_rng(5)
    log = synthetic_log(rng, 4, 80)
    oracle = detect_offline(log, TagRegistry(), StackRegistry(), n_min=2.0,
                            sample_dt_ns=1_000_000)
    rep = ProfileSession.offline(log, TagRegistry(), StackRegistry(),
                                 n_min=2.0, sample_dt_ns=1_000_000).result()
    np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
    assert _ranked(rep) == _ranked(oracle)


# ---------------------------------------------------------------------------
# spill store unit behaviour
# ---------------------------------------------------------------------------

def test_spill_store_roundtrip_and_chunking(tmp_path):
    rng = np.random.default_rng(2)
    log = synthetic_log(rng, 3, 300)
    st = SpillStore(str(tmp_path / "s.bin"), chunk_events=128)
    # append in odd-sized pieces; blocks must still be exactly chunk-sized
    for lo in range(0, len(log), 77):
        c = log.chunk(lo, lo + 77)
        st.append_columns(c.times, c.workers, c.deltas, c.tags, c.stacks)
    assert len(st) == len(log)
    assert st.max_resident_rows <= 128
    back = st.freeze(log.num_workers)
    for col in ("times", "workers", "deltas", "tags", "stacks"):
        np.testing.assert_array_equal(getattr(back, col), getattr(log, col))
    chunks = list(st.iter_chunks(log.num_workers))
    assert sum(len(c) for c in chunks) == len(log)
    assert all(len(c) <= 128 for c in chunks)
    st.close()
    with pytest.raises(ValueError):
        st.append_columns(log.times[:1], log.workers[:1], log.deltas[:1],
                          log.tags[:1], log.stacks[:1])


def test_spill_store_owns_its_file_and_readonly_replays(tmp_path):
    """Regression: a writer store at a reused path must not leak the
    previous run's events into freeze(); replay opens read-only (no
    truncation) — including SpillSource given a bare path."""
    path = str(tmp_path / "reuse.spill")
    log = synthetic_log(np.random.default_rng(1), 2, 30)
    st1 = SpillStore(path, chunk_events=16)
    st1.append_columns(log.times, log.workers, log.deltas, log.tags,
                       log.stacks)
    st1.close()
    # second capture at the same path: first run's rows must be gone
    st2 = SpillStore(path, chunk_events=16)
    c = log.chunk(0, 8)
    st2.append_columns(c.times, c.workers, c.deltas, c.tags, c.stacks)
    assert len(st2) == 8
    assert len(st2.freeze(log.num_workers)) == 8
    st2.close()
    # read-only open indexes the existing file without touching it
    ro = SpillStore.open_readonly(path)
    assert ro.rows_on_disk == 8
    with pytest.raises(ValueError):
        ro.append_columns(c.times, c.workers, c.deltas, c.tags, c.stacks)
    np.testing.assert_array_equal(ro.freeze(log.num_workers).times, c.times)
    # SpillSource(path) replays, and the file survives (not truncated)
    src = SpillSource(path, log.num_workers)
    assert sum(len(ch) for ch in src.chunks()) == 8
    assert SpillStore.open_readonly(path).rows_on_disk == 8


# ---------------------------------------------------------------------------
# per-shard decode budget (max_rows_per_sync)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_max_rows_per_sync_bounds_snapshot_decode_and_final_is_exact(
        backend):
    """With a decode budget, a mid-capture snapshot folds at most one
    budget's worth per shard (bounded latency, lagging report); close()
    consumes the backlog so the final report is complete and equal to the
    offline oracle: bit-equal on ``numpy``, within float32 tolerance on
    ``pallas`` (the attached training cell's drain)."""
    clk = FakeClock()
    budget = 64
    s = ProfileSession(n_min=1.5, clock=clk, capacity=1 << 15,
                       max_rows_per_sync=budget, fold_backend=backend)
    w = [s.register_worker(f"w{i}") for i in range(2)]
    pairs = 2000
    for _ in range(pairs):
        s.begin(w[0], "a")
        clk.advance(1000)
        s.begin(w[1], "b")
        clk.advance(1000)
        s.end(w[1])
        clk.advance(500)
        s.end(w[0])
        clk.advance(500)
    total = 4 * pairs
    assert s.tracer.ring.pending() == total
    mid = s.snapshot()                  # one budgeted flush only
    folded = total - s.tracer.ring.pending()
    assert 0 < folded <= budget * 2     # <= budget per shard
    assert mid.total_slices <= folded
    rep = s.result()                    # close(): full sync, then seal
    assert s.tracer.ring.pending() == 0
    assert rep.total_slices == 2 * pairs
    log = s.freeze()
    log.validate()
    res = compute_numpy(log)
    if backend == "numpy":
        np.testing.assert_array_equal(res.per_worker, rep.per_worker)
    else:
        np.testing.assert_allclose(rep.per_worker, res.per_worker,
                                   rtol=1e-4, atol=1e-6)
    assert len(log) == total


def test_max_rows_per_sync_skewed_shards_times_not_clamped():
    """A sparse worker next to a dense one: capped drains must not merge
    the sparse shard's far future with the dense shard's past — the time
    frontier trims each take, so the accumulated log keeps the exact
    original timestamps (no monotonic-clamp distortion)."""
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, capacity=1 << 15,
                       max_rows_per_sync=64, autoflush=False)
    dense = s.register_worker("dense")
    sparse = s.register_worker("sparse")
    expected = []
    for i in range(800):
        if i % 160 == 0:            # sparse worker fires rarely
            s.begin(sparse, "s")
            expected.append((clk.t, sparse, 1))
            clk.advance(50)
            s.end(sparse)
            expected.append((clk.t, sparse, -1))
            clk.advance(50)
        s.begin(dense, "d")
        expected.append((clk.t, dense, 1))
        clk.advance(100)
        s.end(dense)
        expected.append((clk.t, dense, -1))
        clk.advance(100)
    rep = s.result()
    log = s.freeze()
    log.validate()
    assert len(log) == len(expected)
    exp = sorted(range(len(expected)), key=lambda i: expected[i][0])
    np.testing.assert_array_equal(log.times,
                                  [expected[i][0] for i in exp])
    np.testing.assert_array_equal(log.workers,
                                  [expected[i][1] for i in exp])
    res = compute_numpy(log)
    np.testing.assert_array_equal(res.per_worker, rep.per_worker)


def test_max_rows_per_sync_full_sync_still_complete():
    """Tracer.sync() stays exhaustive under a budget: it bites the backlog
    off in budget-sized flushes instead of one unbounded decode."""
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk, max_rows_per_sync=32)
    w = s.register_worker("w")
    for _ in range(500):
        s.begin(w, "x")
        clk.advance(100)
        s.end(w)
        clk.advance(100)
    s.tracer.sync()
    assert s.tracer.ring.pending() == 0
    assert s.stats()["events_folded"] == 1000


# ---------------------------------------------------------------------------
# chunk_events reaches the offline session
# ---------------------------------------------------------------------------

def test_offline_session_forwards_chunk_events(monkeypatch):
    """ProfileSession.offline(chunk_events=...) must stream the replay
    through bounded chunks — pin the forwarding and the result
    equivalence."""
    from repro.core.session import LogSource
    rng = np.random.default_rng(13)
    log = synthetic_log(rng, 4, 120)
    seen = []
    orig = LogSource.chunks

    def spy(self):
        for part in orig(self):
            seen.append(len(part))
            yield part
    monkeypatch.setattr(LogSource, "chunks", spy)
    oracle = detect_offline(log, TagRegistry(), StackRegistry(), n_min=2.0)
    rep = ProfileSession.offline(log, TagRegistry(), StackRegistry(),
                                 n_min=2.0, sample_dt_ns=None,
                                 chunk_events=77).result()
    assert seen and max(seen) <= 77 and sum(seen) == len(log)
    np.testing.assert_array_equal(rep.per_worker, oracle.per_worker)
    assert _ranked(rep) == _ranked(oracle)


def test_dump_chrome_trace_accepts_sessions(tmp_path):
    from repro.core import dump_chrome_trace
    s = _tiny_live_session()
    p = tmp_path / "live.json"
    dump_chrome_trace(s, str(p))
    assert json.loads(p.read_text())["traceEvents"]
    off = ProfileSession.offline(s.freeze(), s.tags, s.stacks, n_min=1.9)
    off.result()
    p2 = tmp_path / "off.json"
    dump_chrome_trace(off, str(p2))
    assert json.loads(p2.read_text()) == json.loads(p.read_text())
