"""The stats() schemas are a public contract.

``/metrics`` names derive mechanically from the stats dicts
(``flatten_stats``), and the ``session.stats()`` docstring documents
every counter — so these key sets are pinned: removing or renaming one
is a breaking change this test catches; NEW keys are additive and only
require updating the pinned set (and the docstring, which this test also
enforces for the session).
"""
import re
import time
import urllib.request
from pathlib import Path

import repro
from repro.core import ProfileSession
from repro.fleet import (IngestServer, ProfilerService, RemoteSink,
                         attach_remote)
from repro.obs import spans
from repro.obs.prom import flatten_stats
from tests.test_tracer import FakeClock

SESSION_LIVE_KEYS = {
    "mode", "events_folded", "events_pending", "ring_dropped",
    "tolerance_dropped", "store_rows", "store_resident_rows",
    "resident_bytes", "samples", "watch_errors",
}
SESSION_LIVE_SAMPLES_KEYS = {"ticks", "hits", "stored", "dropped"}
SESSION_OFFLINE_KEYS = {
    "mode", "events_folded", "sanitize_dropped", "slices",
    "critical_rows", "done", "watch_errors",
}
FLEET_SOURCE_KEYS = {
    "hosts", "rows_in", "chunks_in", "buffered_rows", "clock_clamped",
    "shed_chunks", "shed_rows", "idle_hosts", "accepting",
}
INGEST_SERVER_KEYS = {
    "address", "connections", "open_connections", "hosts",
    "stale_chunks", "duplicate_chunks", "lost_chunks", "bad_rows",
    "proto_errors", "backfilled_chunks", "backfilled_rows",
    "deadline_closed", "idle_released", "shed_chunks", "shed_rows",
    "journal_errors", "heartbeats", "fleet_dir",
} | FLEET_SOURCE_KEYS
REMOTE_SINK_KEYS = {
    "host_id", "rows_sent", "chunks_sent", "dropped_chunks", "pending",
    "reconnects", "send_errors", "failed", "codec", "replayed_chunks",
    "replayed_rows", "heartbeats_sent", "journal_errors",
    "server_wire_version", "wire_bytes", "raw_bytes", "journal",
}
SERVICE_KEYS = {
    "address", "requests", "connections", "open_connections",
    "http_errors", "stream_clients", "snapshot_count",
    "snapshot_seconds_sum", "snapshot_seconds_last", "window_folds",
    "window_fold_seconds_sum", "whatif_folds", "whatif_fold_seconds_sum",
    "max_window_s", "retention_pruned_blocks", "retention_errors",
}

# host spans (repro.obs.spans): every name the program enters, and the keys
# of each name's aggregate
SPAN_NAMES = {
    "train/loader_wait", "train/h2d", "train/step", "train/host",
    "data/generate", "profiler/drain", "profiler/merge", "profiler/fold",
    "profiler/fold_wait", "profiler/intern", "profiler/sample",
}
SPAN_KEYS = {"count", "seconds_sum", "seconds_max"}


def test_session_live_stats_schema():
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk)
    w = s.register_worker("w")
    s.begin(w, "t")
    clk.advance(100)
    s.end(w)
    st = s.stats()
    assert set(st) == SESSION_LIVE_KEYS
    assert set(st["samples"]) == SESSION_LIVE_SAMPLES_KEYS
    s.result()


def test_session_live_stats_with_sinks_key(tmp_path):
    server = IngestServer()
    server.start()
    fleet = ProfileSession(server.source, n_min=1.0)
    fleet.start()
    try:
        clk = FakeClock()
        s = ProfileSession(n_min=1.0, clock=clk, drain_interval=0.001)
        w = s.register_worker("w")
        sink = attach_remote(s, server.address, host_id="h")
        s.begin(w, "t")
        clk.advance(100)
        s.end(w)
        st = s.stats()
        assert set(st) == SESSION_LIVE_KEYS | {"sinks"}
        assert set(st["sinks"][0]) == REMOTE_SINK_KEYS
        s.result()
        sink.close()
    finally:
        fleet.stop()
        server.close()


def test_session_offline_and_fleet_source_schema():
    server = IngestServer()
    server.start()
    sess = ProfileSession(server.source, n_min=1.0)
    try:
        st = sess.stats()
        assert set(st) == SESSION_OFFLINE_KEYS | {"source"}
        assert set(st["source"]) == FLEET_SOURCE_KEYS
        assert set(server.stats()) == INGEST_SERVER_KEYS
    finally:
        sess.stop()
        server.close()


def test_service_stats_schema():
    s = ProfileSession(n_min=1.0, clock=FakeClock())
    svc = ProfilerService(s)
    try:
        assert set(svc.stats()) == SERVICE_KEYS
    finally:
        svc.close()
        s.result()


def test_session_stats_docstring_documents_every_key():
    doc = ProfileSession.stats.__doc__
    for key in (SESSION_LIVE_KEYS | SESSION_OFFLINE_KEYS | {"sinks"}
                | FLEET_SOURCE_KEYS):
        assert f"``{key}``" in doc, f"stats() docstring missing {key!r}"


def test_metric_names_derived_from_schema_are_stable():
    """The gauge names a dashboard would reference: prefix + key, with
    nested dicts joined — pin the derivation for the session schema."""
    clk = FakeClock()
    s = ProfileSession(n_min=1.0, clock=clk)
    names = {n for n, _, _ in flatten_stats("gapp_session", s.stats())}
    assert names == {
        "gapp_session_events_folded", "gapp_session_events_pending",
        "gapp_session_ring_dropped", "gapp_session_tolerance_dropped",
        "gapp_session_store_rows", "gapp_session_store_resident_rows",
        "gapp_session_resident_bytes", "gapp_session_samples_ticks",
        "gapp_session_samples_hits", "gapp_session_samples_stored",
        "gapp_session_samples_dropped", "gapp_session_watch_errors",
    }   # "mode" is a string -> identity, not telemetry
    s.result()


def test_program_span_names_are_pinned():
    """The benchmark's per-layer metrics and the ``gapp_span_*`` gauges
    read spans by name: a renamed span fails here first."""
    names = set()
    for path in Path(repro.__file__).parent.rglob("*.py"):
        names.update(re.findall(r'spans\.span\("([^"]+)"\)',
                                path.read_text()))
    assert names == SPAN_NAMES


def test_span_stats_are_exported_at_metrics(monkeypatch):
    monkeypatch.setattr(spans, "_LOG", spans.SpanLog())
    for name in SPAN_NAMES:
        with spans.span(name):
            pass
    st = spans.stats()
    assert set(st) == SPAN_NAMES | {"records_dropped"}
    assert all(set(st[n]) == SPAN_KEYS for n in SPAN_NAMES)
    want = {f"gapp_span_{n.replace('/', '_')}_{k}"
            for n in SPAN_NAMES for k in SPAN_KEYS}
    assert {n for n, _, _ in flatten_stats("gapp_span", st)} \
        == want | {"gapp_span_records_dropped"}
    s = ProfileSession(n_min=1.0, clock=FakeClock())
    svc = s.serve()
    try:
        url = "http://%s:%d/metrics" % svc.address
        with urllib.request.urlopen(url, timeout=5) as r:
            text = r.read().decode()
    finally:
        svc.close()
        s.result()
    exported = {line.split(" ")[0] for line in text.splitlines()
                if line.startswith("gapp_span_")}
    assert exported == want | {"gapp_span_records_dropped"}
