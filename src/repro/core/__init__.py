"""GAPP core: criticality-metric serialization-bottleneck profiler.

Architecture — capture, analysis and output are one streaming pipeline
around a :class:`~repro.core.session.ProfileSession`::

    EventSource (``session.py``)
      ├── TracerSource   live sharded lock-free capture (``tracer.py``)
      ├── LogSource      offline EventLog replay in chunk_events batches
      └── SpillSource    replay of a disk-spilled capture (``spill.py``)
        │
        ▼  background drain+fold worker (overlaps capture)
    drain      k-way-merge the per-worker shards by timestamp
    sanitize   §3.2 tolerance rules against the carried per-worker state
    fold       carry-resumable ``fold_chunk``/``FoldCarry`` (``cmetric.py``)
               — the paper's Table-1 eBPF-map state, advanced batch-wise;
               two paths: the numpy float64 oracle | the Pallas device path
    store      accumulated log: in-RAM ``EventStore`` or an append-only
               disk ``SpillStore`` (resident memory O(chunk_events))
        │
        ▼  at any time, without stopping the workload
    session.snapshot()  →  Detector (``detector.py``, fully vectorised
                           over the columnar SliceTable of ``slices.py``):
                           sample attachment, path merge, tag tables
        │
        ▼
    BottleneckReport → exporter registry (``exporters.py``:
        text | json | chrome | callback | watch) — ``session.export(fmt)``
        or live push via ``session.watch(callback, every=...)``

``session.result()`` quiesces and returns the final report — bit-equal on
the ``numpy`` backend to ``detect_offline`` over the frozen log, for any
drain/snapshot schedule.  ``ProfileSession.offline(log, ...)`` is the same
session over a finished log.

Multi-host: the :mod:`repro.fleet` package streams drained chunks over a
socket (``RemoteSink`` → ``IngestServer``, attached via
``session.export("remote", addr=...)``) and merges N host streams into
one session through ``FleetSource`` — same pipeline, reports carry host
provenance (``report.worker_hosts`` / per-host exporter lanes).

The offline dataflow (``detect_offline``) is the same pipeline driven
synchronously: EventLog → sanitize → CMetric fold → SliceTable →
detector → report; ``detect_offline(chunk_events=...)`` streams it through
the identical chunk fold in bounded memory.
"""
from repro.core.events import (ACTIVATE, DEACTIVATE, EventLog, EventRing,
                               EventStore, ShardedEventRing, sanitize_chunk,
                               synthetic_log, tolerance_keep)
from repro.core.slices import (CriticalBuffer, CriticalSlice, CriticalTable,
                               SliceTable)
from repro.core.cmetric import (CMetricResult, FoldCarry, compute,
                                compute_numpy, fold_chunk)
from repro.core.tracer import (LockedTracer, StackRegistry, TagRegistry,
                               Tracer, WorkerHandle)
from repro.core.sampler import SampleBuffer, SamplingProbe, simulate_samples
from repro.core.detector import (BottleneckReport, PathProfile, build_report,
                                 detect, detect_offline, merge_table)
from repro.core.report import imbalance_stats, render_text, to_json
from repro.core.spill import SpillStore
from repro.core.exporters import (available_exporters, export, get_exporter,
                                  register_exporter)
from repro.core.session import (EventSource, LogSource, ProfileSession,
                                SpillSource, TracerSource)

__all__ = [
    "ACTIVATE", "DEACTIVATE", "EventLog", "EventRing", "EventStore",
    "ShardedEventRing", "sanitize_chunk", "synthetic_log", "tolerance_keep",
    "SliceTable", "CriticalTable", "CriticalBuffer", "CriticalSlice",
    "CMetricResult", "FoldCarry", "compute", "compute_numpy", "fold_chunk",
    "StackRegistry", "TagRegistry", "Tracer", "LockedTracer", "WorkerHandle",
    "SampleBuffer", "SamplingProbe", "simulate_samples",
    "BottleneckReport", "PathProfile", "build_report", "detect",
    "detect_offline", "merge_table", "imbalance_stats", "render_text",
    "to_json",
    "SpillStore", "available_exporters", "export", "get_exporter",
    "register_exporter",
    "ProfileSession", "EventSource", "TracerSource", "LogSource",
    "SpillSource",
]
from repro.core.wakers import (classify_report, classify_tag,  # noqa: E402
                               critical_wakers, waker_edges)

__all__ += ["classify_report", "classify_tag", "critical_wakers",
            "waker_edges"]
from repro.core.timeline import dump_chrome_trace, to_chrome_trace  # noqa: E402,F401

__all__ += ["dump_chrome_trace", "to_chrome_trace"]
