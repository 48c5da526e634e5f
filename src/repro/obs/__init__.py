"""Observability primitives for the continuous-profiling service.

Small, dependency-free building blocks the serving layer
(:mod:`repro.fleet.service`) composes:

* :mod:`repro.obs.http` — just enough HTTP/1.1 to parse a GET and frame
  a response (plus chunked transfer for ``/api/stream``), all pure
  functions over byte buffers so the selector event loop never blocks;
* :mod:`repro.obs.prom` — Prometheus text exposition over the profiler's
  own stats dicts (no client library);
* :mod:`repro.obs.payload` — the shared top-N/host-lanes payload builder
  behind ``session.watch(..., payload=True)`` and ``GET /api/stream``;
* :mod:`repro.obs.dashboard` — the inline no-dependency HTML dashboard
  served at ``GET /``;
* :mod:`repro.obs.spans` — named host spans on the program's own threads,
  in JAX traces and as ``count`` / ``seconds_sum`` / ``seconds_max``.

``repro.core`` imports :mod:`repro.obs.spans`, so this package's own
import pulls in nothing from ``repro.core`` (import ``build_watch_payload``
from :mod:`repro.obs.payload`).
"""
from repro.obs.http import (HttpError, Request, chunk, parse_request,
                            response, stream_head)
from repro.obs.prom import flatten_stats, render_metrics

__all__ = [
    "HttpError", "Request", "chunk",
    "flatten_stats", "parse_request", "render_metrics", "response",
    "stream_head",
]
