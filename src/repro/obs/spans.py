"""Host spans on the program's own threads.

``with span("train/step"): ...`` does three things:

* while a JAX trace runs, it enters ``jax.profiler.TraceAnnotation(name)``,
  so the span shows on its thread's host line in the trace, on the clock
  of the trace's device planes (an annotation entered with no trace
  running records nothing, so none is made then; nor while JAX is not
  imported: no JAX trace can run, and importing JAX is not this module's
  to do);
* it adds its duration, on ``time.perf_counter_ns``, to the name's
  ``count`` / ``seconds_sum`` / ``seconds_max`` (:func:`stats`, exported
  at ``/metrics`` as ``gapp_span_<name>_*``), always;
* while a JAX trace runs, at both its entry and its exit, it appends
  ``(name, thread id, start_ns, end_ns)`` to a bounded ring
  (:func:`records`).  A span the trace's start or stop cuts is not
  recorded, so the records cover traced windows and nothing else.  Past
  the ring's capacity the newest record overwrites the oldest, counted in
  ``records_dropped``.

Spans never touch a profiling session: they add no GAPP worker, tag or
event.
"""
from __future__ import annotations

import sys
import threading
import time

CAPACITY = 1 << 16

#: One recorded span: (name, thread id, start_ns, end_ns).
Record = tuple

_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is imported, else None."""
    global _annotation_cls
    if _annotation_cls is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls


class SpanLog:
    """Per-name aggregates and the bounded record ring, thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._agg: dict[str, list[int]] = {}    # guarded-by: self._lock
        self._records: list = [None] * CAPACITY  # guarded-by: self._lock
        self._n = 0                             # guarded-by: self._lock

    def add(self, name: str, start_ns: int, end_ns: int,
            traced: bool) -> None:
        d = end_ns - start_ns
        tid = threading.get_ident()
        with self._lock:
            a = self._agg.get(name)
            if a is None:
                self._agg[name] = [1, d, d]
            else:
                a[0] += 1
                a[1] += d
                if d > a[2]:
                    a[2] = d
            if traced:
                self._records[self._n % CAPACITY] = (
                    name, tid, start_ns, end_ns)
                self._n += 1

    def stats(self) -> dict:
        """``{name: {count, seconds_sum, seconds_max}}`` for every span
        name entered so far, and ``records_dropped``."""
        with self._lock:
            out: dict = {name: {"count": c, "seconds_sum": s * 1e-9,
                                "seconds_max": m * 1e-9}
                         for name, (c, s, m) in sorted(self._agg.items())}
            out["records_dropped"] = max(0, self._n - CAPACITY)
        return out

    def records(self, since_ns: int = 0) -> list[Record]:
        """Recorded spans that started at ``since_ns`` or later and are
        still in the ring, in the order they ended."""
        with self._lock:
            i = self._n % CAPACITY
            recs = (self._records[:self._n] if self._n <= CAPACITY
                    else self._records[i:] + self._records[:i])
        return [r for r in recs if r[2] >= since_ns]


_LOG = SpanLog()


class span:
    """Context manager: one host span named ``name`` (see the module)."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        cls = _annotation()
        self._ann = cls(self.name) if cls is not None and cls.is_enabled() \
            else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        traced = False
        if self._ann is not None:
            traced = self._ann.is_enabled()
            self._ann.__exit__(*exc)
        _LOG.add(self.name, self._t0, t1, traced)


def stats() -> dict:
    """The process's span aggregates (:meth:`SpanLog.stats`)."""
    return _LOG.stats()


def records(since_ns: int = 0) -> list[Record]:
    """The process's recorded spans (:meth:`SpanLog.records`)."""
    return _LOG.records(since_ns)
