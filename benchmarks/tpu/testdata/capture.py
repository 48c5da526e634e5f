"""A small synthetic capture for the CPU tests and the recorded test trace.

A copy of ``chip_smoke.build_capture``: workers run short parallel spans
with a periodic serial section.  It stands for no deployment and no cell
runs it; it gives the plain fold something to agree with the program on,
and ``record.py`` something to replay on the chip.  ``generate`` returns
plain numpy columns (what the plain reference reads); ``to_program`` wraps
them in the profiler's own types.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ACTIVATE, DEACTIVATE = 1, -1
NO_STACK = -1
EPOCH_NS = 10**9

SMALL = {
    "workers": 64,
    "seconds": 0.05,
    "parallel_tags": 32,
    "busy_ns": [20000, 50000],
    "idle_ns": [30000, 110000],
    "serial_period_s": 0.1,
    "serial_s": 0.012,
    "n_min_share": 0.5,
    "parallel_spans": 25000,
}


@dataclasses.dataclass
class Capture:
    times: np.ndarray            # int64[E] ns, time-sorted
    workers: np.ndarray          # int32[E]
    deltas: np.ndarray           # int8[E] +1 switch-in, -1 switch-out
    tags: np.ndarray             # int32[E] tag id of the span
    stacks: np.ndarray           # int32[E] path id on switch-out, else -1
    num_workers: int
    tag_names: list[str]
    paths: list[tuple[int, ...]]  # path id -> tag ids, caller first
    serial_path: int
    n_min: float

    def __len__(self) -> int:
        return int(self.times.size)


def generate(c: dict, seed: int) -> Capture:
    """The job ``c`` (see ``SMALL``) describes: every ``serial_period_s`` worker 0 runs
    ``serial/commit`` alone for ``serial_s`` while every other worker
    waits; in between, all workers run ``busy_ns`` spans of
    ``parallel_tags`` tags, ``idle_ns`` apart, the tags chosen with weights
    ``1/sqrt(k+1)`` so the path totals are well separated.  Exactly
    ``parallel_spans`` parallel spans are kept, whatever the seed."""
    num_workers, n_tags = c["workers"], c["parallel_tags"]
    period_s, serial_s = c["serial_period_s"], c["serial_s"]
    busy_ns, idle_ns = tuple(c["busy_ns"]), tuple(c["idle_ns"])
    rng = np.random.default_rng(seed)
    tag_names = ["step"] + [f"parallel/t{k:02d}" for k in range(n_tags)] \
        + ["serial/commit"]
    step, serial_tag = 0, n_tags + 1
    par_tags = np.arange(1, n_tags + 1, dtype=np.int32)
    paths = [(step, int(t)) for t in par_tags] + [(step, serial_tag)]
    par_paths = np.arange(n_tags, dtype=np.int32)
    serial_path = n_tags

    periods = max(1, int(round(c["seconds"] / period_s)))
    par_ns = int(round((period_s - serial_s) * 1e9))    # parallel part
    ser_ns = int(round(serial_s * 1e9))
    t_par = periods * par_ns
    cycle = (sum(busy_ns) + sum(idle_ns)) / 2
    k = int(t_par / cycle * 1.05) + 16
    busy = rng.integers(*busy_ns, size=(num_workers, k))
    gap = rng.integers(*idle_ns, size=(num_workers, k))
    start = (rng.integers(0, idle_ns[1], size=(num_workers, 1))
             + np.cumsum(busy + gap, axis=1) - (busy + gap))
    end = start + busy
    # spans live on a parallel-only clock; a span that crosses into the
    # next serial section is dropped, the rest shift past the sections
    keep = (end < t_par) & (start // par_ns == end // par_ns)
    # every seed gets the same number of spans (so the same program
    # shapes): surplus spans, drawn from the seed, are left out
    surplus = int(keep.sum()) - c["parallel_spans"]
    if surplus < 0:
        raise ValueError(f"the capture holds {keep.sum()} parallel spans, "
                         f"fewer than the {c['parallel_spans']} configured")
    kept = np.flatnonzero(keep)
    keep.flat[rng.choice(kept, size=surplus, replace=False)] = False
    shift = (start // par_ns + 1) * ser_ns
    w = np.broadcast_to(np.arange(num_workers, dtype=np.int32)[:, None],
                        start.shape)[keep]
    p = 1.0 / np.sqrt(np.arange(1, n_tags + 1))
    pick = rng.choice(n_tags, size=int(keep.sum()), p=p / p.sum())
    s_start = np.concatenate([(start + shift)[keep],
                              np.arange(periods) * (par_ns + ser_ns)])
    s_end = np.concatenate([(end + shift)[keep],
                            np.arange(periods) * (par_ns + ser_ns) + ser_ns])
    s_worker = np.concatenate([w, np.zeros(periods, np.int32)])
    s_tag = np.concatenate([par_tags[pick],
                            np.full(periods, serial_tag, np.int32)])
    s_path = np.concatenate([par_paths[pick],
                             np.full(periods, serial_path, np.int32)])
    n = s_start.size
    times = np.concatenate([s_start, s_end]).astype(np.int64) + EPOCH_NS
    workers = np.concatenate([s_worker, s_worker]).astype(np.int32)
    deltas = np.concatenate([np.full(n, ACTIVATE, np.int8),
                             np.full(n, DEACTIVATE, np.int8)])
    # equal timestamps: switch-out first, like the live tracer's merge
    order = np.lexsort((workers, deltas, times))
    return Capture(
        times=times[order], workers=workers[order], deltas=deltas[order],
        tags=np.concatenate([s_tag, s_tag])[order].astype(np.int32),
        stacks=np.concatenate([np.full(n, NO_STACK, np.int32),
                               s_path])[order].astype(np.int32),
        num_workers=num_workers, tag_names=tag_names, paths=paths,
        serial_path=serial_path,
        n_min=num_workers * c["n_min_share"])


def to_program(cap: Capture):
    """``(EventLog, TagRegistry, StackRegistry)`` of the profiler, holding
    the capture with the same tag and path ids."""
    from repro.core.events import EventLog
    from repro.core.tracer import StackRegistry, TagRegistry
    tags, stacks = TagRegistry(), StackRegistry()
    for i, name in enumerate(cap.tag_names):
        if tags.intern(name) != i:
            raise RuntimeError("tag ids do not follow the capture's order")
    for i, path in enumerate(cap.paths):
        if stacks.intern(path) != i:
            raise RuntimeError("path ids do not follow the capture's order")
    log = EventLog(times=cap.times, workers=cap.workers, deltas=cap.deltas,
                   tags=cap.tags, stacks=cap.stacks,
                   num_workers=cap.num_workers)
    return log, tags, stacks
