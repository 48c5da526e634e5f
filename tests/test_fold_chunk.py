"""Carry-resumable chunked fold: any chunk partition == whole-log oracle.

The contract (ISSUE 2 tentpole): ``fold_chunk(carry, chunk)`` over *every*
partition of a log — including size-1 chunks and chunk boundaries that cut
through open timeslices — must reproduce ``compute_numpy`` on the whole
log, bit-equal (float64) on the ``numpy`` path and within float32
tolerance on the ``pallas`` device path.
"""
import numpy as np
import pytest

try:                                   # `python -m pytest` from the repo root
    from tests.conftest import given, settings, st
except ImportError:                    # plain `pytest` (tests/ on sys.path)
    from conftest import given, settings, st

from repro.core import (EventLog, FoldCarry, SliceTable, StackRegistry,
                        TagRegistry, compute_numpy,
                        detect_offline, fold_chunk, sanitize_chunk,
                        synthetic_log)

ALL_BACKENDS = ("numpy", "pallas")


def _fold_partition(log, splits, backend):
    """Run the chunk fold over the given chunk sizes; returns (carry, table)."""
    carry = FoldCarry.init(log.num_workers)
    parts = []
    lo = 0
    for s in splits:
        hi = min(lo + s, len(log))
        carry, tbl = fold_chunk(carry, log.chunk(lo, hi), backend=backend)
        parts.append(tbl)
        lo = hi
        if lo >= len(log):
            break
    if lo < len(log):
        carry, tbl = fold_chunk(carry, log.chunk(lo, len(log)),
                                backend=backend)
        parts.append(tbl)
    return carry, SliceTable.concat(parts)


def _assert_matches_oracle(log, carry, tbl, exact):
    oracle = compute_numpy(log)
    assert carry.slices == oracle.num_slices == len(tbl)
    if exact:
        # float64 numpy chunk fold: bit-equal to the oracle, any split
        np.testing.assert_array_equal(carry.cm_hash, oracle.per_worker)
        assert carry.idle == oracle.idle_time
        assert carry.total_time == oracle.total_time
        for col in ("worker", "start_ns", "end_ns", "cm", "threads_av",
                    "n_at_exit"):
            np.testing.assert_array_equal(getattr(tbl, col),
                                          getattr(oracle.table, col),
                                          err_msg=col)
    else:
        np.testing.assert_allclose(carry.cm_hash, oracle.per_worker,
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(carry.idle, oracle.idle_time, rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(tbl.cm, oracle.table.cm, rtol=1e-3,
                                   atol=1e-6)
        np.testing.assert_array_equal(tbl.worker, oracle.table.worker)


PARTITIONS = {
    "whole": lambda e: [e],                     # single chunk == whole log
    "size1": lambda e: [1] * e,                 # size-1 chunks
    "mid_slice": lambda e: [7] * (e // 7 + 1),  # boundary mid-timeslice
    "ragged": lambda e: [3, 1, e],
    "one_cut": lambda e: [e // 2, e],
}


@pytest.mark.parametrize("partition", list(PARTITIONS))
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_deterministic_partitions_match_oracle(backend, partition):
    rng = np.random.default_rng(3)
    log = synthetic_log(rng, 5, 30)        # 300 events
    splits = PARTITIONS[partition](len(log))
    carry, tbl = _fold_partition(log, splits, backend)
    _assert_matches_oracle(log, carry, tbl, exact=backend == "numpy")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_workers_registered_mid_stream_match_oracle(backend):
    """Workers that first appear in a later chunk grow the carry's
    per-worker maps (``FoldCarry.ensure_workers``), as a live tracer's
    chunks do while workers register, without disturbing the state of the
    workers folded before them."""
    rng = np.random.default_rng(9)
    early = synthetic_log(rng, 2, 10)      # workers 0, 1
    late = synthetic_log(rng, 5, 10)       # then 0..4: 2, 3 and 4 join
    shift = int(early.times[-1]) + 1000
    cat = lambda a, b: np.concatenate([a, b])
    log = EventLog(cat(early.times, late.times + shift),
                   cat(early.workers, late.workers),
                   cat(early.deltas, late.deltas),
                   cat(early.tags, late.tags),
                   cat(early.stacks, late.stacks), 5)
    log.validate()
    carry = FoldCarry.init(1)
    parts = []
    for lo in range(0, len(log), 7):
        hi = min(lo + 7, len(log))
        part = log.chunk(lo, hi)
        seen = int(log.workers[:hi].max()) + 1      # registered so far
        part = EventLog(part.times, part.workers, part.deltas, part.tags,
                        part.stacks, seen)
        carry, tbl = fold_chunk(carry, part, backend=backend)
        parts.append(tbl)
    assert carry.num_workers == 5
    _assert_matches_oracle(log, carry, SliceTable.concat(parts),
                           exact=backend == "numpy")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_chunk_boundary_mid_timeslice(backend):
    """A cut between a worker's ACTIVATE and its DEACTIVATE exercises the
    carry's local_cm/slice_start/open maps explicitly."""
    from repro.core.events import ACTIVATE, DEACTIVATE, NO_STACK, NO_TAG
    ev = [(0, 0, ACTIVATE), (2, 1, ACTIVATE), (5, 1, DEACTIVATE),
          (9, 0, DEACTIVATE), (11, 0, ACTIVATE), (15, 0, DEACTIVATE)]
    t, w, d = zip(*ev)
    log = EventLog(
        times=(np.asarray(t, np.float64) * 1e9).astype(np.int64),
        workers=np.asarray(w, np.int32),
        deltas=np.asarray(d, np.int8),
        tags=np.full(len(ev), NO_TAG, np.int32),
        stacks=np.full(len(ev), NO_STACK, np.int32),
        num_workers=2)
    # cut inside w0's [0,9) slice and inside its [11,15) slice
    for splits in ([2, 2, 2], [1, 4, 1], [3, 2, 1]):
        carry, tbl = _fold_partition(log, splits, backend)
        _assert_matches_oracle(log, carry, tbl, exact=backend == "numpy")
        assert not carry.open.any()        # every slice closed at the end


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.integers(1, 25), st.integers(0, 10_000),
       st.integers(1, 60))
def test_random_partitions_match_oracle_all_backends(num_workers, slices,
                                                     seed, chunk):
    """Hypothesis property: for random logs and random chunk sizes, the
    chunked fold equals the whole-log numpy oracle on all four backends."""
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    e = len(log)
    splits = []
    lo = 0
    srng = np.random.default_rng(seed + 1)
    while lo < e:
        s = int(srng.integers(1, chunk + 1))
        splits.append(s)
        lo += s
    for backend in ALL_BACKENDS:
        carry, tbl = _fold_partition(log, splits, backend)
        _assert_matches_oracle(log, carry, tbl, exact=backend == "numpy")


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 6), st.integers(1, 20), st.integers(0, 10_000))
def test_carry_is_exactly_table1_state(num_workers, slices, seed):
    """Mid-stream, the carry equals the oracle's eBPF-map state recomputed
    on the prefix: global_cm, idle, cm_hash, thread_count, open mask."""
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    e = len(log)
    cut = max(1, e // 3)
    carry = FoldCarry.init(log.num_workers)
    carry, _ = fold_chunk(carry, log.chunk(0, cut), backend="numpy")
    prefix = log.chunk(0, cut)
    res = compute_numpy(prefix)
    np.testing.assert_array_equal(carry.cm_hash, res.per_worker)
    assert carry.idle == res.idle_time
    assert carry.thread_count == int(prefix.deltas.astype(np.int64).sum())
    open_expect = np.zeros(log.num_workers, bool)
    for wi, di in zip(prefix.workers, prefix.deltas):
        open_expect[wi] = di == 1
    np.testing.assert_array_equal(carry.open, open_expect)


def test_sanitize_chunked_equals_whole_log():
    """Chunk-wise sanitize with carried open state keeps exactly the events
    whole-log sanitize keeps, for any chunking of a dirty stream."""
    from repro.core.events import NO_STACK, NO_TAG
    rng = np.random.default_rng(5)
    e = 300
    t = np.sort(rng.integers(0, 10**7, e)).astype(np.int64)
    w = rng.integers(0, 4, e).astype(np.int32)
    d = rng.choice([1, -1], e).astype(np.int8)
    log = EventLog(t, w, d, np.full(e, NO_TAG, np.int32),
                   np.full(e, NO_STACK, np.int32), 4)
    whole = log.sanitize()
    for chunk in (1, 7, 64, e):
        active = np.zeros(4, bool)
        parts = []
        for lo in range(0, e, chunk):
            part, active, _ = sanitize_chunk(log.chunk(lo, lo + chunk),
                                             active)
            parts.append(part)
        times = np.concatenate([p.times for p in parts])
        deltas = np.concatenate([p.deltas for p in parts])
        workers = np.concatenate([p.workers for p in parts])
        np.testing.assert_array_equal(times, whole.times)
        np.testing.assert_array_equal(deltas, whole.deltas)
        np.testing.assert_array_equal(workers, whole.workers)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_detect_offline_streaming_equals_whole(backend):
    """detect_offline(chunk_events=...) == detect_offline on the same log:
    same ranking, same per-worker CMetrics, same critical count."""
    rng = np.random.default_rng(11)
    log = synthetic_log(rng, 8, 60, skew=np.r_[np.ones(7), 8.0])
    tags, stacks = TagRegistry(), StackRegistry()
    n_min = 4.0
    whole = detect_offline(log, tags, stacks, n_min, sample_dt_ns=None,
                           backend=backend)
    for chunk in (17, 128, len(log)):
        part = detect_offline(log, tags, stacks, n_min, sample_dt_ns=None,
                              backend=backend, chunk_events=chunk)
        rtol = 0 if backend == "numpy" else 1e-4
        np.testing.assert_allclose(part.per_worker, whole.per_worker,
                                   rtol=rtol, atol=1e-9)
        assert part.total_slices == whole.total_slices
        if backend == "numpy":
            # float64 chunk fold: the report is *identical*
            assert part.total_critical == whole.total_critical
            assert [p.stack for p in part.paths] == [p.stack
                                                     for p in whole.paths]
            assert part.idle_time == whole.idle_time
            assert part.total_time == whole.total_time
        else:
            # float32 backends: a slice sitting exactly on the n_min
            # threshold may flip under the different summation order
            assert abs(part.total_critical - whole.total_critical) <= 2


def test_detect_offline_streaming_sanitizes_dirty_logs():
    """The streaming path applies §3.2 tolerance chunk-wise: dirty streams
    produce the same report as the whole-log sanitize+compute route."""
    from repro.core.events import NO_STACK, NO_TAG
    rng = np.random.default_rng(7)
    e = 400
    t = np.sort(rng.integers(0, 10**8, e)).astype(np.int64)
    w = rng.integers(0, 5, e).astype(np.int32)
    d = rng.choice([1, -1], e).astype(np.int8)
    log = EventLog(t, w, d, np.full(e, NO_TAG, np.int32),
                   np.full(e, NO_STACK, np.int32), 5)
    tags, stacks = TagRegistry(), StackRegistry()
    whole = detect_offline(log, tags, stacks, 2.0, backend="numpy")
    part = detect_offline(log, tags, stacks, 2.0, backend="numpy",
                          chunk_events=37)
    np.testing.assert_array_equal(part.per_worker, whole.per_worker)
    assert part.total_critical == whole.total_critical
    assert part.total_slices == whole.total_slices


def test_empty_and_trivial_chunks():
    carry = FoldCarry.init(3)
    empty = EventLog(np.zeros(0, np.int64), np.zeros(0, np.int32),
                     np.zeros(0, np.int8), np.zeros(0, np.int32),
                     np.zeros(0, np.int32), 3)
    carry, tbl = fold_chunk(carry, empty, backend="numpy")
    assert len(tbl) == 0 and carry.events == 0
    # a single ACTIVATE: opens a slice, emits nothing
    one = EventLog(np.asarray([5], np.int64), np.asarray([1], np.int32),
                   np.asarray([1], np.int8), np.asarray([-1], np.int32),
                   np.asarray([-1], np.int32), 3)
    carry, tbl = fold_chunk(carry, one, backend="numpy")
    assert len(tbl) == 0
    assert carry.open[1] and carry.thread_count == 1
