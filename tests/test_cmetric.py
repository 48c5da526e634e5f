"""CMetric algorithm: paper Figure-1 hand example, backend equivalence,
hypothesis invariants."""
import numpy as np
import pytest

try:                                   # `python -m pytest` from the repo root
    from tests.conftest import given, settings, st
except ImportError:                    # plain `pytest` (tests/ on sys.path)
    from conftest import given, settings, st

from repro.core import EventLog, compute_numpy, compute, synthetic_log
from repro.core.cmetric import compute_pallas
from repro.core.events import ACTIVATE, DEACTIVATE, NO_STACK, NO_TAG


def make_log(events, num_workers):
    """events: list of (t_seconds, worker, delta)."""
    t, w, d = zip(*events)
    order = np.argsort(np.asarray(t, np.float64), kind="stable")
    e = len(events)
    return EventLog(
        times=(np.asarray(t, np.float64) * 1e9).astype(np.int64)[order],
        workers=np.asarray(w, np.int32)[order],
        deltas=np.asarray(d, np.int8)[order],
        tags=np.full(e, NO_TAG, np.int32),
        stacks=np.full(e, NO_STACK, np.int32),
        num_workers=num_workers,
    )


FIG1 = make_log([
    (0, 0, ACTIVATE), (2, 1, ACTIVATE), (4, 2, ACTIVATE),
    (8, 1, DEACTIVATE), (10, 0, DEACTIVATE), (12, 2, DEACTIVATE),
], num_workers=3)

# hand-computed: intervals [0,2)n1 [2,4)n2 [4,8)n3 [8,10)n2 [10,12)n1
FIG1_CM = np.array([2 + 1 + 4 / 3 + 1, 1 + 4 / 3, 4 / 3 + 1 + 2])


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_figure1_hand_example(backend):
    res = compute(FIG1, backend=backend)
    np.testing.assert_allclose(res.per_worker, FIG1_CM, rtol=1e-5)
    assert res.num_slices == 3
    assert res.idle_time == 0.0
    assert res.total_time == pytest.approx(12.0)
    # thread 0's slice spans [0,10): harmonic avg parallelism = 10/5.333
    i = list(res.slice_worker).index(0)
    assert res.slice_threads_av[i] == pytest.approx(10 / FIG1_CM[0])


def test_timeslice_records_match_paper_rule():
    # worker 1's slice [2,8) spans three switching intervals; its CMetric
    # must be global_cm(8) - global_cm(2) (the local_cm snapshot rule)
    res = compute_numpy(FIG1)
    i = list(res.slice_worker).index(1)
    assert res.slice_start[i] == pytest.approx(2.0)
    assert res.slice_end[i] == pytest.approx(8.0)
    assert res.slice_cm[i] == pytest.approx(1 + 4 / 3)


def test_idle_time_accounted():
    log = make_log([(0, 0, ACTIVATE), (1, 0, DEACTIVATE),
                    (3, 1, ACTIVATE), (4, 1, DEACTIVATE)], 2)
    res = compute_numpy(log)
    assert res.idle_time == pytest.approx(2.0)
    np.testing.assert_allclose(res.per_worker, [1.0, 1.0])


def test_straggler_dominates():
    rng = np.random.default_rng(7)
    skew = np.ones(16)
    skew[3] = 10.0
    log = synthetic_log(rng, 16, 80, skew=skew)
    res = compute_numpy(log)
    assert res.per_worker.argmax() == 3
    # the straggler's CMetric share must exceed its time share
    assert res.per_worker[3] / res.per_worker.sum() > 0.3


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.integers(1, 40), st.integers(0, 10_000))
def test_backends_agree(num_workers, slices, seed):
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    log.validate()
    r0 = compute_numpy(log)
    r = compute_pallas(log)
    np.testing.assert_allclose(r.per_worker, r0.per_worker,
                               rtol=1e-4, atol=1e-6)
    assert r.num_slices == r0.num_slices
    np.testing.assert_allclose(np.sort(r.slice_cm),
                               np.sort(r0.slice_cm), rtol=1e-3,
                               atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(1, 30), st.integers(0, 10_000))
def test_conservation_invariant(num_workers, slices, seed):
    """Σ_w CMetric(w) + idle == wall time (the CMetric partitions time)."""
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    res = compute_numpy(log)
    assert res.per_worker.sum() + res.idle_time == pytest.approx(
        res.total_time, rel=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 8), st.integers(1, 20), st.integers(0, 10_000))
def test_slice_bounds_invariant(num_workers, slices, seed):
    """Per-slice: dur/n_workers <= CMetric <= dur; threads_av in [1, W]."""
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    res = compute_numpy(log)
    dur = res.slice_end - res.slice_start
    assert np.all(res.slice_cm <= dur + 1e-9)
    assert np.all(res.slice_cm >= dur / num_workers - 1e-9)
    assert np.all(res.slice_threads_av >= 1 - 1e-9)
    assert np.all(res.slice_threads_av <= num_workers + 1e-9)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(1, 20), st.integers(0, 10_000))
def test_worker_relabel_equivariance(num_workers, slices, seed):
    rng = np.random.default_rng(seed)
    log = synthetic_log(rng, num_workers, slices)
    perm = np.random.default_rng(seed + 1).permutation(num_workers)
    relabeled = EventLog(log.times, perm[log.workers].astype(np.int32),
                         log.deltas, log.tags, log.stacks, num_workers)
    a = compute_numpy(log).per_worker
    b = compute_numpy(relabeled).per_worker
    np.testing.assert_allclose(b[perm], a, rtol=1e-9)


def test_empty_and_single_event():
    empty = make_log([], 2) if False else EventLog(
        times=np.zeros(0, np.int64), workers=np.zeros(0, np.int32),
        deltas=np.zeros(0, np.int8), tags=np.zeros(0, np.int32),
        stacks=np.zeros(0, np.int32), num_workers=2)
    for backend in ("numpy", "pallas"):
        r = compute(empty, backend=backend)
        assert r.num_slices == 0 and r.per_worker.sum() == 0
