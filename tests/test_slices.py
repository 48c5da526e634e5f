"""Columnar slice-table IR: round-trips, growable buffer, vectorized merge
vs the retained Python-loop oracle, threads_av fallback consistency, and
adversarial event streams on both fold paths."""
import numpy as np
import pytest

from repro.core import (ACTIVATE, DEACTIVATE, CriticalBuffer, CriticalSlice,
                        EventLog, SliceTable, Tracer, compute, compute_numpy,
                        detect_offline, merge_table, simulate_samples)
from repro.core import detector as detector_lib
from repro.core.events import NO_STACK, NO_TAG

try:                                   # `python -m pytest` from the repo root
    from tests.test_tracer import FakeClock
except ImportError:                    # plain `pytest` (tests/ on sys.path)
    from test_tracer import FakeClock

BACKENDS = ("numpy", "pallas")


def _mklog(events, num_workers):
    """events: list of (t_ns, worker, delta)."""
    e = len(events)
    t = np.asarray([ev[0] for ev in events], np.int64)
    w = np.asarray([ev[1] for ev in events], np.int32)
    d = np.asarray([ev[2] for ev in events], np.int8)
    order = np.argsort(t, kind="stable")
    return EventLog(t[order], w[order], d[order],
                    np.full(e, NO_TAG, np.int32),
                    np.full(e, NO_STACK, np.int32), num_workers)


def _random_workload(seed, workers=4, steps=40):
    """Traced workload with varying parallelism, tags and refined frames."""
    rng = np.random.default_rng(seed)
    clk = FakeClock()
    tr = Tracer(n_min=workers - 0.5, clock=clk)
    wids = [tr.register_worker(f"w{i}") for i in range(workers)]
    tags = ["alpha", "beta", "gamma", "delta"]
    for _ in range(steps):
        active = rng.choice(wids, size=int(rng.integers(1, workers + 1)),
                            replace=False)
        for wid in active:
            tr.begin(int(wid), str(rng.choice(tags)))
            if rng.random() < 0.3:
                tr.push(int(wid), "inner")
        clk.advance(int(rng.integers(10_000, 1_000_000)))
        for wid in active:
            tr.end(int(wid))
        clk.advance(int(rng.integers(1_000, 100_000)))
    return tr


# ---------------------------------------------------------------------------
# table / buffer mechanics
# ---------------------------------------------------------------------------

def test_table_record_roundtrip():
    rows = [CriticalSlice(1, 10, 20, 1e-6, 1.5, 0, 2),
            CriticalSlice(0, 15, 40, 2e-6, 1.1, -1, 1)]
    t = SliceTable.from_records(rows)
    assert len(t) == 2
    t.validate()
    assert t.to_records() == rows
    assert t[1] == rows[1]
    assert list(t) == rows


def test_table_filter_and_critical():
    t = SliceTable.from_arrays([0, 1, 2], [0, 10, 20], [5, 15, 25],
                               [1e-6, 2e-6, 3e-6], [1.0, 2.0, 3.0],
                               [0, 1, 2], [1, 2, 3])
    crit = t.critical(2.5)
    assert len(crit) == 2
    assert crit.n_min == 2.5
    np.testing.assert_array_equal(crit.worker, [0, 1])
    sub = t[t.worker >= 1]
    assert len(sub) == 2
    np.testing.assert_array_equal(sub.start_ns, [10, 20])
    assert len(SliceTable.empty()) == 0
    assert len(SliceTable.concat([t, sub])) == 5


def test_critical_buffer_grows_and_indexes():
    buf = CriticalBuffer(capacity=2)
    for i in range(100):
        buf.append(i % 3, i * 10, i * 10 + 5, i * 1e-9, 1.0 + i, i, 1)
    assert len(buf) == 100
    assert buf[0].start_ns == 0
    assert buf[-1].start_ns == 990
    assert buf[7].threads_av == pytest.approx(8.0)
    with pytest.raises(IndexError):
        buf[100]
    t = buf.table()
    assert len(t) == 100
    np.testing.assert_array_equal(t.worker, np.arange(100) % 3)


def test_tracer_critical_is_columnar():
    tr = _random_workload(3)
    assert isinstance(tr.critical, CriticalBuffer)
    t = tr.critical.table()
    assert len(t) == len(tr.critical)
    if len(t):
        assert t[0] == tr.critical[0]


# ---------------------------------------------------------------------------
# vectorized merge == Python-loop oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_merge_table_matches_python_oracle(backend):
    tr = _random_workload(0)
    log = tr.freeze()
    n_min = tr._resolved_n_min()
    samples = simulate_samples(log, 50_000, n_min)
    res = compute(log, backend=backend)
    crit = res.critical_table(n_min)
    assert len(crit) > 0
    profiles, attached = merge_table(crit, samples, tr.stacks, n_min)
    oracle, attached_o = detector_lib._merge_python(
        crit.to_records(), samples, tr.stacks, n_min)
    assert attached == attached_o > 0
    assert [p.stack for p in profiles] == list(oracle.keys())
    for p in profiles:
        o = oracle[p.stack]
        assert p.slices == o.slices
        assert p.cmetric == pytest.approx(o.cmetric, rel=1e-9, abs=1e-15)
        assert p.tag_counts == o.tag_counts
        assert p.stack_top_counts == o.stack_top_counts


def test_merge_table_no_samples_stack_top_fallback():
    tr = _random_workload(5)
    log = tr.freeze()
    n_min = tr._resolved_n_min()
    crit = compute_numpy(log).critical_table(n_min)
    profiles, attached = merge_table(crit, None, tr.stacks, n_min)
    oracle, _ = detector_lib._merge_python(crit.to_records(), None,
                                           tr.stacks, n_min)
    assert attached == 0
    for p in profiles:
        assert p.stack_top_counts == oracle[p.stack].stack_top_counts
        assert sum(p.tag_counts.values()) == 0


def test_merge_table_boundary_sample_matches_oracle():
    """A sample exactly on a shared slice boundary (end of one slice ==
    start of the next, same worker) attaches to BOTH slices in the per-slice
    oracle's inclusive [start, end] check — the vectorized attachment must
    reproduce that, including zero-duration slices stacked on the same ns."""
    from repro.core import SampleBuffer, StackRegistry
    stacks = StackRegistry()
    a = stacks.intern((1,))
    b = stacks.intern((2,))
    table = SliceTable.from_arrays(
        worker=[0, 0, 0, 1], start_ns=[100, 200, 200, 150],
        end_ns=[200, 200, 300, 250], cm=[1e-6, 0.0, 2e-6, 1e-6],
        threads_av=[1.0, 1.0, 1.0, 1.0], stack_id=[a, b, a, b],
        n_at_exit=[1, 1, 1, 1])
    buf = SampleBuffer()
    buf.append(200, 0, 7)      # on the triple boundary: slices 0, 1 and 2
    buf.append(250, 1, 8)      # on worker 1's slice end
    buf.append(99, 0, 9)       # before any slice: unattached
    profiles, attached = merge_table(table, buf, stacks, n_min=2.0)
    oracle, attached_o = detector_lib._merge_python(table.to_records(), buf,
                                                    stacks, n_min=2.0)
    assert attached == attached_o == 4
    for p in profiles:
        o = oracle[p.stack]
        assert p.tag_counts == o.tag_counts
        assert p.stack_top_counts == o.stack_top_counts


def test_merge_table_pallas_hist_matches_bincount():
    tr = _random_workload(7)
    log = tr.freeze()
    n_min = tr._resolved_n_min()
    samples = simulate_samples(log, 50_000, n_min)
    crit = compute_numpy(log).critical_table(n_min)
    a, _ = merge_table(crit, samples, tr.stacks, n_min, use_pallas_hist=False)
    b, _ = merge_table(crit, samples, tr.stacks, n_min, use_pallas_hist=True)
    assert [p.stack for p in a] == [p.stack for p in b]
    for pa, pb in zip(a, b):
        assert pa.tag_counts == pb.tag_counts


def test_reports_equivalent_across_backends():
    tr = _random_workload(1)
    log = tr.freeze()
    n_min = tr._resolved_n_min()
    reports = {b: detect_offline(log, tr.tags, tr.stacks, n_min,
                                 sample_dt_ns=50_000, backend=b)
               for b in BACKENDS}
    r0 = reports["numpy"]
    assert r0.paths
    for b, r in reports.items():
        np.testing.assert_allclose(r.per_worker, r0.per_worker, rtol=1e-4,
                                   atol=1e-6, err_msg=b)
        assert r.total_critical == r0.total_critical, b
        assert r.total_slices == r0.total_slices, b
        assert [r.path_str(p) for p in r.paths] == \
            [r0.path_str(p) for p in r0.paths], b
        for p, p0 in zip(r.paths, r0.paths):
            assert p.cmetric == pytest.approx(p0.cmetric, rel=1e-3,
                                              abs=1e-9), b
            assert p.slices == p0.slices, b


# ---------------------------------------------------------------------------
# threads_av fallback: zero-CMetric slices (regression — vector/pallas used
# to hardcode 1.0 while the numpy oracle used the exit-time active count)
# ---------------------------------------------------------------------------

def test_threads_av_zero_cm_fallback_consistent():
    # w1 runs a zero-duration slice while w0 is active: slice_cm == 0, and
    # the active count at w1's exit is 2 (itself + w0)
    log = _mklog([(0, 0, ACTIVATE), (5_000_000, 1, ACTIVATE),
                  (5_000_000, 1, DEACTIVATE), (10_000_000, 0, DEACTIVATE)], 2)
    vals = {}
    for b in BACKENDS:
        res = compute(log, backend=b)
        i = list(res.slice_worker).index(1)
        assert res.slice_cm[i] == pytest.approx(0.0, abs=1e-12)
        vals[b] = float(res.slice_threads_av[i])
        assert res.table.n_at_exit[i] == 2
    assert all(v == pytest.approx(2.0) for v in vals.values()), vals
    # the slice must be equally (non-)critical under every backend
    for n_min in (1.5, 2.5):
        crits = {b: int(np.sum(compute(log, backend=b).critical_mask(n_min)))
                 for b in BACKENDS}
        assert len(set(crits.values())) == 1, (n_min, crits)


# ---------------------------------------------------------------------------
# adversarial event streams (paper §3.2 tolerance), both fold paths
# ---------------------------------------------------------------------------

def _dirty_logs():
    ms = 1_000_000
    return {
        "double_activate": _mklog(
            [(0, 0, ACTIVATE), (1 * ms, 0, ACTIVATE), (2 * ms, 1, ACTIVATE),
             (3 * ms, 0, DEACTIVATE), (4 * ms, 1, DEACTIVATE)], 2),
        "unmatched_deactivate": _mklog(
            [(0, 0, DEACTIVATE), (1 * ms, 0, ACTIVATE), (2 * ms, 1, ACTIVATE),
             (3 * ms, 1, DEACTIVATE), (4 * ms, 1, DEACTIVATE),
             (5 * ms, 0, DEACTIVATE)], 2),
        "trailing_open": _mklog(
            [(0, 0, ACTIVATE), (1 * ms, 0, DEACTIVATE),
             (2 * ms, 1, ACTIVATE)], 2),
    }


def test_sanitize_matches_live_tracer_tolerance():
    for name, log in _dirty_logs().items():
        clean = log.sanitize()
        clean.validate()          # alternation restored
        # the live probe body applied to the same stream keeps the same
        # events: per-worker CMetrics agree exactly
        tr = Tracer(n_min=0.0)    # n_min 0: no critical capture needed
        for _ in range(log.num_workers):
            tr.register_worker("w")
        for t, w, d in zip(log.times, log.workers, log.deltas):
            tr.ingest(int(t), int(w), int(d))
        res = compute_numpy(clean)
        np.testing.assert_allclose(res.per_worker, tr.per_worker_cm(),
                                   rtol=1e-9, err_msg=name)
        assert len(tr.freeze()) == len(clean), name


def test_sanitize_vectorized_matches_tracer_on_random_dirty_logs():
    """Fuzz the greedy-filter equivalence: the vectorised run-collapse must
    keep exactly the events the live probe body would have recorded."""
    rng = np.random.default_rng(11)
    for _ in range(5):
        e = 200
        t = np.sort(rng.integers(0, 10**7, e)).astype(np.int64)
        w = rng.integers(0, 5, e).astype(np.int32)
        d = rng.choice([1, -1], e).astype(np.int8)
        log = _mklog(list(zip(t.tolist(), w.tolist(), d.tolist())), 5)
        clean = log.sanitize()
        clean.validate()
        tr = Tracer(n_min=0.0)
        for _ in range(5):
            tr.register_worker("w")
        for ti, wi, di in zip(log.times, log.workers, log.deltas):
            tr.ingest(int(ti), int(wi), int(di))
        frozen = tr.freeze()
        assert len(frozen) == len(clean)
        np.testing.assert_array_equal(frozen.times, clean.times)
        np.testing.assert_array_equal(frozen.workers, clean.workers)
        np.testing.assert_array_equal(frozen.deltas, clean.deltas)
        res = compute_numpy(clean)
        np.testing.assert_allclose(res.per_worker, tr.per_worker_cm(),
                                   rtol=1e-9)


def test_sanitize_noop_on_clean_log():
    tr = _random_workload(2)
    log = tr.freeze()
    assert log.is_well_formed()
    assert log.sanitize() is log


@pytest.mark.parametrize("case", ["double_activate", "unmatched_deactivate",
                                  "trailing_open"])
def test_adversarial_streams_agree_across_backends(case):
    log = _dirty_logs()[case]
    from repro.core.tracer import StackRegistry, TagRegistry
    reports = {b: detect_offline(log, TagRegistry(), StackRegistry(),
                                 n_min=1.5, backend=b) for b in BACKENDS}
    r0 = reports["numpy"]
    for b, r in reports.items():
        np.testing.assert_allclose(r.per_worker, r0.per_worker, rtol=1e-4,
                                   atol=1e-9, err_msg=(case, b))
        assert r.total_slices == r0.total_slices, (case, b)
        assert r.total_critical == r0.total_critical, (case, b)


def test_gapp_offline_report_cross_validates_live():
    from repro.core import ProfileSession
    clk = FakeClock()
    g = ProfileSession(n_min=1.9, clock=clk)
    ws = [g.register_worker(f"t{i}") for i in range(3)]
    for _ in range(6):
        for w in ws[:2]:
            g.begin(w, "parallel")
        clk.advance(2_000_000)
        for w in ws[:2]:
            g.end(w)
        g.begin(ws[2], "serial")
        clk.advance(5_000_000)
        g.end(ws[2])
    live = g.snapshot()
    for backend in ("numpy", "pallas"):
        off = g.offline_report(backend=backend)
        np.testing.assert_allclose(off.per_worker, live.per_worker,
                                   rtol=1e-4, atol=1e-9)
        assert off.total_critical == live.total_critical
        assert [off.path_str(p) for p in off.paths] == \
            [live.path_str(p) for p in live.paths]


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_log_all_backends(backend):
    empty = _mklog([], 3)
    res = compute(empty, backend=backend)
    assert res.num_slices == 0
    assert res.per_worker.shape == (3,)
    assert res.per_worker.sum() == 0.0
    from repro.core.tracer import StackRegistry, TagRegistry
    rep = detect_offline(empty, TagRegistry(), StackRegistry(), n_min=1.5,
                         backend=backend)
    assert rep.paths == [] and rep.total_critical == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_single_worker_log_all_backends(backend):
    ms = 1_000_000
    log = _mklog([(0, 0, ACTIVATE), (2 * ms, 0, DEACTIVATE),
                  (3 * ms, 0, ACTIVATE), (7 * ms, 0, DEACTIVATE)], 1)
    res = compute(log, backend=backend)
    assert res.num_slices == 2
    # a lone worker owns all elapsed busy time
    assert res.per_worker[0] == pytest.approx(6e-3, rel=1e-5)
    np.testing.assert_allclose(res.slice_threads_av, [1.0, 1.0], rtol=1e-5)
