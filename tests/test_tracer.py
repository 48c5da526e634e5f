"""Tracer online state == offline recompute; span/ingest semantics."""
import numpy as np
import pytest

from repro.core import (ACTIVATE, DEACTIVATE, ProfileSession, Tracer,
                        compute_numpy)


class FakeClock:
    """Deterministic ns clock for tracer tests."""

    def __init__(self):
        self.t = 0

    def advance(self, ns):
        self.t += int(ns)

    def __call__(self):
        return self.t


def test_online_matches_offline():
    clk = FakeClock()
    tr = Tracer(n_min=1.5, clock=clk)
    w = [tr.register_worker(f"w{i}") for i in range(3)]
    # deterministic schedule
    for rep in range(5):
        for wid in w:
            tr.begin(wid, "work")
            clk.advance(1000)
        for wid in w:
            tr.end(wid)
            clk.advance(500)
        tr.begin(w[0], "solo")
        clk.advance(3000)
        tr.end(w[0])
    log = tr.freeze()
    log.validate()
    res = compute_numpy(log)
    np.testing.assert_allclose(res.per_worker, tr.per_worker_cm(), rtol=1e-9)
    assert res.idle_time == pytest.approx(tr.idle_time)
    # online critical slices == offline threshold application
    offline_crit = int(np.sum(res.critical_mask(1.5)))
    assert offline_crit == len(tr.critical)


def test_critical_capture_only_when_low_parallelism():
    clk = FakeClock()
    tr = Tracer(n_min=2, clock=clk)
    a = tr.register_worker("a")
    b = tr.register_worker("b")
    tr.begin(a, "par")
    tr.begin(b, "par")
    clk.advance(10_000)
    tr.end(a)
    tr.end(b)          # parallel work: threads_av == 2 -> not critical
    tr.begin(a, "serial")
    clk.advance(10_000)
    tr.end(a)          # alone -> critical
    assert len(tr.critical) == 1
    path = tr.stacks.paths[tr.critical[0].stack_id]
    assert tr.tags.names[path[-1]] == "serial"


def test_nested_frames_in_call_path():
    clk = FakeClock()
    tr = Tracer(n_min=10, clock=clk)
    w = tr.register_worker("w")
    tr.begin(w, "train_step")
    with tr.frame(w, "layer_3"):
        with tr.frame(w, "moe_dispatch"):
            clk.advance(1000)
    clk.advance(10)
    tr.end(w)
    # the DEACTIVATE recorded the stack as it was at end: only train_step
    # remains after frames popped; push/pop refine *during* the span, so the
    # critical path uses the stack captured at end()
    assert len(tr.critical) == 1
    # now capture with frames still open
    tr.begin(w, "train_step")
    tr.push(w, "layer_4")
    clk.advance(1000)
    tr.end(w)          # layer_4 on stack at capture
    names = [tr.tags.names[t] for t in
             tr.stacks.paths[tr.critical[-1].stack_id]]
    assert names == ["train_step", "layer_4"]


def test_ingest_external_trace():
    tr = Tracer(n_min=2)
    w = [tr.register_worker(f"h{i}", "host") for i in range(4)]
    # host 2 is a straggler: 3x longer steps
    t = 0
    for step in range(10):
        for h in w:
            tr.ingest(t, h, ACTIVATE, "step")
        t += 1_000_000
        for h in w[:3] + []:
            pass
        for h in (0, 1, 3):
            tr.ingest(t, w[h], DEACTIVATE)
        t += 2_000_000
        tr.ingest(t, w[2], DEACTIVATE)
    cm = tr.per_worker_cm()
    assert cm.argmax() == 2
    assert cm[2] > 2 * cm[0]


def test_ring_overflow_counted():
    # autoflush=False: a full shard drops new events (counted, BPF ringbuf
    # semantics) instead of draining itself through the fold
    tr = Tracer(capacity=8, autoflush=False)
    w = tr.register_worker("w")
    for i in range(10):
        tr.begin(w, "x")
        tr.end(w)
    assert tr.ring.dropped == 12
    assert tr.ring.dropped_per_shard() == [12]
    # the surviving prefix still freezes to a valid log
    log = tr.freeze()
    assert len(log) == 8
    log.validate()


def test_autoflush_drains_instead_of_dropping():
    clk = FakeClock()
    tr = Tracer(n_min=0.0, capacity=8, clock=clk)
    w = tr.register_worker("w")
    for i in range(50):
        tr.begin(w, "x")
        clk.advance(1000)
        tr.end(w)
        clk.advance(100)
    assert tr.ring.dropped == 0
    log = tr.freeze()
    assert len(log) == 100
    log.validate()
    res = compute_numpy(log)
    np.testing.assert_array_equal(res.per_worker, tr.per_worker_cm())


def test_gapp_facade_live(tmp_path):
    import time
    g = ProfileSession(n_min=None, dt=0.001)
    ws = [g.register_worker(f"t{i}") for i in range(4)]
    with g.running():
        for _ in range(3):
            for w in ws[:3]:
                g.begin(w, "parallel")
            time.sleep(0.003)
            for w in ws[:3]:
                g.end(w)
            g.begin(ws[3], "bottleneck")
            time.sleep(0.006)
            g.end(ws[3])
    rep = g.snapshot()
    assert rep.paths, "no critical paths found"
    assert "bottleneck" in rep.path_str(rep.paths[0])
    assert rep.per_worker.argmax() == 3
    # offline recompute from the ring agrees
    log = g.freeze()
    res = compute_numpy(log)
    np.testing.assert_allclose(res.per_worker, rep.per_worker, rtol=1e-6)
