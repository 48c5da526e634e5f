"""Detector post-processing: sample attachment, path merge, top-N ranking,
stack-top fallback, offline sampling replay."""
import pytest

from repro.core import (SampleBuffer, Tracer, detect, detect_offline,
                        simulate_samples)
from tests.test_tracer import FakeClock


def _bottleneck_trace(n_min=1.9, fold_backend="numpy"):
    """3 workers: w0/w1 parallel bursts, w2 long serial sections under two
    different call paths."""
    clk = FakeClock()
    tr = Tracer(n_min=n_min, clock=clk, fold_backend=fold_backend)
    w = [tr.register_worker(f"w{i}") for i in range(3)]
    for rep in range(8):
        tr.begin(w[0], "par")
        tr.begin(w[1], "par")
        clk.advance(2_000_000)
        tr.end(w[0])
        tr.end(w[1])
        tr.begin(w[2], "io_phase")
        tr.push(w[2], "flush" if rep % 2 else "compress")
        clk.advance(5_000_000)
        tr.pop(w[2])
        tr.end(w[2])
    return tr, clk, w


def test_merge_and_rank():
    """Slices sharing a call path merge: CMetrics summed, slices counted."""
    tr, clk, w = _bottleneck_trace()
    rep = detect(tr, None, top_n=5)
    assert rep.total_critical == 8
    # the inner flush/compress frames are popped before switch-out, so all 8
    # serial slices share the "io_phase" call path and merge into one entry
    # (the inner frames are what the sampling probe attributes — tested in
    # test_offline_pipeline_with_simulated_sampler)
    assert rep.path_str(rep.paths[0]) == "io_phase"
    assert rep.paths[0].slices == 8
    assert rep.paths[0].cmetric == pytest.approx(8 * 5e-3, rel=1e-6)


def test_distinct_paths_ranked_separately():
    """Different span tags produce separate ranked entries, ordered by
    cumulative CMetric."""
    clk = FakeClock()
    tr = Tracer(n_min=1.9, clock=clk)
    w = tr.register_worker("w")
    tr.register_worker("other")
    for rep in range(6):
        tr.begin(w, "slow_path")
        clk.advance(4_000_000)
        tr.end(w)
        tr.begin(w, "fast_path")
        clk.advance(1_000_000)
        tr.end(w)
    rep = detect(tr, None, top_n=5)
    assert rep.path_str(rep.paths[0]) == "slow_path"
    assert rep.path_str(rep.paths[1]) == "fast_path"
    assert rep.paths[0].cmetric == pytest.approx(4 * rep.paths[1].cmetric,
                                                 rel=1e-6)


def test_stack_top_fallback():
    """Critical slice with zero samples attaches the stack-top tag."""
    tr, clk, w = _bottleneck_trace()
    rep = detect(tr, None, top_n=5)           # no sampler at all
    top = rep.paths[0]
    assert sum(top.tag_counts.values()) == 0
    assert sum(top.stack_top_counts.values()) == top.slices


def test_sample_attachment_window():
    tr, clk, w = _bottleneck_trace()
    buf = SampleBuffer()
    # one sample inside w2's 3rd serial slice, one outside any slice
    crit = tr.critical[2]
    buf.append((crit.start_ns + crit.end_ns) // 2, crit.worker, 7)
    buf.append(crit.end_ns + 10, crit.worker, 9)
    rep = detect(tr, buf, top_n=5)
    counts = {}
    for p in rep.paths:
        for t, c in p.tag_counts.items():
            counts[t] = counts.get(t, 0) + c
    assert counts.get(7) == 1
    assert 9 not in counts


@pytest.mark.parametrize("backend,routed", [("numpy", False),
                                             ("pallas", True)])
def test_live_histogram_route_follows_fold_backend(monkeypatch, backend,
                                                   routed):
    """Where the kernels compile natively, a live tracer on the fused
    backend builds its tag tables with the Pallas ``tag_hist`` kernel."""
    from repro.kernels import ops
    tr, clk, w = _bottleneck_trace(fold_backend=backend)
    crit = tr.critical[2]                    # folds every pending event
    buf = SampleBuffer()
    buf.append((crit.start_ns + crit.end_ns) // 2, crit.worker, 7)
    calls = []
    hist = ops.tag_histogram

    def counted(*a, **k):
        calls.append(k["num_bins"])
        return hist(*a, **{**k, "interpret": True})
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(ops, "tag_histogram", counted)
    rep = detect(tr, buf, top_n=5)
    assert bool(calls) == routed
    assert rep.paths[0].tag_counts == {7: 1}


def test_offline_pipeline_with_simulated_sampler():
    tr, clk, w = _bottleneck_trace()
    log = tr.freeze()
    rep = detect_offline(log, tr.tags, tr.stacks, n_min=1.9,
                         sample_dt_ns=500_000, backend="pallas", top_n=5)
    assert rep.total_critical == 8
    top_names = [rep.path_str(p) for p in rep.paths[:2]]
    assert any("io_phase" in n for n in top_names)
    # sampled tags should hit the refined frames (flush/compress)
    top = rep.paths[0]
    assert sum(top.tag_counts.values()) > 0
    sampled = {rep.tag_name(t) for t in top.tag_counts}
    assert sampled & {"flush", "compress", "io_phase"}


def test_simulate_samples_only_below_nmin():
    tr, clk, w = _bottleneck_trace()
    log = tr.freeze()
    buf = simulate_samples(log, dt_ns=250_000, n_min=2)
    t, sw, tags = buf.frozen()
    # all samples must fall inside w2's solo sections (active count == 1)
    assert len(buf) > 0
    assert set(sw.tolist()) == {2}


def test_cr_and_totals():
    tr, clk, w = _bottleneck_trace()
    rep = detect(tr, None)
    assert rep.total_slices == 24
    assert rep.critical_ratio == pytest.approx(8 / 24)
    assert rep.total_time == pytest.approx(8 * 7e-3, rel=1e-6)
