"""Trace reduction on a small trace recorded on a TPU v5e
(``testdata/window.xplane.pb``, made by ``testdata/record.py``)."""
from pathlib import Path

import pytest

import trace_reduce

TRACE = Path(__file__).resolve().parent / "testdata" / "window.xplane.pb"
SPANS = {"replay", "replay/chunked", "train/loader_wait", "detect"}


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE, SPANS)


@pytest.fixture(scope="module")
def device_ops():
    """(start, end, name) of every op on the chip inside the window,
    read straight from the trace."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(TRACE)).planes)
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events if e.name == "window"]
    assert len(window) == 1
    w0, w1 = window[0]
    dev = [p for p in planes if p.name == "/device:TPU:0"]
    assert len(dev) == 1
    ops = [ln for ln in dev[0].lines if ln.name == "XLA Ops"][0]
    return w0, w1, [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1),
                     e.name) for e in ops.events
                    if e.start_ns + e.duration_ns > w0 and e.start_ns < w1]


def test_busy_is_the_union_of_device_intervals(summary, device_ops):
    w0, w1, ops = device_ops
    # union by a sweep over every nanosecond boundary
    edges = sorted({t for s, e, _ in ops for t in (s, e)})
    covered = sum(b - a for a, b in zip(edges, edges[1:])
                  if any(s <= a and b <= e for s, e, _ in ops))
    assert summary.busy_s == pytest.approx(covered * 1e-9, rel=1e-9)
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert 0 < summary.busy_s < summary.window_s
    assert sum(summary.idle_by_span.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)


def test_idle_gaps_go_to_the_enclosing_span(summary):
    # record.py sleeps 50 ms on the host inside train/loader_wait
    assert summary.idle_by_span["train/loader_wait"] >= 0.045
    assert summary.idle_by_span["train/loader_wait"] < 0.1
    assert set(summary.idle_by_span) <= SPANS | {trace_reduce.NO_SPAN}


def test_kernel_names_are_the_ones_the_readers_look_up(summary):
    for kernel in ("fold", "carry_cumsum", "hist"):
        assert summary.op_s.get(kernel, 0) > 0, sorted(summary.op_s)
    assert summary.module_s["jit__fused_pipeline"] > 0
    assert summary.op_calls["carry_cumsum"] == \
        summary.module_calls["jit_carry_cumsum"]
    assert summary.op_in_module_s[("jit__fused_pipeline", "fold.1")] > 0
    names = [n for n, _ in summary.breakdown()["device_ops"]]
    assert len(names) <= 10 and all("/" in n for n in names)


def test_short_names():
    assert trace_reduce.op_short_name(
        "%fold.1 = (s32[1,128]{1,0}) custom-call(f32[1,128]{1,0} %x)") \
        == "fold"
    assert trace_reduce.op_short_name("%carry_cumsum.12 = f32[] x") \
        == "carry_cumsum"
    assert trace_reduce.module_short_name(
        "jit__fused_pipeline(8484706676271650758)") == "jit__fused_pipeline"


def test_union_length_merges_overlaps():
    total, merged = trace_reduce.union_length([(5, 7), (0, 2), (1, 3),
                                               (3, 4)])
    assert total == 6 and merged == [(0, 4), (5, 7)]
