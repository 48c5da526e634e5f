"""Host spans (repro.obs.spans): aggregates from many threads, records only
while a JAX trace runs, the span names on the trace's host plane on its
clock, and the Trainer's spans leaving the profiling session's log as it
was.  CPU only."""
import collections
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.core import ProfileSession
from repro.core.events import ACTIVATE, DEACTIVATE
from repro.core.sampler import SamplingProbe
from repro.core.tracer import Tracer
from repro.obs import spans
from repro.optim import adamw
from repro.train import trainer as trainer_mod
from tests.test_tracer import FakeClock

TRAIN_SPANS = ("train/loader_wait", "train/h2d", "train/step", "train/host")


@pytest.fixture
def log(monkeypatch):
    """A fresh process span log for the test."""
    fresh = spans.SpanLog()
    monkeypatch.setattr(spans, "_LOG", fresh)
    return fresh


def mine(log) -> list:
    """Records of this thread (a daemon thread another test left running
    may record spans of its own while a trace runs)."""
    return [r for r in log.records() if r[1] == threading.get_ident()]


def host_events(trace_dir) -> dict[str, list[tuple[float, float]]]:
    """name -> [(start_ns, end_ns)] of every event on a ``/host:`` plane
    of the trace written under ``trace_dir``."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out[ev.name].append((ev.start_ns, ev.end_ns))
    return out


def test_aggregates_from_many_threads(log):
    threads, per = 8, 300
    durations = [[(t * per + i) % 97 + 1 for i in range(per)]
                 for t in range(threads)]

    def work(ds):
        for d in ds:
            log.add("x", 1000, 1000 + d, traced=False)
            with spans.span("y"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=work, args=(ds,)) for ds in durations]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    st = log.stats()
    assert st["x"]["count"] == threads * per
    assert st["x"]["seconds_sum"] == pytest.approx(
        1e-9 * sum(map(sum, durations)), rel=1e-12)
    assert st["x"]["seconds_max"] == pytest.approx(97e-9)
    assert st["y"]["count"] == threads * per
    assert 0 < st["y"]["seconds_max"] <= st["y"]["seconds_sum"]
    assert st["records_dropped"] == 0 and log.records() == []


def test_records_only_while_a_trace_runs(log, tmp_path):
    with spans.span("before"):
        pass
    assert log.records() == []
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.perf_counter_ns()
        with spans.span("inside"):
            time.sleep(0.001)
    with spans.span("after"):
        pass
    recs = mine(log)
    assert [r[0] for r in recs] == ["inside"]
    name, tid, start, end = recs[0]
    assert tid == threading.get_ident()
    assert t0 <= start and end - start >= 1_000_000
    assert log.records(since_ns=end) == []
    assert {"before", "inside", "after"} <= set(log.stats())


def test_span_names_on_the_host_plane_on_the_trace_clock(log, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("first"):
            time.sleep(0.002)
        time.sleep(0.005)
        with spans.span("second"):
            time.sleep(0.003)
    events = host_events(tmp_path)
    recs = {r[0]: r for r in mine(log)}
    assert set(recs) == {"first", "second"}
    # one offset maps both records onto the trace: the same clock
    offsets = []
    for name in ("first", "second"):
        (start, end), = events[name]
        _, _, r0, r1 = recs[name]
        offsets.append(r0 - start)
        assert abs((r1 - r0) - (end - start)) < 100_000
    assert abs(offsets[0] - offsets[1]) < 100_000


def test_a_span_cut_by_the_trace_is_not_recorded(log, tmp_path):
    outer = spans.span("cut_by_start")
    outer.__enter__()
    jax.profiler.start_trace(str(tmp_path))
    try:
        outer.__exit__(None, None, None)
        with spans.span("whole"):
            pass
        cut = spans.span("cut_by_stop")
        cut.__enter__()
    finally:
        jax.profiler.stop_trace()
    cut.__exit__(None, None, None)
    assert [r[0] for r in mine(log)] == ["whole"]
    assert log.stats()["cut_by_stop"]["count"] == 1


def test_records_past_the_capacity_are_counted_as_dropped(log):
    for i in range(spans.CAPACITY + 3):
        log.add("r", i, i + 1, traced=True)
    recs = log.records()
    # the newest overwrite the oldest, so a later trace still records
    assert len(recs) == spans.CAPACITY
    assert [r[2] for r in recs] == list(range(3, spans.CAPACITY + 3))
    assert [r[2] for r in log.records(since_ns=spans.CAPACITY + 1)] == [
        spans.CAPACITY + 1, spans.CAPACITY + 2]
    st = log.stats()
    assert st["records_dropped"] == 3
    assert st["r"]["count"] == spans.CAPACITY + 3


class CountingSource:
    """The Trainer's batch source, counting the batches drawn."""

    def __init__(self, source):
        self.source, self.drawn = source, 0

    def next_batch(self):
        self.drawn += 1
        return self.source.next_batch()


def run_trainer(tmp_path, steps: int, monkeypatch):
    """A few steps of the Trainer with a trivial step, profiled; returns
    per worker (switch-ins, switch-outs, tags) with the loader's counts
    taken against the batches its source drew."""
    cfg = configs.get_tiny("deepseek-7b")
    tcfg = trainer_mod.TrainerConfig(
        steps=steps, ckpt_every=1 << 30, ckpt_dir=str(tmp_path / "ckpt"),
        batch_per_host=2, seq_len=16, log_every=1 << 30)
    session = ProfileSession(dt=0.002)

    def step_fn(params, opt_state, batch, err):
        return params, opt_state, {"loss": jnp.sum(batch["tokens"])}, err

    sources = []
    program_source = trainer_mod.SyntheticLM
    with monkeypatch.context() as m:
        m.setattr(trainer_mod, "SyntheticLM", lambda *a, **k: sources.append(
            CountingSource(program_source(*a, **k))) or sources[-1])
        tr = trainer_mod.Trainer(cfg, adamw.AdamWConfig(), tcfg,
                                 gapp=session, step_fn=step_fn)
    tr.init_state = lambda key=None: ({"w": jnp.zeros(4)},
                                      {"m": jnp.zeros(4)})
    tr.run()
    assert not tr.loader._thread.is_alive()
    session.result()
    f = session.freeze()
    out = {}
    for wid, name in enumerate(session.tracer.worker_names()):
        mine = f.workers == wid
        ins = int((f.deltas[mine] == ACTIVATE).sum())
        outs = int((f.deltas[mine] == DEACTIVATE).sum())
        if name == "data_loader":
            ins, outs = ins - sources[0].drawn, outs - sources[0].drawn
        tags = {session.tags.names[t] for t in
                f.tags[mine & (f.deltas == ACTIVATE)]}
        out[name] = (ins, outs, tags)
    return out


class NoSpan:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_trainer_spans_once_per_step_and_the_session_log_unchanged(
        log, tmp_path, monkeypatch):
    steps = 4
    with jax.profiler.trace(str(tmp_path / "trace")):
        with_spans = run_trainer(tmp_path / "a", steps, monkeypatch)
    counts = collections.Counter(r[0] for r in mine(log))
    assert {n: counts[n] for n in TRAIN_SPANS} == dict.fromkeys(
        TRAIN_SPANS, steps)
    assert counts["profiler/merge"] >= 1
    assert not {r[0] for r in log.records() if r not in mine(log)} \
        & set(TRAIN_SPANS)
    assert "data/generate" in log.stats()
    events = host_events(tmp_path / "trace")
    assert all(len(events[n]) == steps for n in TRAIN_SPANS)

    monkeypatch.setattr(spans, "span", NoSpan)
    without = run_trainer(tmp_path / "b", steps, monkeypatch)
    assert with_spans == without
    assert with_spans["trainer"][:2] == (steps, steps)
    assert with_spans["data_loader"][:2] == (0, 0)


@pytest.mark.parametrize("backend", ["numpy", "vector", "pallas"])
def test_profiler_spans_at_the_drain_and_the_sampler(log, tmp_path, backend):
    clk = FakeClock()
    tr = Tracer(n_min=2, clock=clk, fold_backend=backend)
    a, b = tr.register_worker("a"), tr.register_worker("b")
    probe = SamplingProbe(tr, n_min=2)
    with jax.profiler.trace(str(tmp_path)):
        tr.begin(a, "x")
        tr.begin(b, "y")
        assert probe.tick() == 0          # two active: stops at the n_min test
        clk.advance(1000)
        tr.end(b)
        assert probe.tick() == 1          # one active: samples it
        clk.advance(1000)
        tr.end(a)
        tr.sync()
    recs = mine(log)
    counts = collections.Counter(r[0] for r in recs)
    on_device = backend != "numpy"      # its prefix is read back from it
    assert counts == {"profiler/sample": 1, "profiler/drain": 1,
                      "profiler/merge": 1, "profiler/fold": 1,
                      "profiler/intern": 1,
                      **({"profiler/fold_wait": 1} if on_device else {})}
    if on_device:
        (_, _, f0, f1), = [r for r in recs if r[0] == "profiler/fold"]
        (_, _, w0, w1), = [r for r in recs if r[0] == "profiler/fold_wait"]
        assert f0 <= w0 <= w1 <= f1
    assert len(tr.critical) >= 1
