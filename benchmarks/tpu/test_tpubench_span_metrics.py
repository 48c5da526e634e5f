"""The readers of the program's host spans, on a synthetic run with
hand-placed span records.  CPU only; nothing here touches a TPU."""
import sys
import threading
from pathlib import Path

import pytest

import harness
import run
from repro.obs import spans

HERE = Path(__file__).resolve().parent
MS = 1_000_000                                  # ns


def reader(name: str):
    return harness.load_module(HERE / "metrics" / f"{name}.py").read


@pytest.fixture
def log(monkeypatch):
    fresh = spans.SpanLog()
    monkeypatch.setattr(spans, "_LOG", fresh)
    return fresh


def place(log, *threads) -> None:
    """Add each list of ``(name, start_ms, end_ms)`` records, traced, from
    a thread of its own (all alive at once, so their ids differ)."""
    barrier = threading.Barrier(len(threads))

    def add(records):
        for name, start, end in records:
            log.add(name, int(start * MS), int(end * MS), traced=True)
        barrier.wait(timeout=10)
    ts = [threading.Thread(target=add, args=(r,)) for r in threads]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
        assert not t.is_alive()
    assert len({r[1] for r in log.records()}) >= len(threads)


def synthetic_run(t0_ms: float) -> run.Run:
    clock = run.WindowClock(30.0)
    clock.t0 = t0_ms * MS * 1e-9
    return run.Run(cell=None, seed=1, setup_s=0.0, clock=clock, counters={},
                   compiles_in_window=0, device_kind="TPU v5 lite")


def test_trainer_host_time_per_step_in_the_window(log):
    place(log, [
        ("train/host", 900, 990),            # before the window: left out
        ("train/step", 950, 1100),           # starts before it: left out
        ("train/host", 1100, 1110),          # after a step cut by t0
        ("train/loader_wait", 1110, 1111),
        ("train/h2d", 1111, 1112),
        ("train/step", 1112, 1262),
        ("train/host", 1262, 1264),
        ("train/loader_wait", 1264, 1265),
        ("train/h2d", 1265, 1266),
        ("train/step", 1266, 1416),
        ("train/host", 1416, 1418),
        ("train/loader_wait", 1418, 1419),
        ("train/h2d", 1419, 1420),
        ("train/step", 1420, 1570),
        ("train/host", 1570, 1590),          # after the last whole step
        ("train/loader_wait", 1590, 1595),   # before a step the trace's
        ("train/h2d", 1595, 1600),           # stop cut: left out
        ("data/generate", 1270, 1300),       # the loader's: not the trainer's
    ])
    got = reader("trainer_host_ms_per_step")(synthetic_run(1000))
    assert got == pytest.approx((2 + 1 + 1 + 2 + 1 + 1) / 2)


def test_profiler_host_time_counts_nested_spans_once(log):
    place(log, [("profiler/drain", 1010, 1020),
                ("profiler/merge", 1010, 1012),
                ("profiler/fold", 1012, 1018),
                ("profiler/fold_wait", 1013, 1017),  # on the device: out
                ("profiler/drain", 1130, 1131),
                ("profiler/fold", 1130, 1131),
                ("profiler/drain", 1199, 1290),      # starts in the window,
                ("profiler/fold", 1199.5, 1289),     # its wait after the
                ("profiler/fold_wait", 1200.5, 1288),  # last step's start
                ("profiler/drain", 990, 1005),       # starts before t0
                ("profiler/drain", 1300, 1310)],     # after the last step
          [("profiler/sample", 1015, 1016)],         # the sampler
          [("train/step", 1000, 1100), ("train/step", 1100, 1200),
           ("train/step", 1200, 1300)])
    r = synthetic_run(1000)
    assert reader("profiler_host_ms_per_step")(r) == pytest.approx(
        (6 + 1 + 3.5 + 1) / 2)
    assert reader("profiler_fold_ms_per_chunk")(r) == pytest.approx(
        (2 + 1 + 2) / 3)


@pytest.mark.parametrize("steps", [0, 1])
def test_no_step_in_the_window_reads_nothing(log, steps):
    place(log, [("train/step", 900, 1050), ("train/host", 1050, 1052),
                ("profiler/drain", 1060, 1070),
                ("profiler/fold", 1062, 1068)]
          + [("train/step", 1100 + 150 * i, 1250 + 150 * i)
             for i in range(steps)])
    r = synthetic_run(1000)
    for name in ("trainer_host_ms_per_step", "profiler_host_ms_per_step",
                 "profiler_fold_ms_per_chunk"):
        assert reader(name)(r) is None


def test_a_detached_run_reads_no_fold(log):
    place(log, [("train/step", 1000, 1100), ("train/host", 1100, 1101),
                ("train/step", 1101, 1201)])
    r = synthetic_run(1000)
    assert reader("profiler_fold_ms_per_chunk")(r) is None
    assert reader("profiler_host_ms_per_step")(r) == 0.0
    assert reader("trainer_host_ms_per_step")(r) == pytest.approx(1.0)


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import repro.obs
    monkeypatch.delattr(repro.obs, "spans")
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    r = synthetic_run(1000)
    for name in ("trainer_host_ms_per_step", "profiler_host_ms_per_step",
                 "profiler_fold_ms_per_chunk"):
        assert reader(name)(r) is None
