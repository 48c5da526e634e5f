"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process on the cell's chips.  It refuses to run unless JAX's backend
is a TPU with enough chips, builds its inputs from ``--seed``, warms every
shape the cell uses (set-up), measures for ``--seconds``, checks what the
window produced against the plain reference, and prints one JSON object as
the last line of stdout.  ``--trace 0`` reports the cell's end-to-end
metrics; ``--trace 1`` traces the window (or its first ``trace_seconds``,
where the mix sets them) with the JAX profiler and reports the per-layer
metrics, the device's busy time and a breakdown.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import flops_bytes  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402


class WindowClock:
    """The measured window on the host clock.

    A driver calls :meth:`start` just before its first timed call (a
    trace starts there) and :meth:`tick` after each unit of work (at the
    start of the next training step); ``tick`` returns True once
    ``seconds`` have passed.  With a tracer, the trace is stopped at the
    first tick past ``trace_seconds``.  With a compile counter, it counts
    the compiles between :meth:`start` and the last tick."""

    def __init__(self, seconds: float, trace_seconds: float | None = None,
                 on_start=None, on_trace_end=None, compiles=None):
        self.seconds = seconds
        self.trace_seconds = trace_seconds
        self._on_start = on_start
        self._on_trace_end = on_trace_end
        self._compiles = compiles
        self._compiles_at_start = 0
        self.compiles_in_window = 0
        self.t0: float | None = None
        self.marks: list[float] = []                   # tick times

    def start(self) -> None:
        if self._on_start is not None:
            self._on_start()
        if self._compiles is not None:
            self._compiles_at_start = self._compiles.count
        self.t0 = time.perf_counter()

    def tick(self) -> bool:
        t = time.perf_counter()
        self.marks.append(t)
        if self._compiles is not None:
            self.compiles_in_window = (self._compiles.count
                                       - self._compiles_at_start)
        if self._on_trace_end is not None and \
                t - self.t0 >= self.trace_seconds:
            self._on_trace_end()
            self._on_trace_end = None
        return t - self.t0 >= self.seconds

    @property
    def elapsed(self) -> float:
        return self.marks[-1] - self.t0 if self.marks else 0.0


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: harness.Cell
    seed: int
    setup_s: float
    clock: WindowClock
    counters: dict
    compiles_in_window: int
    device_kind: str
    trace: trace_reduce.Summary | None = None

    @property
    def peak(self) -> dict:
        return flops_bytes.peaks(self.device_kind)


class Tracer:
    """The JAX profiler around (the start of) the window."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self._span = None

    def start(self) -> None:
        opts = self._jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0         # host spans, no per-call cost
        self._jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = harness.span(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self._jax.profiler.stop_trace()

    def reduce(self, span_names: set[str]) -> trace_reduce.Summary:
        try:
            return trace_reduce.reduce(trace_reduce.find_xplane(self.dir),
                                       span_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def measure(cell: harness.Cell, seed: int, seconds: float, trace: bool,
            devs) -> tuple[dict, list[harness.Check]]:
    compiles = harness.CompileCounter()
    job = cell.driver().Job(cell.config, cell.mix, seed)
    job.setup()
    tracer = Tracer() if trace else None
    restore = harness.wrap_spans(cell.mix.get("host_spans", {})) \
        if trace else None
    trace_s = cell.mix.get("trace_seconds", seconds) if trace else None
    clock = WindowClock(seconds, trace_s, tracer.start if trace else None,
                        tracer.stop if trace else None, compiles)
    job.window(clock)
    if tracer:
        tracer.stop()
        restore()
    setup_s = clock.t0 - PROCESS_START
    harness.log(f"set-up {setup_s:.3f} s; window {clock.elapsed:.3f} s, "
                f"{len(clock.marks)} units; {clock.compiles_in_window} "
                f"compiles in it")
    device = harness.device_info(devs)
    job.release()
    gc.collect()
    try:
        checks = job.check()
    except Exception:                      # noqa: BLE001 -- reported below
        traceback.print_exc()
        checks = [harness.Check("reference_ran", 1.0, 0.0)]
    run = Run(cell=cell, seed=seed, setup_s=setup_s, clock=clock,
              counters=job.counters,
              compiles_in_window=clock.compiles_in_window,
              device_kind=devs[0].device_kind)
    result = {"correct": all(c.ok for c in checks),
              "attempted": int(job.counters["attempted"]),
              "failed": int(job.counters["failed"])}
    metrics = cell.per_layer if trace else cell.end_to_end
    if trace:
        spans = set(cell.mix.get("host_spans", {})) | set(job.SPANS)
        run.trace = tracer.reduce(spans)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    result["metrics"] = {}
    for m in metrics:
        value = cell.reader(m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"] = device
    if trace:
        result["breakdown"] = run.trace.breakdown()
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devs = harness.require_tpu(cell.chips)
    import jax
    harness.log(f"device: platform {devs[0].platform}, device_kind "
                f"{devs[0].device_kind!r}, {len(devs)} in use of "
                f"{len(jax.devices())}; jax {jax.__version__}")
    harness.log(f"compile cache: {harness.use_compile_cache()}")
    result, checks = measure(cell, args.seed, args.seconds, bool(args.trace),
                             devs)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
