"""The benchmark's plumbing: discovery by name, device checks, the window
clock, compile counting, host spans and the result line.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``   sizes of the deployment, with its source;
* ``mixes/<traffic>.json``    the traffic: a ``driver`` name and parameters;
* ``drivers/<driver>.py``     a generator/driver, shared by every mix that
                              names it (see ``Job`` below);
* ``metrics/<metric>.py``     one reader per metric, ``read(run)`` returning
                              a number, or ``None`` when it finds nothing to
                              read (the metric is then left out of the line).

A driver module defines ``Job(config, mix, seed)`` with

* ``setup()``          build inputs from the seed and warm every shape;
* ``window(seconds)``  run the measured work until ``seconds`` have passed;
* ``release()``        drop the program's state (before the reference runs);
* ``check()``          compare what the window produced with the plain
                       reference: a list of ``Check`` rows;
* ``counters``         a dict the metric readers use (events, steps, ...).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import threading
from pathlib import Path
from typing import Callable

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
BENCHMARK = CHECKOUT / "BENCHMARK.json"


def load_module(path: Path, name: str | None = None):
    """Import a file by path (driver and metric files are found by name,
    not by package, and metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path

    def driver(self):
        return load_module(self.root / "drivers" / f"{self.mix['driver']}.py")

    def reader(self, metric: str) -> Callable:
        return load_module(self.root / "metrics" / f"{metric}.py").read


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def find_cell(name: str, benchmark: Path = BENCHMARK) -> Cell:
    """Resolve a workload name into its configuration, mix and metrics.
    Every file is looked up beside ``benchmark``'s own ``paths`` entry."""
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    root = benchmark.parent / spec["paths"][0]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((benchmark.parent / conf["file"]).read_text())
    mix = json.loads((root / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        root=root)


# ---------------------------------------------------------------------------
# correctness rows
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with its limit: the run is correct only while
    ``value <= limit`` for every row (a NaN never passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


# ---------------------------------------------------------------------------
# the device and JAX
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices this run may use; exits (no result) unless JAX runs on
    a TPU with at least ``chips`` chips."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is {backend!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else a fixed directory inside the
    checkout), with every program cached however quickly it compiles, so
    a second run compiles nothing."""
    import jax
    from repro.compile_cache import use_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache hits excluded)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1


def device_info(devs) -> dict:
    """The devices as JAX reports them, with the peak memory in use on the
    fullest chip so far."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def wrap_spans(spans: dict[str, str]) -> Callable[[], None]:
    """Wrap program callables in host spans, from the benchmark's side.

    ``spans`` maps a span name to ``"module:attr"`` or
    ``"module:Class.method"``.  Calls resolved through the module or class
    attribute at call time then show in the trace; returns the undo."""
    undo = []
    for label, target in spans.items():
        mod_name, attr = target.split(":")
        owner = importlib.import_module(mod_name)
        *path, leaf = attr.split(".")
        for p in path:
            owner = getattr(owner, p)
        fn = getattr(owner, leaf)

        def wrapped(*a, __fn=fn, __label=label, **k):
            with span(__label):
                return __fn(*a, **k)
        setattr(owner, leaf, wrapped)
        undo.append((owner, leaf, fn))

    def restore():
        for owner, leaf, fn in reversed(undo):
            setattr(owner, leaf, fn)
    return restore


# ---------------------------------------------------------------------------
# statistics and output
# ---------------------------------------------------------------------------

def percentile(values, q: float) -> float:
    """``q``-th percentile by linear interpolation between order
    statistics (numpy's default), over all values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def log(msg: str) -> None:
    print(msg, flush=True)


def emit(result: dict, checks: list[Check]) -> None:
    """Checks as the last lines of stderr, then the result as the last
    line of stdout, with the checks under the key that comes last."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
