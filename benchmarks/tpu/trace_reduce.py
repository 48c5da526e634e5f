"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

On a TPU the trace holds one plane per chip (``/device:TPU:<i>``) whose
``XLA Modules`` line has one event per program run (``jit_<name>(<id>)``)
and whose ``XLA Ops`` line has one event per HLO op, named by the op's HLO
text (``%fold.1 = (...) custom-call(...)``).  A Pallas kernel is a
custom-call op named after the kernel (``fold``, ``carry_cumsum``,
``hist``).  Host threads sit on ``/host:CPU`` lines; the benchmark's own
``TraceAnnotation`` spans are events there, on the same clock.

``reduce`` computes, inside the benchmark's ``window`` span:

* busy time: the union of the device's op intervals, averaged over chips;
* device time per op (short name) and per module, and per HLO op within
  its module (the breakdown);
* idle gaps: the stretches no op runs, each attributed to the innermost
  benchmark span on the host that encloses the gap's midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW_SPAN = "window"
NO_SPAN = "host/other"


def op_hlo_name(name: str) -> str:
    """``'%fusion.4 = f32[...] fusion(...)'`` -> ``'fusion.4'``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def op_short_name(name: str) -> str:
    """``'%fold.1 = (...) custom-call(...)'`` -> ``'fold'`` (a kernel's
    name, whatever its instruction number)."""
    return re.sub(r"\.\d+$", "", op_hlo_name(name))


def module_short_name(name: str) -> str:
    """``'jit__fused_pipeline(8484706676271650758)'`` ->
    ``'jit__fused_pipeline'``."""
    return re.sub(r"\(\d+\)$", "", name)


def union_length(intervals: list[tuple[float, float]]) -> tuple[
        float, list[tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in time order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


@dataclasses.dataclass
class Summary:
    window_s: float                           # length of the window span
    busy_s: float                             # union of op intervals, mean
    chips: int
    op_s: dict[str, float]                    # short op name -> seconds
    op_calls: dict[str, int]
    module_s: dict[str, float]                # module -> seconds
    module_calls: dict[str, int]
    op_in_module_s: dict[tuple[str, str], float]   # (module, HLO op name)
    idle_by_span: dict[str, float]            # host span -> idle seconds
    idle_gaps: int

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_in_module_s.items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[f"{m}/{o}", s] for (m, o), s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def _host_spans(planes, names: set[str]):
    window = None
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in names:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return window, spans


def _label(mid: float, spans) -> str:
    best, best_len = NO_SPAN, None
    for s, e, name in spans:
        if s <= mid < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(path: str | Path, span_names: set[str] = frozenset()) -> Summary:
    """Summarise the trace at ``path`` inside its ``window`` span;
    ``span_names`` are the benchmark's host spans idle gaps are named by."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    window, spans = _host_spans(planes, set(span_names))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = window
    op_s: dict[str, float] = collections.defaultdict(float)
    op_calls: collections.Counter = collections.Counter()
    module_s: dict[str, float] = collections.defaultdict(float)
    module_calls: collections.Counter = collections.Counter()
    in_mod: dict[tuple[str, str], float] = collections.defaultdict(float)
    idle_by_span: dict[str, float] = collections.defaultdict(float)
    busy_total, chips, gaps = 0.0, 0, 0
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        chips += 1
        mods = []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines \
                else ():
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            name = module_short_name(ev.name)
            module_s[name] += (min(e, w1) - max(s, w0)) * 1e-9
            module_calls[name] += 1
            mods.append((s, e, name))
        mods.sort()
        starts = [m[0] for m in mods]
        intervals = []
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            name = op_short_name(ev.name)
            op_s[name] += (e - s) * 1e-9
            op_calls[name] += 1
            intervals.append((s, e))
            j = _rightmost_le(starts, s)
            mod = mods[j][2] if j >= 0 and s < mods[j][1] else "-"
            in_mod[(mod, op_hlo_name(ev.name))] += (e - s) * 1e-9
        busy, merged = union_length(intervals)
        busy_total += busy * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps += 1
                idle_by_span[_label((a + b) / 2, spans)] += (b - a) * 1e-9
    if chips == 0:
        raise ValueError(f"no TPU device plane with ops in {path}")
    return Summary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / chips, chips=chips,
        op_s=dict(op_s), op_calls=dict(op_calls), module_s=dict(module_s),
        module_calls=dict(module_calls), op_in_module_s=dict(in_mod),
        idle_by_span={k: v / chips for k, v in idle_by_span.items()},
        idle_gaps=gaps)


def _rightmost_le(xs: list[float], x: float) -> int:
    lo, hi = 0, len(xs)
    while lo < hi:
        mid = (lo + hi) // 2
        if xs[mid] <= x:
            lo = mid + 1
        else:
            hi = mid
    return lo - 1
