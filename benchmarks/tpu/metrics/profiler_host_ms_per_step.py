"""Host time of the live profiler per step of the job it profiles: on each
thread, its outermost ``profiler/*`` spans (the drain with the shard
merge, fold and path interning inside it; the sampler's ticks that record
samples) less the fold's wait for its device result inside them
(``profiler/fold_wait``: it queues behind the job's step and costs the job
no host time), summed over threads, per step in the traced part of the
window.  A group counts when its outermost span starts between the first
and the last step traced whole."""
from pathlib import Path

import harness

window_records = harness.load_module(
    Path(__file__).with_name("trainer_host_ms_per_step.py")).window_records

PREFIX = "profiler/"
WAIT = "profiler/fold_wait"


def groups(recs, first, last):
    """Each thread's ``profiler/*`` records grouped under the outermost one
    that encloses them, ``[(outer, [outer and every record inside it])]``,
    for the outermost spans that start in ``[first, last)``."""
    by_thread: dict[int, list] = {}
    for r in recs:
        if r[0].startswith(PREFIX):
            by_thread.setdefault(r[1], []).append(r)
    out = []
    for rs in by_thread.values():
        mine = []
        for r in sorted(rs, key=lambda r: (r[2], -r[3])):
            if mine and r[3] <= mine[-1][0][3]:
                mine[-1][1].append(r)
            else:
                mine.append((r, [r]))
        out += [g for g in mine if first <= g[0][2] < last]
    return out


def host_ns(span, group) -> int:
    """Wall time of ``span`` less the device waits of ``group`` inside it."""
    _, _, start, end = span
    return end - start - sum(e - s for name, _, s, e in group
                             if name == WAIT and start <= s and e <= end)


def read(run):
    window = window_records(run)
    if window is None:
        return None
    recs, first, last, steps = window
    ns = sum(host_ns(outer, group)
             for outer, group in groups(recs, first, last))
    return 1e-6 * ns / steps
