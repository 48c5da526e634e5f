"""CMetric cost: per-event online probe cost + offline fold throughput.

Paper claim: the in-kernel probe is cheap enough for ~4% average overhead.
Our analogue: the probe microbenchmark (sharded lock-free hot path vs the
retained locked seed body, single-thread and contended — see
``bench_probe``), the offline fold paths' events/second (numpy oracle,
Pallas fold), and the carry-resumable chunked
fold's throughput — the numbers behind the PPT column.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (FoldCarry, compute, compute_numpy, fold_chunk,
                        synthetic_log)


def _time(fn, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run():
    rows = []
    # --- online probe cost: sharded hot path vs locked seed body ----------
    from benchmarks.bench_probe import run_probe
    p = run_probe(pairs=10_000, reps=2)
    rows.append(("cmetric_probe_pair", 2 * p["sharded_us_per_event_1t"],
                 f"events/s={1e6 / p['sharded_us_per_event_1t']:.0f};"
                 f"vs_locked_1t={p['speedup_1t']:.1f}x;"
                 f"vs_locked_{p['threads']}t={p['speedup_mt']:.1f}x"))
    rows.append(("cmetric_probe_pair_locked",
                 2 * p["locked_us_per_event_1t"],
                 f"events/s={1e6 / p['locked_us_per_event_1t']:.0f}"))

    # --- offline fold throughput ------------------------------------------
    rng = np.random.default_rng(0)
    log = synthetic_log(rng, 64, 4000)      # 512k events
    e = len(log)
    backends = {
        "numpy": lambda: compute_numpy(log),
        "pallas_interp": lambda: compute(log, backend="pallas"),
    }
    for name, fn in backends.items():
        fn()                                 # warm up / compile
        dt = _time(fn, reps=2 if name != "numpy" else 1)
        rows.append((f"cmetric_fold_{name}", dt / e * 1e6,
                     f"events/s={e / dt:.0f};events={e}"))

    # --- chunked (bounded-memory) fold throughput -------------------------
    def chunked():
        carry = FoldCarry.init(log.num_workers)
        for lo in range(0, e, 65_536):
            carry, _ = fold_chunk(carry, log.chunk(lo, lo + 65_536),
                                  backend="numpy")
        return carry

    chunked()
    dt = _time(chunked, reps=2)
    rows.append(("cmetric_fold_chunked_numpy", dt / e * 1e6,
                 f"events/s={e / dt:.0f};chunk=65536"))
    return rows
