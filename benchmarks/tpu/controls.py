"""Readings of the controls and planted faults each limit is set against.

    python3 benchmarks/tpu/controls.py --config internvl2 --seeds 1 2 3

Not part of a benchmark run.  For each seed it prints one JSON line with
the numbers a cell compares, read for the reference computed with float8
(e4m3) matmul operands (the type below the job's bfloat16) in the
program's place, for the reference fed half of every batch (the
half-batch fault), and for a short window of ``train_attached`` whose
session log is folded by the reference in bfloat16 (the precision below
the device fold's float32) in the profiler's place.

The readings need a chip at the cell's size (the step holds about
13 GiB).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def train_controls(config: dict, seed: int) -> dict:
    import jax.numpy as jnp

    from drivers import train
    ref = train.reference(config, seed)
    out = {"fp8_control": train.compare(
        ref, train.reference(config, seed, mm_dtype=jnp.float8_e4m3fn))}
    half = config["train"]["batch"] // 2
    out["half_batch"] = train.compare(
        ref, train.reference(config, seed, rows=half))
    out["report_bf16_control"] = report_control(config, seed)
    return out


def report_control(config: dict, seed: int, seconds: float = 8.0,
                   mix: dict | None = None) -> dict:
    """A short profiled window of ``mix`` (``train_attached``'s where not
    given); its session log folded in bfloat16 against the float64
    fold."""
    import gc

    import ml_dtypes

    import harness
    import run
    from drivers import train
    from reference import cmetric_f64
    mix = mix or json.loads(
        (HERE / "mixes" / "train_attached.json").read_text())
    job = train.Job(config, mix, seed)
    job.setup()
    job.window(run.WindowClock(seconds))
    f, prof = job.frozen, mix["profiler"]
    job.release()
    gc.collect()
    args = (f.times, f.workers, f.deltas, f.stacks, f.num_workers,
            len(prof["workers"]) / 2, job.paths)
    ref = cmetric_f64.fold(*args, top_n=prof["top_n"])
    low = cmetric_f64.fold(*args, top_n=prof["top_n"],
                           dtype=ml_dtypes.bfloat16)
    harness.log(f"report control: {len(f)} session events")
    return cmetric_f64.compare(low, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    config = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    import harness
    harness.use_compile_cache()
    for seed in args.seeds:
        print(json.dumps({"config": args.config, "seed": seed,
                          **train_controls(config, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
