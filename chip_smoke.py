"""Drive the profiler's device path once on one TPU chip, and check it.

    python chip_smoke.py [--seed N]

One process on one chip; it starts no child process.  It refuses to run
unless JAX's default backend is a TPU: it never falls back to the CPU or to
Pallas interpret mode.  Phases, each of which must pass:

  a. kernels   — ``fold``, ``carry_cumsum`` and ``tag_hist`` compiled
                 natively at 2^22 events, against ``repro.kernels.ref``;
  b. replay    — a seeded 64-worker capture of >= 2^23 events (~10 s, one
                 injected serial section) folded post-mortem through
                 ``ProfileSession.offline(backend="pallas", chunk_events=..)``
                 and whole-log ``detect_offline(backend="pallas")``, each
                 against the float64 numpy fold;
  c. training  — gemma3-1b at its published widths and depth (batch
                 and sequence cut to fit one chip) trained a few steps by
                 ``Trainer`` with a ``pallas``-backed ``ProfileSession``
                 attached, its report re-folded with numpy.

Lines before the last say what was found; times and rates on them are
measured on the chip.  The last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

KERNEL_EVENTS = 1 << 22
HIST_BINS = 4096
WIDE_HIST = (1 << 17, 1 << 20)      # (samples, bins): the largest tag_hist
REPLAY_EVENTS = 1 << 23             # at least this many events in phase b
REPLAY_CHUNK = 1 << 16
PER_WORKER_RTOL = 1e-3
TOP_PATHS = 5
# gemma3-1b on one 16 GB chip with f32 params and AdamW: published widths
# and all 26 layers; only batch and sequence are cut.  In a compile for a
# described v5e chip, batch 2 x 1024 tokens takes 11.17 GiB of arguments and
# 3.69 GiB of temporaries; batch 4 x 1024 needs 17.38 GB of the 15.75 GB.
TRAIN_BATCH = 2
TRAIN_SEQ = 1024
TRAIN_WARMUP_STEPS = 2
TRAIN_STEPS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn, *args, **kwargs):
    """(result, seconds) of one call whose device work has finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def require_native() -> None:
    from repro.kernels import ops
    if ops.default_interpret():
        raise SystemExit("Pallas kernels would run in interpret mode")


# ---------------------------------------------------------------------------
# the capture: 64 workers, tagged spans, one injected serial section
# ---------------------------------------------------------------------------

def build_capture(seed: int, seconds: float = 10.0):
    """A well-formed 64-worker capture built with vectorized numpy.

    Every 100 ms worker 0 runs ``serial/commit`` alone for 12 ms while
    every other worker waits; in between, all workers run 20-50 us spans
    of 32 parallel tags, 30-110 us apart, the tags chosen with weights
    ``1/sqrt(k+1)`` so the path totals are well separated.  Ten seconds
    hold ~10.7 M events.  Returns ``(log, tags, stacks, serial_path)``.
    """
    from repro.core.events import ACTIVATE, DEACTIVATE, NO_STACK, EventLog
    from repro.core.tracer import StackRegistry, TagRegistry

    num_workers, n_tags = 64, 32
    period_s, serial_s = 0.1, 0.012
    busy_ns, idle_ns = (20_000, 50_000), (30_000, 110_000)
    rng = np.random.default_rng(seed)
    tags, stacks = TagRegistry(), StackRegistry()
    step = tags.intern("step")
    par_tags = np.asarray([tags.intern(f"parallel/t{k:02d}")
                           for k in range(n_tags)], np.int32)
    par_paths = np.asarray([stacks.intern((step, int(t))) for t in par_tags],
                           np.int32)
    serial_tag = tags.intern("serial/commit")
    serial_path = stacks.intern((step, serial_tag))

    periods = max(1, int(round(seconds / period_s)))
    par_ns = int(round((period_s - serial_s) * 1e9))    # parallel part
    ser_ns = int(round(serial_s * 1e9))
    t_par = periods * par_ns
    cycle = (sum(busy_ns) + sum(idle_ns)) / 2
    k = int(t_par / cycle * 1.05) + 16
    busy = rng.integers(*busy_ns, size=(num_workers, k))
    gap = rng.integers(*idle_ns, size=(num_workers, k))
    start = (rng.integers(0, idle_ns[1], size=(num_workers, 1))
             + np.cumsum(busy + gap, axis=1) - (busy + gap))
    end = start + busy
    # spans live on a parallel-only clock; a span that crosses into the
    # next serial section is dropped, the rest shift past the sections
    keep = (end < t_par) & (start // par_ns == end // par_ns)
    shift = (start // par_ns + 1) * ser_ns
    w = np.broadcast_to(np.arange(num_workers, dtype=np.int32)[:, None],
                        start.shape)[keep]
    p = 1.0 / np.sqrt(np.arange(1, n_tags + 1))
    pick = rng.choice(n_tags, size=int(keep.sum()), p=p / p.sum())
    s_start = np.concatenate([(start + shift)[keep],
                              np.arange(periods) * (par_ns + ser_ns)])
    s_end = np.concatenate([(end + shift)[keep],
                            np.arange(periods) * (par_ns + ser_ns) + ser_ns])
    s_worker = np.concatenate([w, np.zeros(periods, np.int32)])
    s_tag = np.concatenate([par_tags[pick],
                            np.full(periods, serial_tag, np.int32)])
    s_path = np.concatenate([par_paths[pick],
                             np.full(periods, serial_path, np.int32)])
    n = s_start.size
    times = np.concatenate([s_start, s_end]).astype(np.int64) + 10**9
    workers = np.concatenate([s_worker, s_worker])
    deltas = np.concatenate([np.full(n, ACTIVATE, np.int8),
                             np.full(n, DEACTIVATE, np.int8)])
    # equal timestamps: switch-out first, like the live tracer's merge
    order = np.lexsort((workers, deltas, times))
    cap = EventLog(
        times=times[order], workers=workers[order], deltas=deltas[order],
        tags=np.concatenate([s_tag, s_tag])[order],
        stacks=np.concatenate([np.full(n, NO_STACK, np.int32),
                               s_path])[order],
        num_workers=num_workers)
    return cap, tags, stacks, stacks.paths[serial_path]


# ---------------------------------------------------------------------------
# phase a: the kernels alone
# ---------------------------------------------------------------------------

def phase_kernels(seed: int, events: int = KERNEL_EVENTS,
                  hist_bins: int = HIST_BINS,
                  wide_hist: tuple[int, int] = WIDE_HIST) -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    fold_mod = importlib.import_module("repro.kernels.cmetric_fold")
    require_native()

    cap, _, _, _ = build_capture(seed, seconds=10.0 * events / REPLAY_EVENTS)
    cap = cap.chunk(0, events)
    t64 = cap.slice_seconds()
    t = jnp.asarray(t64, jnp.float32)
    d = jnp.asarray(cap.deltas, jnp.int32)
    dt = jnp.concatenate([t[1:] - t[:-1], jnp.zeros((1,), jnp.float32)])
    e = len(cap)

    ops.cmetric_fold(t, d)                                   # compile
    (n, gcm, total, idle, count), sec = timed(ops.cmetric_fold, t, d)
    n_r, gcm_r, total_r, idle_r, count_r = ref.fold_ref(dt, d)
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n_r))
    scale = float(total_r)
    gerr = float(jnp.max(jnp.abs(gcm - gcm_r))) / scale
    assert gerr < 1e-4, f"fold gcm off by {gerr:.3e} of the total"
    assert abs(float(total) - scale) <= 1e-4 * scale
    assert abs(float(idle) - float(idle_r)) <= 1e-4 * max(float(idle_r), 1e-9)
    assert float(count) == float(count_r)
    log(f"a. fold: {e} events, n exact, max |gcm - fold_ref| = {gerr:.3e} "
        f"of total; {sec * 1e3:.3f} ms, {e / sec:.4g} events/s "
        f"(measured on the chip)")

    # the carry: two resumed calls equal one whole call
    cut = (e // 2) | 1
    interpret = ops.default_interpret()          # False: checked above
    a = fold_mod.fold(dt[:cut], d[:cut], interpret=interpret)
    b = fold_mod.fold(dt[cut:], d[cut:], (a[4], a[2], a[3]),
                      interpret=interpret)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(a[0]), np.asarray(b[0])]), np.asarray(n))
    cerr = float(jnp.max(jnp.abs(jnp.concatenate([a[1], b[1]]) - gcm))) \
        / scale
    assert cerr < 1e-5 and float(b[4]) == float(count), cerr
    log(f"a. fold carry: split at {cut} resumes to the whole fold "
        f"(n exact, gcm within {cerr:.3e} of total)")

    contrib = np.abs(np.random.default_rng(seed).standard_normal(e)) * 1e-6
    idle_c = np.where(np.arange(e) % 7 == 0, 1e-6, 0.0)
    ops.fold_chunk_prefix(0.5, 0.25, contrib, idle_c)         # compile
    (g, idle_end), sec = timed(ops.fold_chunk_prefix, 0.5, 0.25, contrib,
                               idle_c)
    g_ref = 0.5 + np.cumsum(contrib)
    perr = float(np.max(np.abs(g - g_ref))) / g_ref[-1]
    assert perr < 1e-5, perr
    assert abs(idle_end - (0.25 + idle_c.sum())) < 1e-5 * (0.25 + idle_c.sum())
    log(f"a. carry_cumsum: {e} events, max |g - float64| = {perr:.3e} of "
        f"final; {sec * 1e3:.3f} ms with host copies (measured on the chip)")

    rng = np.random.default_rng(seed + 1)
    tg = jnp.asarray(rng.integers(-1, hist_bins, e), jnp.int32)
    wt = jnp.asarray(rng.random(e), jnp.float32)
    ops.tag_histogram(tg, wt, num_bins=hist_bins)             # compile
    (cnt, wsum), sec = timed(ops.tag_histogram, tg, wt, num_bins=hist_bins)
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.asarray(ref.hist_ref(tg, hist_bins)))
    np.testing.assert_allclose(
        np.asarray(wsum), np.asarray(ref.weighted_hist_ref(tg, wt, hist_bins)),
        rtol=1e-4, atol=1e-3)
    log(f"a. tag_hist: {e} samples x {hist_bins} bins, counts exact, weights "
        f"within 1e-4; {sec * 1e3:.3f} ms (measured on the chip)")

    s, k = wide_hist
    tg = jnp.asarray(rng.integers(-1, k, s), jnp.int32)
    ops.tag_histogram(tg, num_bins=k)                         # compile
    (cnt, _), sec = timed(ops.tag_histogram, tg, num_bins=k)
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.asarray(ref.hist_ref(tg, k)))
    log(f"a. tag_hist: {s} samples x {k} bins, counts exact; "
        f"{sec * 1e3:.3f} ms (measured on the chip)")
    log("a. kernels: PASS")


# ---------------------------------------------------------------------------
# phase b: post-mortem replay
# ---------------------------------------------------------------------------

def _slice_rel_err(cm: np.ndarray, cm_ref: np.ndarray) -> str:
    pos = cm_ref > 0
    rel = np.abs(cm[pos] - cm_ref[pos]) / cm_ref[pos]
    p50, p99 = np.percentile(rel, [50, 99])
    return f"p50 {p50:.3e}, p99 {p99:.3e}, max {rel.max():.3e}"


def _check_report(name: str, rep, oracle, serial_path) -> None:
    pw, pw_ref = rep.per_worker, oracle.per_worker
    rel = np.abs(pw - pw_ref) / np.abs(pw_ref)
    assert np.all(rel <= PER_WORKER_RTOL), (name, float(rel.max()))
    top = [p.stack for p in rep.paths[:TOP_PATHS]]
    top_ref = [p.stack for p in oracle.paths[:TOP_PATHS]]
    assert top == top_ref, (name, top, top_ref)
    assert top[0] == serial_path, (name, top[0], serial_path)
    log(f"b. {name}: per-worker CMetric within {rel.max():.3e} of numpy, "
        f"top-{TOP_PATHS} paths identical, serial section first")


def phase_replay(seed: int, events: int = REPLAY_EVENTS,
                 chunk: int = REPLAY_CHUNK) -> None:
    from repro.core import cmetric
    from repro.core.detector import detect_offline
    from repro.core.session import ProfileSession
    require_native()

    t0 = time.perf_counter()
    cap, tags, stacks, serial_path = build_capture(
        seed, seconds=10.0 * events / REPLAY_EVENTS)
    e = len(cap)
    assert e >= events, (e, events)
    span_s = (cap.times[-1] - cap.times[0]) * 1e-9
    log(f"b. capture: {e} events, {cap.num_workers} workers, "
        f"{len(tags)} tags, {span_s:.3f} s of capture time, built in "
        f"{time.perf_counter() - t0:.3f} s")
    n_min = cap.num_workers / 2

    def chunked(backend):
        return ProfileSession.offline(cap, tags, stacks, n_min=n_min,
                                      backend=backend,
                                      chunk_events=chunk).result()

    def whole(backend):
        return detect_offline(cap, tags, stacks, n_min, backend=backend)

    oracle = chunked("numpy")
    for name, run in (("chunked pallas fold", chunked),
                      ("whole-log pallas fold", whole)):
        _, cold = timed(run, "pallas")
        rep, warm = timed(run, "pallas")
        _check_report(name, rep, oracle, serial_path)
        log(f"b. {name}: {warm:.3f} s warm, {e / warm:.4g} events/s; "
            f"first call {cold:.3f} s with compiles (measured on the chip)")

    # per-slice CMetric error of the f32 device folds (row-aligned tables)
    full_ref = cmetric.fold_chunk(cmetric.FoldCarry.init(cap.num_workers),
                                  cap, "numpy")[1]
    carry = cmetric.FoldCarry.init(cap.num_workers)
    parts = []
    for lo in range(0, e, chunk):
        carry, tbl = cmetric.fold_chunk(carry, cap.chunk(lo, lo + chunk),
                                        "pallas")
        parts.append(tbl.cm)
    log("b. per-slice CMetric relative error, chunked pallas vs numpy: "
        + _slice_rel_err(np.concatenate(parts), full_ref.cm))
    whole_tbl = cmetric.compute(cap, "pallas").table
    log("b. per-slice CMetric relative error, whole-log pallas vs numpy: "
        + _slice_rel_err(whole_tbl.cm, full_ref.cm))
    log("b. replay: PASS")


# ---------------------------------------------------------------------------
# phase c: a profiled training job
# ---------------------------------------------------------------------------

def phase_train(seed: int, cfg=None, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, warmup: int = TRAIN_WARMUP_STEPS,
                steps: int = TRAIN_STEPS) -> None:
    import jax
    from repro.core.session import ProfileSession
    from repro.kernels import ops
    from repro.optim import adamw
    from repro.train.step import make_train_step
    from repro.train.trainer import Trainer, TrainerConfig
    require_native()

    if cfg is None:
        from repro.configs import gemma3_1b
        cfg = gemma3_1b.config()
    log(f"c. model: {cfg.name}, d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}, head_dim {cfg.hd}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; {cfg.param_count() / 1e6:.1f}M "
        f"params in f32 with AdamW")
    log(f"c. cuts: none to depth ({cfg.num_layers} layers); batch {batch} "
        f"x sequence {seq} tokens per step, as far as 16 GB of HBM with f32 "
        f"params and AdamW allows")

    compiles = hist_calls = 0

    def count_compile(event, _secs, **_kw):
        nonlocal compiles
        compiles += event == "/jax/core/compile/backend_compile_duration"
    jax.monitoring.register_event_duration_secs_listener(count_compile)
    tag_histogram = ops.tag_histogram

    def counted_hist(*a, **k):
        nonlocal hist_calls
        hist_calls += 1
        return tag_histogram(*a, **k)
    ops.tag_histogram = counted_hist

    total = warmup + steps
    opt_cfg = adamw.AdamWConfig(warmup_steps=1, total_steps=total)
    jitted = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
    step_s, compiles_at = [], []

    def step_fn(*args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(*args))
        step_s.append(time.perf_counter() - t0)
        compiles_at.append(compiles)
        return out

    session = ProfileSession(fold_backend="pallas", dt=0.002)
    try:
        with tempfile.TemporaryDirectory() as ckpt_dir:
            tcfg = TrainerConfig(steps=total, ckpt_every=total + 1,
                                 ckpt_dir=ckpt_dir, batch_per_host=batch,
                                 seq_len=seq, seed=seed, log_every=total)
            trainer = Trainer(cfg, opt_cfg, tcfg, gapp=session,
                              step_fn=step_fn)
            t0 = time.perf_counter()
            trainer.run()
            run_s = time.perf_counter() - t0
        rep = session.result()
        refold = session.offline_report(backend="numpy")
    finally:
        ops.tag_histogram = tag_histogram

    losses = [h["loss"] for h in trainer.history]
    assert len(losses) == total and np.all(np.isfinite(losses)), losses
    names = set(rep.worker_names)
    assert {"trainer", "data_loader", "ckpt_writer"} <= names, names
    assert rep.paths and refold.paths
    assert rep.paths[0].stack == refold.paths[0].stack, (
        rep.path_str(rep.paths[0]), refold.path_str(refold.paths[0]))
    assert hist_calls > 0, "the tag_hist route did not run"
    measured = np.asarray(step_s[warmup:])
    after_warmup = compiles_at[-1] - compiles_at[warmup - 1]
    log(f"c. loss {losses[0]:.4f} -> {losses[-1]:.4f} over {total} steps, "
        f"all finite")
    log(f"c. step time with the profiler attached: mean "
        f"{measured.mean() * 1e3:.3f} ms, min {measured.min() * 1e3:.3f} ms, "
        f"max {measured.max() * 1e3:.3f} ms over {measured.size} steps after "
        f"{warmup} warm-up; first step {step_s[0]:.3f} s; whole run (first-step "
        f"compile, steps, final checkpoint) {run_s:.3f} s (measured on the "
        f"chip)")
    log(f"c. compiles after warm-up, during the measured steps: "
        f"{after_warmup}; in the whole process: {compiles}")
    log(f"c. report: workers {sorted(names)}, {rep.total_critical} critical "
        f"of {rep.total_slices} slices, tag_hist calls {hist_calls}, top "
        f"path '{rep.path_str(rep.paths[0])}' (numpy re-fold agrees)")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"c. peak device memory of the process: "
            f"{stats['peak_bytes_in_use'] / 2**30:.3f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.3f} GiB")
    log("c. training: PASS")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not __debug__:
        raise SystemExit("the checks are asserts: run without -O")

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.default_backend() != "tpu":
        print(f"no TPU: JAX's default backend is {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind}, {len(jax.devices())} "
        f"visible, jax {jax.__version__}")

    t0 = time.perf_counter()
    phase_kernels(args.seed)
    phase_replay(args.seed)
    phase_train(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
