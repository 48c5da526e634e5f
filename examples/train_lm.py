"""End-to-end driver: train a ~100M-param LM with the profiler attached.

Phase 1 trains normally; phase 2 injects a slow data loader (the classic
fleet bottleneck).  The GAPP profile shifts: phase-2 critical paths move
from compute spans to ``train/wait_data``, and the per-worker chart shows
the loader dominating — the paper's workflow ("rank, read the top path,
fix that") on a real training loop with checkpointing and prefetch.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--dmodel 768]
(defaults produce a ~110M-param llama-style model; use --steps 40
--dmodel 256 for a quick pass on a small CPU.)
"""
import argparse

import jax

from repro.compile_cache import use_compile_cache
from repro.core import ProfileSession, render_text
from repro.models.common import ModelConfig
from repro.optim import adamw
from repro.train.step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def build_cfg(d_model: int) -> ModelConfig:
    return ModelConfig(
        name=f"lm-{d_model}", family="dense",
        num_layers=12, d_model=d_model, num_heads=d_model // 64,
        num_kv_heads=d_model // 64, d_ff=4 * d_model, vocab_size=32000,
        block_pattern=("dense",),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--dmodel", type=int, default=768)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    use_compile_cache()

    cfg = build_cfg(args.dmodel)
    n_params = cfg.param_count()
    print(f"model: {cfg.name}, ~{n_params / 1e6:.0f}M params")

    opt_cfg = adamw.AdamWConfig(lr=3e-4, warmup_steps=20,
                                total_steps=args.steps)
    gapp = ProfileSession(dt=0.002)
    half = args.steps // 2
    tcfg = TrainerConfig(steps=half, batch_per_host=args.batch,
                         seq_len=args.seq, ckpt_every=max(half // 2, 1),
                         ckpt_dir="/tmp/repro_example_ckpt",
                         log_every=20)
    step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))

    print("== phase 1: healthy pipeline ==")
    t1 = Trainer(cfg, opt_cfg, tcfg, gapp=gapp, step_fn=step_fn)
    t1.run()
    rep1 = t1.profile_report()
    print(render_text(rep1, max_paths=3))

    # size the injected stall relative to the measured step time so the
    # demo works on any host speed (1.5x the phase-1 mean step)
    step_s = t1.gapp.tracer.per_worker_cm()[t1.w_train] \
        / max(len(t1.history), 1)
    delay = max(1.5 * step_s, 0.05)
    print(f"== phase 2: slow data loader injected ({delay * 1e3:.0f}ms/batch,"
          f" 1.5x the {step_s * 1e3:.0f}ms phase-1 step) ==")
    gapp2 = ProfileSession(dt=0.002)
    tcfg2 = TrainerConfig(steps=half, batch_per_host=args.batch,
                          seq_len=args.seq, ckpt_every=max(half // 2, 1),
                          ckpt_dir="/tmp/repro_example_ckpt2",
                          log_every=20, loader_delay_s=delay)
    t2 = Trainer(cfg, opt_cfg, tcfg2, gapp=gapp2, step_fn=step_fn)
    t2.run()
    rep2 = t2.profile_report()
    print(render_text(rep2, max_paths=3))

    losses = [h["loss"] for h in t1.history]
    print(f"loss: start {losses[0]:.3f} -> end {losses[-1]:.3f} "
          f"(decreased: {losses[-1] < losses[0]})")
    top2 = rep2.path_str(rep2.paths[0]) if rep2.paths else "?"
    print(f"phase-2 top bottleneck path: {top2}")
    hit = any("data/generate" in rep2.path_str(p)
              for p in rep2.paths[:2])
    print("=> GAPP attributed the slowdown to the data pipeline:", hit)


if __name__ == "__main__":
    main()
