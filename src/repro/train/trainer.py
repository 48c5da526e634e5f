"""Trainer: the instrumented host loop tying every substrate together.

The host itself is a set of GAPP workers: the step dispatcher, the data
loader (inside PrefetchLoader), and the checkpoint writer.  Any of them
stalling the others produces exactly the reduced-parallelism slices the
profiler ranks — profile a run, read the top call path, fix that.  This is
the paper's workflow (§5) transplanted onto a training job.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np

from repro.ckpt import checkpoint
from repro.core.session import ProfileSession
from repro.data.pipeline import PrefetchLoader, SyntheticLM
from repro.models import init_lm
from repro.models.attention import attention_stats
from repro.models.common import ModelConfig
from repro.obs import spans
from repro.optim import adamw
from repro.train.step import make_train_step, remat_stats


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_async: bool = True
    batch_per_host: int = 8
    seq_len: int = 128
    seed: int = 0
    log_every: int = 10
    profile: bool = True
    loader_delay_s: float = 0.0      # inject data bottleneck (benchmarks)


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                 tcfg: TrainerConfig, gapp: ProfileSession | None = None,
                 step_fn: Callable | None = None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.gapp = gapp if gapp is not None else (
            ProfileSession(dt=0.002) if tcfg.profile else None)
        self.step_fn = step_fn or jax.jit(
            make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
        front = None
        if cfg.enc_layers:
            front = (tcfg.seq_len // 2, cfg.frontend_dim)
        elif cfg.frontend_dim:
            front = (cfg.num_prefix, cfg.frontend_dim)
        self.source = SyntheticLM(cfg.vocab_size, tcfg.seq_len,
                                  tcfg.batch_per_host, tcfg.seed,
                                  frontend_shape=front)
        self.loader = PrefetchLoader(self.source, depth=2, gapp=self.gapp,
                                     delay_s=tcfg.loader_delay_s)
        self.w_train = self.gapp.register_worker("trainer", "host") \
            if self.gapp else None
        self.w_ckpt = self.gapp.register_worker("ckpt_writer", "thread") \
            if self.gapp else None
        self.history: list[dict] = []
        self._ckpt_thread = None
        self._remat_seen = remat_stats()
        self._attn_seen = attention_stats()

    def _log_trace_choices(self) -> None:
        """Lines for each new trace of the step: whether its backward keeps
        the weight matmuls' outputs or recomputes them, and how many of its
        attention layers take the splash kernel."""
        st = remat_stats()
        if st != self._remat_seen:
            self._remat_seen = st
            print(f"remat: {st['last']} (saved {st['saved_bytes']} B per "
                  f"device, budget {st['budget_bytes']} B)", flush=True)
        at, seen = attention_stats(), self._attn_seen
        if at != seen:
            self._attn_seen = at
            splash = at["splash"] - seen["splash"]
            einsum = at["einsum"] - seen["einsum"]
            how = []
            if splash:
                how.append(f"block_q {at['block_q']}, "
                           f"block_kv {at['block_kv']}")
            if einsum:
                how.append(f"einsum: {at['reason']}")
            print(f"attention: splash {splash}/{splash + einsum} layers "
                  f"({'; '.join(how)})", flush=True)

    def init_state(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self.tcfg.seed)
        params = init_lm(key, self.cfg)
        opt_state = adamw.init(params)
        return params, opt_state

    def restore_or_init(self):
        step = checkpoint.latest_step(self.tcfg.ckpt_dir)
        params, opt_state = self.init_state()
        if step is not None:
            tree = checkpoint.restore(self.tcfg.ckpt_dir, step,
                                      {"params": params, "opt": opt_state})
            return tree["params"], tree["opt"], step
        return params, opt_state, 0

    def _maybe_ckpt(self, step: int, params, opt_state, final=False):
        if step % self.tcfg.ckpt_every and not final:
            return
        if self._ckpt_thread is not None:
            self._ckpt_thread.join()
        tree = {"params": params, "opt": opt_state}
        self._ckpt_thread = checkpoint.save(
            self.tcfg.ckpt_dir, step, tree,
            blocking=not self.tcfg.ckpt_async,
            gapp=self.gapp, wid=self.w_ckpt)

    def run(self, start_step: int | None = None):
        if start_step in (None, 0):
            params, opt_state = self.init_state()
            step0 = 0
        else:
            params, opt_state, step0 = self.restore_or_init()
        err = None
        g = self.gapp
        if g:
            g.start()
        try:
            for step in range(step0, self.tcfg.steps):
                # blocking wait: the trainer is INACTIVE here (paper
                # semantics — a blocked thread leaves TASK_RUNNING), so a
                # slow loader runs alone and its data/generate slices are
                # the ones that turn critical
                with spans.span("train/loader_wait"):
                    batch = self.loader.get()
                if g:
                    g.begin(self.w_train, "train/step")
                with spans.span("train/h2d"):
                    batch = {k: jax.numpy.asarray(v)
                             for k, v in batch.items()}
                with spans.span("train/step"):
                    params, opt_state, metrics, err = self.step_fn(
                        params, opt_state, batch, err)
                    jax.block_until_ready(metrics["loss"])
                if g:
                    g.end(self.w_train)
                with spans.span("train/host"):
                    self._log_trace_choices()
                    self.history.append(
                        {k: float(np.asarray(v)) for k, v in metrics.items()
                         if v is not None and np.ndim(v) == 0})
                    if step % self.tcfg.log_every == 0:
                        print(f"step {step:5d} loss "
                              f"{self.history[-1]['loss']:.4f} gnorm "
                              f"{self.history[-1].get('grad_norm', 0):.3f}",
                              flush=True)
                    self._maybe_ckpt(step + 1, params, opt_state)
            self._maybe_ckpt(self.tcfg.steps, params, opt_state, final=True)
            if self._ckpt_thread is not None:
                self._ckpt_thread.join()
        finally:
            if g:
                g.stop()
            self.loader.stop()
        return params, opt_state

    def profile_report(self, top_n: int = 10):
        assert self.gapp is not None
        return self.gapp.snapshot(top_n)
