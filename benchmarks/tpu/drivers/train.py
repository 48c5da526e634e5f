"""A training job run through the program's ``Trainer``, profiled or not.

The mix's ``profiler`` block attaches a live ``ProfileSession`` (or, when
null, none: the control).  One ``Trainer.run`` holds set-up and window: its
first steps compile and warm up, the window starts at step ``warmup_steps``
and ends at the first step that starts after ``seconds``; the step hook
then stops the run.  No checkpoint falls inside the window.

The live session drains at most ``max_rows_per_sync`` rows per worker at
a time, so every chunk its fold sees is one of a known set of lengths;
set-up runs the chunk prefix once at each of them, and nothing compiles
in the window.

What is compared: the losses of the first three steps, the first
gradient's norm per leaf as the optimizer holds it after step one
(``mu / (1 - b1)``), and each leaf's change after three steps, against the
plain float32 reference run on the same weights and batches.  With a
profiler, also the session's log against what the benchmark counted
itself (one slice per step it drove, one per batch it drew, none for the
checkpoint writer), and the session's report against a float64 fold of
that log with ``n_min`` at half the profiled workers.
"""
from __future__ import annotations

import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import lm_data  # noqa: E402
import weights  # noqa: E402
from reference import cmetric_f64  # noqa: E402

STEP_SPAN = "train/step"
CHECKED_STEPS = 3


class WindowClosed(Exception):
    """Raised by the step hook to end ``Trainer.run`` after the window."""


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the configuration file."""
    import jax.numpy as jnp
    from repro.models.common import ModelConfig
    job = cfg["train"]
    return ModelConfig(
        name=cfg["name"], family="vlm",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        block_pattern=("dense",),
        frontend_dim=cfg["vision"]["patch_embed_dim"],
        num_prefix=cfg["vision"]["num_prefix_tokens"],
        rope_theta=cfg["rope_theta"],
        compute_dtype=jnp.dtype(job["compute_dtype"]),
        param_dtype=jnp.dtype(job["param_dtype"]))


def leaf_gap(prog: np.ndarray, ref: np.ndarray, counted: np.ndarray
             ) -> float:
    """Worst leaf's gap between the two sides' norms, over the larger of
    the reference leaf's norm and the median leaf's."""
    scale = np.maximum(ref, np.median(ref[counted]))
    return float(np.max(np.abs(prog - ref)[counted] / scale[counted]))


def reference(cfg: dict, seed: int, mm_dtype=None, rows: int | None = None
              ) -> dict:
    """Three steps of the plain reference from the seed's weights and
    batches: the losses, the first clipped gradient's norm per leaf and
    each leaf's change after the three.  ``mm_dtype`` is the control's
    matmul type; ``rows`` keeps only the first rows of every batch (the
    half-batch fault)."""
    import jax
    import jax.numpy as jnp
    from reference import lm_f32
    spec = weights.layout(cfg)
    params = weights.make(cfg, seed)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    step = lm_f32.make_step(cfg, mm_dtype)
    data = lm_data.for_job(cfg, seed)
    losses, grads = [], None
    for k in range(CHECKED_STEPS):
        batch = {n: jnp.asarray(v[:rows]) for n, v in
                 data.next_batch().items()}
        params, mu, nu, loss, norms = step(params, mu, nu, batch,
                                           jnp.float32(k + 1))
        losses.append(float(loss))
        if grads is None:
            flat = weights.flatten(norms, spec)
            grads = np.asarray([float(flat[p]) for p in spec])
    del mu, nu
    changes = np.asarray(weights.change_norms(cfg)(
        params, weights.key_of(seed)), np.float64)
    return {"losses": np.asarray(losses), "grads": grads,
            "changes": changes}


def compare(ref: dict, got: dict) -> dict[str, float]:
    """The numbers a training cell is judged by: the worst relative gap of
    the first steps' losses, and the worst leaf's gap of the first
    gradient's and of the three steps' change norms.  Leaves whose
    reference gradient is under a thousandth of the median leaf's (moved
    by rounding alone) are not counted."""
    counted = ref["grads"] >= 1e-3 * np.median(ref["grads"])
    return {
        "loss_gap": float(np.max(np.abs(got["losses"] - ref["losses"])
                                 / np.abs(ref["losses"]))),
        "grad_norm_gap": leaf_gap(got["grads"], ref["grads"], counted),
        "change_gap": leaf_gap(got["changes"], ref["changes"], counted),
    }


class Job:
    SPANS = (STEP_SPAN,)

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.counters = {"attempted": 0, "failed": 0}
        self.losses: list[float] = []
        self.calls = 0
        # (call, dispatched, done) of every step, on the host clock
        self.step_spans: list[tuple[float, float, float]] = []

    def setup(self) -> None:
        import jax
        from repro.core.session import ProfileSession
        from repro.optim import adamw
        from repro.train import trainer as trainer_mod
        from repro.train.step import make_train_step
        cfg, job = self.config, self.config["train"]
        self.mcfg = model_config(cfg)
        self.opt_cfg = adamw.AdamWConfig(**job["optimizer"])
        self.step = jax.jit(make_train_step(self.mcfg, self.opt_cfg),
                            donate_argnums=(0, 1))
        self.leaf_norms = weights.leaf_norms(cfg)
        self.change_norms = weights.change_norms(cfg)
        prof = self.mix["profiler"]
        self.session = None if prof is None else ProfileSession(
            fold_backend=prof["fold_backend"], dt=prof["sample_dt_s"],
            top_n=prof["top_n"], max_rows_per_sync=prof["max_rows_per_sync"])
        self._ckpt_dir = tempfile.TemporaryDirectory(prefix="bench_ckpt_")
        tcfg = trainer_mod.TrainerConfig(
            steps=1 << 62, ckpt_every=1 << 62, ckpt_dir=self._ckpt_dir.name,
            batch_per_host=job["batch"], seq_len=job["text_len"],
            seed=self.seed, log_every=1 << 62, profile=False)
        params = weights.make(cfg, self.seed)
        # the job's batches are the benchmark's, fed through the program's
        # own prefetch loader
        self.batches = lm_data.for_job(cfg, self.seed)
        program_source = trainer_mod.SyntheticLM
        trainer_mod.SyntheticLM = lambda *a, **k: self.batches
        try:
            self.trainer = trainer_mod.Trainer(
                self.mcfg, self.opt_cfg, tcfg, gapp=self.session,
                step_fn=self.hook)
        finally:
            trainer_mod.SyntheticLM = program_source
        self.trainer.init_state = lambda key=None: (params, adamw.init(params))
        if self.session is not None:
            self.warm_drain(prof["max_rows_per_sync"])

    def warm_drain(self, rows_per_worker: int) -> None:
        """Run the live fold's device prefix at every chunk length a
        budgeted drain can hand it (1 to the budget times the workers)."""
        from repro.kernels import ops
        workers = len(self.session.tracer.worker_names())
        for n in range(1, rows_per_worker * workers + 1):
            ops.fold_chunk_prefix(0.0, 0.0, np.zeros(n), np.zeros(n))

    def hook(self, params, opt_state, batch, err):
        """The step the ``Trainer`` calls: the window's own call."""
        import jax
        k, self.calls = self.calls, self.calls + 1
        warmup = self.mix["warmup_steps"]
        if k == warmup:
            self.clock.start()
        elif k > warmup and self.clock.tick():
            raise WindowClosed
        if k >= warmup:
            self.counters["attempted"] += 1
        t0 = time.perf_counter()
        with harness.span(STEP_SPAN):
            out = self.step(params, opt_state, batch, err)
            t1 = time.perf_counter()
            jax.block_until_ready(out)
        self.step_spans.append((t0, t1, time.perf_counter()))
        if k < CHECKED_STEPS:
            self.losses.append(float(out[2]["loss"]))
            if k == 0:
                self.grad_norms = np.asarray(
                    self.leaf_norms(out[1]["mu"]), np.float64) \
                    / (1.0 - self.opt_cfg.b1)
            if k == CHECKED_STEPS - 1:
                self.changes = np.asarray(self.change_norms(
                    out[0], weights.key_of(self.seed)), np.float64)
        return out

    def window(self, clock) -> None:
        self.clock = clock
        try:
            self.trainer.run()
        except WindowClosed:
            pass
        harness.log(f"window: {len(clock.marks)} steps in "
                    f"{clock.elapsed:.6f} s; losses of the first steps "
                    f"{self.losses}")
        self.log_longest_steps()
        # the loader thread ends (its last slice lands before the log is
        # read)
        self.trainer.loader._thread.join(timeout=30)
        if self.session is not None:
            self.report = cmetric_f64.from_report(self.session.result())
            self.frozen = self.session.freeze()
            self.worker_names = self.session.tracer.worker_names()
            self.paths = list(self.session.stacks.paths)

    def log_longest_steps(self, top: int = 3) -> None:
        """The window's longest iterations, each split into the step's
        dispatch, the wait for its result, and the time outside the step
        call (loader wait, the Trainer's host work)."""
        spans = self.step_spans[self.mix["warmup_steps"]:]
        ends = [s[0] for s in spans[1:]] + self.clock.marks[-1:]
        rows = [(b - a[0], a[1] - a[0], a[2] - a[1], b - a[2], k)
                for k, (a, b) in enumerate(zip(spans, ends))]
        for total, send, wait, outside, k in sorted(rows, reverse=True)[:top]:
            harness.log(f"window step {k}: {1e3 * total:.3f} ms: "
                        f"dispatch {1e3 * send:.3f}, wait {1e3 * wait:.3f}, "
                        f"outside the step call {1e3 * outside:.3f}")

    def log_audit(self) -> float:
        """How far the session's log is from what the benchmark counted:
        per profiled worker, switch-ins and switch-outs against the slices
        it drove (the last step's slice stays open: the window closed in
        it), plus every profiled worker missing from the log."""
        f = self.frozen
        want = {"trainer": (self.calls, self.calls - 1),
                "data_loader": (self.batches.drawn, self.batches.drawn),
                "ckpt_writer": (0, 0)}
        gap = len(set(self.mix["profiler"]["workers"])
                  ^ set(self.worker_names))
        for wid, name in enumerate(self.worker_names):
            mine = f.deltas[f.workers == wid]
            got = (int((mine == 1).sum()), int((mine == -1).sum()))
            ins, outs = want.get(name, (0, 0))
            gap += abs(got[0] - ins) + abs(got[1] - outs)
        return float(gap)

    def release(self) -> None:
        self.trainer = self.step = self.session = None
        self._ckpt_dir.cleanup()

    def check(self) -> list[harness.Check]:
        limits = self.mix["limits"]
        ref = reference(self.config, self.seed)
        got = compare(ref, {"losses": np.asarray(self.losses),
                            "grads": self.grad_norms,
                            "changes": self.changes})
        prof = self.mix["profiler"]
        if prof is not None:
            f = self.frozen
            got["log_audit"] = self.log_audit()
            ref_fold = cmetric_f64.fold(
                f.times, f.workers, f.deltas, f.stacks, f.num_workers,
                len(prof["workers"]) / 2, self.paths, top_n=prof["top_n"])
            got.update({f"report_{k}": v for k, v in
                        cmetric_f64.compare(self.report, ref_fold).items()})
        return [harness.Check(name, got.get(name, float("nan")), limit)
                for name, limit in limits.items()]
