"""Train / eval step builders (pjit-able, sharding-annotated).

``make_train_step`` returns a pure function
``(params, opt_state, batch, err) -> (params, opt_state, metrics, err)``
ready for ``jax.jit`` with the shardings produced by
``repro.sharding.params`` — the same function serves the CPU smoke tests
(no mesh binding) and the 512-chip dry-run (bound via ``use_mesh``).

With ``cfg.remat`` the step picks the per-group checkpoint's save policy
while it is traced (``pick_remat_policy``): keep the outputs of the weight
matmuls when they fit the device's memory beside the step's arguments, so
the backward does not run them again, and recompute everything otherwise.
``remat_stats()`` counts the choices.
"""
from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import lm_loss
from repro.models.common import ModelConfig
from repro.models.transformer import remat_saved_bytes
from repro.optim import adamw, compression
from repro.sharding.api import axis_size, constrain, current_binding

# Keeps the outputs of matmuls with no batch dimensions: the weight
# projections, not the attention scores.
SAVE_DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
# Share of the device's byte limit left to the step's own working set
# (gradients, logits, what full remat keeps); see PERF.md, model step.
WORKING_SHARE = 0.25

_stats_lock = threading.Lock()
_stats = dict(saved_dots=0, full_remat=0, last=None,  # guarded-by: _stats_lock
              saved_bytes=None, budget_bytes=None)


def remat_stats() -> dict:
    """Traces of the train step that kept the weight matmuls' outputs
    (``saved_dots``) or recomputed them (``full_remat``), the last choice,
    and the per-device bytes it compared: what the policy would keep and
    the budget left for it (None where no byte limit was known)."""
    with _stats_lock:
        return dict(_stats)


def device_bytes_limit() -> int | None:
    """Memory one device of the step has, or None where the backend reports
    no limit (CPU) or the devices are described, not attached."""
    binding = current_binding()
    device = binding[0].devices.flat[0] if binding else jax.devices()[0]
    try:
        stats = device.memory_stats()
    except jax.errors.JaxRuntimeError:
        return None
    return (stats or {}).get("bytes_limit")


def pick_remat_policy(cfg: ModelConfig, params, opt_state, batch, err=None,
                      *, bytes_limit: int | None, microbatch: int = 1,
                      local_impl: str = "mask"):
    """The per-group checkpoint's save policy for one traced step:
    ``SAVE_DOTS`` when what it keeps for one gradient call (one of
    ``microbatch`` slices of ``batch``), per device, fits in the byte limit
    less ``WORKING_SHARE`` of it and less the step's arguments; None (save
    nothing) otherwise, or when no limit is known.  Works on abstract
    values: nothing is computed."""
    saved = budget = None
    if bytes_limit is not None:
        grad_batch = {k: jax.ShapeDtypeStruct(
            (v.shape[0] // microbatch,) + v.shape[1:], v.dtype)
            for k, v in batch.items()}
        saved = remat_saved_bytes(params, grad_batch, cfg, SAVE_DOTS,
                                  local_impl=local_impl)
        # the kept activations shard over the batch axis at least;
        # tensor-parallel ones split further, so this over-counts
        saved //= axis_size("batch")
        args = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves((params, opt_state, batch, err)))
        budget = int(bytes_limit * (1 - WORKING_SHARE)) - args
    keep = saved is not None and saved <= budget
    with _stats_lock:
        _stats["saved_dots" if keep else "full_remat"] += 1
        _stats.update(last="saved_dots" if keep else "full_remat",
                      saved_bytes=saved, budget_bytes=budget)
    return SAVE_DOTS if keep else None


def make_loss_fn(cfg: ModelConfig, **fw_kwargs) -> Callable:
    def loss_fn(params, batch):
        return lm_loss(params, batch, cfg, **fw_kwargs)
    return loss_fn


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    compress: str = "none", microbatch: int | None = None,
                    **fw_kwargs) -> Callable:
    """Builds the jittable step.  ``microbatch`` splits the per-step batch
    into gradient-accumulation chunks (sequential, remat-friendly)."""
    n_mb = microbatch if microbatch and microbatch > 1 else 1

    def train_step(params, opt_state, batch, err):
        batch = {k: constrain(v, "batch") for k, v in batch.items()}
        policy = None
        if cfg.remat:
            policy = pick_remat_policy(
                cfg, params, opt_state, batch, err,
                bytes_limit=device_bytes_limit(), microbatch=n_mb,
                local_impl=fw_kwargs.get("local_impl", "mask"))
        loss_fn = make_loss_fn(cfg, remat_policy=policy, **fw_kwargs)

        def grad_fn(params, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, {**metrics, "loss": loss}

        if n_mb > 1:
            def mb_body(carry, mb):
                acc, aux_acc = carry
                g, aux = grad_fn(params, mb)
                acc = jax.tree.map(jnp.add, acc, g)
                aux_acc = jax.tree.map(jnp.add, aux_acc,
                                       {"loss": aux["loss"]})
                return (acc, aux_acc), None
            mbs = jax.tree.map(
                lambda x: x.reshape((n_mb, x.shape[0] // n_mb)
                                    + x.shape[1:]), batch)
            zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params)
            (grads, aux_sum), _ = jax.lax.scan(
                mb_body, (zero_g, {"loss": jnp.zeros((), jnp.float32)}), mbs)
            grads = jax.tree.map(lambda g: g / n_mb, grads)
            metrics = {"loss": aux_sum["loss"] / n_mb}
            new_err = err
        else:
            grads, metrics, new_err = compression.wrap_grad_fn(
                grad_fn, compress)(params, batch, err)
            metrics = {"loss": metrics["loss"]}
        params, opt_state, opt_metrics = adamw.update(opt_cfg, grads,
                                                      opt_state, params)
        return params, opt_state, {**metrics, **opt_metrics}, new_err

    return train_step


def make_eval_step(cfg: ModelConfig, **fw_kwargs) -> Callable:
    loss_fn = make_loss_fn(cfg, **fw_kwargs)

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch)
        return metrics
    return eval_step
