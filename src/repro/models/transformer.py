"""Model assembly: decoder-only LM, encoder-decoder, and VLM wrappers.

Layers run as a python loop over ``num_groups`` pattern groups (straight-line
HLO: best overlap and honest ``cost_analysis``) or as ``lax.scan`` over
stacked group params (compact HLO for very deep configs) — ``scan_layers``
selects.  When ``cfg.remat``, each group runs under ``jax.checkpoint``; the
training step picks its save policy (``repro.train.step.pick_remat_policy``):
the weight matmuls' outputs are kept where they fit beside the step's
arguments in the device's memory, and everything is recomputed otherwise.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.extend.core
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models.common import ModelConfig, dense_init, rms_norm, softcap
from repro.sharding.api import constrain


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, cfg.num_groups + 4)
    params: dict[str, Any] = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), in_axis=1,
                            dtype=cfg.param_dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                       dtype=cfg.param_dtype)
    groups = []
    for g in range(cfg.num_groups):
        gk = jax.random.split(ks[2 + g], cfg.group_size)
        groups.append({f"b{i}": blk.init_block(gk[i], cfg, kind)
                       for i, kind in enumerate(cfg.block_pattern)})
    params["groups"] = groups
    if cfg.tail_pattern:
        tk = jax.random.split(jax.random.fold_in(key, 999),
                              len(cfg.tail_pattern))
        params["tail"] = {f"b{i}": blk.init_block(tk[i], cfg, kind)
                          for i, kind in enumerate(cfg.tail_pattern)}
    if cfg.enc_layers:
        ek = jax.random.split(ks[-1], cfg.enc_layers + 2)
        params["enc_frontend"] = dense_init(
            ek[0], (cfg.frontend_dim, cfg.d_model), dtype=cfg.param_dtype)
        params["encoder"] = [blk.init_block(ek[1 + i], cfg, "encoder")
                             for i in range(cfg.enc_layers)]
        params["enc_norm"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
    elif cfg.frontend_dim:      # vlm: patch-embedding projector
        params["frontend"] = dense_init(
            ks[-1], (cfg.frontend_dim, cfg.d_model), dtype=cfg.param_dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, cfg.compute_dtype))
    return constrain(x, "batch", "seq", "embed")


def _unembed(params, x, cfg: ModelConfig):
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(cfg.compute_dtype)
    logits = softcap(logits.astype(jnp.float32), cfg.logits_softcap)
    return constrain(logits, "batch", "seq", "vocab")


def _group_fn(gparams, x, positions, cfg: ModelConfig, *, memory=None,
              memory_positions=None, local_impl="mask", pattern=None):
    aux_sum = None
    for i, kind in enumerate(pattern or cfg.block_pattern):
        x, aux = blk.apply_block(
            gparams[f"b{i}"], x, positions, cfg, kind, memory=memory,
            memory_positions=memory_positions, local_impl=local_impl)
        if aux:
            aux_sum = aux if aux_sum is None else jax.tree.map(
                jnp.add, aux_sum, aux)
    return x, aux_sum


def encode(params, frontend_feats, cfg: ModelConfig):
    """Encoder stack over precomputed (stubbed) frontend embeddings."""
    x = (frontend_feats.astype(cfg.compute_dtype)
         @ params["enc_frontend"].astype(cfg.compute_dtype))
    x = constrain(x, "batch", "seq", "embed")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    for p in params["encoder"]:
        x, _ = blk.apply_block(p, x, positions, cfg, "encoder")
    return rms_norm(x, params["enc_norm"])


def _stack_inputs(params, batch: dict, cfg: ModelConfig):
    """What every layer group takes besides its params: the residual stream
    (embedded tokens after any patch prefix), its positions and, for
    encoder-decoder models, the encoder memory and its positions."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = _embed(params, tokens, cfg)
    memory = memory_positions = None
    if cfg.enc_layers:
        memory = encode(params, batch["frontend"], cfg)
        mp = memory.shape[1]
        memory_positions = jnp.broadcast_to(jnp.arange(mp)[None], (b, mp))
    elif cfg.frontend_dim:
        prefix = (batch["frontend"].astype(cfg.compute_dtype)
                  @ params["frontend"].astype(cfg.compute_dtype))
        x = jnp.concatenate([prefix, x], axis=1)
        s = x.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return x, positions, memory, memory_positions


def forward(params, batch: dict, cfg: ModelConfig, *, scan_layers=False,
            local_impl="mask", remat_policy=None):
    """Full-sequence forward -> (logits, aux).

    batch keys: "tokens" (B,S) int32; optional "frontend" (B,Sf,frontend_dim)
    (audio frames / vision patches, precomputed per the assignment stub);
    optional "positions".  ``remat_policy`` is the save policy of the
    per-group checkpoint when ``cfg.remat`` (None: save nothing).
    """
    x, positions, memory, memory_positions = _stack_inputs(params, batch,
                                                           cfg)
    gfn = functools.partial(_group_fn, cfg=cfg, memory=memory,
                            memory_positions=memory_positions,
                            local_impl=local_impl)
    if cfg.remat:
        gfn = jax.checkpoint(gfn, policy=remat_policy)
    aux_total = None
    if scan_layers:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["groups"])

        def body(carry, gparams):
            y, aux = gfn(gparams, carry, positions)
            return y, aux
        x, auxs = jax.lax.scan(body, x, stacked)
        aux_total = None if auxs is None else jax.tree.map(
            lambda a: jnp.sum(a, axis=0), auxs)
    else:
        for gparams in params["groups"]:
            x, aux = gfn(gparams, x, positions)
            if aux:
                aux_total = aux if aux_total is None else jax.tree.map(
                    jnp.add, aux_total, aux)
    if cfg.tail_pattern:
        tfn = functools.partial(_group_fn, cfg=cfg, memory=memory,
                                memory_positions=memory_positions,
                                local_impl=local_impl,
                                pattern=cfg.tail_pattern)
        if cfg.remat:
            tfn = jax.checkpoint(tfn, policy=remat_policy)
        x, aux = tfn(params["tail"], x, positions)
        if aux:
            aux_total = aux if aux_total is None else jax.tree.map(
                jnp.add, aux_total, aux)
    logits = _unembed(params, x, cfg)
    return logits, (aux_total or {})


def saved_residuals(f, *args) -> list:
    """Avals of what ``jax.linearize`` keeps of ``f`` for its backward,
    leaving out the arguments themselves.  Traced on abstract values:
    nothing is computed or compiled."""
    closed, (_, f_jvp) = jax.make_jaxpr(
        lambda *a: jax.linearize(f, *a), return_shape=True)(*args)
    jaxpr = closed.jaxpr
    n = len(jax.tree.leaves(f_jvp))
    given = set(jaxpr.invars) | set(jaxpr.constvars)
    kept = {v for v in jaxpr.outvars[len(jaxpr.outvars) - n:]
            if isinstance(v, jax.extend.core.Var) and v not in given}
    return [v.aval for v in kept]


def remat_saved_bytes(params, batch: dict, cfg: ModelConfig, policy, *,
                      local_impl="mask") -> int:
    """Bytes the per-group checkpoint keeps for the backward under
    ``policy``, over every group and the tail, counted on abstract values
    of ``params`` and ``batch`` (one group is traced per pattern)."""
    params, batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (params, batch))
    x, positions, memory, memory_positions = jax.eval_shape(
        functools.partial(_stack_inputs, cfg=cfg), params, batch)

    def group_bytes(gparams, pattern):
        def fn(gp, x, positions, memory, memory_positions):
            return _group_fn(gp, x, positions, cfg, memory=memory,
                             memory_positions=memory_positions,
                             local_impl=local_impl, pattern=pattern)
        kept = saved_residuals(jax.checkpoint(fn, policy=policy), gparams, x,
                               positions, memory, memory_positions)
        return sum(a.size * a.dtype.itemsize for a in kept)

    total = cfg.num_groups * group_bytes(params["groups"][0],
                                         cfg.block_pattern)
    if cfg.tail_pattern:
        total += group_bytes(params["tail"], cfg.tail_pattern)
    return total


def lm_loss(params, batch: dict, cfg: ModelConfig, **fw_kwargs):
    """Next-token cross entropy (mean over non-pad tokens) + MoE aux loss."""
    logits, aux = forward(params, batch, cfg, **fw_kwargs)
    tokens = batch["tokens"]
    if cfg.frontend_dim and not cfg.enc_layers:    # vlm: skip patch prefix
        logits = logits[:, -tokens.shape[1]:]
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    mask = (targets >= 0) & (batch.get("mask", jnp.ones_like(tokens)) > 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    nll = (logz - tgt) * mask
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
    metrics = {"loss": loss, "tokens": jnp.sum(mask)}
    if "aux_loss" in aux:
        loss = loss + aux["aux_loss"]
        metrics["moe_aux"] = aux["aux_loss"]
        metrics["moe_dropped"] = aux.get("dropped", 0)
        metrics["expert_load"] = aux.get("expert_load")
    return loss, metrics


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int) -> list:
    state = []
    for g in range(cfg.num_groups):
        state.append({f"b{i}": blk.init_block_state(cfg, kind, batch,
                                                    cache_len)
                      for i, kind in enumerate(cfg.block_pattern)})
    if cfg.tail_pattern:
        state.append({f"b{i}": blk.init_block_state(cfg, kind, batch,
                                                    cache_len)
                      for i, kind in enumerate(cfg.tail_pattern)})
    return state


def decode_step(params, tokens, pos, state, cfg: ModelConfig, *,
                memory=None):
    """One token for every sequence.  tokens: i32[B]; pos: i32[B].

    Returns (logits f32[B,V], new_state).  ``memory``: (k, v) pair or encoder
    output for enc-dec cross attention (projected per block on the fly).
    """
    x = jnp.take(params["embed"], tokens[:, None],
                 axis=0).astype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, cfg.compute_dtype))
    x = constrain(x, "batch", None, "embed")
    new_state = []
    group_list = [(gp, cfg.block_pattern) for gp in params["groups"]]
    if cfg.tail_pattern:
        group_list.append((params["tail"], cfg.tail_pattern))
    for g, (gparams, pattern) in enumerate(group_list):
        gs = dict(state[g])
        for i, kind in enumerate(pattern):
            mem = None
            if kind == "cross" and memory is not None:
                mem = memory
            x, gs[f"b{i}"] = blk.step_block(gparams[f"b{i}"], x, pos,
                                            state[g][f"b{i}"], cfg, kind,
                                            memory=mem)
        new_state.append(gs)
    logits = _unembed(params, x, cfg)
    return logits[:, 0], new_state


def cross_memory(params, cfg: ModelConfig, frontend_feats):
    """Precompute encoder memory K/V inputs for enc-dec decode."""
    mem = encode(params, frontend_feats, cfg)
    b, s, _ = mem.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return mem, positions
