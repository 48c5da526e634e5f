"""The train step's remat rule: keep the weight matmuls' outputs for the
backward when they fit the device's memory, recompute them otherwise."""
import contextlib
import dataclasses
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from repro import configs
from repro.models import init_lm, lm_loss, transformer
from repro.optim import adamw
from repro.train import step as step_lib
from repro.train.trainer import Trainer, TrainerConfig

KEY = jax.random.PRNGKey(0)
B, S = 2, 16
GIB = 1 << 30


def _batch(cfg, s=S):
    b = {"tokens": jax.random.randint(KEY, (B, s), 0, cfg.vocab_size)}
    if cfg.enc_layers:
        b["frontend"] = jax.random.normal(KEY, (B, 12, cfg.frontend_dim))
    elif cfg.frontend_dim:
        b["frontend"] = jax.random.normal(KEY, (B, cfg.num_prefix,
                                                cfg.frontend_dim))
    return b


def _abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _count(key: str) -> int:
    return step_lib.remat_stats()[key]


def _run_step(cfg, params, batch, limit, monkeypatch):
    monkeypatch.setattr(step_lib, "device_bytes_limit", lambda: limit)
    step = jax.jit(step_lib.make_train_step(cfg, adamw.AdamWConfig(lr=1e-3)))
    return step(params, adamw.init(params), batch, None)


def _gap(got, ref) -> np.ndarray:
    """Per leaf: the norm of ``got - ref`` over the norm of ``ref``."""
    return np.asarray([
        np.linalg.norm(np.asarray(g, np.float64) - np.asarray(r, np.float64))
        / max(np.linalg.norm(np.asarray(r, np.float64)), 1e-30)
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref))])


def test_saved_dots_step_matches_full_remat(monkeypatch):
    """Same loss.  The first gradient differs from full remat's by less
    than full remat's differs from the same step computed in float32 (bf16
    rounding, 1-2% at these sizes); the updated params by less than bf16's
    unit roundoff, where they start nonzero (a zero-initialised norm scale
    is its first update alone, whose sign is set by gradients near zero)."""
    cfg = configs.get_tiny("internvl2-2b")
    params = init_lm(KEY, cfg)
    batch = _batch(cfg)
    full0, dots0 = _count("full_remat"), _count("saved_dots")
    full = _run_step(cfg, params, batch, None, monkeypatch)
    assert _count("full_remat") == full0 + 1
    dots = _run_step(cfg, params, batch, 1 << 40, monkeypatch)
    assert _count("saved_dots") == dots0 + 1
    st = step_lib.remat_stats()
    assert st["last"] == "saved_dots"
    assert 0 < st["saved_bytes"] <= st["budget_bytes"]
    f32 = _run_step(dataclasses.replace(cfg, compute_dtype=jnp.float32),
                    params, batch, None, monkeypatch)
    # the forward is the same program: the loss is the same number
    assert float(dots[2]["loss"]) == float(full[2]["loss"])
    bf16_gap = _gap(full[1]["mu"], f32[1]["mu"])    # mu: the first gradient
    assert 0 < bf16_gap.max() < 0.05
    assert np.all(_gap(dots[1]["mu"], full[1]["mu"]) <= bf16_gap)
    nonzero = np.asarray([np.any(np.asarray(p) != 0)
                          for p in jax.tree.leaves(params)])
    assert nonzero.sum() > len(nonzero) // 2
    assert np.all(_gap(dots[0], full[0])[nonzero] <= 2.0 ** -8)


def test_saved_residuals_are_the_weight_matmul_outputs():
    cfg = configs.get_tiny("internvl2-2b")
    params = _abstract(init_lm(KEY, cfg))
    s = S + cfg.num_prefix
    q, kv = cfg.num_heads * cfg.hd, cfg.num_kv_heads * cfg.hd
    x = jax.ShapeDtypeStruct((B, s, cfg.d_model), cfg.compute_dtype)
    pos = jax.ShapeDtypeStruct((B, s), jnp.int32)

    def group(gp, x, pos):
        return transformer._group_fn(gp, x, pos, cfg)

    kept = transformer.saved_residuals(
        jax.checkpoint(group, policy=step_lib.SAVE_DOTS),
        params["groups"][0], x, pos)
    # wq, wk, wv, wo; silu(x @ gate) and x @ up.  The backward needs no
    # output of down; no attention score (B, H, S, S) is kept.
    assert sorted(a.shape for a in kept) == sorted([
        (B, s, q), (B, s, kv), (B, s, kv), (B, s, cfg.d_model),
        (B, s, cfg.d_ff), (B, s, cfg.d_ff)])
    assert all(a.dtype == cfg.compute_dtype for a in kept)
    # jax's own account of the same residuals agrees
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        print_saved_residuals(
            jax.checkpoint(group, policy=step_lib.SAVE_DOTS),
            params["groups"][0], x, pos)
    theirs = [tuple(int(d) for d in m.group(1).split(","))
              for line in out.getvalue().splitlines()
              if "from the argument" not in line
              for m in [re.match(r"\w+\[([\d,]+)\]", line)] if m]
    assert sorted(theirs) == sorted(a.shape for a in kept)
    assert transformer.saved_residuals(jax.checkpoint(group), params[
        "groups"][0], x, pos) == []


@pytest.mark.parametrize("arch,kind", [
    ("internvl2-2b", "dense"), ("gemma3-1b", "local"),
    ("arctic-480b", "moe"), ("recurrentgemma-2b", "rglru"),
    ("rwkv6-1.6b", "rwkv"), ("seamless-m4t-large-v2", "cross")])
@pytest.mark.parametrize("scan_layers", [False, True])
def test_saved_bytes_count_every_block_kind(arch, kind, scan_layers):
    """The per-group count, times the groups, plus the tail, is exactly
    what the policy adds to the whole loss's residuals, also when the
    groups (recurrent blocks included) run inside ``lax.scan``."""
    cfg = configs.get_tiny(arch)
    assert kind in cfg.block_pattern
    params = _abstract(init_lm(KEY, cfg))
    batch = _abstract(_batch(cfg))

    def whole(policy):
        kept = transformer.saved_residuals(
            lambda p, b: lm_loss(p, b, cfg, scan_layers=scan_layers,
                                 remat_policy=policy)[0], params, batch)
        return sum(a.size * a.dtype.itemsize for a in kept)

    counted = transformer.remat_saved_bytes(params, batch, cfg,
                                            step_lib.SAVE_DOTS)
    assert counted > 0
    assert counted == whole(step_lib.SAVE_DOTS) - whole(None)


def test_no_byte_limit_keeps_full_remat():
    cfg = configs.get_tiny("internvl2-2b")
    assert step_lib.device_bytes_limit() is None     # the CPU reports none
    params = init_lm(KEY, cfg)
    before = _count("full_remat")
    jax.jit(step_lib.make_train_step(cfg, adamw.AdamWConfig())).trace(
        params, adamw.init(params), _batch(cfg), None)
    st = step_lib.remat_stats()
    assert st["full_remat"] == before + 1
    assert st["last"] == "full_remat"
    assert st["saved_bytes"] is None and st["budget_bytes"] is None


def test_budget_below_the_saved_bytes_keeps_full_remat():
    cfg = configs.get_tiny("internvl2-2b")
    params = _abstract(init_lm(KEY, cfg))
    opt = _abstract(adamw.init(params))
    batch = _abstract(_batch(cfg))
    saved = transformer.remat_saved_bytes(params, batch, cfg,
                                          step_lib.SAVE_DOTS)
    args = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, opt, batch)))
    # the byte limit whose budget is exactly the saved bytes
    edge = (saved + args) / (1 - step_lib.WORKING_SHARE)
    assert step_lib.pick_remat_policy(
        cfg, params, opt, batch, bytes_limit=int(edge) + 8) \
        is step_lib.SAVE_DOTS
    before = _count("full_remat")
    assert step_lib.pick_remat_policy(
        cfg, params, opt, batch, bytes_limit=int(edge) - 8) is None
    st = step_lib.remat_stats()
    assert st["full_remat"] == before + 1
    assert st["saved_bytes"] == saved > st["budget_bytes"]


def test_full_size_config_against_16_gib_keeps_full_remat():
    """qwen3-32b at a train shape, counted on abstract values (nothing is
    compiled), does not fit a 16 GiB chip: full remat."""
    cfg = configs.get_config("qwen3-32b")
    shape = configs.SHAPES["train_4k"]
    params = jax.eval_shape(lambda: init_lm(KEY, cfg))
    opt = jax.eval_shape(adamw.init, params)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (shape.global_batch, shape.seq_len), jnp.int32)}
    before = _count("full_remat")
    assert step_lib.pick_remat_policy(
        cfg, params, opt, batch, bytes_limit=16 * GIB) is None
    st = step_lib.remat_stats()
    assert st["full_remat"] == before + 1
    # the kept matmul outputs alone exceed the chip
    assert st["saved_bytes"] > 16 * GIB
    assert st["budget_bytes"] < 0


def test_microbatched_step_counts_one_microbatch(monkeypatch):
    cfg = configs.get_tiny("internvl2-2b")
    params = _abstract(init_lm(KEY, cfg))
    batch = _abstract(_batch(cfg))
    half = {k: jax.ShapeDtypeStruct((B // 2,) + v.shape[1:], v.dtype)
            for k, v in batch.items()}
    monkeypatch.setattr(step_lib, "device_bytes_limit", lambda: 1 << 40)
    jax.jit(step_lib.make_train_step(
        cfg, adamw.AdamWConfig(), microbatch=2)).trace(
        params, _abstract(adamw.init(params)), batch, None)
    assert step_lib.remat_stats()["saved_bytes"] == \
        transformer.remat_saved_bytes(params, half, cfg, step_lib.SAVE_DOTS)


def test_trainer_logs_the_choice_once_per_compile(tmp_path, capsys):
    cfg = configs.get_tiny("deepseek-7b")
    tcfg = TrainerConfig(steps=3, batch_per_host=2, seq_len=16,
                         ckpt_dir=str(tmp_path), ckpt_every=100,
                         log_every=100, profile=False)
    Trainer(cfg, adamw.AdamWConfig(), tcfg).run()
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("remat:")]
    assert lines == ["remat: full_remat (saved None B per device, "
                     "budget None B)"]
