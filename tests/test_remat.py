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
from repro.models import attention as attn_lib
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


def _kernel_cfg():
    """internvl2's tiny config with heads the splash kernel tiles: 8 patch
    and 120 text positions, 128 wide."""
    return dataclasses.replace(configs.get_tiny("internvl2-2b"), head_dim=128)


def test_kernel_path_saved_bytes_agree_with_saved_residuals(monkeypatch):
    """With the splash kernel selected (the platform probe reads a TPU; the
    kernel is interpreted here), the per-group count, the policy's choice
    and the whole loss's residuals agree."""
    monkeypatch.setattr(attn_lib, "_platform", lambda: "tpu")
    cfg = _kernel_cfg()
    params = _abstract(init_lm(KEY, cfg))
    batch = _abstract(_batch(cfg, s=120))
    before = attn_lib.attention_stats()

    def whole(policy):
        kept = transformer.saved_residuals(
            lambda p, b: lm_loss(p, b, cfg, remat_policy=policy)[0], params,
            batch)
        return sum(a.size * a.dtype.itemsize for a in kept)

    kept = whole(step_lib.SAVE_DOTS) - whole(None)
    # beside the weight matmuls' outputs, each layer keeps the kernel's
    # output and log-sum-exp: (B, H, S, hd) in bf16 and (B, H, S) in f32
    b, s = 2, 120 + cfg.num_prefix
    per_layer = b * cfg.num_heads * s * (cfg.hd * 2 + 4)
    dots = whole(jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    assert kept - (dots - whole(None)) == cfg.num_layers * per_layer
    st = attn_lib.attention_stats()
    assert st["splash"] > before["splash"] and st["einsum"] == before["einsum"]
    counted = transformer.remat_saved_bytes(params, batch, cfg,
                                            step_lib.SAVE_DOTS)
    assert counted == kept > 0
    # the count's own traces are not the step's calls
    assert attn_lib.attention_stats() == st
    opt = _abstract(adamw.init(params))
    assert step_lib.pick_remat_policy(
        cfg, params, opt, batch, bytes_limit=1 << 40) is step_lib.SAVE_DOTS
    assert step_lib.remat_stats()["saved_bytes"] == counted


# the attention kernel's output (B, H, S, hd) bf16 and log-sum-exp (B, H,
# S) f32 over the cell's 8 layers
KERNEL_KEPT = 8 * (2 * 16 * 1280 * 128 * 2 + 2 * 16 * 1280 * 4)


@pytest.mark.parametrize("platform,path,kept", [
    ("cpu", "einsum", 922_746_880),
    ("tpu", "splash", 922_746_880 + KERNEL_KEPT)])
def test_internvl2_cell_saved_bytes(platform, path, kept, monkeypatch):
    """The benchmark cell's step (8 layers at published widths, 2 x (256 +
    1024) positions), counted on abstract values on either attention
    path: the weight matmuls' outputs, 922,746,880 B, and on the kernel's
    path its output and log-sum-exp too."""
    monkeypatch.setattr(attn_lib, "_platform", lambda: platform)
    cfg = dataclasses.replace(configs.get_config("internvl2-2b"),
                              num_layers=8)
    params = jax.eval_shape(lambda: init_lm(KEY, cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1024), jnp.int32),
             "frontend": jax.ShapeDtypeStruct((2, 256, cfg.frontend_dim),
                                              jnp.float32)}
    before = attn_lib.attention_stats()[path]
    assert transformer.remat_saved_bytes(
        params, batch, cfg, step_lib.SAVE_DOTS) == kept
    jax.eval_shape(lambda p, b: lm_loss(p, b, cfg)[0], params, batch)
    assert attn_lib.attention_stats()[path] > before
