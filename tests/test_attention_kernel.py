"""Full-sequence causal self-attention through the splash kernel (Pallas,
interpreted on the CPU) against the einsum path, and the rule that picks
between them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.kernels import attention as attn_kernel
from repro.models import attention as attn_lib
from repro.models.common import ModelConfig
from repro.sharding.api import ShardingRules, use_mesh

B, S, H, KV, HD = 1, 256, 4, 2, 128
CFG = ModelConfig(d_model=256, num_heads=H, num_kv_heads=KV, head_dim=HD,
                  d_ff=512, vocab_size=64)
# bf16's machine epsilon: both paths round their output (and the einsum
# path its probabilities) to bf16, so they agree to a few of its steps
EPS = 2.0 ** -7
KEYS = jax.random.split(jax.random.PRNGKey(0), 8)


@pytest.fixture
def on_tpu(monkeypatch):
    """The step's device reads as a TPU; the kernel is still interpreted
    on this backend."""
    monkeypatch.setattr(attn_lib, "_platform", lambda: "tpu")


def _close(got, ref, steps=2):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    assert np.max(np.abs(got - ref)) <= steps * EPS * np.max(np.abs(ref))


def _qkv():
    q = jax.random.normal(KEYS[0], (B, S, H, HD), jnp.bfloat16)
    k = jax.random.normal(KEYS[1], (B, S, KV, HD), jnp.bfloat16)
    v = jax.random.normal(KEYS[2], (B, S, KV, HD), jnp.bfloat16)
    return q, k, v


def _sdpa(q, k, v):
    pos = jnp.arange(S)[None]
    mask = attn_lib._band_mask(pos, pos, None, True)[:, None, None]
    return attn_lib._sdpa(q, k, v, mask, CFG)


def _splash(q, k, v):
    return attn_lib._splash(q, k, v, CFG)


def test_kernel_forward_matches_einsum_path():
    q, k, v = _qkv()
    got = jax.jit(_splash)(q, k, v)
    assert got.shape == q.shape and got.dtype == jnp.bfloat16
    _close(got, jax.jit(_sdpa)(q, k, v))


def test_kernel_gradients_match_einsum_path():
    q, k, v = _qkv()
    do = jax.random.normal(KEYS[3], q.shape, jnp.float32)

    def grads(f):
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * do)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for got, ref in zip(grads(_splash), grads(_sdpa)):
        assert got.shape == ref.shape
        _close(got, ref)


def test_attention_layer_on_the_kernel_matches_the_einsum_path(on_tpu):
    """The whole layer: projections and RoPE, the transposes around the
    kernel and the output matmul, forward and gradients of the
    parameters."""
    p = attn_lib.init_attention(KEYS[4], CFG)
    x = jax.random.normal(KEYS[5], (B, S, CFG.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def layer(arange):
        return lambda p, x: attn_lib.attention(
            p, x, pos, CFG, window=None, arange_positions=arange)

    before = attn_lib.attention_stats()
    got, ref = jax.jit(layer(True))(p, x), jax.jit(layer(False))(p, x)
    st = attn_lib.attention_stats()
    assert st["splash"] == before["splash"] + 1
    assert st["einsum"] == before["einsum"] + 1
    _close(got, ref)
    dy = jax.random.normal(KEYS[6], got.shape, jnp.float32)

    def grads(arange):
        loss = lambda p, x: jnp.sum(layer(arange)(p, x).astype(
            jnp.float32) * dy)
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)

    for got, ref in zip(jax.tree.leaves(grads(True)),
                        jax.tree.leaves(grads(False))):
        _close(got, ref)


def _two_devices():
    device = jax.devices()[0]
    # every logical axis unbound: the mesh's size is what the rule reads
    return use_mesh(Mesh(np.array([device, device]), ("data",)),
                    ShardingRules({}))


@pytest.mark.parametrize("case,reason", [
    ("cpu", "platform"), ("window", "mask"), ("bidirectional", "mask"),
    ("cross", "mask"), ("positions", "mask"), ("softcap", "softcap"),
    ("mesh", "mesh"), ("seq_192", "shape"), ("head_dim_64", "shape"),
    ("tpu", None)])
def test_path_selection(case, reason, monkeypatch):
    if case != "cpu":
        monkeypatch.setattr(attn_lib, "_platform", lambda: "tpu")
    cfg, s = CFG, S
    if case == "softcap":
        cfg = dataclasses.replace(CFG, logits_softcap=30.0)
    elif case == "head_dim_64":
        cfg = dataclasses.replace(CFG, head_dim=64)
    elif case == "seq_192":
        s = 192
    p = attn_lib.init_attention(KEYS[4], cfg)
    x = jax.ShapeDtypeStruct((B, s, cfg.d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((B, s), jnp.int32)
    kw = dict(window=None, arange_positions=case != "positions")
    if case == "window":
        kw["window"] = 64
    elif case == "bidirectional":
        kw["causal"] = False
    cross = case == "cross"

    def fn(p, x, pos):
        return attn_lib.attention(p, x, pos, cfg, kv_x=x if cross else None,
                                  kv_positions=pos if cross else None, **kw)

    before = attn_lib.attention_stats()
    if case == "mesh":
        with _two_devices():
            out = jax.eval_shape(fn, p, x, pos)
    else:
        out = jax.eval_shape(fn, p, x, pos)
    assert out.shape == x.shape
    st = attn_lib.attention_stats()
    if reason is None:
        assert st["splash"] == before["splash"] + 1
        assert st["einsum"] == before["einsum"]
        bs = attn_kernel.block_sizes(s)
        assert (st["block_q"], st["block_kv"]) == (bs.block_q, bs.block_kv)
    else:
        assert st["einsum"] == before["einsum"] + 1
        assert st["splash"] == before["splash"]
        assert st["reason"] == reason


@pytest.mark.parametrize("seq_len,block", [(128, 128), (384, 128),
                                           (256, 256), (1280, 640),
                                           (1920, 640), (4096, 512)])
def test_block_size_rule(seq_len, block):
    """The largest of the rule's tiles that divides the sequence, for the
    forward and the fused backward."""
    bs = attn_kernel.block_sizes(seq_len)
    assert seq_len % block == 0
    assert {bs.block_q, bs.block_kv, bs.block_kv_compute, bs.block_q_dkv,
            bs.block_kv_dkv, bs.block_kv_dkv_compute} == {block}
    assert bs.use_fused_bwd_kernel and bs.has_backward_blocks


def test_trainer_logs_the_attention_path_once_per_compile(tmp_path, capsys):
    from repro import configs
    from repro.optim import adamw
    from repro.train.trainer import Trainer, TrainerConfig
    tcfg = TrainerConfig(steps=3, batch_per_host=2, seq_len=16,
                         ckpt_dir=str(tmp_path), ckpt_every=100,
                         log_every=100, profile=False)
    Trainer(configs.get_tiny("deepseek-7b"), adamw.AdamWConfig(), tcfg).run()
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("attention:")]
    # the per-group checkpoint traces the layer group once for both groups,
    # and that trace counts both layers
    assert lines == ["attention: splash 0/2 layers (einsum: platform)"]


@pytest.mark.parametrize("scan_layers", [False, True])
@pytest.mark.parametrize("remat", [False, True])
def test_stats_count_every_layer_once(scan_layers, remat, on_tpu):
    """A layer group traced once for all its repeats (a checkpoint's cached
    trace, a scan's body) and groups traced one by one both count each
    attention layer of the model once."""
    from repro import configs
    from repro.models import init_lm, transformer
    cfg = dataclasses.replace(configs.get_tiny("internvl2-2b"), head_dim=128,
                              remat=remat)
    params = jax.eval_shape(lambda: init_lm(KEYS[7], cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 120), jnp.int32),
             "frontend": jax.ShapeDtypeStruct(
                 (1, cfg.num_prefix, cfg.frontend_dim), jnp.float32)}
    before = attn_lib.attention_stats()
    jax.eval_shape(lambda p, b: transformer.forward(
        p, b, cfg, scan_layers=scan_layers)[0], params, batch)
    st = attn_lib.attention_stats()
    assert st["splash"] - before["splash"] == cfg.num_layers > 1
    assert st["einsum"] == before["einsum"]
