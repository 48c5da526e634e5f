"""Distribution substrate on an 8-placeholder-device mesh (via subprocess
env) is covered by test_dryrun_small.py; here: optimizer, compression,
checkpointing, data pipeline, pipeline parallelism on the host devices."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import checkpoint
from repro.models import init_lm
from repro.optim import adamw, compression


def test_adamw_descends_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                            weight_decay=0.0, clip_norm=0)
    params = {"w": jnp.ones((4,)) * 5.0}
    state = adamw.init(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw.update(cfg, grads, state, params)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1.0
    assert int(state["step"]) == 60


def test_grad_clip_and_schedule():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            clip_norm=1.0)
    s0 = adamw.schedule(cfg, jnp.asarray(0))
    s5 = adamw.schedule(cfg, jnp.asarray(5))
    s10 = adamw.schedule(cfg, jnp.asarray(10))
    assert float(s0) == 0.0 and float(s5) == pytest.approx(0.5)
    assert float(s10) == pytest.approx(1.0)
    params = {"w": jnp.zeros((3,))}
    state = adamw.init(params)
    _, _, m = adamw.update(cfg, {"w": jnp.ones((3,)) * 100}, state, params)
    assert float(m["grad_norm"]) == pytest.approx(100 * np.sqrt(3), rel=1e-5)


def test_int8_error_feedback_converges():
    """With error feedback, quantised SGD still drives a quadratic to zero."""
    def grad_fn(params, batch):
        return {"w": 2 * params["w"]}, {}
    f = compression.wrap_grad_fn(grad_fn, "int8")
    params = {"w": jnp.ones((8,)) * 3.0}
    err = compression.init_error(params)
    for _ in range(200):
        g, _, err = f(params, None, err)
        params = {"w": params["w"] - 0.05 * g["w"]}
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.05


def test_topk_compression_sparsity():
    def grad_fn(params, batch):
        return {"w": jnp.arange(100.0)}, {}
    f = compression.wrap_grad_fn(grad_fn, "topk", topk_frac=0.1)
    params = {"w": jnp.zeros(100)}
    g, _, err = f(params, None, compression.init_error(params))
    nz = int(jnp.sum(g["w"] != 0))
    assert nz == 10
    # residual carries the rest
    assert float(jnp.sum(err["w"])) == pytest.approx(
        float(jnp.sum(jnp.arange(100.0))) - float(jnp.sum(g["w"])))


def test_checkpoint_roundtrip(tmp_path):
    cfg = configs.get_tiny("deepseek-7b")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    tree = {"params": params, "opt": opt}
    checkpoint.save(str(tmp_path), 7, tree)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    restored = checkpoint.restore(str(tmp_path), 7, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_prune(tmp_path):
    tree = {"x": jnp.arange(10)}
    for s in (1, 2, 3):
        t = checkpoint.save(str(tmp_path), s, tree, blocking=False)
        t.join()
    checkpoint.prune(str(tmp_path), keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 3
    assert not os.path.isdir(os.path.join(str(tmp_path), "step_000001"))


def test_checkpoint_elastic_resharding(tmp_path):
    """Save unsharded, restore with explicit shardings (1-device 'mesh')."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    checkpoint.save(str(tmp_path), 1, tree)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored = checkpoint.restore(str(tmp_path), 1, tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == sh["w"]


def test_incomplete_checkpoint_rejected(tmp_path):
    d = tmp_path / "step_000009"
    d.mkdir(parents=True)
    with pytest.raises(FileNotFoundError):
        checkpoint.restore(str(tmp_path), 9, {"x": jnp.zeros(1)})
    assert checkpoint.latest_step(str(tmp_path)) is None


def test_data_pipeline_prefetch_and_profile():
    from repro.core.session import ProfileSession
    from repro.data.pipeline import PrefetchLoader, SyntheticLM
    g = ProfileSession(n_min=4)
    src = SyntheticLM(vocab_size=100, seq_len=8, batch_per_host=2)
    loader = PrefetchLoader(src, depth=2, gapp=g)
    batches = [loader.get() for _ in range(5)]
    loader.stop()
    assert all(b["tokens"].shape == (2, 8) for b in batches)
    assert all(b["tokens"].min() >= 0 and b["tokens"].max() < 100
               for b in batches)
    # loader spans were recorded
    assert g.tracer.per_worker_cm()[0] > 0


def test_straggler_monitor():
    from repro.ft.monitor import StragglerMonitor
    mon = StragglerMonitor(num_hosts=8, zmax=2.0)
    t = 0
    for step in range(20):
        for h in range(8):
            dur = 3_000_000 if h == 5 else 1_000_000
            mon.record_step(h, t, t + dur)
        t += 4_000_000
    v = mon.verdict()
    assert v.host == 5 and v.is_straggler


def test_run_with_restarts():
    from repro.ft.monitor import run_with_restarts
    calls = []

    def train_fn(start_step):
        calls.append(start_step)
        if len(calls) < 3:
            raise RuntimeError("simulated node failure")
        return 100

    assert run_with_restarts(train_fn, max_restarts=5) == 100
    assert calls == [0, -1, -1]


@pytest.mark.skipif(len(jax.devices()) < 1, reason="needs devices")
def test_gpipe_single_stage_identity():
    from repro.pipeline.gpipe import gpipe
    mesh = jax.make_mesh((1,), ("stage",))
    stage_fn = lambda p, x: x * p["scale"]
    params = {"scale": jnp.ones((1,)) * 2.0}
    f = gpipe(stage_fn, mesh, n_stages=1, n_micro=3)
    x = jnp.arange(12.0).reshape(3, 4)
    y = f(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2.0)


def test_gpipe_schedule_bubble_fraction():
    from repro.pipeline.gpipe import schedule_intervals
    iv = schedule_intervals(n_stages=4, n_micro=8)
    span = max(e for _, _, e in iv) - min(s for _, s, _ in iv)
    busy = sum(e - s for _, s, e in iv)
    bubble = 1 - busy / (span * 4)
    assert bubble == pytest.approx((4 - 1) / (8 + 4 - 1))
