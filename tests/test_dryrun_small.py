"""Multi-device integration: tiny configs on an 8-placeholder-device mesh.

XLA device count is locked at first jax init, so these run in a
subprocess with XLA_FLAGS set — the same mechanism the production dry-run
uses with 512 devices.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch.dryrun import rules_for
from repro.models import init_lm, forward
from repro.optim import adamw
from repro.sharding import api as shapi, params as shparams
from repro.train.step import make_train_step, remat_stats

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
out = {}
for arch in json.loads(os.environ["ARCHS"]):
    cfg = configs.get_tiny(arch)
    # pad dims so the 4-way model axis divides
    rules = rules_for(arch, "train")
    rules = dataclasses.replace(rules)
    with shapi.use_mesh(mesh, rules):
        params = init_lm(jax.random.PRNGKey(0), cfg)
        p_sh = shparams.param_shardings(
            jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)),
            mesh, rules)
        params = jax.device_put(params, p_sh)
        opt = adamw.init(params)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
        if cfg.enc_layers:
            batch["frontend"] = jnp.zeros((8, 8, cfg.frontend_dim))
        elif cfg.frontend_dim:
            batch["frontend"] = jnp.zeros((8, cfg.num_prefix,
                                           cfg.frontend_dim))
        bsh = {k: NamedSharding(mesh, P("data") if v.ndim == 2 or True else P())
               for k, v in batch.items()}
        step = jax.jit(make_train_step(cfg, adamw.AdamWConfig(lr=1e-3)),
                       donate_argnums=(0, 1))
        p2, o2, m, _ = step(params, opt, batch, None)
        loss1 = float(m["loss"])
        p3, o3, m2, _ = step(p2, o2, batch, None)
        out[arch] = {"loss0": loss1, "loss1": float(m2["loss"]),
                     "finite": bool(jnp.isfinite(m2["loss"])),
                     "remat": remat_stats()["last"]}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("archs", [
    ["deepseek-7b", "gemma3-1b", "rwkv6-1.6b"],
    ["recurrentgemma-2b", "grok-1-314b", "arctic-480b"],
    ["qwen3-32b", "seamless-m4t-large-v2", "internvl2-2b", "qwen1.5-4b"],
])
def test_sharded_train_step_8dev(archs):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               ARCHS=json.dumps(archs))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    for arch, res in out.items():
        assert res["finite"], (arch, res)
        # two steps on the same batch: loss must drop
        assert res["loss1"] < res["loss0"], (arch, res)
        # host devices report no byte limit: the step keeps full remat
        assert res["remat"] == "full_remat", (arch, res)


GPIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.pipeline.gpipe import gpipe

from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("stage",))
def stage_fn(p, x):
    return jnp.tanh(x @ p["w"])
key = jax.random.PRNGKey(0)
stacked = {"w": jax.random.normal(key, (4, 16, 16)) * 0.5}
f = gpipe(stage_fn, mesh, n_stages=4, n_micro=6)
x = jax.random.normal(key, (6, 8, 16))
y = f(stacked, x)
# reference: sequential application of the 4 stages
ref = x
for s in range(4):
    ref = stage_fn({"w": stacked["w"][s]}, ref)
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("GPIPE OK")
"""


def test_gpipe_4stage_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", GPIPE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "GPIPE OK" in r.stdout
