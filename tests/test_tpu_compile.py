"""Compile the Pallas kernels for a described TPU v5e chip at real sizes.

Nothing runs: the TPU compiler, which is installed with ``libtpu``,
lowers each kernel for a chip that is described but not attached, and
refuses what the chip's Mosaic compiler would refuse (scalar stores into
VMEM, unaligned tiles, too much VMEM).  Interpret-mode tests cannot see
any of that.

The topology is described inside a module-scoped fixture and never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# import by module path: ``repro.kernels.cmetric_fold`` as an attribute is
# the wrapper function re-exported by ``repro.kernels``
fold_mod = importlib.import_module("repro.kernels.cmetric_fold")
hist_mod = importlib.import_module("repro.kernels.tag_hist")
cmetric = importlib.import_module("repro.core.cmetric")
attn_mod = importlib.import_module("repro.kernels.attention")

EVENTS = 1 << 22
PIPELINE_EVENTS = 1 << 20
HIST_SAMPLES = 1 << 17
HIST_BINS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_native_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_fold_compiles(one_chip):
    _assert_native_kernel(fold_mod.fold.lower(
        _spec((EVENTS,), jnp.float32, one_chip),
        _spec((EVENTS,), jnp.int32, one_chip),
        _spec((3,), jnp.float32, one_chip),
        interpret=False))


def test_carry_cumsum_compiles(one_chip):
    _assert_native_kernel(fold_mod.carry_cumsum.lower(
        _spec((EVENTS,), jnp.float32, one_chip),
        _spec((EVENTS,), jnp.float32, one_chip),
        _spec((2,), jnp.float32, one_chip),
        interpret=False))


def test_tag_hist_compiles(one_chip):
    _assert_native_kernel(hist_mod.hist.lower(
        _spec((HIST_SAMPLES,), jnp.int32, one_chip),
        _spec((HIST_SAMPLES,), jnp.float32, one_chip),
        num_bins=HIST_BINS, interpret=False))


def test_fused_pipeline_compiles(one_chip):
    _assert_native_kernel(cmetric._fused_pipeline.lower(
        _spec((PIPELINE_EVENTS,), jnp.float32, one_chip),
        _spec((PIPELINE_EVENTS,), jnp.int32, one_chip),
        _spec((PIPELINE_EVENTS,), jnp.int32, one_chip),
        num_workers=64, block=2048, interpret=False))


def test_causal_attention_compiles(one_chip):
    """The training cell's attention (internvl2: 16 query and 8 KV heads
    of 128, 2 x 1280 positions): the forward and the fused backward."""
    b, h, kv, s, hd = 2, 16, 8, 1280, 128

    def loss(q, k, v):
        out = attn_mod.causal_attention(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _spec((b, h, s, hd), jnp.bfloat16, one_chip),
        _spec((b, kv, s, hd), jnp.bfloat16, one_chip),
        _spec((b, kv, s, hd), jnp.bfloat16, one_chip))
    text = lowered.compile().as_text()
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):
        assert kernel in text
