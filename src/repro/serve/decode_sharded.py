"""Explicit sequence-sharded flash-decode via shard_map.

When the KV cache's sequence dim is sharded over the ``model`` axis, each
chip attends over its local cache slice and the partial softmaxes are
combined with the numerically-stable two-pass rule:

    m  = psum-max of local max
    l  = psum of exp(local_max - m) · local_sum
    o  = psum of exp(local_max - m) · local_weighted_V   / l

GSPMD derives an equivalent program from the jnp path in
``attention.decode_attention``; this explicit version pins the collective
schedule (3 small psums instead of whatever the partitioner picks) and is
the decode-cell §Perf lever.  Works for any kv_heads (no head-divisibility
constraint) — the reason sequence sharding is the default decode layout
(DESIGN §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig


def flash_decode_local(q, k_local, v_local, valid_local, axis_name: str):
    """One-token attention over a sequence-sharded cache.

    q: (B, 1, H, hd) replicated over ``axis_name``;
    k_local/v_local: (B, L/n, KV, hd); valid_local: (B, L/n) bool.
    Returns (B, 1, H, hd), replicated.
    """
    b, _, h, hd = q.shape
    kv = k_local.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd) * (hd ** -0.5)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_local,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid_local[:, None, None, :], s, -jnp.inf)
    m_loc = jnp.max(s, axis=-1, keepdims=True)              # (B,KV,G,1)
    m = jax.lax.pmax(m_loc, axis_name)
    # guard fully-masked shards: exp(-inf - m) -> 0
    w = jnp.exp(jnp.where(jnp.isfinite(s), s - m, -jnp.inf))
    l_loc = jnp.sum(w, axis=-1, keepdims=True)
    o_loc = jnp.einsum("bkgs,bskh->bkgh", w.astype(v_local.dtype), v_local)
    l = jax.lax.psum(l_loc, axis_name)
    o = jax.lax.psum(o_loc.astype(jnp.float32), axis_name)
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def make_flash_decode(mesh, cfg: ModelConfig, axis_name: str = "model"):
    """Returns f(q, k, v, valid) with k/v sequence-sharded over axis_name."""
    fn = functools.partial(flash_decode_local, axis_name=axis_name)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(None, axis_name, None, None),
                  P(None, axis_name, None, None), P(None, axis_name)),
        out_specs=P(),
        check_vma=False,
    )
