"""The profiled job's weights, made on the device from the seed.

The benchmark makes them, hands them to the program, and makes them again
for the plain reference after the window: neither side takes weights from
the other.  The tree is laid out as the program's ``init_lm`` lays out a
decoder with a patch projector (``embed``, ``final_norm``, ``frontend``,
``groups[i]["b0"]``, ``lm_head``); every leaf is drawn on its own from
``fold_in(key, leaf index)``, so one leaf can be drawn again alone.
"""
from __future__ import annotations

import math

import numpy as np


def key_of(seed: int):
    """A JAX key from any whole-number seed (beyond 32 bits too)."""
    import jax
    return jax.random.key(int(np.random.default_rng(seed).integers(2**31)))


def layout(cfg: dict) -> dict:
    """``{path: (shape, init_scale)}`` in the program's tree order; a scale
    of 0 is a norm scale, initialised to zero (``x * (1 + scale)``)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    f = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    fd = cfg["vision"]["patch_embed_dim"]
    out = {
        ("embed",): ((v, d), 1 / math.sqrt(d)),
        ("final_norm",): ((d,), 0.0),
        ("frontend",): ((fd, d), 1 / math.sqrt(fd)),
    }
    for i in range(cfg["num_hidden_layers"]):
        b = ("groups", i, "b0")
        out.update({
            b + ("attn", "wk"): ((d, kv * hd), 1 / math.sqrt(d)),
            b + ("attn", "wo"): ((h * hd, d), 1 / math.sqrt(h * hd)),
            b + ("attn", "wq"): ((d, h * hd), 1 / math.sqrt(d)),
            b + ("attn", "wv"): ((d, kv * hd), 1 / math.sqrt(d)),
            b + ("ffn", "down"): ((f, d), 1 / math.sqrt(f)),
            b + ("ffn", "gate"): ((d, f), 1 / math.sqrt(d)),
            b + ("ffn", "up"): ((d, f), 1 / math.sqrt(d)),
            b + ("ln1",): ((d,), 0.0),
            b + ("ln2",): ((d,), 0.0),
        })
    out[("lm_head",)] = ((d, v), 1 / math.sqrt(d))
    return out


def leaf(key, index: int, shape, scale: float):
    import jax
    import jax.numpy as jnp
    if scale == 0.0:
        return jnp.zeros(shape, jnp.float32)
    k = jax.random.fold_in(key, index)
    return jax.random.normal(k, shape, jnp.float32) * jnp.float32(scale)


def nest(flat: dict) -> dict:
    """``{path: leaf}`` -> the nested tree (``groups`` is a list)."""
    tree: dict = {"groups": []}
    for path, x in flat.items():
        if path[0] != "groups":
            tree[path[0]] = x
            continue
        _, i, block, *rest = path
        while len(tree["groups"]) <= i:
            tree["groups"].append({block: {}})
        node = tree["groups"][i][block]
        for k in rest[:-1]:
            node = node.setdefault(k, {})
        node[rest[-1]] = x
    return tree


def make(cfg: dict, seed: int):
    """The whole tree in float32 (the type the job trains), in one jitted
    call on the default device."""
    import jax
    spec = layout(cfg)

    @jax.jit
    def build(key):
        return nest({p: leaf(key, i, s, sc)
                     for i, (p, (s, sc)) in enumerate(spec.items())})
    return build(key_of(seed))


def change_norms(cfg: dict):
    """``f(params, key) -> f32[leaves]``: per leaf, the norm of the
    change from the seed's initial value (drawn again, leaf by leaf), in
    ``layout`` order."""
    import jax
    import jax.numpy as jnp
    spec = layout(cfg)

    @jax.jit
    def norms(params, key):
        flat = flatten(params, spec)
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(flat[p] - leaf(key, i, s, sc))))
            for i, (p, (s, sc)) in enumerate(spec.items())])
    return norms


def flatten(tree, spec: dict) -> dict:
    out = {}
    for p in spec:
        node = tree
        for k in p:
            node = node[k]
        out[p] = node
    return out


def leaf_norms(cfg: dict):
    """``f(tree) -> f32[leaves]``: per leaf norm, in ``layout`` order."""
    import jax
    import jax.numpy as jnp
    spec = layout(cfg)

    @jax.jit
    def norms(tree):
        flat = flatten(tree, spec)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(flat[p])))
                          for p in spec])
    return norms
