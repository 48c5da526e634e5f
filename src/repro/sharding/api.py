"""Logical-axis sharding: models annotate, rules bind axes to the mesh.

Models never mention physical mesh axes.  They call
``constrain(x, "batch", "seq", "embed")`` with *logical* axis names; a
:class:`ShardingRules` table (chosen per arch × shape by the launcher) maps
logical names to physical mesh axes, and ``use_mesh`` installs the binding
for a region of code.  Outside any binding the constraints are no-ops, so
the same model code runs single-device (smoke tests) and on the production
mesh (dry-run) unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


# Default logical→physical table.  "dp" is the data-parallel super-axis
# (pod × data on the multi-pod mesh).
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),      # activation batch
    "seq": None,                   # activation sequence (set to "model" for SP)
    "resid_seq": None,             # residual stream between blocks — bind to
                                   # "model" for Megatron-style sequence
                                   # parallelism (AG at block entry, RS at
                                   # exit; intra-block tensors keep TP)
    "cache_seq": None,             # KV-cache sequence (set to "model" for
                                   # sequence-sharded flash-decode)
    "embed": None,                 # d_model — replicated
    "heads": "model",              # attention heads (TP)
    "kv_heads": None,              # kv heads — replicated unless divisible
    "head_dim": None,
    "mlp": "model",                # FFN hidden (TP)
    "vocab": "model",              # embedding/logits vocab (TP)
    "experts": "model",            # MoE expert axis of *weights* (EP)
    "experts_act": "model",        # MoE expert axis of dispatched activations
    "expert_in": None,             # per-expert FFN input dim (FSDP-style
                                   # weight sharding for huge expert tables)
    "expert_mlp": None,            # per-expert FFN hidden (TP fallback for
                                   # E < mesh 'model' size)
    "lru": "model",                # RG-LRU width
    "rwkv_heads": "model",
    "stage": "stage",              # pipeline stage (pipeline/ only)
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    table: dict

    def spec(self, *logical) -> P:
        phys = []
        for name in logical:
            if name is None:
                phys.append(None)
            else:
                phys.append(self.table.get(name))
        return P(*phys)

    def replace(self, **updates) -> "ShardingRules":
        t = dict(self.table)
        t.update(updates)
        return ShardingRules(t)


def default_rules(**updates) -> ShardingRules:
    return ShardingRules(dict(DEFAULT_RULES)).replace(**updates) \
        if updates else ShardingRules(dict(DEFAULT_RULES))


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: ShardingRules | None = None):
    prev = getattr(_state, "binding", None)
    _state.binding = (mesh, rules or default_rules()) if mesh is not None \
        else None
    try:
        yield
    finally:
        _state.binding = prev


def current_binding():
    return getattr(_state, "binding", None)


def bound_device():
    """A device the traced computation runs on: the bound mesh's first, or
    the default device when nothing is bound."""
    binding = current_binding()
    return binding[0].devices.flat[0] if binding else jax.devices()[0]


def axis_size(name: str) -> int:
    """Size of the physical axis a logical name maps to (1 if unbound)."""
    b = current_binding()
    if b is None:
        return 1
    mesh, rules = b
    phys = rules.table.get(name)
    if phys is None:
        return 1
    if isinstance(phys, tuple):
        out = 1
        for a in phys:
            out *= mesh.shape[a]
        return out
    return mesh.shape[phys]


def filter_entry(dim: int, names, mesh, used: set | None = None) -> object:
    """Resolve one PartitionSpec entry against a mesh: drop axes the mesh
    doesn't have (e.g. 'pod' on the single-pod mesh), axes already used by
    an earlier dimension (first use wins), and the whole entry if the
    remaining axis product doesn't divide the dimension."""
    if names is None:
        return None
    ns = tuple(n for n in (names if isinstance(names, tuple) else (names,))
               if n in mesh.shape and (used is None or n not in used))
    if not ns:
        return None
    size = 1
    for n in ns:
        size *= mesh.shape[n]
    if dim <= 0 or dim % size != 0:
        return None
    if used is not None:
        used.update(ns)
    return ns if len(ns) > 1 else ns[0]


def filter_spec(shape: tuple, spec: P, mesh) -> P:
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    used: set = set()
    return P(*[filter_entry(d, n, mesh, used) for d, n in
               zip(shape, entries)])


def _filter_spec(x, spec: P) -> P | None:
    b = current_binding()
    if b is None:
        return None
    mesh, _ = b
    return filter_spec(x.shape, spec, mesh)


def constrain(x, *logical):
    """``with_sharding_constraint`` against the active binding (no-op when
    unbound or when an axis size doesn't divide)."""
    b = current_binding()
    if b is None:
        return x
    mesh, rules = b
    spec = _filter_spec(x, rules.spec(*logical))
    if spec is None or all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*logical) -> NamedSharding:
    b = current_binding()
    assert b is not None, "named_sharding requires an active use_mesh binding"
    mesh, rules = b
    return NamedSharding(mesh, rules.spec(*logical))
