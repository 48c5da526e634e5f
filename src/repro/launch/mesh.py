"""Production meshes (TPU v5e target).

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must see the real single device.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None,
                   stage: int | None = None):
    """Small mesh over whatever local devices exist (tests/examples)."""
    n = len(jax.devices())
    if stage is not None:
        return make_mesh((stage,), ("stage",))
    data = data if data is not None else n // model
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (~quoted per-direction)
