"""Pallas TPU kernels: the CMetric interval fold (paper §4.1 hot loop).

At fleet scale the profiler ingests tens of millions of events per run
(every span begin/end across hosts, stages and experts).  The fold below is
the post-processing hot spot the paper keeps fast ("PPT" column of Table 2):
for every event we need the active-worker count during the preceding
interval and the running ``global_cm`` prefix

    n[i]   = n_in  + Σ_{e<=i} delta[e]
    gcm[i] = gcm_in + Σ_{e<i}  dt[e] / max(n[e], 1) * (n[e] > 0)

i.e. two coupled prefix scans over the event stream.  TPU adaptation: the
stream is tiled into (1, B) VMEM blocks (B a multiple of 128 lanes); within a
block the scan is a Hillis–Steele shift-add ladder (log2 B vector steps on
the VPU); the inter-block carry (running count, running gcm, idle time) lives
in a small VMEM accumulator block that persists across the sequential TPU
grid, written whole (Mosaic stores no scalars into VMEM).  HBM traffic is
exactly 3 input + 2 output streams — the kernel is memory-bound by design,
matching its roofline on the VPU.

Both kernels are **carry-resumable**: the scan state enters as a small
``carry0`` input and the final state comes back in the scalars output, so a
log too large for one call (or one host) streams through in chunks —
exactly the cross-block carry trick, lifted one level up to cross-call
(see :class:`repro.core.cmetric.FoldCarry`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _ladder_cumsum(x):
    """Inclusive Hillis-Steele cumsum along the last axis of a (1, B) block.

    Unrolled log2(B) shift-add steps; every step is a full-width VPU add, so
    the ladder costs ~log2(B) vector ops per block (B must be a power of 2).
    """
    b = x.shape[-1]
    shift = 1
    while shift < b:
        shifted = jnp.pad(x, ((0, 0), (shift, 0)))[:, :b]
        x = x + shifted
        shift *= 2
    return x


def _lanes(*vals):
    """Pack (1, 1) values into lanes 0..k-1 of one (1, LANES) row.

    The TPU has no scalar store into VMEM, so the carry and the final
    scalars are written as whole blocks built with a lane ``iota``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out = jnp.zeros((1, LANES), jnp.float32)
    for k, v in enumerate(vals):
        out = jnp.where(lane == k, v, out)
    return out


def _fold_kernel(dt_ref, delta_ref, carry0_ref, n_ref, gcm_ref, carry_ref,
                 scalars_ref):
    """Grid is 1-D over event blocks; TPU executes it sequentially, so the
    carry block implements the cross-block prefix.  ``carry0`` seeds the
    scan (count, gcm, idle) so a chunked caller can resume a prior fold."""
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _init():
        carry_ref[...] = carry0_ref[...]

    count_in = carry_ref[:, 0:1]   # running count (f32; exact to 2^24)
    gcm_in = carry_ref[:, 1:2]     # running gcm
    idle_in = carry_ref[:, 2:3]    # running idle time

    delta = delta_ref[...].astype(jnp.float32)
    dt = dt_ref[...]

    n = _ladder_cumsum(delta) + count_in            # inclusive count prefix
    pos = n > 0.5
    contrib = jnp.where(pos, dt / jnp.maximum(n, 1.0), 0.0)
    incl = _ladder_cumsum(contrib)
    gcm = gcm_in + incl - contrib                    # exclusive prefix
    idle_blk = jnp.sum(jnp.where((~pos) & (dt > 0), dt, 0.0), axis=1,
                       keepdims=True)

    n_ref[...] = n.astype(jnp.int32)
    gcm_ref[...] = gcm

    count = n[:, -1:]
    total = gcm_in + incl[:, -1:]
    idle = idle_in + idle_blk
    carry_ref[...] = _lanes(count, total, idle)

    @pl.when(blk == pl.num_programs(0) - 1)
    def _finalize():
        scalars_ref[...] = _lanes(total, idle, count)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fold(dt, deltas, carry=None, *, block: int = 2048,
         interpret: bool = True):
    """Blocked, carry-resumable CMetric fold.  See
    :func:`repro.kernels.ref.fold_ref`.

    Args:
      dt:     f32[E] interval lengths (last entry 0).
      deltas: i32[E] state-change deltas (+1/-1, 0 padding).
      carry:  optional (count, gcm, idle) f32 triple resuming a prior call
              (defaults to a fresh scan).
      block:  events per VMEM tile (power of two, multiple of 128).

    Returns (n i32[E], gcm f32[E], total_cm f32, idle f32, count f32) — the
    final (total_cm, idle, count) triple is the carry for the next chunk.
    """
    assert block % LANES == 0 and block & (block - 1) == 0, block
    e = dt.shape[0]
    pad = (-e) % block
    dt_p = jnp.pad(dt.astype(jnp.float32), (0, pad)).reshape(1, -1)
    de_p = jnp.pad(deltas.astype(jnp.int32), (0, pad)).reshape(1, -1)
    nblk = dt_p.shape[1] // block
    if carry is None:
        carry = (0.0, 0.0, 0.0)
    carry0 = jnp.zeros((1, LANES), jnp.float32).at[0, :3].set(
        jnp.asarray(carry, jnp.float32))

    n, gcm, _, scalars = pl.pallas_call(
        _fold_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),  # carry seed
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),  # carry accumulator
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),  # final scalars
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nblk * block), jnp.int32),
            jax.ShapeDtypeStruct((1, nblk * block), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(dt_p, de_p, carry0)
    return (n[0, :e], gcm[0, :e], scalars[0, 0], scalars[0, 1],
            scalars[0, 2])


def _cumsum_kernel(contrib_ref, idle_ref, carry0_ref, g_ref, carry_ref,
                   scalars_ref):
    """Carry-seeded dual prefix: inclusive cumsum of ``contrib`` (the
    per-event global_cm contributions, already divided by the active count
    host-side) plus a running idle total."""
    blk = pl.program_id(0)

    @pl.when(blk == 0)
    def _init():
        carry_ref[...] = carry0_ref[...]

    g_in = carry_ref[:, 0:1]       # running gcm
    idle_in = carry_ref[:, 1:2]    # running idle

    contrib = contrib_ref[...]
    incl = _ladder_cumsum(contrib)
    g_ref[...] = g_in + incl                  # inclusive: gcm *at* event i
    idle_blk = jnp.sum(idle_ref[...], axis=1, keepdims=True)

    g_end = g_in + incl[:, -1:]
    idle = idle_in + idle_blk
    carry_ref[...] = _lanes(g_end, idle)

    @pl.when(blk == pl.num_programs(0) - 1)
    def _finalize():
        scalars_ref[...] = _lanes(g_end, idle)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def carry_cumsum(contrib, idle_contrib, carry, *, block: int = 2048,
                 interpret: bool = True):
    """Carry-seeded blocked cumsum used by the Pallas chunked fold.

    Returns (g f32[E], gcm_end f32, idle_end f32): ``g[i]`` is the carried
    gcm value *at* event i (inclusive of event i's contribution).
    """
    assert block % LANES == 0 and block & (block - 1) == 0, block
    e = contrib.shape[0]
    pad = (-e) % block
    c_p = jnp.pad(contrib.astype(jnp.float32), (0, pad)).reshape(1, -1)
    i_p = jnp.pad(idle_contrib.astype(jnp.float32), (0, pad)).reshape(1, -1)
    nblk = c_p.shape[1] // block
    carry0 = jnp.zeros((1, LANES), jnp.float32).at[0, :2].set(
        jnp.asarray(carry, jnp.float32))

    g, _, scalars = pl.pallas_call(
        _cumsum_kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nblk * block), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(c_p, i_p, carry0)
    return g[0, :e], scalars[0, 0], scalars[0, 1]
