"""Pipeline parallelism: GPipe schedule via shard_map + collective_permute.

The layer stack is split into ``n_stages`` stages sharded over a ``stage``
mesh axis; microbatches flow stage-to-stage with ``lax.ppermute``.  The
schedule is the classic GPipe fill/steady/drain loop of length
``n_micro + n_stages - 1`` — the warm-up and drain slots are *bubbles*, i.e.
exactly the reduced-parallelism intervals GAPP's CMetric is built to expose
(see examples/pipeline_bubbles.py: the per-stage busy intervals of this
schedule are ingested into the profiler and the bubble fraction appears as
stage-0/stage-N-1 criticality).

This module is exercised by tests and examples on a host-local mesh; the
40-cell dry-run uses the assigned DP×TP mesh (no stage axis) per the
assignment.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn, mesh: Mesh, n_stages: int, n_micro: int,
          stage_axis: str = "stage"):
    """Build a pipelined apply: (stacked_params, x) -> y.

    stage_fn: (params_for_stage, activation) -> activation, same shape.
    stacked_params: leaves with leading dim n_stages (sharded over stage).
    x: (n_micro, mb, ...) microbatched input, replicated over stage.
    Returns y of the same shape (outputs of the last stage).
    """

    def pipelined(stacked_params, x):
        def body(local_params, xloc):
            # local_params leaves: (1, ...) -> squeeze; xloc: full (replicated)
            params = jax.tree.map(lambda p: p[0], local_params)
            idx = jax.lax.axis_index(stage_axis)
            n_steps = n_micro + n_stages - 1
            mb_shape = xloc.shape[1:]
            carry = jnp.zeros(mb_shape, xloc.dtype)   # incoming activation
            outs = jnp.zeros_like(xloc)               # last-stage outputs
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            for t in range(n_steps):
                mb_id = t - idx                        # microbatch at stage
                # stage 0 ingests microbatch t (if any) from x
                feed = xloc[jnp.clip(t, 0, n_micro - 1)]
                inp = jnp.where(idx == 0, feed, carry)
                y = stage_fn(params, inp)
                active = (mb_id >= 0) & (mb_id < n_micro)
                y = jnp.where(active, y, jnp.zeros_like(y))
                # last stage banks its output at slot mb_id
                is_last = idx == n_stages - 1
                slot = jnp.clip(mb_id, 0, n_micro - 1)
                outs = jnp.where(
                    active & is_last,
                    jax.lax.dynamic_update_index_in_dim(outs, y, slot, 0),
                    outs)
                # shift activations to the next stage
                carry = jax.lax.ppermute(y, stage_axis, perm)
            # deliver outs (only the last stage's copy is meaningful):
            # masked psum broadcasts it to every stage member
            if n_stages > 1:
                outs = jax.lax.psum(
                    jnp.where(idx == n_stages - 1, outs,
                              jnp.zeros_like(outs)), stage_axis)
            return outs

        pspec = jax.tree.map(lambda _: P(stage_axis), stacked_params)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P()), out_specs=P(),
            check_vma=False,
        )(stacked_params, x)

    return pipelined


def schedule_intervals(n_stages: int, n_micro: int, t_stage: float = 1.0):
    """The GPipe schedule as (stage, start, end) busy intervals — the
    ground-truth activity trace used to drive the profiler in tests and in
    examples/pipeline_bubbles.py.  Bubble fraction = (n_stages-1)/(n_micro +
    n_stages-1)."""
    out = []
    for s in range(n_stages):
        for m in range(n_micro):
            t0 = (s + m) * t_stage
            out.append((s, t0, t0 + t_stage))
    return out
