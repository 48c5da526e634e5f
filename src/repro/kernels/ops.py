"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to False on a TPU backend, where the kernels compile
natively through Mosaic (TPU v5e is the target), and to True on every
other backend, where Pallas interprets them (the CPU test suite).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import cmetric_fold as _fold
from repro.kernels import tag_hist as _hist
from repro.obs import spans


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def cmetric_fold(times_s, deltas, carry=None, *, block: int = 2048,
                 interpret: bool | None = None):
    """Fold an event stream into (n, gcm, total_cm, idle, count).

    ``times_s`` are event times (f32 seconds, rebased); dt is derived here so
    callers hand over the raw stream.  ``carry`` optionally resumes a prior
    fold from its (count, gcm, idle) scalars — the final (total_cm, idle,
    count) triple of the return value is exactly the next chunk's carry.
    """
    interpret = default_interpret() if interpret is None else interpret
    dt = jnp.concatenate([times_s[1:] - times_s[:-1],
                          jnp.zeros((1,), times_s.dtype)])
    return _fold.fold(dt, deltas, carry, block=block, interpret=interpret)


def fold_chunk_prefix(gcm0: float, idle0: float, contrib, idle_contrib, *,
                      block: int = 2048, interpret: bool | None = None):
    """Device prefix for the chunked CMetric fold (see
    :func:`repro.core.cmetric._fold_chunk`): carry-seeded blocked cumsum of
    the per-event contributions on the Pallas scan kernel.

    Returns ``(g float64[E], idle_end float)`` where ``g[i]`` is the
    global_cm value at event ``i``.  The wait for the device's result
    (which queues behind whatever the device runs already) is the span
    ``profiler/fold_wait``.
    """
    interpret = default_interpret() if interpret is None else interpret
    g, _, idle_end = _fold.carry_cumsum(
        jnp.asarray(contrib, jnp.float32),
        jnp.asarray(idle_contrib, jnp.float32),
        jnp.asarray([gcm0, idle0], jnp.float32),
        block=block, interpret=interpret)
    with spans.span("profiler/fold_wait"):
        jax.block_until_ready((g, idle_end))
    return np.asarray(g, np.float64), float(idle_end)


def tag_histogram(tags, weights=None, *, num_bins: int, block: int = 1024,
                  interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    return _hist.hist(tags, weights, num_bins=num_bins, block=block,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("num_workers", "block",
                                             "interpret"))
def _fused_pipeline(times_s, workers, deltas, num_workers: int, block: int,
                    interpret: bool):
    """Fold (Pallas kernel) + pairing + segment-sum as ONE jitted program —
    the gcm prefix never leaves the device between stages."""
    from repro.core import cmetric as cmetric_lib  # avoid import cycle
    _, gcm, _, idle, _ = cmetric_fold(times_s, deltas, block=block,
                                      interpret=interpret)
    return cmetric_lib._pair_core(times_s, workers, deltas, gcm, idle,
                                  num_workers)


def compute_pallas(log, *, block: int = 2048, interpret: bool | None = None):
    """CMetric backend: the Pallas fold kernel fused with the shared pairing
    /aggregation core (see :func:`repro.core.cmetric.drive_pairing`)."""
    from repro.core import cmetric as cmetric_lib  # avoid import cycle
    interpret = default_interpret() if interpret is None else interpret
    return cmetric_lib.drive_pairing(
        log, functools.partial(_fused_pipeline, block=block,
                               interpret=interpret))
