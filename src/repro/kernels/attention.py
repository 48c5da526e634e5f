"""Causal self-attention on the TPU through the installed splash kernel.

``causal_attention`` runs ``jax.experimental.pallas.ops.tpu.splash_attention``
over grouped-query operands in heads-major layout: one MQA kernel serves the
``G = H / KV`` query heads of each KV head, mapped over batch and KV heads,
so KV is never repeated.  The mask is ``CausalMask``: blocks above the
diagonal are skipped in the forward and in the backward, and the (S, S)
scores live in VMEM a block at a time, never in HBM.  The backward is one
fused kernel (dq, dk and dv in one pass over the blocks).  The forward
names its output and log-sum-exp ``RESIDUALS``, so that a checkpoint
policy can keep them for the backward instead of running the forward
again (``repro.train.step.SAVE_DOTS``).

Numerics: bf16 (compute-dtype) q/k/v into the MXU with f32 accumulation,
the softmax in f32 (online, with the log-sum-exp kept for the backward).
The backward casts the probabilities to the operands' dtype before its
matmuls, as the einsum path does; the installed forward multiplies v by
the f32 probabilities, so its output is rounded once, where the einsum
path rounds the probabilities to bf16 first.

Block sizes follow from the sequence length by one rule (``block_sizes``).
The kernel needs ``S`` and the head dim to be multiples of 128
(``supports``).  Like the profiler's kernels (``repro.kernels.ops``) it
compiles natively on a TPU and is interpreted on any other backend.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.ops import default_interpret

LANES = 128
# q and kv tile edges, largest first; the rule takes the largest that
# divides the sequence (PERF.md, model step: the rules measured)
BLOCKS = (640, 512, 256, 128)
# checkpoint name of the forward's output and log-sum-exp
RESIDUALS = "attention_residuals"


def supports(seq_len: int, head_dim: int) -> bool:
    """Whether the kernel tiles a sequence of ``seq_len`` with heads of
    ``head_dim``."""
    return seq_len % LANES == 0 and head_dim % LANES == 0


def block_sizes(seq_len: int) -> splash.BlockSizes:
    """The forward's and the fused backward's tiles for ``seq_len``: the
    largest of ``BLOCKS`` that divides it, for q and kv alike."""
    b = next(b for b in BLOCKS if seq_len % b == 0)
    return splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=16)
def _kernel(seq_len: int, groups: int, interpret: bool):
    """The MQA kernel for ``groups`` query heads over one KV head.  Its
    mask tables are numpy-built constants, made once per shape and outside
    any trace."""
    mask = splash.MultiHeadMask([splash.CausalMask((seq_len, seq_len))]
                                * groups)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa(
            mask, block_sizes=block_sizes(seq_len), head_shards=1,
            q_seq_shards=1, interpret=interpret,
            residual_checkpoint_name=RESIDUALS)


def causal_attention(q, k, v, *, interpret: bool | None = None):
    """``softmax(q kᵀ) v`` under the causal mask, per KV head's group.

    q: (B, H, S, hd), already scaled; k, v: (B, KV, S, hd) with ``KV``
    dividing ``H`` (query head ``h`` reads KV head ``h // (H / KV)``).
    Returns (B, H, S, hd) in q's dtype."""
    b, h, s, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    interpret = default_interpret() if interpret is None else interpret
    kernel = _kernel(s, g, interpret)
    out = jax.vmap(kernel)(q.reshape(b * kv, g, s, hd),
                           k.reshape(b * kv, s, hd),
                           v.reshape(b * kv, s, hd))
    return out.reshape(b, h, s, hd)
