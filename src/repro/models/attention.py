"""Attention substrate: GQA with RoPE, qk-norm, bias, local windows, caches.

Covers every assigned attention variant:
  * MHA / GQA with arbitrary kv_heads (deepseek 32, qwen1.5 20, qwen3 8, ...)
  * qk_norm (qwen3), QKV bias (qwen1.5), logit softcap (grok)
  * sliding-window ("local") attention with either a banded mask (baseline)
    or exact chunked evaluation (optimised path for long prefill)
  * bidirectional encoder attention and cross attention (seamless enc-dec)
  * decode against a KV cache, including the sequence-sharded two-pass
    flash-decode combine used when the cache is sharded over the model axis.

Full-sequence causal self-attention takes the fused splash kernel
(``repro.kernels.attention``) where the trace can observe that it applies:
a TPU, one device, positions 0..S-1, no window and no soft cap, and shapes
the kernel tiles.  Every other call takes the einsum path (``_sdpa``), the
reference.  ``attention_stats()`` counts the choices.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp

from repro.kernels import attention as attn_kernel
from repro.models.common import ModelConfig, dense_init, rms_norm, rope, softcap
from repro.sharding.api import bound_device, constrain, current_binding

NEG_INF = -2.3819763e38

_stats_lock = threading.Lock()
_stats = dict(splash=0, einsum=0, reason=None,  # guarded-by: _stats_lock
              block_q=None, block_kv=None)
_layers = threading.local()


def attention_stats() -> dict:
    """Full-sequence attention layers of the traced programs that took the
    splash kernel (``splash``) or the einsum path (``einsum``), the last
    fallback's reason (``platform``, ``mask``, ``softcap``, ``mesh`` or
    ``shape``) and the last kernel's block sizes.  A traced call counts
    the layers it stands for (``counted_as``)."""
    with _stats_lock:
        return dict(_stats)


@contextlib.contextmanager
def counted_as(layers: int):
    """Each attention call traced inside stands for ``layers`` layers: a
    layer group traced once for all its repeats (a checkpoint's cached
    trace, a scan's body) counts each repeat, and 0 leaves
    ``attention_stats()`` as it is (abstract evaluations that are not the
    step's layers, and repeats whose trace was counted already)."""
    prev = getattr(_layers, "n", 1)
    _layers.n = prev * layers
    try:
        yield
    finally:
        _layers.n = prev


def _count(reason: str | None, blocks=None) -> None:
    n = getattr(_layers, "n", 1)
    if not n:
        return
    with _stats_lock:
        if reason is None:
            _stats["splash"] += n
            _stats.update(block_q=blocks.block_q, block_kv=blocks.block_kv)
        else:
            _stats["einsum"] += n
            _stats["reason"] = reason


def _platform() -> str:
    """Platform of the device the traced step runs on."""
    return bound_device().platform


def _kernel_fallback(x, cfg: ModelConfig, *, window, causal, kv_x,
                     arange_positions: bool) -> str | None:
    """Why this call keeps the einsum path, or None where the kernel
    applies."""
    if _platform() != "tpu":
        return "platform"
    if kv_x is not None or not causal or window is not None \
            or not arange_positions:
        return "mask"
    if cfg.logits_softcap:
        return "softcap"
    binding = current_binding()
    if binding is not None and binding[0].devices.size > 1:
        return "mesh"
    if not attn_kernel.supports(x.shape[1], cfg.hd):
        return "shape"
    return None


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype=cfg.param_dtype),
        "wk": dense_init(ks[1], (d, kv * hd), dtype=cfg.param_dtype),
        "wv": dense_init(ks[2], (d, kv * hd), dtype=cfg.param_dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype=cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((kv * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((kv * hd,), cfg.param_dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), cfg.param_dtype)
        p["k_norm"] = jnp.zeros((hd,), cfg.param_dtype)
    return p


def _project_q(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    q = x @ p["wq"].astype(cfg.compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cfg.compute_dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    q = rope(q, positions, cfg.rope_theta)
    return constrain(q, "batch", "seq", "heads", "head_dim")


def _project_kv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    k = x @ p["wk"].astype(cfg.compute_dtype)
    v = x @ p["wv"].astype(cfg.compute_dtype)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(cfg.compute_dtype)
        v = v + p["bv"].astype(cfg.compute_dtype)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    if positions is not None:
        k = rope(k, positions, cfg.rope_theta)
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    return k, v


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """Grouped scaled dot-product attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); mask: broadcastable to
    (B, KV, G, Sq, Sk) or None.

    opt_level>=1 switches to the repeated-KV layout: scores carry the full
    H head dim (shardable over the model axis even when KV < TP degree —
    the grouped (KV, G) layout replicates the O(S²) scores whenever KV
    doesn't divide the axis, which is every GQA arch here).  The repeat
    costs O(S·H·hd) extra KV bytes — negligible next to O(H·S²) scores.
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if cfg.opt_level >= 1:
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        q = q * (hd ** -0.5)
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                            preferred_element_type=jnp.float32)
        scores = softcap(scores, cfg.logits_softcap)
        if mask is not None:
            scores = jnp.where(
                jnp.broadcast_to(mask, (b, kv, g) + scores.shape[-2:])
                .reshape(b, h, *scores.shape[-2:])
                if mask.shape[1:3] != (1, 1) else mask.reshape(
                    mask.shape[0], 1, *mask.shape[-2:]),
                scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)
    q = q.reshape(b, sq, kv, g, hd) * (hd ** -0.5)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = softcap(scores, cfg.logits_softcap)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(b, sq, h, hd)


def _band_mask(q_pos, k_pos, window: int | None, causal: bool):
    """(B?, Sq, Sk) boolean mask; window is the local-attention band."""
    m = jnp.ones(jnp.broadcast_shapes(q_pos[..., :, None].shape,
                                      k_pos[..., None, :].shape), bool)
    if causal:
        m &= q_pos[..., :, None] >= k_pos[..., None, :]
    if window is not None:
        m &= q_pos[..., :, None] - k_pos[..., None, :] < window
    return m


def attention(p, x, positions, cfg: ModelConfig, *, window: int | None,
              causal: bool = True, kv_x=None, kv_positions=None,
              arange_positions: bool = False) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).  ``kv_x`` switches to
    cross attention (keys/values from encoder memory, no causal mask).
    ``arange_positions`` says that ``positions`` is 0..S-1 in every row, so
    that the causal mask is exactly the kernel's."""
    reason = _kernel_fallback(x, cfg, window=window, causal=causal,
                              kv_x=kv_x, arange_positions=arange_positions)
    q = _project_q(p, x, cfg, positions)
    if kv_x is None:
        k, v = _project_kv(p, x, cfg, positions)
    else:
        k, v = _project_kv(p, kv_x, cfg, kv_positions)
    if reason is None:
        out = _splash(q, k, v, cfg)
    else:
        _count(reason)
        mask = None
        if kv_x is None:
            mask = _band_mask(positions, positions, window,
                              causal)[:, None, None]   # (B, 1, 1, Sq, Sk)
        out = _sdpa(q, k, v, mask, cfg)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    b, s, _, _ = out.shape
    y = out.reshape(b, s, -1) @ p["wo"].astype(cfg.compute_dtype)
    return constrain(y, "batch", "seq", "embed")


def _splash(q, k, v, cfg: ModelConfig):
    """``_sdpa`` under the causal mask, through the splash kernel: q, k, v
    transposed to heads-major for it and its output back (XLA folds the
    transposes into the layouts of the projections and the output
    matmul)."""
    _count(None, attn_kernel.block_sizes(q.shape[1]))
    heads = lambda a: a.transpose(0, 2, 1, 3)          # (B, heads, S, hd)
    out = attn_kernel.causal_attention(heads(q) * (cfg.hd ** -0.5),
                                       heads(k), heads(v))
    return heads(out)


def attention_blockwise(p, x, positions, cfg: ModelConfig, *,
                        q_chunk: int, window: int | None = None,
                        causal: bool = True) -> jnp.ndarray:
    """Exact full attention evaluated per q-chunk (flash-style, jnp-level).

    The (Sq, Sk) score matrix is never materialised whole — only
    (q_chunk, Sk) slabs, unrolled as straight-line HLO (no while loop, so
    ``cost_analysis`` stays faithful and XLA can overlap slabs).  Causal
    chunks additionally skip keys beyond the chunk's last query.  This is
    the optimised path for long-prefill dense archs where the S² scores of
    the naive path dominate the memory term (§Perf)."""
    b, s, _ = x.shape
    q = _project_q(p, x, cfg, positions)
    k, v = _project_kv(p, x, cfg, positions)
    nq = -(-s // q_chunk)
    outs = []
    for i in range(nq):
        lo, hi = i * q_chunk, min((i + 1) * q_chunk, s)
        qp = positions[:, lo:hi]
        k_hi = hi if causal else s      # causal: keys beyond hi are masked
        mask = _band_mask(qp, positions[:, :k_hi], window, causal)
        outs.append(_sdpa(q[:, lo:hi], k[:, :k_hi], v[:, :k_hi],
                          mask[:, None, None], cfg))
    out = jnp.concatenate(outs, axis=1)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    y = out.reshape(b, s, -1) @ p["wo"].astype(cfg.compute_dtype)
    return constrain(y, "batch", "seq", "embed")


def attention_chunked_local(p, x, positions, cfg: ModelConfig, *,
                            window: int) -> jnp.ndarray:
    """Exact sliding-window attention in O(S·w) instead of O(S²).

    The sequence is cut into chunks of length ``window``; each chunk attends
    to itself and its predecessor under the banded mask — exact for causal
    windows ≤ chunk length.  This is the optimised path for long local
    prefill (gemma3 32k: 32× less attention compute than the banded mask
    over full S²)."""
    b, s, d = x.shape
    w = window
    assert s % w == 0 and s >= 2 * w, (s, w)
    q = _project_q(p, x, cfg, positions)
    k, v = _project_kv(p, x, cfg, positions)
    nc = s // w
    # (B, nc, w, H, hd); keys get a 2-window tail: [prev chunk | this chunk]
    qc = q.reshape(b, nc, w, cfg.num_heads, cfg.hd)
    kc = k.reshape(b, nc, w, cfg.num_kv_heads, cfg.hd)
    vc = v.reshape(b, nc, w, cfg.num_kv_heads, cfg.hd)
    k2 = jnp.concatenate([jnp.pad(kc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0),
                                               (0, 0))), kc], axis=2)
    v2 = jnp.concatenate([jnp.pad(vc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0),
                                               (0, 0))), vc], axis=2)
    pc = positions.reshape(b, nc, w)
    p2 = jnp.concatenate([jnp.pad(pc[:, :-1], ((0, 0), (1, 0), (0, 0)),
                                  constant_values=-10**9), pc], axis=2)
    mask = _band_mask(pc, p2, w, causal=True)[:, :, None, None]  # B,nc,1,1,w,2w
    bn = b * nc
    out = _sdpa(qc.reshape(bn, w, cfg.num_heads, cfg.hd),
                k2.reshape(bn, 2 * w, cfg.num_kv_heads, cfg.hd),
                v2.reshape(bn, 2 * w, cfg.num_kv_heads, cfg.hd),
                mask.reshape(bn, 1, 1, w, 2 * w), cfg)
    y = out.reshape(b, s, cfg.num_heads * cfg.hd) @ p["wo"].astype(
        cfg.compute_dtype)
    return constrain(y, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# decode (one new token against a cache)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, length: int,
                  dtype=None) -> dict:
    dtype = dtype or cfg.compute_dtype
    shape = (batch, length, cfg.num_kv_heads, cfg.hd)
    return {
        "k": constrain(jnp.zeros(shape, dtype),
                       "batch", "cache_seq", "kv_heads", "head_dim"),
        "v": constrain(jnp.zeros(shape, dtype),
                       "batch", "cache_seq", "kv_heads", "head_dim"),
    }


def decode_attention(p, x, pos, cache, cfg: ModelConfig, *,
                     window: int | None, kv_memory=None) -> tuple:
    """One-token decode step.  ``pos``: i32[B] absolute positions.

    The new (k, v) is written at ``pos % cache_len`` (ring semantics for
    local windows, linear for full caches — callers size the cache
    accordingly).  Attention itself runs over the full cache with a validity
    mask, so the same code serves both layouts; when the cache's sequence
    dim is sharded over the model axis, XLA partitions the softmax
    reductions into the two-pass flash-decode combine (see
    serve/decode_sharded.py for the explicit shard_map variant)."""
    b = x.shape[0]
    positions = pos[:, None]                     # (B, 1)
    q = _project_q(p, x, cfg, positions)
    if kv_memory is not None:                    # cross attention: no cache
        k, v = kv_memory
        out = _sdpa(q, k, v, None, cfg)
        y = out.reshape(b, 1, -1) @ p["wo"].astype(cfg.compute_dtype)
        return constrain(y, "batch", None, "embed"), cache
    k_new, v_new = _project_kv(p, x, cfg, positions)
    length = cache["k"].shape[1]
    slot = (pos % length).astype(jnp.int32)      # (B,)
    rows = jnp.arange(b)
    k = constrain(cache["k"].at[rows, slot].set(k_new[:, 0]),
                  "batch", "cache_seq", "kv_heads", "head_dim")
    v = constrain(cache["v"].at[rows, slot].set(v_new[:, 0]),
                  "batch", "cache_seq", "kv_heads", "head_dim")
    # validity: cache slot s holds absolute position p_s; with ring writes
    # p_s = s + length*floor((pos-s-1)/length + 1)... for the dry-run step we
    # mask by "slot was written and within window".
    slots = jnp.arange(length)[None, :]          # (1, L)
    written = slots <= pos[:, None]              # linear-fill semantics
    if window is not None:
        written &= slots > pos[:, None] - window
    mask = written[:, None, None, None, :]       # (B,1,1,1,L)
    out = _sdpa(q, k, v, mask, cfg)
    y = out.reshape(b, 1, -1) @ p["wo"].astype(cfg.compute_dtype)
    y = constrain(y, "batch", None, "embed")
    return y, {"k": k, "v": v}
