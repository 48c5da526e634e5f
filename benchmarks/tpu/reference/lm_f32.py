"""Plain reference of the profiled job: the InternLM2 decoder with a patch
prefix, its next-token loss, and AdamW, in float32 ``jax.numpy``.

Written from the published description (InternLM2: pre-norm RMSNorm,
grouped-query attention with rotary embeddings, SwiGLU MLP; InternVL: a
prefix of projected patch embeddings), with the job's numerics stated in
the configuration file: RMSNorm scales ``x * (1 + w)`` with eps 1e-6,
RoPE on split halves with theta from the config, logits at every position
and the loss over the text's next tokens.  No kernels, caches or code of
the program.  Every matmul runs at ``HIGHEST`` precision; ``mm_dtype``
rounds both operands of every matmul to a lower type first (the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(a, b, mm_dtype):
    if mm_dtype is not None:
        a, b = a.astype(mm_dtype), b.astype(mm_dtype)
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def _einsum(spec, a, b, mm_dtype):
    if mm_dtype is not None:
        a, b = a.astype(mm_dtype), b.astype(mm_dtype)
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """x: (B, S, heads, hd); position p rotates pair (i, i + hd/2) by
    p * theta^(-i / (hd/2))."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(p, x, cfg, mm_dtype):
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    bsz, s, _ = x.shape
    y = rms_norm(x, p["ln1"])
    q = _mm(y, p["attn"]["wq"], mm_dtype).reshape(bsz, s, h, hd)
    k = _mm(y, p["attn"]["wk"], mm_dtype).reshape(bsz, s, kv, hd)
    v = _mm(y, p["attn"]["wv"], mm_dtype).reshape(bsz, s, kv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)       # head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=2)
    scores = _einsum("bqhd,bkhd->bhqk", q, k, mm_dtype) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = _einsum("bhqk,bkhd->bqhd", attn, v, mm_dtype).reshape(bsz, s, h * hd)
    x = x + _mm(o, p["attn"]["wo"], mm_dtype)
    y = rms_norm(x, p["ln2"])
    f = p["ffn"]
    g = jax.nn.silu(_mm(y, f["gate"], mm_dtype)) * _mm(y, f["up"], mm_dtype)
    return x + _mm(g, f["down"], mm_dtype)


def loss(params, batch, cfg, mm_dtype=None):
    """Mean next-token cross entropy over the text (the patch prefix is
    context only)."""
    tokens = batch["tokens"]
    t = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)
    prefix = _mm(batch["frontend"], params["frontend"], mm_dtype)
    x = jnp.concatenate([prefix, x], axis=1)
    body = jax.checkpoint(functools.partial(layer, cfg=cfg,
                                            mm_dtype=mm_dtype))
    for p in params["groups"]:
        x = body(p["b0"], x)
    x = rms_norm(x[:, -t:], params["final_norm"])
    logits = _mm(x, params["lm_head"], mm_dtype)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                              axis=-1)[..., 0]
    return jnp.mean(logz[:, :-1] - tgt)


def adamw_step(params, mu, nu, grads, step, opt):
    """One AdamW step with global-norm clipping, warmup then cosine decay,
    and decoupled weight decay on matrices; ``step`` counts from 1."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-12))
    s = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(s / max(opt["warmup_steps"], 1), 1.0)
    frac = jnp.clip((s - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    lr = opt["lr"] * warm * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                             * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1 - b1 ** s, 1 - b2 ** s

    def one(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - lr * u, m, v

    out = jax.tree.map(one, params, mu, nu, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), scale


def make_step(cfg: dict, mm_dtype=None):
    """``(params, mu, nu, batch, step) -> (params, mu, nu, loss, the
    clipped gradient's norm per leaf)``, donating params and moments."""
    opt = cfg["train"]["optimizer"]

    def step_fn(params, mu, nu, batch, step):
        value, grads = jax.value_and_grad(loss)(params, batch, cfg, mm_dtype)
        norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
        params, mu, nu, scale = adamw_step(params, mu, nu, grads, step, opt)
        return params, mu, nu, value, jax.tree.map(lambda n: n * scale,
                                                   norms)
    return jax.jit(step_fn, donate_argnums=(0, 1, 2))
