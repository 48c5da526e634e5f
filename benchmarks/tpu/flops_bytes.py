"""Operations of the measured training step, from its shapes, and the
chip's published peaks.

These are the algorithm's needs, not what a compiler happened to emit:
MFU divides them by device time taken from the trace.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lm_forward_flops(cfg: dict, batch: int, text_len: int) -> float:
    """Matmul FLOPs of one forward pass of the profiled LM, as the job
    computes it: every layer over the patch prefix plus the text, full
    (unmasked) S x S attention scores, logits at every position.  Norms,
    RoPE and softmax are left out (under 1% here)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    f = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    prefix = cfg["vision"]["num_prefix_tokens"]
    front = cfg["vision"]["patch_embed_dim"]
    s = prefix + text_len
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * f
    per_layer = 2 * s * (proj + mlp) + 2 * (2 * s * s * h * hd)
    per_seq = layers * per_layer + 2 * prefix * front * d + 2 * s * d * v
    return float(batch * per_seq)


def train_step_flops(cfg: dict, batch: int, text_len: int) -> float:
    """Model FLOPs of one training step: forward plus backward (twice the
    forward), with no activation recomputation counted."""
    return 3.0 * lm_forward_flops(cfg, batch, text_len)
