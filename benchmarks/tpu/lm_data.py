"""The profiled job's training batches, generated from the seed.

A copy of the program's ``SyntheticLM`` (zipfian unigram tokens with a
learnable next-token correlation, plus the patch-embedding prefix), kept
with the benchmark so that the traffic cannot change under a later PR.
The driver feeds it to the program's own ``PrefetchLoader``; the plain
reference draws the same batches again from the same seed.
"""
from __future__ import annotations

import numpy as np


class Batches:
    def __init__(self, vocab_size: int, seq_len: int, batch_per_host: int,
                 seed: int = 0, frontend_shape: tuple | None = None):
        self.vocab = vocab_size
        self.seq = seq_len
        self.batch = batch_per_host
        self.frontend_shape = frontend_shape
        self._rng = np.random.default_rng(seed)
        self._support = min(vocab_size, 4096)
        ranks = np.arange(1, self._support + 1, dtype=np.float64)
        self._probs = (1.0 / ranks) / np.sum(1.0 / ranks)
        self.drawn = 0

    def next_batch(self) -> dict:
        self.drawn += 1
        base = self._rng.choice(self._support, size=(self.batch, self.seq),
                                p=self._probs)
        shifted = (base + 1) % self._support
        mix = self._rng.random((self.batch, self.seq)) < 0.5
        tokens = np.where(mix, np.roll(shifted, 1, axis=1), base)
        out = {"tokens": tokens.astype(np.int32)}
        if self.frontend_shape is not None:
            out["frontend"] = self._rng.standard_normal(
                (self.batch,) + self.frontend_shape).astype(np.float32)
        return out


def for_job(cfg: dict, seed: int) -> Batches:
    job = cfg["train"]
    v = cfg["vision"]
    return Batches(cfg["vocab_size"], job["text_len"], job["batch"], seed,
                   (v["num_prefix_tokens"], v["patch_embed_dim"]))
