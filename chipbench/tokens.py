"""Seeded token traffic: Zipf-distributed ids with a bigram structure.

A copy of the program's ``data.pipeline.SyntheticLM`` kept with the
benchmark, so that a change to the program cannot change the token mix and
with it the MoE routing.  Batch ``index`` of ``stream`` depends only on
``(seed, stream, index)``; every row of every batch is drawn afresh.
"""
from __future__ import annotations

import numpy as np

TRAIN, PROMPTS = 0, 1


class ZipfSource:
    """``batch(i)`` -> {"tokens", "labels"} of shape [batch, seq], int32."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int,
                 zipf_a: float = 1.2, stream: int = TRAIN):
        self.seed, self.stream = seed, stream
        self.shape = (batch, seq)
        self.vocab, self.zipf_a = vocab, zipf_a

    def batch(self, index: int) -> dict:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.stream, index]))
        b, s = self.shape
        base = rng.zipf(self.zipf_a, size=(b, s + 1)) % self.vocab
        # token[t+1] == f(token[t]) half the time
        follow = (base[:, :-1] * 31 + 7) % self.vocab
        coin = rng.random((b, s)) < 0.5
        seq = base[:, 1:].copy()
        seq[coin] = follow[coin]
        tokens = np.concatenate([base[:, :1], seq], axis=1).astype(np.int32)
        return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
