"""Seeded synthetic token batches, a copy of the program's ``SyntheticLM``.

Batch ``i`` is a pure function of (seed, i): an affine chain over the vocab
with random resets and noise, built position by position in Python as the
program's pipeline does.  Every row differs.
"""
from __future__ import annotations

import numpy as np


class SyntheticLM:
    def __init__(self, vocab: int, seq: int, batch: int, seed: int) -> None:
        self.vocab, self.seq, self.batch_size, self.seed = vocab, seq, batch, seed
        base = np.random.Generator(np.random.Philox(key=seed))
        self.mult = int(base.integers(2, max(3, vocab // 2))) * 2 + 1
        self.add = int(base.integers(1, vocab))
        self.reset_p = 0.02
        self.noise_p = 0.05

    def batch(self, index: int) -> dict[str, np.ndarray]:
        rng = np.random.Generator(
            np.random.Philox(key=self.seed, counter=[0, 0, 0, index]))
        B, S, V = self.batch_size, self.seq, self.vocab
        toks = np.empty((B, S + 1), np.int64)
        toks[:, 0] = rng.integers(0, V, B)
        resets = rng.random((B, S)) < self.reset_p
        noise = rng.random((B, S)) < self.noise_p
        rand_toks = rng.integers(0, V, (B, S))
        for t in range(1, S + 1):
            nxt = (toks[:, t - 1] * self.mult + self.add) % V
            nxt = np.where(noise[:, t - 1], rand_toks[:, t - 1], nxt)
            toks[:, t] = np.where(resets[:, t - 1], rand_toks[:, t - 1], nxt)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "labels": toks[:, 1:].astype(np.int32)}
