"""Token batches for the train cells, made on the host from the seed.

A copy of ``repro.data.pipeline.SyntheticCorpus.batch``, kept here so that a
change to the program cannot change the yardstick: Zipf(a) token ids with a
motif of ``motif_len`` ids repeated through each row, every batch a pure
function of (seed, step, shard). The parameters come from the cell's traffic
file.
"""
from __future__ import annotations

import numpy as np


class Corpus:
    def __init__(self, vocab_size: int, seed: int, zipf_a: float = 1.3, motif_len: int = 8):
        self.vocab_size = vocab_size
        self.seed = seed
        self.zipf_a = zipf_a
        self.motif_len = motif_len

    def batch(self, step: int, shard_id: int, batch_size: int, seq_len: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step, shard_id]))
        v = self.vocab_size
        base = rng.zipf(self.zipf_a, size=(batch_size, seq_len)).astype(np.int64) % v
        motif = rng.integers(0, v, size=(batch_size, self.motif_len))
        reps = seq_len // (2 * self.motif_len)
        for r in range(reps):
            at = 2 * r * self.motif_len
            base[:, at:at + self.motif_len] = motif
        return base.astype(np.int32)
