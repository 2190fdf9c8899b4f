"""The seeded synthetic token stream the training cells feed, written out
again so the reference makes its own batches: a Zipf(1.3) unigram stream,
each row keyed by (seed, step, row).  The program's loader
(``repro.data.pipeline``) draws the same rows; a loader that feeds other
tokens shows up as a loss gap."""
from __future__ import annotations

import numpy as np


def _probs(vocab: int, seed: int):
    z = np.random.default_rng(seed).zipf(1.3, size=vocab).astype(np.float64)
    return z / z.sum()


def batch(vocab: int, seed: int, seq: int, global_batch: int, step: int):
    """(tokens, labels), each (global_batch, seq) int32, of ``step``."""
    p = _probs(vocab, seed)
    toks = np.empty((global_batch, seq + 1), np.int32)
    for g in range(global_batch):
        off = (np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
               + np.uint64(step) * np.uint64(global_batch)
               + np.uint64(g)) * np.uint64(seq)
        off = int(off % (2 ** 62))
        rng = np.random.default_rng(np.uint64(off) * 2654435761 % 2 ** 63)
        row = rng.choice(vocab, size=seq + 1, p=p)
        if (seq + 1) % 64 == 0:
            row = row.reshape(-1, 64)
            row[:, 32:] = row[:, :32]
            row = row.reshape(-1)
        toks[g] = row
    return toks[:, :-1].copy(), toks[:, 1:].copy()
