"""Token batches from the seed.

A copy of the program's counter hash (``repro.data.pipeline._hash_tokens``),
kept here so that no later change to the program moves the inputs: batch
``step`` of a run is a pure function of ``(seed, step)``, its rows all
differ, and the reference regenerates the same batches.
"""
from __future__ import annotations

import numpy as np


def hash_tokens(seed: int, step: int, shape, vocab: int) -> np.ndarray:
    """SplitMix64-style counter hash -> int32 tokens in [0, vocab)."""
    n = int(np.prod(shape))
    idx = np.arange(n, dtype=np.uint64) + np.uint64(step) * np.uint64(n) \
        + (np.uint64(seed % (1 << 64)) << np.uint64(32))
    z = idx + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(vocab)).astype(np.int32).reshape(shape)


def batch(seed: int, step: int, rows: int, seq_len: int, vocab: int):
    """One training batch: ``rows`` sequences of ``seq_len + 1`` tokens
    (step t consumes token t and predicts token t + 1)."""
    return {"tokens": hash_tokens(seed, step, (rows, seq_len + 1), vocab)}
