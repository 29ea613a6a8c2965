"""Seeded token batches: the benchmark's one traffic generator.

A copy of the process of ``repro.data.synthetic`` (an order-1 affine Markov
chain per sequence, ``x_{t+1} = (a x_t + b) mod V``, with a share ``noise``
of tokens drawn uniformly), kept here so that no change to the program can
change the traffic.  Rows are drawn for the whole batch at once, so one
batch of 64 x 512 tokens takes milliseconds on the host.

Step ``i`` of seed ``s`` draws from ``SeedSequence([s, i])``: the same seed
gives the same batches, and every step's rows differ from every other's.
"""
from __future__ import annotations

import numpy as np


def make_batch(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    """Global batch for one step: int32 leaves ``[M, B/M, S]``."""
    B, S = traffic["global_batch"], traffic["seq_len"]
    M = traffic["n_microbatches"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    a = rng.integers(2, 8, size=B, dtype=np.int64)
    b = rng.integers(0, vocab, size=B, dtype=np.int64)
    noisy = rng.random((S, B)) < traffic["noise"]
    fresh = rng.integers(0, vocab, size=(S, B), dtype=np.int64)
    x = np.empty((S + 1, B), np.int64)
    x[0] = rng.integers(0, vocab, size=B, dtype=np.int64)
    for t in range(S):
        x[t + 1] = np.where(noisy[t], fresh[t], (a * x[t] + b) % vocab)
    seqs = x.T
    tokens = seqs[:, :-1].reshape(M, B // M, S).astype(np.int32)
    labels = seqs[:, 1:].reshape(M, B // M, S).astype(np.int32)
    return {"tokens": tokens, "labels": labels,
            "mask": np.ones_like(tokens)}
