"""The benchmark's own token traffic: a copy of the program's Markov stream.

Each token prefers one of 8 successors drawn per token from the seed, with
10% uniform noise, as ``repro.data.synthetic.TokenStream`` does.  A cell's
batches are made once, in set-up, as a pool of ``pool_batches`` batches per
worker; every row of the pool is drawn independently, so the rows of any
two rounds within the pool differ.
"""

from __future__ import annotations

import numpy as np

SUCCESSORS = 8
NOISE = 0.1


def token_pool(seed: int, vocab: int, workers: int, batches: int,
               rows: int, seq_len: int) -> np.ndarray:
    """(workers, batches, rows, seq_len + 1) int32 token rows from ``seed``.

    Columns ``[:-1]`` are a row's inputs and ``[1:]`` its labels.
    """
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, SUCCESSORS))
    n = workers * batches * rows
    toks = np.empty((n, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=n)
    for t in range(seq_len):
        nxt = succ[toks[:, t], rng.integers(0, SUCCESSORS, size=n)]
        noise = rng.random(n) < NOISE
        toks[:, t + 1] = np.where(noise, rng.integers(0, vocab, size=n), nxt)
    return toks.reshape(workers, batches, rows, seq_len + 1)
