"""Token traffic for the training cells, from a mix file and a seed.

A vectorised copy of the program's ``SyntheticDataset`` semantics: each
row starts from a Zipf-distributed token; every next token is the planted
bigram successor of the one before with probability ``follow``, and
otherwise a fresh Zipf draw. Zipf draws are inverse-CDF lookups
(``searchsorted``), and one position of every row is drawn at once, so a
batch costs milliseconds at a 152k vocabulary.

Rows are the ``seq + 1`` tokens a row's inputs and labels are cut from.
The first ``CHECKED_STEPS`` batches drive the steps that the reference
repeats; the window cycles over ``pool`` further batches. Every row
differs from every other.
"""
from __future__ import annotations

import numpy as np

CHECKED_STEPS = 3


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def rows(mix: dict, vocab: int, n_rows: int, seed: int) -> np.ndarray:
    """(n_rows, seq + 1) int32 tokens."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=vocab).astype(np.int32)
    cdf = zipf_cdf(vocab, mix["zipf"])
    S = mix["seq"]
    draws = np.minimum(np.searchsorted(cdf, rng.random((S + 1, n_rows))),
                       vocab - 1).astype(np.int32)
    follow = rng.random((S, n_rows)) < mix["follow"]
    toks = np.empty((S + 1, n_rows), np.int32)
    toks[0] = draws[0]
    for t in range(S):
        toks[t + 1] = np.where(follow[t], succ[toks[t]], draws[t + 1])
    return np.ascontiguousarray(toks.T)


def batches(mix: dict, vocab: int, seed: int) -> list:
    """The run's batches: ``CHECKED_STEPS`` then ``pool`` for the window,
    each ``{"tokens", "labels"}`` of shape (batch, seq)."""
    B = mix["batch"]
    n = CHECKED_STEPS + mix["pool"]
    r = rows(mix, vocab, n * B, seed)
    return [{"tokens": r[i * B:(i + 1) * B, :-1],
             "labels": r[i * B:(i + 1) * B, 1:]} for i in range(n)]
