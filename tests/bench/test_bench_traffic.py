"""The benchmark's token generator keeps the program's synthetic-data
semantics: Zipf(1.1) draws and the planted bigram successor followed
with probability 0.8, from the seed."""
import time

import numpy as np
import pytest

from bench import traffic
from repro.data import SyntheticDataset

MIX = {"batch": 8, "seq": 512, "zipf": 1.1, "follow": 0.8, "pool": 5}


def _succ(vocab, seed):
    return np.random.default_rng(seed).integers(0, vocab, size=vocab)


def test_same_seed_same_rows_and_large_seeds():
    seed = 2**31 + 12345
    a = traffic.batches(MIX, 1000, seed)
    b = traffic.batches(MIX, 1000, seed)
    assert len(a) == traffic.CHECKED_STEPS + MIX["pool"]
    for x, y in zip(a, b, strict=True):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        assert x["tokens"].shape == (8, 512) and x["tokens"].dtype == np.int32
        np.testing.assert_array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    c = traffic.batches(MIX, 1000, seed + 1)
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])


def test_every_row_differs():
    rows = traffic.rows(MIX, 1000, 64, 7)
    assert len({r.tobytes() for r in rows}) == 64


def test_follow_rate_and_zipf_match_the_program_generator():
    vocab, seed = 1000, 3
    rows = traffic.rows(MIX, vocab, 64, seed)
    succ = _succ(vocab, seed)
    followed = np.mean(rows[:, 1:] == succ[rows[:, :-1]])
    # a fresh draw can land on the successor by chance: 0.8 + 0.2 * P(succ)
    assert 0.79 < followed < 0.83
    ds = SyntheticDataset(vocab, 512, 64, seed=seed)
    ref = ds.batch(0)["tokens"]
    ref_followed = np.mean(ref[:, 1:] == ds._succ[ref[:, :-1]])
    assert abs(followed - ref_followed) < 0.02
    # first tokens are pure Zipf draws: the top token's share is
    # 1 / H(vocab, 1.1), in both generators
    p_top = 1 / np.sum(1.0 / np.arange(1, vocab + 1) ** 1.1)
    first = traffic.rows(MIX | {"seq": 1}, vocab, 20000, 11)[:, 0]
    assert np.mean(first == 0) == pytest.approx(p_top, abs=0.01)


def test_milliseconds_per_batch_at_a_152k_vocabulary():
    t = time.perf_counter()
    traffic.batches({**MIX, "seq": 4096, "pool": 1}, 151_936, 5)
    per_batch = (time.perf_counter() - t) / (traffic.CHECKED_STEPS + 1)
    assert per_batch < 0.25        # the program's generator takes seconds
