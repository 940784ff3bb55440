"""Seeded Zipf traffic: the vocabulary's counts and draws from them.

``zipf_counts`` is ``bench.py``'s (copied): count(rank) = 1e9 / (rank + 10)^1.07,
floored at 5, the profile of a large web corpus's vocabulary.
"""

import numpy as np


def zipf_counts(v: int) -> np.ndarray:
    return np.maximum(1e9 / (np.arange(v) + 10.0) ** 1.07, 5.0)


def draw(rng: np.random.Generator, v: int, n: int) -> np.ndarray:
    """n word ids with the vocabulary's own frequency profile, in random order:
    one multinomial draw of how often each word comes, then a shuffle (a
    searchsorted over the cumulative counts takes five times as long)."""
    counts = zipf_counts(v)
    times = rng.multinomial(n, counts / counts.sum())
    ids = np.repeat(np.arange(v, dtype=np.int32), times)
    rng.shuffle(ids)
    return ids


def words_of(v: int) -> list:
    """The vocabulary's words, "w<rank>" (a plain comprehension: numpy's
    char.add takes two and a half times as long for the same list)."""
    return [f"w{i}" for i in range(v)]
