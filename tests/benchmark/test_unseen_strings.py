"""The benchmark's unseen strings (benchmark/harness/unseen.py): typos of
Zipf-drawn words, none of them a word, none empty, the same for the same seed,
a quarter of the queries, as long as the tokens people type."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import unseen, words, zipf  # noqa: E402

V, DRAWS = 20000, 100_000


@pytest.fixture(scope="module")
def vocabulary():
    strings = words.make_words(13, V)
    return strings, {w: i for i, w in enumerate(strings)}


@pytest.fixture(scope="module")
def drawn(vocabulary):
    strings, index = vocabulary
    return unseen.mixed_queries(np.random.default_rng([13, 0x9E4]), strings, index,
                                DRAWS, 0.25)


@pytest.mark.parametrize("op, u, want", [
    (0, 0.0, "Xord"), (0, 0.99, "worX"), (1, 0.0, "Xword"), (1, 0.99, "wordX"),
    (1, 0.5, "woXrd"), (2, 0.0, "ord"), (2, 0.99, "wor"), (3, 0.0, "owrd"),
    (3, 0.99, "wodr")])
def test_one_edit_at_a_uniform_position(op, u, want):
    assert unseen.edit("word", op, u, "X") == want


def test_an_edit_with_no_position_gives_the_word_back():
    assert unseen.edit("a", 3, 0.5, "X") == "a"       # nothing to transpose
    assert unseen.edit("a", 2, 0.5, "X") == ""        # thrown away by the caller


def test_none_is_a_word_and_none_is_empty(vocabulary, drawn):
    strings, index = vocabulary
    queries, ids = drawn
    made = [q for q, i in zip(queries, ids) if i < 0]
    assert made and all(made) and not any(q in index for q in made)
    assert all(strings[i] == q for q, i in zip(queries, ids) if i >= 0)


def test_a_quarter_of_the_queries_and_the_rest_by_the_zipf_counts(drawn):
    queries, ids = drawn
    share = float((ids < 0).mean())
    assert abs(share - 0.25) < 0.01, share
    seen = ids[ids >= 0]
    counts = zipf.zipf_counts(V)
    # the most frequent hundred words take the share of the draws their counts say
    want = counts[:100].sum() / counts.sum()
    assert abs((seen < 100).mean() - want) < 0.01


def test_the_same_seed_gives_the_same_strings(vocabulary, drawn):
    strings, index = vocabulary
    again = unseen.mixed_queries(np.random.default_rng([13, 0x9E4]), strings, index,
                                 DRAWS, 0.25)
    assert again[0] == drawn[0] and (again[1] == drawn[1]).all()
    other = unseen.mixed_queries(np.random.default_rng([14, 0x9E4]), strings, index,
                                 1000, 0.25)
    assert other[0] != drawn[0][:1000]


def test_a_typos_length_follows_the_tokens(vocabulary, drawn):
    """One edit of a Zipf-drawn word: a character more, a character fewer or as
    many, so the mean length stays within half a character of the tokens'
    (short words' typos are words more often and are drawn again, which moves
    it up a little) and the n-grams of 3 to 6 stay near the tokens'."""
    strings, _ = vocabulary
    queries, ids = drawn
    tokens = np.mean([len(q) for q, i in zip(queries, ids) if i >= 0])
    typos = np.mean([len(q) for q, i in zip(queries, ids) if i < 0])
    assert abs(typos - tokens) < 0.5, (typos, tokens)
    longest = max(map(len, strings))
    assert max(len(q) for q in queries) <= longest + 1


def test_every_typo_is_one_edit_from_a_word(vocabulary, drawn):
    strings, index = vocabulary
    queries, ids = drawn
    letters = "abcdefghijklmnopqrstuvwxyz"

    def near(t):
        for at in range(len(t) + 1):
            yield t[:at] + t[at + 1:]                              # was an insert
            for c in letters:
                yield t[:at] + c + t[at:]                          # was a delete
                yield t[:at] + c + t[at + 1:]                      # was a substitute
            yield t[:at] + t[at + 1:at + 2] + t[at:at + 1] + t[at + 2:]   # a transpose
    for typo in [q for q, i in zip(queries, ids) if i < 0][:300]:
        assert any(w in index for w in near(typo)), typo
