"""Seeded strings that a vocabulary lacks: typos of the words people type.

A neighbour service over a subword model is asked for whatever its users
typed, and 10-15% of what people type into a search box is misspelt (Cucerzan
& Brill, EMNLP 2004). An unseen string here is made the way a misspelling is:
draw a word by the vocabulary's Zipf counts and apply ONE edit at a uniform
position: substitute, insert, delete or transpose two neighbours, each with
probability 1/4, a new letter by ``words.py``'s English letter frequencies.
A result that is empty or is itself a word of the vocabulary is thrown away and
another word drawn. So the strings' lengths follow the tokens' (about 5.8
characters, 18 n-grams of 3 to 6) and each shares most of its n-grams with a
real word. The same seed gives the same strings.
"""

import numpy as np

from harness import words, zipf

_LETTERS = "".join(words._LETTER_PER_CENT)
_LETTER_P = np.array(list(words._LETTER_PER_CENT.values())) / sum(
    words._LETTER_PER_CENT.values())


def edit(word: str, op: int, u: float, letter: str) -> str:
    """One edit of ``word``: ``op`` 0 substitute, 1 insert, 2 delete, 3
    transpose; ``u`` in [0, 1) places it uniformly among the positions the
    edit has (none: the word comes back as it was)."""
    n = len(word)
    if op == 1:
        at = int(u * (n + 1))
        return word[:at] + letter + word[at:]
    if op == 3:
        if n < 2:
            return word
        at = int(u * (n - 1))
        return word[:at] + word[at + 1] + word[at] + word[at + 2:]
    at = int(u * n)
    return word[:at] + (letter if op == 0 else "") + word[at + 1:]


def typos(rng: np.random.Generator, strings: list, known, base: np.ndarray) -> list:
    """One unseen string for each entry of ``base`` (word ids, drawn by the
    caller from the Zipf counts); ``known`` answers ``in`` for the vocabulary.
    Where the edit of a base word gives nothing new, another word is drawn."""
    out = [None] * base.shape[0]
    todo = np.arange(base.shape[0])
    while todo.size:
        op = rng.integers(0, 4, todo.size)
        u = rng.random(todo.size)
        letter = rng.choice(len(_LETTERS), todo.size, p=_LETTER_P)
        again = []
        for j, i in enumerate(todo):
            made = edit(strings[int(base[j])], int(op[j]), float(u[j]),
                        _LETTERS[letter[j]])
            if made and made not in known:
                out[i] = made
            else:
                again.append(i)
        todo = np.asarray(again, np.int64)
        base = zipf.draw(rng, len(strings), todo.size)
    return out


def mixed_queries(rng: np.random.Generator, strings: list, known, n: int,
                  unseen_share: float):
    """``n`` queries: each, by seed, with probability ``unseen_share`` an
    unseen string and else a word drawn by the vocabulary's Zipf counts.
    Returns the strings and, beside them, each one's word id (-1: unseen)."""
    ids = zipf.draw(rng, len(strings), n)
    unseen = np.flatnonzero(rng.random(n) < unseen_share)
    queries = [strings[int(i)] for i in ids]
    for at, made in zip(unseen, typos(rng, strings, known, ids[unseen])):
        queries[at] = made
    ids = ids.copy()
    ids[unseen] = -1
    return queries, ids
