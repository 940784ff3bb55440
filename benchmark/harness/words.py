"""Seeded word strings for a vocabulary: what a string path needs to exercise.

``zipf.words_of`` names its words ``"w<rank>"``: a letter and digits, with no
structure below the word, which is why no cell before the subword one could
exercise a path that reads a word's characters (n-grams, a query for an unseen
string). A published vocabulary cannot be fetched here, so this makes V
distinct lower-case strings from ``--seed`` by a law that has English's rough
shape: frequent words are short, rare ones long, letters come by English
letter frequency.

    length(rank r) = 1 + floor(0.35 * log2(r + 2)) + U{0..3}

At V = 2,519,370 that is a type mean of 9.0 characters, a token mean of 5.8
under ``zipf.zipf_counts`` and a longest word of 11 (English text reads about
8-9 and 5). Letters are independent draws; a string that an earlier rank already
holds is drawn again at the same length. The same seed gives the same words.
"""

import numpy as np

# English letter frequencies, per cent (Lewand, Cryptological Mathematics, 2000)
_LETTER_PER_CENT = {
    "e": 12.70, "t": 9.06, "a": 8.17, "o": 7.51, "i": 6.97, "n": 6.75, "s": 6.33,
    "h": 6.09, "r": 5.99, "d": 4.25, "l": 4.03, "c": 2.78, "u": 2.76, "m": 2.41,
    "w": 2.36, "f": 2.23, "g": 2.02, "y": 1.97, "p": 1.93, "b": 1.29, "v": 0.98,
    "k": 0.77, "j": 0.15, "x": 0.15, "q": 0.10, "z": 0.07}


def word_lengths(seed: int, v: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5EED, 1])
    base = 1 + np.floor(0.35 * np.log2(np.arange(v) + 2.0)).astype(np.int64)
    return base + rng.integers(0, 4, v)


def make_words(seed: int, v: int) -> list:
    """V distinct strings, rank 0 first."""
    rng = np.random.default_rng([int(seed), 0x5EED, 2])
    letters = np.frombuffer("".join(_LETTER_PER_CENT).encode(), np.uint8)
    p = np.array(list(_LETTER_PER_CENT.values()))
    # the letter of each of 65,536 equal slices of [0, 1): a draw is a lookup
    by_slice = letters[np.searchsorted(np.cumsum(p / p.sum()),
                                       (np.arange(1 << 16) + 0.5) / (1 << 16))]
    length = word_lengths(seed, v)
    width = int(length.max())
    in_word = np.arange(width)[None, :] < length[:, None]
    chars = np.zeros((v, width), np.uint8)
    redo = np.arange(v)
    while redo.size:
        draw = by_slice[rng.integers(0, 1 << 16, (redo.size, width), np.uint16)]
        chars[redo] = np.where(in_word[redo], draw, 0)
        # all but the first holder of a string draw again
        keys = chars.view(f"S{width}").ravel()
        _, first = np.unique(keys, return_index=True)
        dup = np.ones(v, bool)
        dup[first] = False
        redo = np.flatnonzero(dup)
    return [w.decode("ascii") for w in chars.view(f"S{width}").ravel().tolist()]
