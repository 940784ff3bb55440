"""Plain reference: fastText's sentence vector over a subword model, from the
written definition.

facebookresearch/fastText ``FastText::getSentenceVector``, the branch for
unsupervised models (Python ``get_sentence_vector``, CLI
``print-sentence-vectors``), over a model of Bojanowski et al. 2017
(arXiv:1607.04606). ``A`` is the trained input table, V word rows then K bucket
rows, x D. For a string t,

    N(t)  = [fnv1a(g) mod K for g in every substring of min_n..max_n characters
             of "<" + t + ">"]                       (by code point; a bucket listed
                                                      as often as it occurs)
    G(t)  = [id(t)] ++ [V + b for b in N(t)]         where t is a word of the dictionary
    G(t)  = [V + b for b in N(t)]                    where it is not: n-grams alone
    h(t)  = (1 / |G(t)|) * sum_{r in G(t)} A[r]      zeros where G(t) is empty ("")
    v(s)  = (1 / c) * sum_{t in s, |h(t)| > 0} h(t) / |h(t)|
            c = #{t in s : |h(t)| > 0}               zeros where c = 0

for a sentence s = (t1 ... tn) as the caller split it. Every token counts each
time it occurs, nothing is dropped as out of vocabulary, and a token of zero
norm is left out of sum and count alike.

NumPy float64 from float32 rows, a Python loop over sentences and tokens: no
capacity, no slide, no padding, no jit of its own. It imports nothing of the
program: the hash and the n-gram walk are ``subword_ref.py``'s loop written
from the paper, the dictionary is built here from the strings handed in.
``A`` is never handed over whole: ``rows_fn(ids) -> [len(ids), D] float32``
makes the rows asked for (the benchmark's tables are a formula of the seed).

Departures from fastText, each on purpose:

- h(t) is the MEAN over G(t), as fastText's ``getWordVector`` /
  ``computeSubwords`` path divides too; the normalisation that follows makes
  v(s) blind to it, h(t) itself is what ``transform(word)`` returns;
- splitting on whitespace is left to the caller (fastText reads words with
  ``iss >> word``): a sentence arrives as a list of strings;
- no ``</s>`` is added (the unsupervised branch adds none).
"""

import jax.numpy as jnp
import numpy as np

from harness import weights
from reference.subword_ref import word_rows

# ids go to ``rows_fn`` in whole pieces of this many, so that one compiled
# program makes them all
_PIECE = 1 << 14


def seeded_rows(seed: int, dim: int, half_width: float):
    """``rows_fn`` of the benchmark's input table (V word rows, then K bucket
    rows): ids -> A[ids], [len(ids), dim] float32, a formula of the seed."""
    s32 = weights.seed32(seed)
    return lambda ids: weights.rows_uniform(s32, 0, ids, dim, dim, half_width)


def dictionary(strings) -> dict:
    """word -> id, from the vocabulary's strings in rank order."""
    return {w: r for r, w in enumerate(strings)}


def token_lists(tokens, index: dict, buckets: int, min_n: int = 3, max_n: int = 6) -> dict:
    """G(t) of every distinct token, by the paper's loop."""
    v = len(index)
    return {t: word_rows(t, index.get(t), v, buckets, min_n, max_n) for t in set(tokens)}


def hasher_mismatches(strings, lists, buckets: int, min_n: int = 3, max_n: int = 6) -> int:
    """Strings whose list of buckets in ``lists`` (another hasher's: the
    program's native one) is not, in order, what the paper's loop lists."""
    return sum(list(map(int, got)) != word_rows(s, None, 0, buckets, min_n, max_n)
               for s, got in zip(strings, lists))


def _rows(rows_fn, ids: np.ndarray, dim: int) -> np.ndarray:
    out = np.zeros((ids.shape[0], dim), np.float64)
    for lo in range(0, ids.shape[0], _PIECE):
        part = np.zeros(_PIECE, np.int32)
        n = min(_PIECE, ids.shape[0] - lo)
        part[:n] = ids[lo:lo + n]
        out[lo:lo + n] = np.asarray(rows_fn(jnp.asarray(part)), np.float64)[:n, :dim]
    return out


def token_vectors(lists: dict, rows_fn, dim: int) -> dict:
    """h(t) float64 of every token of ``lists``."""
    need = np.asarray(sorted({r for g in lists.values() for r in g}), np.int64)
    rows = _rows(rows_fn, need, dim)
    at = {int(r): i for i, r in enumerate(need)}
    out = {}
    for t, g in lists.items():
        total = np.zeros(dim, np.float64)
        for r in g:
            total += rows[at[r]]
        out[t] = total / len(g) if g else total
    return out


def sentence_vectors(sentences, index: dict, rows_fn, dim: int, buckets: int,
                     min_n: int = 3, max_n: int = 6) -> np.ndarray:
    """v(s) of every sentence: ``float32[len(sentences), dim]``."""
    h = token_vectors(
        token_lists([t for s in sentences for t in s], index, buckets, min_n, max_n),
        rows_fn, dim)
    out = np.zeros((len(sentences), dim), np.float64)
    for i, s in enumerate(sentences):
        total, c = np.zeros(dim, np.float64), 0
        for t in s:
            norm = float(np.sqrt(np.dot(h[t], h[t])))
            if norm > 0.0:
                total += h[t] / norm
                c += 1
        if c:
            out[i] = total / c
    return out.astype(np.float32)
