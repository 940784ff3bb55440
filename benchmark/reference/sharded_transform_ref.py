"""Plain reference: ``transform(dataset)`` over a table that no one chip holds,
from the written definition, with no mesh, no shard and no partial sums.

kiminh/glint-word2vec's ``ServerSideGlintWord2VecModel.transform`` (ml:428-460)
sends each 10,000-row slide's word indices to the parameter servers as
``matrix.pullAverage(sentenceIndices)`` (ml:453): every server sums, sentence by
sentence, the rows IT holds, and the partial sums are added and divided. What
comes back is the mean of ALL the sentence's in-vocabulary rows wherever they
lie, which is all this file computes: how the rows are partitioned (upstream:
cyclically, row mod servers; the program under test: by range) appears nowhere
in it, and the answer does not depend on it.

    id(w)  = the rank r of the word "w<r>", 0 <= r < V       this file's OWN dictionary,
                                                              built from zipf.words_of(V)
    K(s)   = [w in s : w is a key of that dictionary]         in order, WITH multiplicity
    u_r    = rows_fn(r)                                       row r of syn0 (the benchmark's:
                                                              rows_uniform(seed, 0, r), :func:`seeded_rows`)
    v(s)   = (1 / |K(s)|) * sum over w in K(s) of u_id(w)     where K(s) is not empty
    v(s)   = 0                                                where it is

Departures from upstream, each stated: the empty sentence's zeros (an empty
sentence, or one of out-of-vocabulary tokens alone: upstream leaves the case to
its matrix fork; Spark ML's ``Word2VecModel.transform`` returns zeros, as
reference/transform_ref.py); words are ``w<rank>`` strings and rows a formula of
the seed (no published vocabulary or table can be fetched here).

It imports nothing of the program and takes nothing the program holds: no
``Vocabulary``, no jit, no capacity, no padding, no batching, no mesh. The rows
it needs are made from the seed once, for the distinct ids of the sentences
asked for; the sums are a Python loop over sentences in NumPy float64, returned
as float32.
"""

import jax.numpy as jnp
import numpy as np

from harness import weights, zipf


def seeded_rows(seed: int, dim: int, half_width: float):
    """``rows_fn`` of the benchmark's table: ids -> u[ids], [len(ids), dim]
    float32, a formula of the seed."""
    s32 = weights.seed32(seed)
    return lambda ids: weights.rows_uniform(s32, 0, ids, dim, dim, half_width)


def dictionary(vocab_size: int) -> dict:
    """word -> id, from the words a vocabulary of ``vocab_size`` ranks has."""
    return {w: r for r, w in enumerate(zipf.words_of(vocab_size))}


def kept_ids(sentences, index: dict) -> list:
    """K(s) of every sentence, as ids."""
    return [[index[w] for w in s if w in index] for s in sentences]


def sentence_vectors(sentences, index: dict, rows_fn, dim: int) -> np.ndarray:
    """v(s) of every sentence: ``float32[len(sentences), dim]``."""
    kept = kept_ids(sentences, index)
    need = sorted({r for k in kept for r in k})
    rows = np.zeros((0, dim), np.float64)
    if need:
        rows = np.asarray(rows_fn(jnp.asarray(need, jnp.int32)), np.float64)[:, :dim]
    at = {r: i for i, r in enumerate(need)}
    out = np.zeros((len(sentences), dim), np.float64)
    for i, k in enumerate(kept):
        if k:
            total = np.zeros(dim, np.float64)
            for r in k:
                total += rows[at[r]]
            out[i] = total / len(k)
    return out.astype(np.float32)
