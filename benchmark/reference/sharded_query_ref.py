"""Plain reference: ``findSynonyms`` over a table too large for one chip, from
the written definition, with no mesh, no shard, no merge and no two stages.

Straightforward float32 ``jax.numpy`` on ONE device, matmul precision
"highest". It imports nothing of the program and takes nothing the program
holds: the table is a formula of the seed (``harness/weights.rows_uniform``,
stream 0), made here in blocks of at most 500,000 rows, scored against the
queries and dropped again. kiminh/glint-word2vec's
``ServerSideGlintWord2VecModel.findSynonyms`` (mllib:583-630): the query's row
is pulled, every server multiplies its rows by it, the products are divided by
the rows' norms, and the best k that are not the query word are kept.

    T[r]        = rows_fn(r)                                  row r of the table, 0 <= r < V
                  (the benchmark's: rows_uniform(seed, 0, r), :func:`seeded_rows`)
    score(q, r) = T[q] . T[r] / (|T[q]| |T[r]|)               0 where |T[r]| = 0
    reply(q, k) = [r_1, ..., r_k]: the k rows r != q of highest score(q, r),
                  score(q, r_1) >= ... >= score(q, r_k), and of two rows of
                  equal score the LOWER row first (so also: kept before a
                  higher row of the same score that the k-th place leaves out)

The rule for ties is ``lax.top_k``'s over the whole [Q, V] block, which the
program promises to keep across its shards; here it is a comparison of
(score, row) pairs in NumPy.
"""

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

BLOCK_ROWS = 500_000


def seeded_rows(seed: int, dim: int, half_width: float):
    """``rows_fn`` of the benchmark's table: ids -> T[ids], [len(ids), dim]
    float32, a formula of the seed."""
    s32 = weights.seed32(seed)
    return lambda ids: weights.rows_uniform(s32, 0, ids, dim, dim, half_width)


def _cosines(q, t):
    """score of every query row of ``q`` [Q, D] against ``t``: the rows of one
    block [R, D], or each query's own rows [Q, n, D]."""
    with jax.default_matmul_precision("highest"):
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        tn = jnp.linalg.norm(t, axis=-1)
        dots = qn @ t.T if t.ndim == 2 else jnp.einsum("qd,qnd->qn", qn, t)
        return jnp.where(tn > 0, dots / jnp.maximum(tn, 1e-12), 0.0)


def pair_scores(rows_fn, query_ids, row_ids) -> np.ndarray:
    """score(q_i, r_ij) for ``row_ids`` [Q, n]: each query against its own rows."""
    @jax.jit
    def scores(qids, rids):
        t = rows_fn(rids.reshape(-1))
        return _cosines(rows_fn(qids), t.reshape(rids.shape + t.shape[1:]))

    return np.asarray(scores(jnp.asarray(query_ids, jnp.int32),
                             jnp.asarray(row_ids, jnp.int32)))


def _best(scores: np.ndarray, rows: np.ndarray, k: int):
    """The k best (score, row) pairs of one query, best first, ties toward the
    lower row."""
    if scores.shape[0] > k:
        kth = np.partition(scores, scores.shape[0] - k)[scores.shape[0] - k]
        keep = scores >= kth            # every tie at the k-th place, for the sort
        scores, rows = scores[keep], rows[keep]
    order = np.lexsort((rows, -scores))[:k]
    return scores[order], rows[order]


def top_k(rows_fn, num_rows: int, query_ids, k: int, block: int = BLOCK_ROWS):
    """reply(q, k) of every query word: (scores [Q, k] float32, rows [Q, k]
    int64), over all ``num_rows`` rows, block by block with a running best."""
    query_ids = np.asarray(query_ids, np.int32)
    block = min(block, num_rows)

    @jax.jit
    def block_scores(qids, lo):
        ids = jnp.minimum(lo + jax.lax.iota(jnp.int32, block), num_rows - 1)
        return _cosines(rows_fn(qids), rows_fn(ids))

    best = [(np.empty(0, np.float32), np.empty(0, np.int64)) for _ in query_ids]
    for lo in range(0, num_rows, block):
        live = min(block, num_rows - lo)
        sc = np.asarray(block_scores(jnp.asarray(query_ids), jnp.int32(lo)))[:, :live]
        rows = np.arange(lo, lo + live, dtype=np.int64)
        for i, q in enumerate(query_ids):
            mine = sc[i]
            if lo <= q < lo + live:
                mine = mine.copy()
                mine[q - lo] = -np.inf          # a word is not its own synonym
            s, r = _best(mine, rows, k)
            best[i] = _best(np.concatenate([best[i][0], s]),
                            np.concatenate([best[i][1], r]), k)
    return np.stack([b[0] for b in best]), np.stack([b[1] for b in best])
