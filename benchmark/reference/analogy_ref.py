"""Plain reference: word2vec's accuracy test (3CosAdd) over a whole table.

The equations of ``compute-accuracy.c`` (https://code.google.com/archive/p/word2vec/;
Mikolov et al. 2013, arXiv:1301.3781 §4.1; gensim's ``evaluate_word_analogies`` and
``most_similar(positive=[b, c], negative=[a])``), in straightforward float32
``jax.numpy`` under matmul precision "highest". With û_w row w of the table over its
norm (0 for a row of zero norm):

- a question is four words a b c d. Where any of the four is not among the first T
  rows (the tool's ``threshold``; T = V where it is off) the question is skipped:
  counted as seen, never scored;
- q = û_b − û_a + û_c; score(w) = q · û_w / ‖q‖ for every row w < T except a, b and
  c, which are set to -inf; the answer is the argmax (``lax.top_k``: ties toward the
  lower row); the question is correct where the answer is d;
- accuracy = correct / scored.

Departures from the C tool, the program's too: no upper-casing (the caller's
business); ties toward the lower row (the tool's strict ``>`` over an ascending loop
does the same); the best row is the answer whatever its sign (the tool's ``bestd``
starts at 0 and answers nothing where no score is positive).

The table is given as ``rows_fn(row_ids) -> [R, D] float32`` and scored in blocks of
rows so that it fits; every question is scored against every block (no tiling by
questions, no kernels). It imports nothing of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _unit(rows):
    norm = jnp.linalg.norm(rows, axis=1, keepdims=True)
    return jnp.where(norm > 0, rows / jnp.where(norm > 0, norm, 1.0), 0.0)


def scores_and_answers(rows_fn, num_rows: int, questions: np.ndarray, k: int = 1,
                       candidates=None, block: int = 1 << 17):
    """For ``questions`` (``int[N, 3]`` row ids a, b, c, all under ``candidates``):
    the ``[N, k]`` best cosines and their rows over rows [0, candidates), a, b and c
    excluded, best first; and ``score_of(rows) -> [N, len]``, the reference's cosine
    of given rows (``int[N, M]``) for each question, for a served answer's error."""
    t_rows = num_rows if candidates is None else min(int(candidates), num_rows)
    abc = jnp.asarray(np.asarray(questions)[:, :3], jnp.int32)

    @jax.jit
    def queries(abc):
        with jax.default_matmul_precision("highest"):
            u = _unit(rows_fn(abc.reshape(-1))).reshape(abc.shape[0], 3, -1)
            q = u[:, 1] - u[:, 0] + u[:, 2]
            return q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)

    q = queries(abc)

    @jax.jit
    def block_best(q, abc, lo):
        with jax.default_matmul_precision("highest"):
            ids = lo + jnp.arange(block, dtype=jnp.int32)
            cos = q @ _unit(rows_fn(jnp.minimum(ids, num_rows - 1))).T
            out = ((ids[None, :] == abc[:, 0:1]) | (ids[None, :] == abc[:, 1:2])
                   | (ids[None, :] == abc[:, 2:3]) | (ids[None, :] >= t_rows))
            best, at = jax.lax.top_k(jnp.where(out, -jnp.inf, cos), min(k, block))
            return best, lo + at

    parts = [block_best(q, abc, jnp.int32(lo)) for lo in range(0, t_rows, block)]
    scores = np.concatenate([np.asarray(s) for s, _ in parts], axis=1)
    rows = np.concatenate([np.asarray(r) for _, r in parts], axis=1)
    # best first, ties toward the lower row: the blocks lie in ascending row and
    # each block's own ties do too, so a stable sort by score keeps that order
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    scores, rows = (np.take_along_axis(scores, order, 1),
                    np.take_along_axis(rows, order, 1))

    @jax.jit
    def cosines_of(q, given):
        with jax.default_matmul_precision("highest"):
            u = _unit(rows_fn(given.reshape(-1))).reshape(*given.shape, -1)
            return jnp.einsum("nd,nmd->nm", q, u)

    def score_of(given: np.ndarray) -> np.ndarray:
        return np.asarray(cosines_of(q, jnp.asarray(given, jnp.int32)))

    return scores, rows, score_of


def accuracy(rows_fn, num_rows: int, questions: np.ndarray, candidates=None,
             block: int = 1 << 17) -> dict:
    """The tool's counts over ``questions`` (``int[N, 4]`` row ids a, b, c, d; -1 a
    word the vocabulary lacks): seen, scored, skipped, correct, accuracy; and of the
    live questions, in the order asked, the answers (``rows``, ``int[scored]``),
    their ``cosines`` and ``score_of`` (:func:`scores_and_answers`)."""
    t_rows = num_rows if candidates is None else min(int(candidates), num_rows)
    questions = np.asarray(questions)
    live = ((questions >= 0) & (questions < t_rows)).all(axis=1)
    asked = questions[live]
    rows, cosines, score_of = np.zeros(0, np.int64), np.zeros(0, np.float32), None
    if len(asked):
        best, at, score_of = scores_and_answers(rows_fn, num_rows, asked, 1, candidates,
                                                block)
        rows, cosines = at[:, 0], best[:, 0]
    correct = int((rows == asked[:, 3]).sum())
    scored = int(live.sum())
    return {"seen": len(questions), "scored": scored, "skipped": len(questions) - scored,
            "correct": correct, "accuracy": correct / scored if scored else 0.0,
            "rows": rows, "cosines": cosines, "score_of": score_of}
