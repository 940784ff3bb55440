"""The sharded query cell's plain reference, and the program held to it, on the CPU.

``benchmark/reference/sharded_query_ref.py`` knows no mesh: it is held here to a
float64 brute force of its docstring's equations (blocks that do not divide the
table, ties inside and across blocks and at the k-th place, a zero-norm row, the
query word left out). Then ``Word2VecModel(plan=make_mesh(d, n))`` on 1x2, 1x4,
1x8 and 2x2 virtual devices is held to the reference through the very
comparison a benchmark run makes (``kinds/query_sharded.compare_with_reference``),
and four planted faults must each fail a NAMED reading of it: a merge that leaves
a shard out, local ids returned without their shard's offset, ties broken toward
the higher row, a bfloat16 table.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import weights  # noqa: E402
from harness.common import Checks  # noqa: E402
from kinds import query_sharded as kind  # noqa: E402
from reference import sharded_query_ref as ref  # noqa: E402

V, D, HALF_WIDTH, K, SEED = 20000, 40, 0.3, 10, 2**31 + 45
TF = {"num_synonyms": K, "check_queries": 16, "check_offshard_share": 0.5}
# the configuration's ``tiny`` limits: the CPU's float32 matmul is near exact
LIMITS = {"score_abs_err_mean": 1e-5, "score_abs_err_max": 1e-4, "rank_gap_max": 1e-5}


def _table() -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(jnp.arange(V, dtype=jnp.int32)))


def _brute(table: np.ndarray, qids, k: int, higher_row_first: bool = False):
    """The docstring's equations in float64: (scores [Q, k], rows [Q, k])."""
    t = table.astype(np.float64)
    norms = np.linalg.norm(t, axis=1)
    out_s, out_r = [], []
    for q in qids:
        dots = t @ (t[q] / max(norms[q], 1e-12))
        score = np.where(norms > 0, dots / np.maximum(norms, 1e-12), 0.0)
        score[q] = -np.inf
        rows = np.arange(len(t))
        order = np.lexsort((-rows if higher_row_first else rows, -score))[:k]
        out_s.append(score[order])
        out_r.append(order)
    return np.stack(out_s), np.stack(out_r)


def _rows_fn(table: np.ndarray):
    import jax.numpy as jnp
    held = jnp.asarray(table)
    return lambda ids: held[ids]


@pytest.mark.parametrize("block", [20000, 7000, 1024])
def test_reference_is_the_brute_force_over_all_rows(block):
    table = _table()
    qids = [3, 4999, 5000, 12345, V - 1]
    scores, rows = ref.top_k(ref.seeded_rows(SEED, D, HALF_WIDTH), V, qids, K, block=block)
    want_s, want_r = _brute(table, qids, K)
    np.testing.assert_array_equal(rows, want_r)
    np.testing.assert_allclose(scores, want_s, rtol=0, atol=1e-6)
    pairs = ref.pair_scores(ref.seeded_rows(SEED, D, HALF_WIDTH), qids, want_r)
    np.testing.assert_allclose(pairs, want_s, rtol=0, atol=1e-6)


def test_reference_breaks_ties_toward_the_lower_row_and_scores_a_zero_row_0():
    """One row planted five times (inside a block, across blocks, and where
    the k-th place cuts the run of equals), and a zero-norm row."""
    table = _table()[:3000].copy()
    table[[40, 41, 1500, 2900, 2999]] = table[7]
    table[9] = 0.0
    rows_fn = _rows_fn(table)
    scores, rows = ref.top_k(rows_fn, 3000, [7, 9, 40], 3, block=1024)
    assert rows[0].tolist() == [40, 41, 1500]       # 2900 and 2999 cut, not 40
    assert rows[2].tolist() == [7, 41, 1500]
    np.testing.assert_allclose(scores[[0, 2]], 1.0, atol=1e-6)
    # a zero-norm query scores 0 against every row: the lowest rows, itself out
    assert rows[1].tolist() == [0, 1, 2] and not scores[1].any()
    assert ref.pair_scores(rows_fn, [7], [[9, 40]]).tolist() == [[0.0, 1.0]]


# -- the program against the reference, through the benchmark's comparison ------------


def _finished(qids, replies):
    return [(0.0, 1.0, int(q), reply) for q, reply in zip(qids, replies)]


def _query_ids(shards: int):
    """16 words: 8 of the first shard, 8 beyond it, the last row among them."""
    rng = np.random.default_rng(5)
    per = V // shards
    return np.concatenate([rng.choice(per, 8, replace=False),
                           per + rng.choice(V - per - 1, 7, replace=False), [V - 1]])


def _compare(finished, shards, rows_fn=None, limits=LIMITS) -> dict:
    checks = Checks()
    kind.compare_with_reference(
        SEED, dict(v=V, d=D, half_width=HALF_WIDTH, shards=shards), finished, TF,
        limits, checks, rows_fn=rows_fn)
    return {name: ok for name, _, _, ok in checks.rows}


@pytest.mark.parametrize("mesh", [(1, 2), (1, 4), (1, 8), (2, 2)],
                         ids=lambda m: f"{m[0]}x{m[1]}")
def test_model_on_a_mesh_passes_the_benchmarks_comparison(mesh):
    """Tables made shard by shard from the seed, as kinds/query_sharded.py makes
    them; the model's replies pass every reading, and are the reference's rows."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    plan = make_mesh(*mesh)
    syn0 = weights.make_table(SEED, 0, V, D, D, HALF_WIDTH, jnp.float32,
                              sharding=plan.embedding)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(V)], np.ones(V, np.int64))
    model = Word2VecModel(vocab, syn0, config=Word2VecConfig(vector_size=D), plan=plan)
    assert model._full0 is syn0                     # placed: taken as it is
    qids = _query_ids(plan.num_model)
    replies = model.find_synonyms_batch([f"w{q}" for q in qids], K)
    engaged = kind.scan_engagement(model, K)
    assert engaged["shards"] == plan.num_model
    assert engaged["merge_rows"] == plan.num_model * (K + 1)
    readings = _compare(_finished(qids, replies), plan.num_model)
    assert all(readings.values()), readings
    _, want = ref.top_k(ref.seeded_rows(SEED, D, HALF_WIDTH), V, qids, K)
    assert [[int(w[1:]) for w, _ in r] for r in replies] == want.tolist()
    model.stop()


def _faulty_replies(table, qids, fault: str, shards: int = 4):
    """What a program with one planted fault would serve, in NumPy."""
    per = len(table) // shards
    t = table.astype(np.float32)
    if fault == "bfloat16_table":
        import jax.numpy as jnp
        t = np.asarray(jnp.asarray(t).astype(jnp.bfloat16).astype(jnp.float32))
    scores, rows = _brute(t, qids, len(t) if fault == "shard_left_out" else K,
                          higher_row_first=fault == "ties_toward_higher_row")
    replies = []
    for s, r in zip(scores, rows):
        if fault == "shard_left_out":               # shard 2's candidates never merged
            keep = (r // per != 2)
            s, r = s[keep][:K], r[keep][:K]
        if fault == "local_ids":                    # a shard's row without its first row
            r = r % per
        replies.append([(f"w{int(i)}", float(x)) for x, i in zip(s, r)])
    return replies


@pytest.mark.parametrize("fault,failed", [
    ("none", set()),
    ("shard_left_out", {"rank_gap_max", "shards_missing_from_neighbours"}),
    ("local_ids", {"score_abs_err_mean", "score_abs_err_max", "rank_gap_max",
                   "shards_missing_from_neighbours"}),
    ("bfloat16_table", {"score_abs_err_mean"}),
])
def test_planted_fault_fails_a_named_reading(fault, failed):
    table, qids = _table(), _query_ids(4)
    readings = _compare(_finished(qids, _faulty_replies(table, qids, fault)), 4)
    got = {name for name, ok in readings.items() if not ok}
    assert failed <= got, (fault, readings)
    if not failed:
        assert not got
    else:
        assert "reply_order_faults" not in got and "rows_past_vocabulary" not in got


def test_ties_toward_the_higher_row_fail_the_order_reading():
    """One row planted on three shards: a merge that hands equal scores back
    higher row first serves the right neighbours in the wrong order."""
    table = _table().copy()
    table[[6000, 15000]] = table[100]
    qids = _query_ids(4)
    qids[0] = 100
    for fault, ok in (("none", True), ("ties_toward_higher_row", False)):
        readings = _compare(_finished(qids, _faulty_replies(table, qids, fault)), 4,
                            rows_fn=_rows_fn(table))
        assert readings["reply_order_faults"] is ok, (fault, readings)
        assert all(v for name, v in readings.items() if name != "reply_order_faults")


def test_a_row_past_the_vocabulary_and_a_short_sample_are_named():
    table, qids = _table(), _query_ids(4)
    replies = _faulty_replies(table, qids, "none")
    replies[3][4] = (f"w{V + 3}", replies[3][4][1])       # a mesh's padding row
    readings = _compare(_finished(qids, replies), 4)
    assert not readings["rows_past_vocabulary"]
    first_shard_only = [f for f in _finished(qids, _faulty_replies(table, qids, "none"))
                        if f[2] < V // 4]
    assert not _compare(first_shard_only, 4)["offshard_replies_compared_short"]
