"""word2vec's accuracy test as one operation of the model, on the CPU.

``benchmark/reference/analogy_ref.py`` is held to a float64 NumPy brute force of
its docstring's equations (exclusions, skipped questions, a threshold, ties,
a row of zero norm). Then ``Word2VecModel.analogies`` / ``analogy_accuracy`` are
held to it on seeded tables: rows and cosines inside the configuration's limits;
a, b and c never an answer, also where one of them is the argmax; a question
with a missing word skipped and counted, in any place; ``restrict_vocab``; ties
toward the lower row; any N against any capacity, and a call of several
programs equal to its questions asked one at a time; the answers of
``find_synonyms_batch`` over host-built unit-vector queries with the three words
filtered; ``analogy`` left as it was. Last, the comparison a benchmark run makes
(``kinds/analogy``), where planted faults must each fail a NAMED reading.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader, weights  # noqa: E402
from harness.common import Checks  # noqa: E402
from kinds import analogy as kind  # noqa: E402
from reference import analogy_ref as ref  # noqa: E402

D, HALF_WIDTH, SEED = 40, 0.5, 2**31 + 55
# sgns-analogy-3m-300's own limits (the CPU's float32 matmul reads far under them)
LIMITS = {"score_abs_err_mean": 3e-4, "score_abs_err_max": 1.2e-3, "rank_gap_max": 1e-3}


def _table(v: int) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(weights.rows_uniform(weights.seed32(SEED), 0,
                                           jnp.arange(v, dtype=jnp.int32), D, D, HALF_WIDTH))


def _rows_fn(table: np.ndarray):
    import jax.numpy as jnp
    held = jnp.asarray(table)
    return lambda ids: held[ids]


def _model(table: np.ndarray, dtype=None):
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    v = len(table)
    vocab = Vocabulary.from_words_and_counts([f"w{i}" for i in range(v)],
                                             np.arange(v, 0, -1).astype(np.int64))
    return Word2VecModel(vocab, jnp.asarray(table, dtype or jnp.float32),
                         config=Word2VecConfig(vector_size=D))


def _brute(table: np.ndarray, questions: np.ndarray, k: int, candidates=None):
    """float64, the docstring's equations, one question at a time."""
    t = table.astype(np.float64)
    norm = np.linalg.norm(t, axis=1, keepdims=True)
    u = np.where(norm > 0, t / np.where(norm > 0, norm, 1), 0)
    top = len(t) if candidates is None else candidates
    scores, rows = [], []
    for a, b, c in questions[:, :3]:
        q = u[b] - u[a] + u[c]
        cos = (u[:top] @ q) / max(np.linalg.norm(q), 1e-12)
        cos[[w for w in (a, b, c) if w < top]] = -np.inf
        order = np.lexsort((np.arange(top), -cos))[:k]
        scores.append(cos[order])
        rows.append(order)
    return np.asarray(scores), np.asarray(rows)


def _questions(v: int, n: int, seed: int = 5) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.stack([rng.choice(v, 4, replace=False) for _ in range(n)]).astype(np.int32)


@pytest.fixture(scope="module")
def small():
    table = _table(2000)
    return table, _model(table)


@pytest.mark.parametrize("block", [256, 1 << 17], ids=["many_blocks", "one_block"])
@pytest.mark.parametrize("k", [1, 4])
def test_the_reference_is_its_docstrings_equations(small, block, k):
    table, _ = small
    asked = _questions(2000, 40)
    scores, rows, score_of = ref.scores_and_answers(_rows_fn(table), 2000, asked, k, block=block)
    want_scores, want_rows = _brute(table, asked, k)
    assert (rows == want_rows).all()
    assert np.abs(scores - want_scores).max() < 2e-6
    assert np.abs(score_of(rows) - want_scores).max() < 2e-6


def test_the_reference_counts_skips_restricts_and_breaks_ties_toward_the_lower_row():
    table = _table(600).copy()
    table[77] = table[500]      # d planted twice: the lower row is the answer
    table[13] = 0.0             # a row of zero norm scores 0, never nan
    asked = _questions(600, 30)
    asked[0] = (1, 2, 3, -1)    # a word the vocabulary lacks
    asked[1] = (1, 599, 3, 4)   # a word past the threshold
    asked[2] = (13, 5, 6, 7)    # a question over the zero row
    got = ref.accuracy(_rows_fn(table), 600, asked, candidates=550)
    live = ((asked >= 0) & (asked < 550)).all(axis=1)
    _, want = _brute(table, asked[live], 1, candidates=550)
    assert got["seen"] == 30 and got["scored"] == int(live.sum()) == 30 - got["skipped"]
    assert (got["rows"] == want[:, 0]).all() and got["rows"].max() < 550
    assert got["correct"] == int((want[:, 0] == asked[live][:, 3]).sum())
    # q = u_500 exactly where a = b: the planted copy at row 77 wins the tie, and c
    # itself, the argmax, is excluded
    tie = np.asarray([[9, 9, 500, 77]], np.int32)
    got = ref.accuracy(_rows_fn(table), 600, tie)
    assert got["rows"].tolist() == [77] and got["correct"] == 1


@pytest.mark.parametrize("v", [2000, 20000])
@pytest.mark.parametrize("num", [1, 5])
def test_analogies_answer_as_the_reference_inside_the_configurations_limits(v, num, monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v
    monkeypatch.setattr(w2v, "_ANALOGY_BLOCK_ROWS", 4096)     # five blocks and a part of one
    table = _table(v)
    model = _model(table)
    asked = _questions(v, 300)
    got = model.analogies(asked[:, :3], num=num)
    scores, rows, score_of = ref.scores_and_answers(_rows_fn(table), v, asked, num)
    served_rows = np.asarray([[int(w[1:]) for w, _ in g] for g in got])
    served = np.asarray([[s for _, s in g] for g in got])
    at_served = score_of(served_rows)
    assert served_rows.shape == (300, num)
    assert np.abs(served - at_served).mean() <= LIMITS["score_abs_err_mean"]
    assert np.abs(served - at_served).max() <= LIMITS["score_abs_err_max"]
    assert (scores - at_served).max() <= LIMITS["rank_gap_max"]
    assert (served_rows == rows).mean() > 0.99       # the CPU's float32: the same rows
    assert not (served_rows[:, :, None] == asked[:, None, :3]).any()
    counts = model.analogy_accuracy(asked)
    assert counts == {"seen": 300, "scored": 300, "skipped": 0,
                      "correct": int((rows[:, 0] == asked[:, 3]).sum()),
                      "accuracy": int((rows[:, 0] == asked[:, 3]).sum()) / 300}
    model.stop()


def test_a_b_and_c_are_never_an_answer_also_where_one_is_the_argmax(small):
    table, model = small
    # a = b: q is c's own unit vector, whose best row is c; b = c: q is u_c + (u_c - u_a)
    asked = np.asarray([[9, 9, 500, 1], [3, 44, 44, 1], [7, 8, 7, 1]], np.int32)
    for num in (1, 3):
        got = model.analogies(asked[:, :3], num=num)
        _, want = _brute(table, asked, num)
        for g, row, w in zip(got, asked, want):
            assert [int(x[1:]) for x, _ in g] == w.tolist()
            assert not {int(x[1:]) for x, _ in g} & set(row[:3].tolist())


@pytest.mark.parametrize("place", [0, 1, 2, 3])
def test_a_question_with_a_missing_word_is_skipped_and_counted(small, place):
    table, model = small
    asked = _questions(2000, 20)
    strings = [tuple(f"w{w}" for w in row) for row in asked.tolist()]
    lost = list(strings[5])
    lost[place] = "nope"
    strings[5] = tuple(lost)
    counts = model.analogy_accuracy(strings)
    ids = asked.copy()
    ids[5, place] = -1
    want = ref.accuracy(_rows_fn(table), 2000, ids)
    assert counts["seen"] == 20 and counts["scored"] == 19 and counts["skipped"] == 1
    assert counts["correct"] == want["correct"]
    assert model.analogy_accuracy(ids) == counts        # row ids in, the same counts
    if place < 3:
        answers = model.analogies([q[:3] for q in strings])
        assert answers[5] is None and all(a is not None for a in answers[:5] + answers[6:])


def test_restrict_vocab_skips_a_word_outside_it_and_never_answers_one(small):
    table, model = small
    asked = _questions(2000, 200)
    top = 700
    counts = model.analogy_accuracy(asked, restrict_vocab=top)
    want = ref.accuracy(_rows_fn(table), 2000, asked, candidates=top)
    live = (asked < top).all(axis=1)
    assert 0 < live.sum() < 200
    assert {k: counts[k] for k in ("seen", "scored", "skipped", "correct")} == {
        k: want[k] for k in ("seen", "scored", "skipped", "correct")}
    # a d past the threshold alone skips the question too
    one = np.asarray([[1, 2, 3, 1500]], np.int32)
    assert model.analogy_accuracy(one, restrict_vocab=top)["skipped"] == 1
    got = model.analogies(asked[:, :3], num=3, restrict_vocab=top)
    live3 = (asked[:, :3] < top).all(axis=1)
    assert [g is not None for g in got] == live3.tolist()
    _, rows = _brute(table, asked[live3], 3, candidates=top)
    served = np.asarray([[int(w[1:]) for w, _ in g] for g in got if g is not None])
    assert served.max() < top and (served == rows).all()


def test_ties_go_to_the_lower_row_within_a_block_and_across_blocks(monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v
    monkeypatch.setattr(w2v, "_ANALOGY_BLOCK_ROWS", 256)
    table = _table(1000).copy()
    table[[40, 41, 700]] = table[900]        # one row four times, in three blocks
    model = _model(table)
    got = model.analogies(np.asarray([[9, 9, 900]], np.int32), num=3)[0]
    assert [w for w, _ in got] == ["w40", "w41", "w700"]
    assert len({s for _, s in got}) == 1
    assert model.analogy_accuracy(np.asarray([[9, 9, 900, 40]], np.int32))["correct"] == 1
    model.stop()


@pytest.mark.parametrize("n", [0, 1, 15, 16, 17, 33, 50], ids=lambda n: f"n{n}")
def test_any_number_of_questions_over_any_capacity_is_the_questions_one_at_a_time(n, monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v
    monkeypatch.setattr(w2v, "_ANALOGY_MAX_QUESTIONS", 16)   # 50 questions: four programs
    monkeypatch.setattr(w2v, "_ANALOGY_CAP_FLOOR", 8)
    monkeypatch.setattr(w2v, "_ANALOGY_BLOCK_ROWS", 512)
    table = _table(2000)
    model = _model(table)
    asked = _questions(2000, n, seed=n) if n else np.zeros((0, 4), np.int32)
    if n > 3:
        asked[2, 1] = -1
    whole = model.analogies(asked[:, :3], num=2)
    assert len(whole) == n
    singly = [model.analogies(asked[i:i + 1, :3], num=2)[0] for i in range(n)]
    assert whole == singly
    counts = model.analogy_accuracy(asked)
    assert counts["seen"] == n and counts["skipped"] == int(n > 3)
    assert counts["correct"] == sum(
        a is not None and a[0][0] == f"w{d}" for a, d in zip(whole, asked[:, 3]))
    assert counts["accuracy"] == (counts["correct"] / counts["scored"] if counts["scored"] else 0.0)
    model.stop()


def test_the_answers_are_the_exact_scans_over_host_built_unit_vector_queries(small):
    """``find_synonyms_batch`` of q = u_b - u_a + u_c built on the host, a, b and c
    filtered out of ``num`` + 3 neighbours: the exact scan the repo has."""
    table, model = small
    asked = _questions(2000, 64)
    inv = np.asarray(model._inverse_norms())[:2000]
    u = table * inv[:, None]
    queries = [u[b] - u[a] + u[c] for a, b, c in asked[:, :3]]
    by_scan = [[(w, s) for w, s in reply if int(w[1:]) not in set(row[:3].tolist())][:5]
               for reply, row in zip(model.find_synonyms_batch(queries, 8), asked)]
    got = model.analogies(asked[:, :3], num=5)
    assert [[w for w, _ in g] for g in got] == [[w for w, _ in r] for r in by_scan]
    np.testing.assert_allclose([[s for _, s in g] for g in got],
                               [[s for _, s in r] for r in by_scan], rtol=0, atol=5e-7)


def test_analogy_keeps_its_semantics_raw_rows_through_find_synonyms(small):
    table, model = small
    a, b, c = "w10", "w20", "w30"
    want = [(w, s) for w, s in model.find_synonyms(table[20] - table[10] + table[30], 8)
            if w not in (a, b, c)][:5]
    assert model.analogy(a, b, c, 5) == want


def test_the_call_is_recorded_span_by_span(small, monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v
    from glint_word2vec_tpu.obs.spans import default_tracer
    monkeypatch.setattr(w2v, "_ANALOGY_MAX_QUESTIONS", 16)
    monkeypatch.setattr(w2v, "_ANALOGY_CAP_FLOOR", 8)
    _, model = small
    asked = _questions(2000, 40)
    asked[3, 0] = -1
    tracer = default_tracer()
    tracer.configure(True)
    try:
        model.analogy_accuracy(asked)
        events = tracer.events()
    finally:
        tracer.configure(False)
        tracer.clear()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (call,) = by_name["eval.call"]
    assert call["args"] == {"num": 1, "candidates": 2000, "questions": 40, "scored": 39,
                            "skipped": 1}
    (encode,) = by_name["eval.encode"]
    assert encode["args"]["words"] == 160 and 0 < encode["args"]["distinct"] <= 117
    enqueued = [e["args"] for e in by_name["eval.enqueue"]]
    assert [a["questions"] for a in enqueued] == [16, 16, 7]
    assert [a["cap"] for a in enqueued] == [16, 16, 8]
    assert all(a["programs"] == 3 for a in enqueued)
    assert [a["inflight"] for a in enqueued] == [0, 1, 1]       # two in flight, never three
    assert len(by_name["eval.fetch"]) == 3
    for e in by_name["eval.encode"] + by_name["eval.enqueue"] + by_name["eval.fetch"]:
        assert e["parent"] == call["id"]
    counters = kind.span_counters(events)
    assert counters["slice_calls"] == 3 and counters["slice_eval_calls"] == 1
    assert counters["slice_questions"] == 39 and counters["slice_capacity"] == 40
    assert counters["slice_skipped"] == 1 and counters["slice_seen"] == 40


def test_a_table_partitioned_by_rows_raises_naming_the_operation():
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    plan = make_mesh(1, 2)
    syn0 = weights.make_table(SEED, 0, 512, D, D, HALF_WIDTH, jnp.float32,
                               sharding=plan.embedding)
    vocab = Vocabulary.from_words_and_counts([f"w{i}" for i in range(512)],
                                             np.ones(512, np.int64))
    model = Word2VecModel(vocab, syn0, config=Word2VecConfig(vector_size=D), plan=plan)
    with pytest.raises(NotImplementedError, match="analogies"):
        model.analogy_accuracy(np.asarray([[1, 2, 3, 4]], np.int32))
    model.stop()


# -- the comparison a benchmark run makes ------------------------------------------

CELL_NAME = "sgns-analogy-3m-300.analogy-sections-closed1"


@pytest.fixture(scope="module")
def cell():
    return loader.resolve(loader.load_manifest(ROOT), CELL_NAME, ROOT)


def _readings(cell, model, sizes, strings, ids, said_of=lambda said: said, rows_of=None):
    """``compare_with_reference`` over every section called once."""
    sample = [(s, said_of(model.analogy_accuracy(strings[s]))) for s in range(len(strings))]
    replayed = kind.replay(model, sample, ids)
    if rows_of is not None:
        replayed = [rows_of(*r) for r in replayed]
    checks = Checks()
    kind.compare_with_reference(SEED, sizes, cell, True, sample, replayed, ids,
                                {**LIMITS, "accuracy_gap": 0.02}, checks)
    return {name: (value, ok) for name, value, _, ok in checks.rows}


def test_the_planted_relations_make_the_questions_the_configuration_states(cell):
    cfg, tf = loader.sizes(cell["config"], False), loader.sizes(cell["traffic"], False)
    assert sum(cfg["section_sizes"]) == 19544 and len(cfg["section_sizes"]) == 14
    assert [kind.pairs_of(n) for n in (506, 4524, 992)] == [23, 68, 32]
    tiny = loader.sizes(cell["config"], True)
    y_ids, y_rows, sections = kind.plant_relations(SEED, tiny, loader.sizes(cell["traffic"], True),
                                                   tiny["vector_size"], HALF_WIDTH)
    assert [len(s) for s in sections] == tiny["section_sizes"]
    words = np.concatenate([s.reshape(-1) for s in sections])
    assert len(np.unique(y_ids)) == len(y_ids) and words.max() < tf["word_ranks"]
    for s in sections:       # every question's four words are four words
        assert all(len(set(row)) == 4 for row in s.tolist())
    again = kind.plant_relations(SEED, tiny, loader.sizes(cell["traffic"], True),
                                 tiny["vector_size"], HALF_WIDTH)
    assert (again[1] == y_rows).all() and all((a == b).all() for a, b in zip(again[2], sections))


def test_the_benchmarks_comparison_passes_a_sound_model_and_names_planted_faults(cell):
    model, sizes, strings, ids = kind.build_model(cell, SEED, True)
    sound = _readings(cell, model, sizes, strings, ids)
    assert all(ok for _, ok in sound.values()), sound
    assert 0.02 < model.analogy_accuracy(strings[1])["accuracy"] < 0.98
    # a call that counted a skipped question as scored
    off = _readings(cell, model, sizes, strings, ids,
                    said_of=lambda said: {**said, "scored": said["scored"] + 1,
                                          "skipped": said["skipped"] - 1})
    assert not off["counts_differ"][1] and off["score_abs_err_mean"][1]
    # a window whose counts the same program does not give again
    off = _readings(cell, model, sizes, strings, ids,
                    said_of=lambda said: {**said, "correct": said["correct"] + 3})
    assert not off["replay_differs"][1] and not off["accuracy_gap"][1]
    # c returned as the answer; a cosine off by a norm left out
    first_c = [ids[s][(ids[s] >= 0).all(axis=1)][:, 2] for s in range(len(ids))]
    turn = iter(first_c)
    off = _readings(cell, model, sizes, strings, ids, rows_of=lambda rows, cos: (next(turn), cos))
    assert not off["excluded_words_returned"][1] and not off["rank_gap_max"][1]
    off = _readings(cell, model, sizes, strings, ids, rows_of=lambda rows, cos: (rows, cos * 1.01))
    assert not off["score_abs_err_max"][1] and off["excluded_words_returned"][1]
    model.stop()


def test_a_bfloat16_table_fails_the_score_limits(cell):
    model, sizes, strings, ids = kind.build_model(cell, SEED, True, table_dtype="bfloat16")
    control = _readings(cell, model, sizes, strings, ids)
    assert not control["score_abs_err_mean"][1], control
    model.stop()
