"""The sentence-vector cell's plain reference, and the comparison that decides
``correct``, on the CPU.

``benchmark/reference/sentvec_ref.py`` is held to a literal float64 computation
of its docstring's equations on a handful of sentences (a word, an unseen
string, repeats, ``""``, an empty sentence). Then
``Word2VecModel.sentence_vectors`` is held to it through the very comparison a
benchmark run makes (``kinds/sentvec``: ``make_slides``, ``check_rows_of``,
``compare_with_reference``; ``kinds/transform``: ``keep_rows``,
``draw_sample``), and planted faults must each fail a NAMED reading of it: a
sum where the mean is, an unnormalised token, unseen tokens dropped, a
zero-norm token counted, the word's own row missing from G, a list cut at a
capacity, rows in another order, bfloat16 tables, an empty sentence answered
with something, a hasher that lists another bucket.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import words  # noqa: E402
from harness.common import Checks  # noqa: E402
from kinds import sentvec as kind  # noqa: E402
from kinds.transform import draw_sample, keep_rows  # noqa: E402
from reference import sentvec_ref as ref  # noqa: E402
from reference.subword_ref import fnv1a, ngrams  # noqa: E402

V, D, K, HALF_WIDTH, SEED = 5000, 40, 3000, 0.3, 2**31 + 52
MIN_N, MAX_N = 3, 6
TF = {"callers": 1, "slides_per_caller": 3, "slide_rows": 200, "unseen_share": 0.05,
      "empty_share": 0.02, "check_rows_per_call": 4, "check_sentences": 12,
      "sentence_len": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 1, "max": 1000}}
STRINGS = words.make_words(SEED, V)
INDEX = ref.dictionary(STRINGS)
SIZES = dict(v=V, d=D, k=K, half_width=HALF_WIDTH, strings=STRINGS, ngram=(MIN_N, MAX_N))
# the configuration's ``tiny`` limits: the CPU sums in float32 as the chip does
LIMITS = {"row_rel_err_mean": 1e-6, "row_rel_err_max": 1e-5, "unit_norm_err_max": 1e-6}


@pytest.fixture(scope="module")
def table() -> np.ndarray:
    """The trained input table [V + K, D] the reference makes from the seed."""
    import jax.numpy as jnp
    return np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(
        jnp.arange(V + K, dtype=jnp.int32)))


@pytest.fixture(scope="module")
def slides():
    made, planted = kind.make_slides(SEED, STRINGS, INDEX, TF)
    return made, planted, kind.check_rows_of(SEED, made, planted, TF)


def _h(table, token: str) -> np.ndarray:
    """h(t), literally: the word's own row if it is one, and the bucket row of
    every substring of 3-6 characters of "<t>", averaged."""
    rows = ([INDEX[token]] if token in INDEX else []) + [
        V + fnv1a(g.encode("utf-8")) % K for g in ngrams(token, MIN_N, MAX_N)]
    return table[rows].astype(np.float64).mean(axis=0) if rows else np.zeros(D)


def test_the_reference_is_its_docstrings_equations(table):
    a, b, c = STRINGS[1], STRINGS[4321], STRINGS[7]
    sents = [[a, "zzq", a], [], [""], ["zzq"], [b, "", c, "naïve"], [c] * 5]
    got = ref.sentence_vectors(sents, INDEX, lambda ids: table[np.asarray(ids)], D, K,
                               MIN_N, MAX_N)

    def u(token):
        return _h(table, token) / np.linalg.norm(_h(table, token))

    want = np.zeros((len(sents), D))
    want[0] = (2 * u(a) + u("zzq")) / 3
    want[3] = u("zzq")
    want[4] = (u(b) + u(c) + u("naïve")) / 3          # "" has no n-gram: left out
    want[5] = u(c)
    assert got.dtype == np.float32 and got.shape == (6, D)
    assert np.abs(got - want).max() <= 6e-8
    assert not got[1].any() and not got[2].any()
    assert abs(np.linalg.norm(got[3]) - 1) <= 1e-7
    # an unseen string has no row of its own; a word has
    assert "zzq" not in INDEX and len(ref.token_lists(["zzq", a], INDEX, K)["zzq"]) == 6
    assert ref.token_lists([a], INDEX, K)[a][0] == 1


def test_slides_are_the_traffic_files(slides):
    made, planted, rows = slides
    assert len(made) == 1 and len(made[0]) == 3
    for (sentences, empty), plant, picked in zip(made[0], planted[0], rows[0]):
        lens = np.array([len(s) for s in sentences])
        assert len(sentences) == 200 and lens.max() <= 1000
        assert 15 <= np.median(lens) <= 27
        assert empty.tolist() == (lens == 0).tolist()
        lost = np.array([t not in INDEX for s in sentences for t in s])
        assert 0.03 <= lost.mean() <= 0.09
        assert len(sentences[plant]) == 1 and sentences[plant][0] not in INDEX
        assert picked[0] == 199 and len(set(picked.tolist())) == 4
        assert plant in picked and (empty[picked[1]] or not empty.any())
        # fresh str objects, not the vocabulary's own (but the one-letter
        # words: the interpreter keeps one object a letter)
        assert not any(t is STRINGS[INDEX[t]] for s in sentences[:20] for t in s
                       if t in INDEX and len(t) > 1)
    again = kind.make_slides(SEED, STRINGS, INDEX, TF)
    assert again[0][0][1][0] == made[0][1][0] and again[1] == planted
    assert kind.make_slides(SEED + 1, STRINGS, INDEX, TF)[0][0][1][0] != made[0][1][0]


def _readings(slides, vectors, rows_fn) -> dict:
    """One call a slide through ``vectors(sentences) -> reply``, kept as the
    kind's caller threads keep it, through the kind's comparison."""
    made, _, rows = slides
    finished = []
    for j, (sentences, _) in enumerate(made[0]):
        fault, kept = keep_rows(vectors(sentences), rows[0][j], len(sentences), D)
        finished.append((0.0, 1.0, (0, j), kept, fault))
    checks = Checks()
    kind.compare_with_reference(SEED, SIZES, INDEX,
                                draw_sample(SEED, made, rows, finished, TF),
                                LIMITS, checks, rows_fn)
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    return {name: ok for name, _, _, ok in checks.rows}


def _plain(table, sentences, mean=True, normalise=True, drop_unseen=False,
           count_zero=False, own_row=True, list_cap=None, empty_value=0.0):
    """The operation in NumPy float32 with a fault planted: ``mean`` False
    leaves the sums, ``normalise`` False adds h and not h / |h| for an unseen
    token, ``drop_unseen`` leaves the unseen tokens out (upstream's rule),
    ``count_zero`` counts a token of zero norm, ``own_row`` False leaves a
    word's own row out of G, ``list_cap`` cuts a list there, ``empty_value``
    answers a sentence with no vector."""
    lists = ref.token_lists([t for s in sentences for t in s], INDEX, K, MIN_N, MAX_N)
    out = np.zeros((len(sentences), D), np.float32)
    for i, s in enumerate(sentences):
        total, c = np.zeros(D, np.float32), 0
        for t in s:
            g = lists[t] if own_row or t not in INDEX else lists[t][1:]
            g = g[:list_cap]
            if drop_unseen and t not in INDEX:
                continue
            h = table[g].astype(np.float32).sum(axis=0) / max(len(g), 1)
            norm = float(np.linalg.norm(h))
            if norm > 0:
                total += h / norm if normalise or t in INDEX else h
            c += int(norm > 0 or count_zero)
        out[i] = total / (c if mean else 1) if c else empty_value
    return out


def test_the_program_passes_the_kinds_comparison(table, slides):
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    def rows_fn(ids):
        return table[np.asarray(ids)]

    for resident in ("all", "rows"):
        model = Word2VecModel(
            Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64)), table[:V], None,
            config=kind._subword_config(D, K, MIN_N, MAX_N), subword_buckets=table[V:],
            resident=resident)
        readings = _readings(slides, model.sentence_vectors, rows_fn)
        model.stop()
        assert all(readings.values()), readings
    assert all(_readings(slides, lambda s: _plain(table, s), rows_fn).values())
    # and with the rows the reference makes itself from the seed
    assert all(_readings(slides, lambda s: _plain(table, s), None).values())


FAULTS = {
    "a_sum_where_the_mean_is": (dict(mean=False), "row_rel_err_max"),
    "an_unnormalised_token": (dict(normalise=False), "unit_norm_err_max"),
    "unseen_tokens_dropped": (dict(drop_unseen=True), "row_rel_err_max"),
    "the_words_own_row_missing_from_g": (dict(own_row=False), "row_rel_err_max"),
    "a_list_cut_at_a_capacity": (dict(list_cap=12), "row_rel_err_max"),
    "an_empty_sentence_answered_with_something": (dict(empty_value=1e-30),
                                                  "empty_rows_not_zero"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_its_reading(table, slides, fault):
    how, reading = FAULTS[fault]
    readings = _readings(slides, lambda s: _plain(table, s, **how),
                         lambda ids: table[np.asarray(ids)])
    assert readings[reading] is False, readings


def test_an_unnormalised_or_dropped_token_fails_the_error_readings_too(table, slides):
    for how in (dict(normalise=False), dict(drop_unseen=True)):
        readings = _readings(slides, lambda s: _plain(table, s, **how),
                             lambda ids: table[np.asarray(ids)])
        assert readings["row_rel_err_mean"] is False and readings["row_rel_err_max"] is False


def test_a_zero_norm_token_counted_fails_the_error_readings(slides):
    """A table whose bucket rows are zeros: every unseen token has h = 0 and is
    left out; a program that counts it divides by too much."""
    import jax.numpy as jnp
    made, _, _ = slides
    own = ref.seeded_rows(SEED, D, HALF_WIDTH)

    def rows_fn(ids):
        ids = jnp.asarray(ids)
        return jnp.where((ids < V)[:, None], own(ids), 0.0)

    dead = np.asarray(rows_fn(np.arange(V + K, dtype=np.int32)))
    # a word keeps its own row over |G|: only the unseen tokens are of zero norm
    sound = _readings(slides, lambda s: _plain(dead, s), rows_fn)
    # the planted token has no vector here, so no one-token row has a norm to read
    no_vector = ("planted_rows_compared_short", "unit_norm_err_max")
    assert all(ok for name, ok in sound.items() if name not in no_vector)
    assert not any(sound[name] for name in no_vector)
    readings = _readings(slides, lambda s: _plain(dead, s, count_zero=True), rows_fn)
    assert readings["row_rel_err_max"] is False and readings["empty_rows_not_zero"] is True


def test_bfloat16_tables_fail_both_error_readings(table, slides):
    import jax.numpy as jnp
    rounded = np.asarray(jnp.asarray(table).astype(jnp.bfloat16).astype(jnp.float32))
    readings = _readings(slides, lambda s: _plain(rounded, s),
                         lambda ids: table[np.asarray(ids)])
    assert readings["row_rel_err_mean"] is False and readings["row_rel_err_max"] is False
    assert readings["unit_norm_err_max"] is True        # a unit vector all the same


def test_a_dropped_last_sentence_is_a_shape_fault_and_a_wrong_last_row(table, slides):
    readings = _readings(slides, lambda s: _plain(table, s)[:-1],
                         lambda ids: table[np.asarray(ids)])
    assert readings["reply_shape_faults"] is False
    assert readings["row_rel_err_max"] is False


def test_rows_in_another_order_fail_the_error_readings(table, slides):
    readings = _readings(slides, lambda s: _plain(table, s)[::-1],
                         lambda ids: table[np.asarray(ids)])
    assert readings["row_rel_err_max"] is False and readings["reply_shape_faults"] is True


def test_a_hasher_that_lists_another_bucket_is_counted(table, slides, monkeypatch):
    from glint_word2vec_tpu.data import subword

    def off_by_one(strings, min_n, max_n, buckets):
        ids, counts, native = real(strings, min_n, max_n, buckets)
        ids = ids.copy()
        ids[0] = (ids[0] + 1) % buckets
        return ids, counts, native

    real = subword.ngram_rows
    monkeypatch.setattr(subword, "ngram_rows", off_by_one)
    readings = _readings(slides, lambda s: _plain(table, s),
                         lambda ids: table[np.asarray(ids)])
    assert readings["reference_hasher_mismatches"] is False
    assert ref.hasher_mismatches(["abc"], [[1, 2]], K) == 1


def test_an_engagement_counter_outside_the_configurations_fails():
    expect = {"rows_per_word_max": 1.15, "list_slots_per_row_max": 1.15}
    sound = {"rows": 313000, "rows_cap": 327680, "passes": 1, "list_rows": 286000,
             "list_cap": 294912, "unseen": 16500, "unseen_cap": 32768}
    for engaged, failed in (
            (sound, set()),
            ({**sound, "rows_cap": 163840, "list_cap": 147456, "passes": 2},
             {"slide_passes_off"}),
            ({**sound, "rows_cap": 393216}, {"rows_per_word"}),
            # a [U, 48] block a token: 2.77 slots a live row
            ({**sound, "list_cap": 16500 * 48}, {"list_slots_per_row"}),
            ({}, {"slide_passes_off", "rows_per_word", "list_slots_per_row"})):
        checks = Checks()
        kind.add_engagement_checks(checks, engaged, expect)
        assert {name for name, _, _, ok in checks.rows if not ok} == failed


def test_a_program_without_the_operation_is_refused_before_the_tables(monkeypatch):
    """The parent's constructor takes no ``resident``: the run ends with a
    message (exit code 1 through SystemExit), as asked of a 64-word model."""
    from glint_word2vec_tpu.models import word2vec as w2v
    kind.require_sentence_vectors()
    monkeypatch.setattr(kind, "slide_engagement",
                        lambda model, slide: {"rows": 2, "rows_cap": 128, "passes": 1})
    with pytest.raises(SystemExit, match="nothing of a list capacity"):
        kind.require_sentence_vectors()
    monkeypatch.undo()
    parents = w2v.Word2VecModel.__init__.__wrapped__

    def init_without(self, *args, resident=None, **kw):
        if resident is not None:
            raise TypeError("__init__() got an unexpected keyword argument 'resident'")
        parents(self, *args, **kw)

    monkeypatch.setattr(w2v.Word2VecModel, "__init__", init_without)
    with pytest.raises(SystemExit, match="cannot run the cell.*resident"):
        kind.require_sentence_vectors()


def test_span_counters_are_the_slices_sums():
    events = [
        {"name": "transform.slide", "args": {"sentences": 4, "words": 90, "unseen": 5}},
        {"name": "transform.enqueue", "args": {
            "rows": 90, "rows_cap": 128, "passes": 1, "inflight": 0, "list_rows": 80,
            "list_cap": 128, "unseen": 5, "unseen_cap": 128}},
        {"name": "transform.enqueue", "args": {
            "rows": 300, "rows_cap": 160, "passes": 2, "inflight": 1, "list_rows": 500,
            "list_cap": 256, "unseen": 30, "unseen_cap": 128}},
        {"name": "serve.batch", "args": {"size": 3}}]
    assert kind.span_counters(events) == {
        "slice_rows_live": 390, "slice_rows_handed": 448, "slice_list_rows": 580,
        "slice_list_slots": 640, "slice_unseen": 35, "slice_inflight_and_self": 3,
        "slice_enqueues": 2}
    # a program whose transform.enqueue knows no lists gives nothing to read
    assert kind.span_counters([{"name": "transform.enqueue", "args": {
        "rows": 90, "rows_cap": 128, "passes": 1, "inflight": 0}}]) == {}
    assert kind.span_counters(events[-1:]) == {}


def test_the_cost_counts_live_rows_at_whole_lanes_their_ids_and_the_result():
    from costs import sentvec_gather
    cost = sentvec_gather.cost(rows=313000.0, list_rows=286000.0, unseen=16500.0,
                               sentences=10000, dim=300, table_dtype="float32")
    assert cost["bytes"] == ((313000 + 286000) * 384 * 4
                             + 4 * (3 * 313000 + 2 * 286000 + 16500 + 10000)
                             + 4 * 10000 * 300)
    assert cost["flops"] < cost["bytes"]
