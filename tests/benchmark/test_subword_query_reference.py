"""The plain reference of fastText's ``nn`` over composed vectors
(benchmark/reference/subword_query_ref.py): held to a vocabulary of 5 words and
16 buckets worked by hand, with the faults a program could make planted one at
a time, each of which must move an answer; its NumPy hasher held to the paper's
loop; a rehearsal of kind ``query_subword`` on the CPU; a planted program fault
coming out not correct."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader, words  # noqa: E402
from kinds import query_subword as kind  # noqa: E402
from reference import subword_query_ref as ref  # noqa: E402
from reference import subword_ref  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "subword-nn-2.5m-300.query-unseen25-closed64"

# -- the vocabulary worked by hand ------------------------------------------------------
# five words, K = 16 buckets, n-grams of 3 to 6 characters of "<w>", by start
# then by length, with the bucket (FNV-1a mod 16) each lands in
WORDS = ["ab", "abc", "b", "cab", "bca"]
V, K, D = 5, 16, 4
NGRAMS = {
    "ab": (["<ab", "<ab>", "ab>"], [4, 14, 12]),
    "abc": (["<ab", "<abc", "<abc>", "abc", "abc>", "bc>"], [4, 5, 1, 11, 15, 4]),
    "b": (["<b>"], [15]),
    "cab": (["<ca", "<cab", "<cab>", "cab", "cab>", "ab>"], [11, 11, 15, 1, 13, 12]),
    "bca": (["<bc", "<bca", "<bca>", "bca", "bca>", "ca>"], [8, 11, 15, 9, 5, 5]),
    # two strings the vocabulary lacks
    "abd": (["<ab", "<abd", "<abd>", "abd", "abd>", "bd>"], [4, 0, 10, 10, 12, 7]),
    "ba": (["<ba", "<ba>", "ba>"], [14, 0, 6]),
}
TABLE = (np.arange((V + K) * D, dtype=np.float32).reshape(V + K, D) % 7 - 3.0
         + np.arange(V + K, dtype=np.float32)[:, None] * 0.25)


def rows_fn(ids):
    import jax.numpy as jnp
    return jnp.asarray(TABLE)[ids]


def by_hand(fault=None):
    """Replies to every word and to the two unseen strings, worked from the
    lists above with NumPy alone; ``fault`` plants one mistake."""
    def buckets_of(string):
        if fault == "marks_left_off":
            return [subword_ref.fnv1a(string[i:i + n].encode()) % K
                    for i in range(len(string)) for n in range(3, 7)
                    if i + n <= len(string)]
        if fault == "modulo_left_off":
            # the hash itself as a row of the table, wrapped where the table
            # ends and not where the buckets do
            return [subword_ref.fnv1a(g.encode()) % (V + K) - V for g in NGRAMS[string][0]]
        return NGRAMS[string][1]

    def h(string, word_id):
        rows = [V + b for b in buckets_of(string)]
        if word_id is not None or fault == "unseen_given_an_own_row":
            rows = [0 if word_id is None else word_id] + rows
        if not rows:
            return np.zeros(D, np.float32)
        total = TABLE[rows].sum(axis=0)
        return total if fault == "sum_for_mean" else total / len(rows)

    scanned = (TABLE[:V] if fault == "raw_rows_scanned"
               else np.stack([h(w, i) for i, w in enumerate(WORDS)]))
    out = {}
    for string in WORDS + ["abd", "ba"]:
        wid = WORDS.index(string) if string in WORDS else None
        q = h(string, wid)
        norms = np.linalg.norm(scanned, axis=1) * np.linalg.norm(q)
        score = np.where(norms > 0, scanned @ q / np.maximum(norms, 1e-30), 0.0)
        if wid is not None and fault != "query_word_not_excluded":
            score[wid] = -np.inf
        if wid is None and fault == "excluded_for_an_unseen_string":
            score[int(np.argmax(score))] = -np.inf
        order = np.argsort(-score, kind="stable")[:2]
        out[string] = ([int(i) for i in order], score[order])
    return out


def by_reference():
    lists = ref.bucket_lists(WORDS, K)
    table = ref.composed_table(rows_fn, lists, V, block=4)
    out = {}
    for string in WORDS + ["abd", "ba"]:
        wid = WORDS.index(string) if string in WORDS else None
        scores = ref.cosine_scores(table, ref.vector(rows_fn, string, wid, V, K)[None])[0]
        ids = ref.reply(scores, 2, wid)
        out[string] = (ids, scores[ids])
    return out


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "reference", "subword_query_ref.py")).read()
    assert "glint_word2vec_tpu" not in source.split('"""', 2)[2]
    assert '"highest"' in source and "float32" in source


def test_the_hand_worked_lists_are_the_papers_and_the_hashers():
    lists = ref.bucket_lists(list(NGRAMS), K)
    for row, (string, (grams, buckets)) in zip(lists, NGRAMS.items()):
        assert subword_ref.ngrams(string) == grams
        assert [subword_ref.fnv1a(g.encode()) % K for g in grams] == buckets
        assert row[row != ref.NO_BUCKET].tolist() == buckets
    assert subword_ref.fnv1a(b"<ab") == 1218209508
    assert ref.hasher_mismatches(list(NGRAMS), lists, range(len(NGRAMS)), V, K) == 0


def test_the_reference_answers_the_hand_worked_vocabulary():
    want, got = by_hand(), by_reference()
    for string in want:
        assert got[string][0] == want[string][0], string
        np.testing.assert_allclose(got[string][1], want[string][1], rtol=0, atol=1e-6)
    # a word is left out of its own reply, an unseen string of nothing
    assert all(i not in got[w][0] for i, w in enumerate(WORDS))
    table = ref.composed_table(rows_fn, ref.bucket_lists(WORDS, K), V, block=2)
    np.testing.assert_allclose(
        np.asarray(table[1]), TABLE[[1] + [V + b for b in NGRAMS["abc"][1]]].mean(axis=0),
        rtol=1e-6)
    np.testing.assert_allclose(ref.vector(rows_fn, "ba", None, V, K),
                               TABLE[[V + b for b in NGRAMS["ba"][1]]].mean(axis=0), rtol=1e-6)
    assert not ref.vector(rows_fn, "", None, V, K).any()      # no n-gram: zeros


def _moved(want, got):
    return [s for s in want if got[s][0] != want[s][0]
            or np.abs(got[s][1] - want[s][1]).max() > 1e-4]


@pytest.mark.parametrize("fault", [
    "unseen_given_an_own_row", "marks_left_off", "modulo_left_off",
    "query_word_not_excluded", "excluded_for_an_unseen_string", "raw_rows_scanned"])
def test_a_planted_fault_moves_an_answer_the_reference_gives(fault):
    assert _moved(by_reference(), by_hand(fault)), fault


def test_a_sum_for_the_mean_shows_in_the_composed_rows_alone():
    """A cosine does not see a vector's length, so no score and no rank moves:
    why the check reads the composed rows themselves."""
    assert not _moved(by_reference(), by_hand("sum_for_mean"))
    table = np.asarray(ref.composed_table(rows_fn, ref.bucket_lists(WORDS, K), V))
    summed = np.stack([TABLE[[i] + [V + b for b in NGRAMS[w][1]]].sum(axis=0)
                       for i, w in enumerate(WORDS)])
    rel = np.linalg.norm(summed - table, axis=1) / np.linalg.norm(table, axis=1)
    assert rel.min() > 0.5


@pytest.mark.parametrize("seed, v", [(3, 1000), (2**31 + 5, 1000), (11, 20000)])
def test_numpy_hasher_is_the_papers_loop_on_seeded_words(seed, v):
    strings = words.make_words(seed, v)
    lists = ref.bucket_lists(strings, 10000)
    sample = np.random.default_rng(seed).choice(v, 1000, replace=False)
    assert ref.hasher_mismatches(strings, lists, sample, v, 10000) == 0
    assert lists.shape == (v, (max(map(len, strings)) + 2) * 4)
    with pytest.raises(ValueError):
        ref.bucket_lists(["héllo"], 16)


# -- the kind ---------------------------------------------------------------------------

def _cell() -> dict:
    return loader.resolve(MANIFEST, CELL)


def test_tiny_rehearsal_of_the_kind_is_correct(capsys):
    out = kind.run(_cell(), seed=2**31 + 51, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    printed = capsys.readouterr().out
    assert "check unseen_composed_minus_sent: 0 (limit 0) ok" in printed
    assert "check overflow_strings: 0 (limit 0) ok" in printed
    assert "check reference_hasher_mismatches: 0 (limit 0) ok" in printed
    counters = out["counters"]
    assert counters["unseen_sent"] == counters["unseen_composed"] > 0
    assert counters["compose_s"] > 0
    assert set(out["end_to_end"]) == {"query_per_s", "query_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["own_row_added_to_an_unseen_string",
                                   "raw_rows_scanned"])
def test_a_planted_program_fault_comes_out_not_correct(fault, monkeypatch, capsys):
    """The timed path broken underneath: an unseen string's list given one row
    more (as if it had a row of its own), or the words' raw rows scanned in
    place of the composed ones. ``correct`` must see it."""
    from glint_word2vec_tpu.data import subword as data_sw
    from glint_word2vec_tpu.models import word2vec as w2v
    if fault == "own_row_added_to_an_unseen_string":
        real = data_sw.ngram_lists

        def faulty(strings, min_n, max_n, buckets, capacity):
            lists, over = real(strings, min_n, max_n, buckets, capacity - 1)
            lists = np.concatenate(
                [np.zeros((len(strings), 1), np.int32), lists], axis=1)
            return lists, over
        monkeypatch.setattr(data_sw, "ngram_lists", faulty)
    else:
        monkeypatch.setattr(
            w2v.Word2VecModel, "_compose",
            lambda self, vocab, config, syn0, buckets, rows=None: _raw(
                self, vocab, config, syn0, buckets))
    out = kind.run(_cell(), seed=77, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is False and out["failed"] == 0
    printed = capsys.readouterr().out
    assert "FAILED" in printed
    if fault == "raw_rows_scanned":
        for name in ("score_abs_err_mean", "score_abs_err_max", "rank_gap_max",
                     "composed_row_rel_err_max"):
            assert f"check {name}: " in printed
            line = next(l for l in printed.splitlines() if l.startswith(f"check {name}: "))
            assert line.endswith("FAILED"), line


def _raw(model, vocab, config, syn0, buckets):
    """``_compose`` that composes nothing: the words' own rows as the table."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.data.subword import list_capacity
    from glint_word2vec_tpu.ops.subword import lane_padded
    model._raw0 = jnp.asarray(syn0)
    model._buckets = lane_padded(jnp.asarray(buckets))
    model._list_cap = list_capacity(max(map(len, vocab.words)), config.subword_min_n,
                                    config.subword_max_n)
    model.compose_time = 1e-9
    return model._raw0.astype(jnp.float32)


@pytest.mark.parametrize("control", [False, True], ids=["program", "bfloat16_tables"])
def test_limits_pass_the_program_and_fail_bfloat16_tables(control):
    cell = _cell()
    limits = loader.sizes(cell["config"], True)["check"]["query_subword"]
    got, = kind.check_readings(cell, [43], control=control, tiny=True)
    failing = [name for name, limit in limits.items() if got[name] > limit]
    assert bool(failing) == control, (got, limits)
    if control:
        assert "composed_row_rel_err_max" in failing
    assert got["unseen_composed_minus_sent"] == 0 and got["overflow_strings"] == 0
