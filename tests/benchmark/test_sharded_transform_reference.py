"""The sharded transform cell's plain reference, and the comparison that decides
``correct``, on the CPU.

``benchmark/reference/sharded_transform_ref.py`` is held to a float64 NumPy brute
force of its docstring's equations and to the one-chip cell's reference (two
files, one definition: the partition appears in neither). Then
``Word2VecModel.transform_sentences`` over a 1x4 mesh of virtual devices is held
to it through the very comparison a benchmark run makes (``kinds/transform_sharded``:
``make_slides``, ``check_rows_of``, ``compare_with_reference``,
``add_engagement_checks``), and planted faults must each fail a NAMED reading of
it: a shard's partial sums left out, a sum where the mean is, OOV tokens counted
in the denominator, bfloat16 rows, a planted last-shard sentence answered with
zeros, an all-OOV sentence answered with something, owned counts that do not add
up, another number of shards.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import zipf  # noqa: E402
from harness.common import Checks  # noqa: E402
from kinds import transform as slides_kind  # noqa: E402
from kinds import transform_sharded as kind  # noqa: E402
from reference import sharded_transform_ref as ref  # noqa: E402
from reference import transform_ref as one_chip_ref  # noqa: E402

V, D, HALF_WIDTH, SEED, SHARDS = 5000, 40, 0.5, 2**31 + 59, 4
PER = V // SHARDS
TF = {"callers": 1, "slides_per_caller": 3, "slide_rows": 200, "oov_share": 0.05,
      "empty_share": 0.02, "check_rows_per_call": 4, "check_sentences": 12,
      "last_shard_sentences": 1, "last_shard_tokens": 8,
      "sentence_len": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 1, "max": 1000}}
SIZES = dict(v=V, d=D, half_width=HALF_WIDTH, shards=SHARDS)
# the configuration's ``tiny`` limits: the CPU sums in float32 as the chip does
LIMITS = {"row_rel_err_mean": 1e-6, "row_rel_err_max": 1e-5, "rows_per_word_max": 1.6,
          "shards": SHARDS}


@pytest.fixture(scope="module")
def table() -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(jnp.arange(V, dtype=jnp.int32)))


@pytest.fixture(scope="module")
def slides():
    made, planted = kind.make_slides(SEED, V, SHARDS, TF)
    return made, planted, kind.check_rows_of(SEED, made, planted, TF)


def test_the_reference_is_its_docstrings_equations_and_the_one_chip_cells(table):
    sents = [["w1", "w2", "w1"], [], ["nope"], ["w4999", "nope", "w0"], ["w7"] * 5,
             [f"w{(i * 31) % V}" for i in range(1000)], ["w3750", "w4999", "w4000"]]
    rows_fn = ref.seeded_rows(SEED, D, HALF_WIDTH)
    got = ref.sentence_vectors(sents, ref.dictionary(V), rows_fn, D)
    t = table.astype(np.float64)
    want = np.zeros((len(sents), D))
    want[0] = (2 * t[1] + t[2]) / 3
    want[3] = (t[4999] + t[0]) / 2
    want[4] = t[7]
    want[5] = t[[(i * 31) % V for i in range(1000)]].mean(axis=0)
    want[6] = (t[3750] + t[4999] + t[4000]) / 3
    assert got.dtype == np.float32 and got.shape == (7, D)
    assert np.abs(got - want).max() <= 6e-8 * HALF_WIDTH
    assert not got[1].any() and not got[2].any()
    assert ref.kept_ids(sents[:4], ref.dictionary(V)) == [[1, 2, 1], [], [], [4999, 0]]
    assert np.array_equal(got, one_chip_ref.sentence_vectors(
        sents, one_chip_ref.dictionary(V), one_chip_ref.seeded_rows(SEED, D, HALF_WIDTH), D))
    text = open(ref.__file__).read().split('"""')[2]
    assert "shard" not in text and "psum" not in text and "mesh" not in text


def test_every_slide_carries_a_sentence_of_the_last_shard_alone(slides):
    made, planted, rows = slides
    plain, index = slides_kind.make_slides(SEED, V, TF), ref.dictionary(V)
    for j, (sentences, empty) in enumerate(made[0]):
        (p,) = planted[0][j].tolist()
        assert len(sentences) == 200 and p < 199 and not empty[p]
        assert len(sentences[p]) == 8
        assert all(PER * (SHARDS - 1) <= int(w[1:]) < V for w in sentences[p])
        # the one-chip kind's lengths, empty sentences and lost tokens, place for
        # place (the slide's own seed); the words are dealt from one draw over all
        # slides and are other words by the same law
        theirs = plain[0][j][0]
        assert np.array_equal(empty, plain[0][j][1])
        for i, (mine, other) in enumerate(zip(sentences, theirs)):
            if i != p:
                assert len(mine) == len(other)
                assert [w for w in mine if w[0] == "q"] == [w for w in other if w[0] == "q"]
                assert [w[0] for w in mine] == [w[0] for w in other]
        picked = rows[0][j].tolist()
        assert picked[0] == 199 and p in picked and len(set(picked)) == 4
        assert empty[picked[1]] or not empty.any()
        assert kind.owned_counts(sentences, V, SHARDS).sum() == sum(
            w in index for s in sentences for w in s)
    again, planted_again = kind.make_slides(SEED, V, SHARDS, TF)
    assert again[0][1][0] == made[0][1][0]
    assert np.array_equal(planted_again[0][1], planted[0][1])


def _readings(slides, transform, rows_fn=None) -> dict:
    """One call a slide through ``transform(sentences) -> reply``, kept as the
    kind's caller threads keep it, and compared as a run compares it."""
    made, planted, rows = slides
    finished = []
    for j, (sentences, _) in enumerate(made[0]):
        fault, kept = slides_kind.keep_rows(transform(sentences), rows[0][j],
                                            len(sentences), D)
        finished.append((0.0, 1.0, (0, j), kept, fault))
    checks = Checks()
    kind.compare_with_reference(
        SEED, SIZES, slides_kind.draw_sample(SEED, made, rows, finished, TF),
        kind._planted_ids(made, planted), LIMITS, checks, rows_fn)
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    return {name: ok for name, _, _, ok in checks.rows}


def _by_shards(table, sentences, left_out=None, mean=True, count_oov=False,
               empty_value=0.0):
    """The sharded transform in NumPy, partial sums by range-partitioned shard,
    with a fault planted: ``left_out`` drops that shard's partial from the sum,
    ``mean`` False leaves the sums, ``count_oov`` divides by every token,
    ``empty_value`` answers an all-OOV sentence."""
    index = ref.dictionary(V)
    out = np.zeros((len(sentences), D), np.float32)
    for i, s in enumerate(sentences):
        ids = np.array([index[w] for w in s if w in index], np.int64)
        if not len(ids):
            out[i] = empty_value
            continue
        total = np.zeros(D, np.float32)
        for j in range(SHARDS):
            mine = ids[ids // PER == j]
            if j != left_out and len(mine):
                total += table[mine].astype(np.float32).sum(axis=0)
        out[i] = total / ((len(s) if count_oov else len(ids)) if mean else 1)
    return out


def test_the_program_on_a_mesh_passes_the_kinds_comparison(table, slides):
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64)), table,
        plan=make_mesh(1, SHARDS))
    readings = _readings(slides, model.transform_sentences)
    engaged = slides_kind.slide_engagement(model, slides[0][0][0][0])
    model.stop()
    assert all(readings.values()), readings
    checks = Checks()
    kind.add_engagement_checks(checks, engaged, slides[0][0][0][0], SIZES, LIMITS)
    assert checks.ok, checks.rows
    assert all(_readings(slides, lambda s: _by_shards(table, s)).values())


FAULTS = {
    "the_first_shards_partial_left_out": (dict(left_out=0), "row_rel_err_max"),
    "the_last_shards_partial_left_out": (dict(left_out=SHARDS - 1), "last_shard_rows_zero"),
    "a_sum_where_the_mean_is": (dict(mean=False), "row_rel_err_max"),
    "oov_tokens_counted_in_the_denominator": (dict(count_oov=True), "row_rel_err_max"),
    "an_all_oov_sentence_answered_with_something": (dict(empty_value=1e-30),
                                                    "empty_rows_not_zero"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_its_reading(table, slides, fault):
    how, reading = FAULTS[fault]
    readings = _readings(slides, lambda s: _by_shards(table, s, **how))
    assert readings[reading] is False, readings


@pytest.mark.parametrize("left_out", [1, 2, 3])
def test_any_shard_left_out_fails_the_widest_error(table, slides, left_out):
    readings = _readings(slides, lambda s: _by_shards(table, s, left_out=left_out))
    assert readings["row_rel_err_max"] is False, readings


def test_bfloat16_rows_fail_both_error_readings_and_the_planted_ones(table, slides):
    import jax.numpy as jnp
    rounded = np.asarray(jnp.asarray(table).astype(jnp.bfloat16).astype(jnp.float32))
    readings = _readings(slides, lambda s: _by_shards(rounded, s))
    assert readings["row_rel_err_mean"] is False and readings["row_rel_err_max"] is False
    assert readings["last_shard_row_rel_err_max"] is False


def test_an_engagement_counter_outside_the_configurations_fails(slides):
    slide = slides[0][0][0][0]
    owned = kind.owned_counts(slide, V, SHARDS)
    live, most = int(owned.sum()), int(owned.max())
    good = {"rows": live, "rows_cap": live + 64, "passes": 1, "shards": 4, "owned_max": most}
    for engaged, failed in (
            (good, set()),
            ({**good, "shards": 2}, {"slide_shards_off"}),
            ({**good, "owned_max": most - 1}, {"owned_max_off"}),
            ({**good, "rows": live - 1}, {"owned_rows_sum_off"}),
            ({**good, "rows_cap": live // 2 + 64, "passes": 2}, {"slide_passes_off"}),
            ({k: v for k, v in good.items() if k not in ("shards", "owned_max")},
             {"slide_shards_off", "owned_max_off"})):
        checks = Checks()
        kind.add_engagement_checks(checks, engaged, slide, SIZES, LIMITS)
        assert {name for name, _, _, ok in checks.rows if not ok} == failed, engaged
    assert owned[0] > owned[1:].sum()       # the Zipf draw's hot shard


def test_span_counters_add_the_busiest_shards_ids():
    events = [
        {"name": "transform.slide", "args": {"sentences": 4, "words": 90, "oov": 10, "empty": 1}},
        {"name": "transform.enqueue", "args": {"rows": 90, "rows_cap": 128, "passes": 1,
                                               "inflight": 0, "shards": 4, "owned_max": 80}},
        {"name": "transform.slide", "args": {"sentences": 4, "words": 300, "oov": 0, "empty": 0}},
        {"name": "transform.enqueue", "args": {"rows": 300, "rows_cap": 320, "passes": 1,
                                               "inflight": 1, "shards": 4, "owned_max": 290}}]
    got = kind.span_counters(events)
    assert got["slice_owned_max"] == 370 and got["slice_rows_live"] == 390
    from readers import counter
    assert counter.read({"num": "slice_owned_max", "den": "slice_rows_live"},
                        {"counters": got}) == 370 / 390
    # a program whose span says nothing of it (the parent's): no such counter
    for e in events:
        e["args"].pop("owned_max", None)
    assert "slice_owned_max" not in kind.span_counters(events)
    assert kind.span_counters([]) == {}


def test_the_cost_counts_the_busiest_chips_rows_and_stays_under_the_one_chip_cost():
    from costs import sharded_transform_gather, transform_gather
    shapes = dict(rows=313000.0, sentences=10000, dim=300, table_dtype="float32")
    cost = sharded_transform_gather.cost(owned_rows=294000.0, chips=4, **shapes)
    assert cost["bytes"] == (294000 * 384 * 4 + 8 * 313000 + 4 * 10000
                             + 2 * 4 * 10000 * 384 + 4 * 10000 * 300)
    assert cost["flops"] < cost["bytes"]
    # what one chip must read cannot pass what one chip alone would read plus
    # the partial sums it writes and the collective reads
    alone = transform_gather.cost(**shapes)
    assert cost["bytes"] <= alone["bytes"] + 2 * 4 * 10000 * 384
    even = sharded_transform_gather.cost(owned_rows=313000.0 / 4, chips=4, **shapes)
    assert even["bytes"] < cost["bytes"]
