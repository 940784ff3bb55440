"""The transform cell's plain reference, and the comparison that decides
``correct``, on the CPU.

``benchmark/reference/transform_ref.py`` is held to a float64 NumPy brute force
of its docstring's equations (repeats, out-of-vocabulary tokens, empty and
all-OOV sentences). Then ``Word2VecModel.transform_sentences`` is held to it
through the very comparison a benchmark run makes (``kinds/transform``:
``check_rows_of``, ``keep_rows``, ``draw_sample``, ``compare_with_reference``),
and planted faults must each fail a NAMED reading of it: a sum where the mean
is, OOV tokens counted in the denominator, a bfloat16 table, a last sentence
dropped, a segment boundary off by one, a sentence's rows cut at a capacity,
rows returned in another order, an all-OOV sentence answered with something.
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
from kinds import transform as kind  # noqa: E402
from reference import transform_ref as ref  # noqa: E402

V, D, HALF_WIDTH, SEED = 5000, 40, 0.5, 2**31 + 48
TF = {"callers": 1, "slides_per_caller": 3, "slide_rows": 200, "oov_share": 0.05,
      "empty_share": 0.02, "check_rows_per_call": 4, "check_sentences": 12,
      "sentence_len": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 1, "max": 1000}}
SIZES = dict(v=V, d=D, half_width=HALF_WIDTH)
# the configuration's ``tiny`` limits: the CPU sums in float32 as the chip does
LIMITS = {"row_rel_err_mean": 1e-6, "row_rel_err_max": 1e-5}


@pytest.fixture(scope="module")
def table() -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(jnp.arange(V, dtype=jnp.int32)))


@pytest.fixture(scope="module")
def slides():
    made = kind.make_slides(SEED, V, TF)
    return made, kind.check_rows_of(SEED, made, TF)


def test_the_reference_is_its_docstrings_equations(table):
    sents = [["w1", "w2", "w1"], [], ["nope"], ["w4999", "nope", "w0"], ["w7"] * 5,
             [f"w{(i * 31) % V}" for i in range(1000)]]
    got = ref.sentence_vectors(sents, ref.dictionary(V), ref.seeded_rows(SEED, D, HALF_WIDTH), D)
    t = table.astype(np.float64)
    want = np.zeros((len(sents), D))
    want[0] = (2 * t[1] + t[2]) / 3
    want[3] = (t[4999] + t[0]) / 2
    want[4] = t[7]
    want[5] = t[[(i * 31) % V for i in range(1000)]].mean(axis=0)
    assert got.dtype == np.float32 and got.shape == (6, D)
    assert np.abs(got - want).max() <= 6e-8 * HALF_WIDTH
    assert not got[1].any() and not got[2].any()


def test_slides_are_the_traffic_files(slides):
    made, rows = slides
    assert len(made) == 1 and len(made[0]) == 3
    index = ref.dictionary(V)
    for (sentences, empty), picked in zip(made[0], rows[0]):
        lens = np.array([len(s) for s in sentences])
        assert len(sentences) == 200 and lens.min() >= 1 and lens.max() <= 1000
        assert 15 <= np.median(lens) <= 27
        lost = np.array([w not in index for s in sentences for w in s])
        assert 0.03 <= lost.mean() <= 0.14
        for s, e in zip(sentences, empty):
            assert e == (not any(w in index for w in s))
        assert picked[0] == 199 and len(set(picked.tolist())) == 4
        assert empty[picked[1]] or not empty.any()
    again = kind.make_slides(SEED, V, TF)
    assert again[0][1][0] == made[0][1][0]
    assert kind.make_slides(SEED + 1, V, TF)[0][1][0] != made[0][1][0]


def _finished(slides, transform):
    """One call a slide through ``transform(sentences) -> reply``, kept as the
    kind's caller threads keep it."""
    made, rows = slides
    out = []
    for j, (sentences, _) in enumerate(made[0]):
        fault, kept = kind.keep_rows(transform(sentences), rows[0][j], len(sentences), D)
        out.append((0.0, 1.0, (0, j), kept, fault))
    return out


def _readings(slides, transform, rows_fn=None) -> dict:
    made, rows = slides
    finished = _finished(slides, transform)
    checks = Checks()
    kind.compare_with_reference(SEED, SIZES, kind.draw_sample(SEED, made, rows, finished, TF),
                                LIMITS, checks, rows_fn)
    checks.add("reply_shape_faults", sum(q[4] for q in finished), 0)
    return {name: ok for name, _, _, ok in checks.rows}


def _plain(table, sentences, mean=True, count_oov=False, shift=0, cap=None, empty_value=0.0):
    """The transform in NumPy with a fault planted: ``mean`` False leaves the
    sums, ``count_oov`` divides by every token, ``shift`` moves the segment
    boundaries by that many ids, ``cap`` cuts the slide's ids there,
    ``empty_value`` answers an all-OOV sentence."""
    index = ref.dictionary(V)
    ids = [[index[w] for w in s if w in index] for s in sentences]
    flat = np.array([r for k in ids for r in k], np.int64)
    ends = np.cumsum([len(k) for k in ids])
    flat = flat[:cap]
    out = np.zeros((len(sentences), D), np.float32)
    for i, s in enumerate(sentences):
        a, b = (ends[i - 1] if i else 0) + (shift if i else 0), ends[i] + shift
        rows = table[flat[a:b]].astype(np.float32)
        den = len(s) if count_oov else len(ids[i])
        if not ids[i]:
            out[i] = empty_value
        elif len(rows):
            out[i] = rows.sum(axis=0) / (den if mean else 1)
    return out


def test_the_program_passes_the_kinds_comparison(table, slides):
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64)), table)
    readings = _readings(slides, model.transform_sentences)
    model.stop()
    assert all(readings.values()), readings
    assert all(_readings(slides, lambda s: _plain(table, s)).values())


FAULTS = {
    "a_sum_where_the_mean_is": (dict(mean=False), "row_rel_err_max"),
    "oov_tokens_counted_in_the_denominator": (dict(count_oov=True), "row_rel_err_max"),
    "a_segment_boundary_off_by_one": (dict(shift=1), "row_rel_err_max"),
    "a_sentences_rows_cut_at_the_capacity": (dict(cap=-3), "row_rel_err_max"),
    "an_all_oov_sentence_answered_with_something": (dict(empty_value=1e-30),
                                                    "empty_rows_not_zero"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_its_reading(table, slides, fault):
    how, reading = FAULTS[fault]
    readings = _readings(slides, lambda s: _plain(table, s, **how))
    assert readings[reading] is False, readings


def test_a_bfloat16_table_fails_both_error_readings(table, slides):
    import jax.numpy as jnp
    rounded = np.asarray(jnp.asarray(table).astype(jnp.bfloat16).astype(jnp.float32))
    readings = _readings(slides, lambda s: _plain(rounded, s))
    assert readings["row_rel_err_mean"] is False and readings["row_rel_err_max"] is False


def test_a_dropped_last_sentence_is_a_shape_fault_and_a_wrong_last_row(table, slides):
    readings = _readings(slides, lambda s: _plain(table, s)[:-1])
    assert readings["reply_shape_faults"] is False
    assert readings["row_rel_err_max"] is False


def test_rows_in_another_order_fail_the_error_readings(table, slides):
    readings = _readings(slides, lambda s: _plain(table, s)[::-1])
    assert readings["row_rel_err_max"] is False and readings["reply_shape_faults"] is True


def test_an_engagement_counter_outside_the_configurations_fails():
    for engaged, failed in (
            ({"rows": 313000, "rows_cap": 327680, "passes": 1}, set()),
            ({"rows": 313000, "rows_cap": 163840, "passes": 2}, {"slide_passes_off"}),
            ({"rows": 313000, "rows_cap": 393216, "passes": 1}, {"rows_per_word"}),
            ({}, {"slide_passes_off", "rows_per_word"})):
        checks = Checks()
        kind.add_engagement_checks(checks, engaged, {"rows_per_word_max": 1.15})
        assert {name for name, _, _, ok in checks.rows if not ok} == failed


def test_a_program_without_a_row_capacity_is_refused_before_the_tables(monkeypatch):
    """The parent's program records no ``transform.enqueue``: the run ends with
    a message (exit code 1 through SystemExit), as asked of a 64-row table."""
    kind.require_row_capacity()
    monkeypatch.setattr(kind, "slide_engagement", lambda model, slide: {})
    with pytest.raises(SystemExit, match="nothing of a row capacity"):
        kind.require_row_capacity()


def test_span_counters_are_the_slices_sums():
    events = [
        {"name": "transform.slide", "args": {"sentences": 4, "words": 90, "oov": 10, "empty": 1}},
        {"name": "transform.enqueue", "args": {"rows": 90, "rows_cap": 128, "passes": 1, "inflight": 0}},
        {"name": "transform.slide", "args": {"sentences": 4, "words": 300, "oov": 0, "empty": 0}},
        {"name": "transform.enqueue", "args": {"rows": 300, "rows_cap": 160, "passes": 2, "inflight": 1}},
        {"name": "serve.batch", "args": {"size": 3}}]
    got = kind.span_counters(events)
    assert got == {"slice_oov": 10, "slice_tokens": 400, "slice_rows_live": 390,
                   "slice_rows_handed": 448, "slice_inflight_and_self": 3, "slice_enqueues": 2}
    assert kind.span_counters(events[-1:]) == {}


def test_the_cost_counts_live_rows_at_whole_lanes_and_the_result():
    from costs import transform_gather
    cost = transform_gather.cost(rows=313000.0, sentences=10000, dim=300, table_dtype="float32")
    assert cost["bytes"] == 313000 * 384 * 4 + 8 * 313000 + 4 * 10000 + 4 * 10000 * 300
    assert cost["flops"] < cost["bytes"]
