"""``Word2VecModel.sentence_vectors`` (fastText's ``get_sentence_vector``, the
unsupervised branch) held to the plain reference on the CPU.

The program (``models/word2vec.py``: one fixed-shape program a slide, a
two-level ragged reduction, flat bucket-row lists -> unseen tokens -> unit
vectors -> sentences, beside the word rows scaled by their inverse norms,
which the host's encode takes and hands over dense)
against ``benchmark/reference/sentvec_ref.py`` (its own dictionary and hasher, a
Python loop in float64) on seeded tables: a model built each way (every table
resident, and ``resident="rows"``), float32 and float64, ragged slides with
unseen tokens, empty sentences, sentences of unseen tokens alone, ``""``, a
non-ASCII token, a string over the query path's 48-slot list, planted rows of
zero norm, slides over each capacity, a short last slide, several threads, the
native walk and hasher against ``dict.get`` and ``ngram_buckets``. Stated
tolerances: a row is a mean of unit vectors, every entry within 1; float32
tables within ``F32_TOL`` of the reference (float32 norms and sums of up to
1,000 terms against float64 ones), float64 tables within ``F64_TOL`` (the one
rounding of the result to float32).
"""

import os
import sys
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import unseen, words  # noqa: E402
from reference import sentvec_ref as ref  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data import subword as subword_module  # noqa: E402
from glint_word2vec_tpu.data import vocab as vocab_module  # noqa: E402
from glint_word2vec_tpu.data.vocab import Vocabulary  # noqa: E402
from glint_word2vec_tpu.models import word2vec as w2v  # noqa: E402
from glint_word2vec_tpu.models.word2vec import Word2VecModel  # noqa: E402
from glint_word2vec_tpu.obs.spans import default_tracer  # noqa: E402

V, D, K, SEED = 1500, 40, 600, 2**31 + 52
MIN_N, MAX_N = 3, 6
F32_TOL, F64_TOL = 2e-6, 1.2e-7
STRINGS = words.make_words(SEED, V)
INDEX = ref.dictionary(STRINGS)
CONFIG = Word2VecConfig(vector_size=D, min_count=1, subword=True, subword_min_n=MIN_N,
                        subword_max_n=MAX_N, subword_buckets=K)
# a string whose n-grams' bucket rows are all zeros in TABLE: h = 0, left out
DEAD = "qzqz"


def _table(dtype=np.float32) -> np.ndarray:
    """The trained input table [V + K, D]: seeded, with DEAD's bucket rows
    zeroed."""
    table = np.random.default_rng(SEED).uniform(-0.3, 0.3, (V + K, D)).astype(dtype)
    table[V + np.asarray(subword_module.ngram_buckets(DEAD, MIN_N, MAX_N, K))] = 0.0
    return table


TABLE = _table()


def make_model(resident="all", dtype=np.float32) -> Word2VecModel:
    table = _table(dtype)
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    return Word2VecModel(vocab, table[:V], None, config=CONFIG,
                         subword_buckets=table[V:], resident=resident)


@pytest.fixture(scope="module", params=["all", "rows"])
def model(request):
    m = make_model(request.param)
    yield m
    m.stop()


@pytest.fixture(params=["dict", "native"])
def lookup(request, monkeypatch):
    """Small slides resolved by ``dict.get`` (their size's own route) and by
    the native table and hasher (a slide of 10,000 sentences' route)."""
    if request.param == "native":
        if vocab_module._load_native() is None or subword_module._load_native() is None:
            pytest.skip("no native toolchain on this host")
        monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)
    return request.param


def sentences(seed: int, n: int, unseen_share=0.1, empty_share=0.05, max_len=60):
    """``n`` ragged sentences of the vocabulary's words, a share of the
    tokens typos the vocabulary lacks, a share of the sentences empty."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        length = 0 if rng.random() < empty_share else int(rng.integers(1, max_len))
        ids = rng.integers(0, V, length)
        tokens = [STRINGS[int(i)] for i in ids]
        lost = np.flatnonzero(rng.random(length) < unseen_share)
        for at, made in zip(lost, unseen.typos(rng, STRINGS, INDEX, ids[lost])):
            tokens[at] = made
        out.append(tokens)
    return out


def expected(sents, table=TABLE) -> np.ndarray:
    return ref.sentence_vectors(sents, INDEX, lambda ids: table[np.asarray(ids)], D, K,
                                MIN_N, MAX_N)


LONG = "pneumonoultramicroscopicsilicovolcanoconiosis"      # 178 n-grams of 3-6
CASES = {
    "ragged_with_unseen": lambda: sentences(1, 300),
    "empty_and_unseen_alone": lambda: [[], ["zzq"], ["zzq", "qqz"], [STRINGS[3]], []],
    "the_empty_string_has_no_vector": lambda: [[""], ["", STRINGS[5], ""], ["", "zzq"]],
    "a_dead_string_is_left_out": lambda: [[DEAD], [DEAD, STRINGS[9], DEAD], [DEAD, "zzq"]],
    "repeats_count": lambda: [[STRINGS[5]] * 7 + ["zzq"] * 2, ["zzq", STRINGS[5], "zzq"]],
    "one_token_sentences": lambda: [[STRINGS[i]] for i in range(50)] + [["zzq"], ["qx"]],
    "non_ascii_tokens": lambda: [["é日本", STRINGS[2]], ["naïve", "ß"], ["日"]],
    "a_string_over_48_ngrams": lambda: [[LONG], [STRINGS[1], LONG, "zzq"]],
    "a_1000_token_sentence": lambda: [
        [STRINGS[1]], [STRINGS[(i * 7) % V] if i % 9 else f"zz{i}" for i in range(1000)]],
    "tuples_and_arrays": lambda: [(STRINGS[1], "zzq"), np.array([STRINGS[3], "qqz"])],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_meets_the_reference(model, lookup, case):
    sents = CASES[case]()
    got = model.sentence_vectors(sents)
    assert got.dtype == np.float32 and got.shape == (len(sents), D)
    assert np.abs(got - expected(sents)).max() <= F32_TOL
    for i, s in enumerate(sents):       # the zero-vector branch is exact
        if all(t in ("", DEAD) for t in s):
            assert not got[i].any()


def test_a_lone_unseen_token_is_its_unit_vector(model):
    (row,) = model.sentence_vectors([["zzq"]])
    h = model.transform("zzq")
    assert abs(float(np.linalg.norm(row)) - 1.0) <= 1e-6
    assert np.abs(row - h / np.linalg.norm(h)).max() <= 1e-6
    assert len(subword_module.ngram_buckets(LONG, MIN_N, MAX_N, K)) > 48 >= model._list_cap


def test_no_sentences_at_all(model):
    assert model.sentence_vectors([]).shape == (0, D)
    assert model.sentence_vectors([[], []]).tolist() == [[0.0] * D] * 2


@pytest.mark.parametrize("batch_size,n", [(64, 64), (64, 200), (50, 151), (7, 20)])
def test_slides_and_a_short_last_slide(model, lookup, batch_size, n):
    sents = sentences(n + batch_size, n)
    got = model.sentence_vectors(sents, batch_size=batch_size)
    assert np.abs(got - expected(sents)).max() <= F32_TOL
    assert np.array_equal(got, model.sentence_vectors(sents, batch_size=n + 1))


@pytest.fixture()
def tracer():
    t = default_tracer()
    t.clear()
    t.configure(True)
    yield t
    t.configure(False)
    t.clear()


def _list_rows(sents) -> int:
    return sum(len(subword_module.ngram_buckets(t, MIN_N, MAX_N, K))
               for s in sents for t in s if t not in INDEX)


@pytest.mark.parametrize("over,passes", [("words", 2), ("words", 3), ("lists", 2),
                                         ("lists", 3), ("both", 2)])
def test_a_slide_over_a_capacity_runs_further_passes(model, tracer, monkeypatch,
                                                     over, passes):
    """Word rows, list rows, or both over ``_TRANSFORM_MAX_ROWS``: further
    passes of the one program, the lists cut between tokens, nothing
    truncated."""
    share = {"words": 0.02, "lists": 0.6, "both": 0.12}[over]
    sents = sentences(11, 120, unseen_share=share, empty_share=0.0)
    live = sum(t in INDEX for s in sents for t in s)
    listed = _list_rows(sents)
    most = {"words": live, "lists": listed, "both": max(live, listed)}[over]
    assert (live > listed) == (over == "words") or over == "both"
    one = model.sentence_vectors(sents)
    monkeypatch.setattr(w2v, "_TRANSFORM_MAX_ROWS", -(-most // passes))
    tracer.clear()
    got = model.sentence_vectors(sents)
    (said,) = [e["args"] for e in tracer.events() if e["name"] == "transform.enqueue"]
    assert said["passes"] == passes
    assert said["rows"] == live and said["rows_cap"] * passes >= live
    assert said["list_rows"] == listed and said["list_cap"] * passes >= listed
    assert said["unseen"] == sum(t not in INDEX for s in sents for t in s)
    assert said["unseen"] <= said["unseen_cap"] * passes
    assert np.abs(got - expected(sents)).max() <= F32_TOL
    # the same sums in another association: within a rounding of one pass
    assert np.abs(got - one).max() <= 1e-6


@partial(jax.jit, static_argnames=("segments", "dim"))
def _scaled_on_the_device(table, scale, ids, seg, lists, counts, carried, segments, dim):
    """The slide's program as it was before PR 60, kept here as the plain
    form the program is held to: ``scale`` is the model's ``[V]`` vector of
    inverse norms ON THE DEVICE and the program gathers ``scale[ids]`` itself
    (``rows * inv[ids][:, None]``). ``ops/transform._sentence_means`` is handed
    those values by the host and multiplies the same two numbers."""
    rows = table.at[ids].get(mode="fill", fill_value=0)
    acc = jnp.promote_types(rows.dtype, jnp.float32)
    unit = rows.astype(acc) * scale.at[ids].get(
        mode="fill", fill_value=0).astype(acc)[:, None]
    sums = jax.ops.segment_sum(unit, seg, num_segments=segments,
                               indices_are_sorted=True)
    kept = None
    if lists is not None:
        buckets, list_rows, token, token_seg = lists
        listed = buckets.at[list_rows].get(mode="fill", fill_value=0).astype(acc)
        h = jax.ops.segment_sum(listed, token, num_segments=token_seg.shape[0],
                                indices_are_sorted=True)[:, :sums.shape[1]]
        norm = jnp.sqrt((h * h).sum(axis=1))
        live = norm > 0
        h = jnp.where(live[:, None], h / jnp.where(live, norm, 1)[:, None], 0)
        sums = sums + jax.ops.segment_sum(h.astype(acc), token_seg,
                                          num_segments=segments, indices_are_sorted=True)
        kept = jax.ops.segment_sum(live.astype(jnp.int32), token_seg,
                                   num_segments=segments, indices_are_sorted=True)
    if carried is not None:
        sums = sums + carried[0]
        if kept is not None:
            kept = kept + carried[1]
    if counts is None:
        return sums, kept
    if kept is not None:
        counts = counts + kept
    return (sums[:, :dim] / jnp.maximum(counts, 1)[:, None].astype(
        sums.dtype)).astype(jnp.float32)


def _with_a_dead_word(resident, dtype):
    """A subword model one of whose WORDS has a composed row of zero norm:
    its own row and every bucket row of its n-grams zeroed."""
    table = _table(dtype)
    table[7] = 0.0
    table[V + np.asarray(subword_module.ngram_buckets(STRINGS[7], MIN_N, MAX_N, K))] = 0.0
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    return Word2VecModel(vocab, table[:V], None, config=CONFIG,
                         subword_buckets=table[V:], resident=resident)


def _without_subwords(resident, dtype):
    table = _table(dtype)[:V]
    table[7] = 0.0
    return Word2VecModel(
        Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64)), table)


SCALED = {      # build, resident, dtype, passes, rows of zero norm
    "float32_rows": (make_model, "rows", np.float32, 1, 0),
    "float32_all": (make_model, "all", np.float32, 1, 0),
    "float64_rows": (make_model, "rows", np.float64, 1, 0),
    "float64_all": (make_model, "all", np.float64, 1, 0),
    "rows_of_zero_norm": (_with_a_dead_word, "rows", np.float32, 1, 1),
    "a_second_pass": (make_model, "rows", np.float32, 2, 0),
    "without_subwords": (_without_subwords, "all", np.float32, 2, 1),
}


@pytest.mark.parametrize("case", sorted(SCALED))
def test_the_hosts_scales_give_the_rows_the_device_gather_gave(case, monkeypatch):
    """PR 60: the encode takes a slide's inverse norms from the model's host
    copy and the program is handed them slot for slot beside the ids. Every
    row equals, BIT FOR BIT, what the program gave while it gathered
    ``scale[ids]`` from the ``[V]`` vector on the device
    (:func:`_scaled_on_the_device`): the same two numbers multiplied."""
    build, resident, dtype, passes, dead = SCALED[case]
    with jax.enable_x64(dtype == np.float64):
        m = build(resident, dtype)
        sents = (sentences(61, 90) + [[STRINGS[7]], [STRINGS[7], STRINGS[3], "zzq"], [],
                                      [DEAD, STRINGS[7]]])
        live = sum(t in INDEX for s in sents for t in s)
        if passes > 1:
            most = max(live, _list_rows(sents) if m.composes_unseen else 0)
            monkeypatch.setattr(w2v, "_TRANSFORM_MAX_ROWS", -(-most // passes))
        got = m.sentence_vectors(sents)
        assert m._zero_rows.tolist() == [7] * dead
        assert m._host_inv.shape == (V,) and m._host_inv.dtype == m._inv_norms.dtype
        handed = []

        def gathers_its_scales(table, scale, ids, *rest):
            real = ids < table.shape[0]
            # the operand: one scale a slot, the live slots' the ids' own
            assert scale.shape == ids.shape and scale.dtype == m._host_inv.dtype
            assert np.array_equal(scale[real], np.asarray(m._inv_norms)[ids[real]])
            handed.append(int(real.sum()))
            return _scaled_on_the_device(table, m._inv_norms, ids, *rest)

        monkeypatch.setattr(w2v, "_sentence_means", gathers_its_scales)
        want = m.sentence_vectors(sents)
        m.stop()
    assert len(handed) == passes
    assert sum(handed) == live - dead * sum(t == STRINGS[7] for s in sents for t in s)
    assert got.dtype == np.float32 and got.any()
    assert np.array_equal(got, want)


def test_four_threads_call_at_once(model, lookup):
    sets = [sentences(100 + i, 90) for i in range(4)]
    got, errors = [None] * 4, []

    def call(i):
        try:
            for _ in range(3):
                got[i] = model.sentence_vectors(sets[i], batch_size=32)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads)
    for i in range(4):
        assert np.abs(got[i] - expected(sets[i])).max() <= F32_TOL
    assert model._slides_inflight == 0


@pytest.mark.parametrize("resident", ["all", "rows"])
def test_float64_tables_meet_the_reference_to_one_rounding(resident):
    with jax.enable_x64():
        m = make_model(resident, np.float64)
        sents = sentences(31, 80) + CASES["a_1000_token_sentence"]() + [[DEAD, "zzq"]]
        got = m.sentence_vectors(sents)
        m.stop()
    assert got.dtype == np.float32
    assert np.abs(got - expected(sents, _table(np.float64))).max() <= F64_TOL


def test_bfloat16_tables_are_outside_the_float32_tolerance():
    """What the tolerance is for: the same model in the next precision down
    is orders of magnitude outside it."""
    table = _table()
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    m = Word2VecModel(vocab, jnp.asarray(table[:V], jnp.bfloat16), None, config=CONFIG,
                      subword_buckets=jnp.asarray(table[V:], jnp.bfloat16))
    sents = sentences(41, 60)
    got = m.sentence_vectors(sents)
    m.stop()
    assert np.abs(got - expected(sents)).max() > 50 * F32_TOL


def test_without_the_native_parts_the_rows_are_the_same_bit_for_bit(model, monkeypatch):
    """``GLINT_DISABLE_NATIVE=1`` (what makes both ``_load_native`` answer
    None): ``dict.get`` and ``ngram_buckets`` give the same lists, so the
    program is handed the same arrays."""
    if vocab_module._load_native() is None or subword_module._load_native() is None:
        pytest.skip("no native toolchain on this host")
    sents = sentences(71, 150) + CASES["non_ascii_tokens"]() + [[""], [LONG]]
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)
    native = model.sentence_vectors(sents)
    monkeypatch.setattr(vocab_module, "_load_native", lambda: None)
    monkeypatch.setattr(subword_module, "_load_native", lambda: None)
    assert np.array_equal(model.sentence_vectors(sents), native)


def test_the_native_hasher_is_ngram_buckets():
    if subword_module._load_native() is None:
        pytest.skip("no native toolchain on this host")
    rng = np.random.default_rng(5)
    strings = unseen.typos(rng, STRINGS, INDEX, rng.integers(0, V, 2000)) + [
        "", "a", "ab", "é", "日本語", "naïve", LONG, "x" * 300]
    for min_n, max_n, buckets in ((3, 6, 2_000_000), (5, 5, 97), (1, 2, 7), (2, 9, 1 << 20)):
        rows, counts, native = subword_module.ngram_rows(strings, min_n, max_n, buckets)
        assert native and rows.dtype == counts.dtype == np.int32
        want = [subword_module.ngram_buckets(s, min_n, max_n, buckets) for s in strings]
        assert counts.tolist() == [len(w) for w in want]
        assert rows.tolist() == [b for w in want for b in w]
        # the same strings as the walk hands them on: bytes and byte ends
        encoded = [s.encode("utf-8") for s in strings]
        packed = (np.frombuffer(b"".join(encoded), np.uint8),
                  np.cumsum([len(e) for e in encoded]).astype(np.int64))
        again = subword_module.ngram_rows(packed, min_n, max_n, buckets)
        assert np.array_equal(again[0], rows) and np.array_equal(again[1], counts)
    assert ref.hasher_mismatches(
        strings, np.split(rows, np.cumsum(counts)[:-1]), 1 << 20, 2, 9) == 0


def test_the_walk_hands_the_missing_tokens_on(lookup):
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    slide = [[STRINGS[1], "zzq", STRINGS[5], ""], [], ("é日本", STRINGS[9], "nope"),
             [STRINGS[3]], ["qqz"]]
    ids, counts, missing, tokens, native = vocab.lookup_sentences_misses(slide)
    assert native == (lookup == "native")
    assert ids.tolist() == [1, 5, 9, 3] and counts.tolist() == [2, 0, 1, 1, 0]
    assert missing.tolist() == [2, 0, 2, 0, 1]
    if native:
        raw, end = tokens
        data = raw.tobytes()
        tokens = [data[s:e].decode() for s, e in zip([0] + end[:-1].tolist(), end.tolist())]
    assert tokens == ["zzq", "", "é日本", "nope", "qqz"]
    # and lookup_sentences is what it was
    got = vocab.lookup_sentences(slide)
    assert got[0].tolist() == [1, 5, 9, 3] and got[1].tolist() == counts.tolist()
    assert got[2] == 5


def test_a_model_without_subwords_leaves_unseen_tokens_out():
    """One rule, no branch: an unseen token has no rows there, h = 0. And a
    word whose row is zero is left out of sum and count alike."""
    table = TABLE[:V].copy()
    table[7] = 0.0
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    m = Word2VecModel(vocab, table)
    sents = [[STRINGS[1], "zzq", STRINGS[2]], ["zzq"], [STRINGS[7]],
             [STRINGS[7], STRINGS[3], STRINGS[7]], []] + sentences(81, 40)
    got = m.sentence_vectors(sents)
    unit = table / np.maximum(np.linalg.norm(table, axis=1, keepdims=True), 1e-30)
    for row, s in zip(got, sents):
        kept = [INDEX[t] for t in s if t in INDEX and INDEX[t] != 7]
        want = unit[kept].astype(np.float64).mean(axis=0) if kept else np.zeros(D)
        assert np.abs(row - want).max() <= F32_TOL
    assert not got[1].any() and not got[2].any() and not got[4].any()
    m.stop()


def test_a_rows_only_model_refuses_what_it_holds_no_table_for(tmp_path):
    whole, rows = make_model("all"), make_model("rows")
    assert rows.resident == "rows" and whole.resident == "all"
    assert rows._full0 is None and rows._full1 is None and rows._raw0 is None
    assert rows._lanes.shape == (V, 128) and not np.asarray(rows._lanes[:, D:]).any()
    for what, call in {
            "find_synonyms": lambda: rows.find_synonyms(STRINGS[1], 3),
            "find_synonyms ": lambda: rows.find_synonyms_batch([STRINGS[1], "zzq"], 3),
            "save": lambda: rows.save(str(tmp_path / "m")),
            "syn1": lambda: rows.syn1, "syn0": lambda: rows.syn0,
            "multiply": lambda: rows.multiply(np.ones(D, np.float32)),
            "get_vectors": rows.get_vectors, "to_local": rows.to_local,
            "iter_vectors": lambda: next(rows.iter_vectors()),
            "export_word2vec": lambda: rows.export_word2vec(str(tmp_path / "v.txt"))}.items():
        with pytest.raises(RuntimeError, match=f"{what.strip()} needs a table.*resident='rows'"):
            call()
    # what reads rows answers as the whole model does, bit for bit
    sents = sentences(91, 70) + [["zzq"], []]
    assert np.array_equal(rows.transform_sentences(sents, batch_size=32),
                          whole.transform_sentences(sents, batch_size=32))
    # (the norms of the same rows, summed over 128 lanes here and over D there)
    assert np.abs(rows.sentence_vectors(sents) - whole.sentence_vectors(sents)).max() <= 2e-7
    assert np.array_equal(rows.pull([4, 0, V - 1]), whole.pull([4, 0, V - 1]))
    assert np.array_equal(np.stack(list(rows.transform_words(STRINGS[:5]))),
                          np.stack(list(whole.transform_words(STRINGS[:5]))))
    assert np.array_equal(rows.transform(STRINGS[8]), whole.transform(STRINGS[8]))
    assert np.array_equal(rows.transform("zzq"), whole.transform("zzq"))
    assert np.array_equal(np.asarray(rows.subword_buckets), np.asarray(whole.subword_buckets))
    assert rows.vector_size == D and rows.num_words == V
    lanes, buckets, inv = rows._lanes, rows._buckets, rows._inv_norms
    rows.stop()
    assert lanes.is_deleted() and buckets.is_deleted() and inv.is_deleted()
    assert rows._lanes is None and rows._inv_norms is None
    with pytest.raises(RuntimeError, match="stopped"):
        rows.sentence_vectors([["zzq"]])
    whole.stop()


def test_resident_rows_is_a_subword_models_on_one_device_and_is_loaded_so(tmp_path):
    vocab = Vocabulary.from_words_and_counts(STRINGS, np.ones(V, np.int64))
    with pytest.raises(ValueError, match="needs subword_buckets"):
        Word2VecModel(vocab, TABLE[:V], resident="rows")
    with pytest.raises(ValueError, match="'all' or 'rows'"):
        Word2VecModel(vocab, TABLE[:V], resident="scan")
    whole = Word2VecModel(vocab, TABLE[:V], TABLE[:V] * 2, config=CONFIG,
                          subword_buckets=TABLE[V:])
    whole.save(str(tmp_path / "m"))
    rows = Word2VecModel.load(str(tmp_path / "m"), resident="rows")
    sents = sentences(95, 50)
    assert rows.resident == "rows" and rows._full1 is None
    assert np.abs(rows.sentence_vectors(sents) - whole.sentence_vectors(sents)).max() <= 2e-7
    assert Word2VecModel.load(str(tmp_path / "m")).resident == "all"
    rows.stop()
    whole.stop()


def test_the_default_model_makes_its_row_forms_lazily_and_stop_frees_them():
    m = make_model()
    assert m._lanes is None and m._inv_norms is None
    m.find_synonyms(STRINGS[1], 3)
    assert m._lanes is None and m._inv_norms is None     # a scan makes neither
    m.sentence_vectors([[STRINGS[1], "zzq"]])
    lanes, inv, host = m._lanes, m._inv_norms, m._host_inv
    assert lanes.shape == (V, 128) and inv.shape == (V,)
    # the encode's copy of the inverse norms: the same numbers, on the host
    assert isinstance(host, np.ndarray) and np.array_equal(host, np.asarray(inv))
    m.sentence_vectors([[STRINGS[2]]])
    assert m._lanes is lanes and m._inv_norms is inv and m._host_inv is host
    m.stop()
    assert lanes.is_deleted() and inv.is_deleted() and m._host_inv is None


def test_spans_of_a_call_of_three_slides(model, tracer, lookup):
    sents = sentences(51, 70, empty_share=0.2) + [["", DEAD]]
    model.sentence_vectors(sents, batch_size=32)
    events = tracer.events()
    slides = [e for e in events if e["name"] == "transform.slide"]
    assert [e["args"]["sentences"] for e in slides] == [32, 32, 7]
    for lo, slide in zip((0, 32, 64), slides):
        part = sents[lo:lo + 32]
        words_ = sum(t in INDEX for s in part for t in s)
        composed = [t for s in part for t in s if t not in INDEX and t not in ("", DEAD)]
        dead_known = sum(t == "" for s in part for t in s)
        args = slide["args"]
        assert args["words"] == words_ and args["oov"] == 0
        assert args["unseen"] == len(composed) + sum(t == DEAD for s in part for t in s)
        assert args["zero_norm"] == dead_known
        assert args["empty"] == sum(all(t == "" for t in s) for s in part)
        children = [e for e in events if e.get("parent") == slide["id"]]
        assert [c["name"] for c in children] == [
            "transform.encode", "transform.enqueue", "transform.fetch"]
        (hashed,) = [e for e in events if e["name"] == "transform.ngram_hash"
                     and e["parent"] == children[0]["id"]]
        assert hashed["args"]["strings"] == args["unseen"] + dead_known
        assert hashed["args"]["list_rows"] == _list_rows(part)
        assert hashed["args"]["native"] == int(
            subword_module._load_native() is not None)
        # one take of the live ids' inverse norms a slide, inside its encode
        (scaled,) = [e for e in events if e["name"] == "transform.scale"
                     and e["parent"] == children[0]["id"]]
        assert scaled["args"] == {"rows": words_}
        enqueue = children[1]["args"]
        assert enqueue["rows"] == words_ and enqueue["passes"] == 1
        assert enqueue["list_rows"] == _list_rows(part)
        assert enqueue["unseen"] == args["unseen"]
        for live, cap in (("rows", "rows_cap"), ("list_rows", "list_cap")):
            assert enqueue[live] <= enqueue[cap] <= enqueue[live] * 17 // 16 + 128
        assert enqueue["unseen"] <= enqueue["unseen_cap"] <= max(128, 2 * enqueue["unseen"])
    assert [e["args"]["inflight"] for e in events
            if e["name"] == "transform.enqueue"] == [0, 1, 1]
    walks = [e for e in events if e["name"] == "transform.encode.walk"]
    assert len(walks) == (3 if lookup == "native" else 0)
    assert sum(e["name"] == "transform.scale" for e in events) == 3


def test_compose_says_its_lanes_and_init_its_residency():
    t = default_tracer()
    # by id, not by position: the store is a ring, and full once a worker's
    # earlier files have pinned its 1,024 spans
    before = max((e["id"] for e in t.setup_events()), default=-1)
    for resident, lanes in (("all", D), ("rows", 128)):
        make_model(resident).stop()
        new = [e for e in t.setup_events() if e["id"] > before]
        (compose,) = [e for e in new if e["name"] == "model.compose"]
        (init,) = [e for e in new if e["name"] == "model.init"]
        assert compose["args"]["lanes"] == lanes
        assert init["args"] == {"words": V, "subword": 1, "resident": resident}
        if resident == "rows":      # the norms are made with the model, once
            assert [e["parent"] for e in new if e["name"] == "model.norms"] == [init["id"]]
        before = max(e["id"] for e in new)


_COMPILED = []      # every backend compile of this process, by function


def _on_compile(name, seconds, **kw):
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILED.append(kw.get("fun_name"))


def test_no_compile_after_the_first_slide_of_a_shape(model):
    """A shape's first slide compiles the slide's one program; slides of other
    sentences at the same three capacities compile nothing."""
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    first = [[STRINGS[i], STRINGS[i + 1], f"zq{i}"] for i in range(53)]
    model.sentence_vectors(first, batch_size=53)
    mark = len(_COMPILED)
    again = [[STRINGS[3 * i]] * 2 + [f"qz{i}"] for i in range(53)]
    model.sentence_vectors(again, batch_size=53)
    model.sentence_vectors(first + again, batch_size=53)
    assert _COMPILED[mark:] == []
