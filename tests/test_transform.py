"""``Word2VecModel.transform_sentences`` (the upstream ``transform(dataset)``,
ml:428-460) held to the plain reference on the CPU.

The program (``models/word2vec.py``: one fixed-shape program a slide, rows
gathered from the whole-lane form of syn0, a segment mean on the device, slides
overlapped within a call) against ``benchmark/reference/transform_ref.py`` (its
own dictionary, a Python loop in float64) on seeded tables: ragged slides with
out-of-vocabulary tokens, all-OOV and empty sentences, repeated words, one-word
sentences, a 1,000-token sentence, slides over the row capacity, a short last
slide, a call of several slides, four threads at once, the compat wrapper. The
stated tolerances: float32 tables within ``F32_TOL`` of the table's half width
(a float32 sum of up to 1,000 rows against a float64 one), float64 tables
within ``F64_TOL`` (the one rounding of the result to float32).
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import zipf  # noqa: E402
from reference import transform_ref as ref  # noqa: E402

from glint_word2vec_tpu.data.vocab import Vocabulary  # noqa: E402
from glint_word2vec_tpu.models import word2vec as w2v  # noqa: E402
from glint_word2vec_tpu.models.compat import ServerSideGlintWord2VecModel  # noqa: E402
from glint_word2vec_tpu.models.word2vec import Word2VecModel  # noqa: E402
from glint_word2vec_tpu.obs.spans import default_tracer  # noqa: E402

V, D, HALF_WIDTH, SEED = 2000, 40, 0.5, 2**31 + 48
F32_TOL, F64_TOL = 4e-6 * HALF_WIDTH, 1.2e-7 * HALF_WIDTH
ROWS_FN = ref.seeded_rows(SEED, D, HALF_WIDTH)
INDEX = ref.dictionary(V)


def make_model(v=V, dtype=jnp.float32, plan=None) -> Word2VecModel:
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(v), np.ones(v, np.int64))
    table = np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(
        jnp.arange(v, dtype=jnp.int32))).astype(dtype)
    return Word2VecModel(vocab, table, plan=plan)


@pytest.fixture(scope="module")
def model():
    m = make_model()
    yield m
    m.stop()


def sentences(seed: int, n: int, oov_share=0.1, empty_share=0.05, max_len=60):
    """``n`` ragged sentences of ``w<rank>`` words, a share of the tokens
    strings no vocabulary holds, a share of the sentences all such."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        length = int(rng.integers(0, max_len))
        words = [f"w{int(r)}" for r in rng.integers(0, V, length)]
        lost = rng.random(length) < (1.0 if rng.random() < empty_share else oov_share)
        out.append([f"oov{i}_{j}" if lost[j] else w for j, w in enumerate(words)])
    return out


def expected(sents, rows_fn=ROWS_FN) -> np.ndarray:
    return ref.sentence_vectors(sents, INDEX, rows_fn, D)


CASES = {
    "ragged_with_oov": lambda: sentences(1, 300),
    "all_oov_and_empty": lambda: [[], ["nope"], ["nope", "never"], ["w3"], []],
    "repeated_words": lambda: [["w5"] * 7 + ["w9"], ["w9", "w5", "w9", "w9"]],
    "one_word_sentences": lambda: [[f"w{i}"] for i in range(97)],
    "a_1000_token_sentence": lambda: [
        ["w1"], [f"w{(i * 7) % V}" for i in range(1000)], ["w2", "zz"]],
    "tuples_and_arrays": lambda: [("w1", "w2"), np.array(["w3", "zz", "w3"])],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_meets_the_reference(model, case):
    sents = CASES[case]()
    got = model.transform_sentences(sents)
    assert got.dtype == np.float32 and got.shape == (len(sents), D)
    want = expected(sents)
    assert np.abs(got - want).max() <= F32_TOL
    for i, s in enumerate(sents):       # the zero-vector branch is exact
        if not any(w in INDEX for w in s):
            assert not got[i].any()


def test_no_sentences_at_all(model):
    assert model.transform_sentences([]).shape == (0, D)
    assert model.transform_sentences([[], []]).tolist() == [[0.0] * D] * 2


@pytest.mark.parametrize("batch_size,n", [(64, 64), (64, 200), (50, 151), (7, 20)])
def test_slides_and_a_short_last_slide(model, batch_size, n):
    """A call of several slides, the last shorter than ``batch_size``: every
    row lands where its sentence was, whatever slide held it."""
    sents = sentences(n + batch_size, n)
    got = model.transform_sentences(sents, batch_size=batch_size)
    assert np.abs(got - expected(sents)).max() <= F32_TOL
    assert np.array_equal(got, model.transform_sentences(sents, batch_size=n + 1))


@pytest.fixture()
def tracer():
    t = default_tracer()
    t.clear()
    t.configure(True)
    yield t
    t.configure(False)
    t.clear()


@pytest.mark.parametrize("passes", [2, 3])
def test_a_slide_over_the_row_capacity_runs_further_passes(
        model, tracer, monkeypatch, passes):
    sents = sentences(11, 120, oov_share=0.0, empty_share=0.0)
    live = sum(len(s) for s in sents)
    one = model.transform_sentences(sents)
    monkeypatch.setattr(w2v, "_TRANSFORM_MAX_ROWS", -(-live // passes))
    tracer.clear()
    got = model.transform_sentences(sents)
    said = [e["args"] for e in tracer.events() if e["name"] == "transform.enqueue"]
    assert [a["passes"] for a in said] == [passes]
    assert said[0]["rows"] == live and said[0]["rows_cap"] * passes >= live
    assert said[0]["rows_cap"] * (passes - 1) < live
    assert np.abs(got - expected(sents)).max() <= F32_TOL
    # the same sums in another association: within a rounding of one pass
    assert np.abs(got - one).max() <= 1e-6 * HALF_WIDTH


def test_four_threads_call_at_once(model):
    sets = [sentences(100 + i, 90) for i in range(4)]
    got, errors = [None] * 4, []

    def call(i):
        try:
            for _ in range(3):
                got[i] = model.transform_sentences(sets[i], batch_size=32)
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


def test_compat_transform_on_lists_and_dicts(model):
    compat = ServerSideGlintWord2VecModel(model)
    sents = sentences(21, 12)
    want = expected(sents)
    assert np.abs(compat.transform(sents) - want).max() <= F32_TOL
    rows = compat.transform([{"id": i, "sentence": s} for i, s in enumerate(sents)])
    assert [r["id"] for r in rows] == list(range(12))
    assert np.abs(np.stack([r["vector"] for r in rows]) - want).max() <= F32_TOL


def test_float64_tables_meet_the_reference_to_one_rounding():
    with jax.enable_x64():
        m = make_model(dtype=jnp.float64)
        table = np.asarray(m.syn0)
        sents = sentences(31, 80) + CASES["a_1000_token_sentence"]()
        got = m.transform_sentences(sents)
        want = expected(sents, lambda ids: table[np.asarray(ids)])
        m.stop()
    assert got.dtype == np.float32
    assert np.abs(got - want).max() <= F64_TOL


def test_a_bfloat16_table_is_outside_the_float32_tolerance():
    """What the tolerance is for: the same model in the next precision down
    is an order of magnitude outside it."""
    m = make_model(dtype=jnp.bfloat16)
    sents = sentences(41, 60)
    got = m.transform_sentences(sents)
    m.stop()
    assert np.abs(got - expected(sents)).max() > 10 * F32_TOL


_COMPILED = []      # every backend compile of this process, by function


def _on_compile(name, seconds, **kw):
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILED.append(kw.get("fun_name"))


def test_no_compile_after_the_first_slide_of_a_shape():
    """A shape's first slide compiles the slide's one program (and, once a
    model, the whole-lane form's pad); slides of other sentences and lengths
    at the same capacity compile nothing."""
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    # a table of its own, so no other test has compiled these shapes
    m = make_model(v=V - 123)
    first = [[f"w{i}", f"w{i + 1}", "zz"] for i in range(48)]
    m.transform_sentences(first, batch_size=48)
    programs = [f for f in _COMPILED if f == "jit(_segment_means)"]
    assert len(programs) == 1
    mark = len(_COMPILED)
    # other words, other lengths, OOV elsewhere: 90-96 live ids, one capacity
    again = [[f"w{3 * i}"] * (1 + (i % 3)) for i in range(47)] + [["zz", "w1"]]
    assert sum(len(s) for s in again) - 1 in range(90, 97)
    m.transform_sentences(again, batch_size=48)
    m.transform_sentences(first + again, batch_size=48)
    assert _COMPILED[mark:] == []
    m.stop()


def test_the_whole_lane_form_is_made_once_and_freed_by_stop():
    m = make_model()
    m.find_synonyms("w1", 3)
    assert m._lanes is None             # a scan never makes it
    m.transform_sentences([["w1", "w2"]])
    lanes = m._lanes
    assert lanes.shape == (V, 128) and not np.asarray(lanes[:, D:]).any()
    m.pull([1, 2])
    list(m.transform_words(["w1"]))
    assert m._lanes is lanes
    m.stop()
    assert m._lanes is None and lanes.is_deleted()
    with pytest.raises(RuntimeError, match="stopped"):
        m.transform_sentences([["w1"]])


def test_a_table_on_a_mesh_keeps_the_gather_of_its_view():
    """ROADMAP B14 (c) stays open: over a mesh the rows are gathered from the
    ``[:V]`` view as they were, no whole-lane form is made, and the answers
    are the one-device program's."""
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one, sharded = make_model(v=1203), make_model(v=1203, plan=make_mesh(1, 4))
    sents = [[f"w{(i * 13 + j) % 1203}" for j in range(i % 9)] + ["zz"]
             for i in range(70)]
    got = sharded.transform_sentences(sents, batch_size=32)
    assert sharded._lanes is None and sharded._full0.shape[0] > 1203
    assert np.abs(got - one.transform_sentences(sents)).max() <= 1e-6 * HALF_WIDTH
    assert np.array_equal(sharded.pull([5, 1202]), one.pull([5, 1202]))
    one.stop()
    sharded.stop()


def test_spans_of_a_call_of_three_slides(model, tracer):
    sents = sentences(51, 70, empty_share=0.2)
    model.transform_sentences(sents, batch_size=32)
    events = tracer.events()
    slides = [e for e in events if e["name"] == "transform.slide"]
    assert [e["args"]["sentences"] for e in slides] == [32, 32, 6]
    for lo, slide in zip((0, 32, 64), slides):
        part = sents[lo:lo + 32]
        kept = [sum(w in INDEX for w in s) for s in part]
        assert slide["args"]["words"] == sum(kept)
        assert slide["args"]["oov"] == sum(map(len, part)) - sum(kept)
        assert slide["args"]["empty"] == sum(k == 0 for k in kept)
        children = [e for e in events if e.get("parent") == slide["id"]]
        assert [c["name"] for c in children] == [
            "transform.encode", "transform.enqueue", "transform.fetch"]
        enqueue = children[1]["args"]
        assert enqueue["rows"] == sum(kept) and enqueue["passes"] == 1
        assert enqueue["rows"] <= enqueue["rows_cap"] <= enqueue["rows"] * 17 // 16 + 128
    # the second slide is enqueued while the first is out, and so on
    assert [e["args"]["inflight"] for e in events
            if e["name"] == "transform.enqueue"] == [0, 1, 1]


def test_nothing_is_recorded_with_tracing_off(model):
    t = default_tracer()
    t.clear()
    model.transform_sentences(sentences(61, 10))
    assert t.events() == []


def test_transform_words_names_the_missing_word_and_pull_reads_rows(model):
    table = np.asarray(model.syn0)
    got = list(model.transform_words(["w7", "w3", "w7"], batch_size=2))
    assert np.array_equal(np.stack(got), table[[7, 3, 7]])
    with pytest.raises(KeyError, match="w-not-there not in vocabulary"):
        list(model.transform_words(["w1", "w-not-there", "nor-this"]))
    assert np.array_equal(model.pull([4, 0, 1999]), table[[4, 0, 1999]])


def test_vocabulary_lookup_is_get_in_bulk(model):
    tokens = ["w5", "nope", "w0", "w1999", "", "w5"]
    ids = model.vocab.lookup(tokens)
    assert ids.dtype == np.int32
    assert ids.tolist() == [model.vocab.get(t) for t in tokens] == [5, -1, 0, 1999, -1, 5]
    assert model.vocab.lookup([]).shape == (0,)


@pytest.mark.parametrize("n", [1, 7, 8, 129, 4097, 313_000, 1 << 19])
def test_row_capacities_are_whole_tiles_with_a_sixteenth_of_room(n):
    cap = w2v._grid_up(n, 128)
    tile = max(128, (1 << (n.bit_length() - 1)) // 16)
    assert cap >= n and cap % tile == 0 and cap - n < tile
    assert w2v._grid_up(cap, 128) == cap
    if tile > 128:      # past the floor: at most a sixteenth is padding
        assert cap * 16 <= n * 17 + 16


# -- Vocabulary.lookup's native table (native/lookup.cpp) --------------------------------

def _dict_ids(vocab, tokens):
    return [vocab.get(t) for t in tokens]


@pytest.fixture()
def small_batches_go_native(monkeypatch):
    from glint_word2vec_tpu.data import vocab as vocab_module
    if vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here: dict.get answers")
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)


def test_the_native_table_answers_as_the_dict(small_batches_go_native):
    words = ["a", "é", "", "a", "b c", "ab", "日本語", "w" * 300]
    vocab = Vocabulary.from_words_and_counts(words, np.ones(len(words), np.int64))
    tokens = ["a", "é", "", "zz", "b c", "b", "ab", "abc", "日本語", "日本", "w" * 300,
              "w" * 299, "A"]
    got = vocab.lookup(tokens)
    assert vocab._native.handle is not None
    assert got.dtype == np.int32 and got.tolist() == _dict_ids(vocab, tokens)
    assert vocab.get("a") == 3          # a word twice keeps its last position
    rng = np.random.default_rng(3)
    many = [f"w{int(i)}" for i in rng.integers(0, 2 * V, 100_000)]
    big = Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64))
    assert big.lookup(many).tolist() == _dict_ids(big, many)    # several threads' parts
    assert big.lookup(["w1"] * 70_000 + [""] * 3).tolist() == [1] * 70_000 + [-1] * 3


@pytest.mark.parametrize("tokens", [
    ["w1", "w2\nw3", "w4"],             # a token holds the separator
    ["w1", "\udc80", "w2"],             # a lone surrogate does not encode
    ["w1", b"w2", "w3"],                # not a string: the dict's own answer
], ids=["separator", "surrogate", "bytes"])
def test_what_the_native_table_cannot_answer_goes_to_the_dict(
        small_batches_go_native, model, tokens):
    assert model.vocab.lookup(tokens).tolist() == _dict_ids(model.vocab, tokens)


def test_lookup_without_the_native_library(monkeypatch, model):
    from glint_word2vec_tpu.data import vocab as vocab_module
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)
    monkeypatch.setattr(vocab_module, "_load_native", lambda: None)
    tokens = ["w5", "nope", "w0"] * 10
    assert model.vocab.lookup(tokens).tolist() == _dict_ids(model.vocab, tokens)


def test_four_threads_look_up_at_once(small_batches_go_native):
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64))
    rng = np.random.default_rng(5)
    sets = [[f"w{int(i)}" for i in rng.integers(0, 2 * V, 50_000)] for _ in range(4)]
    got = [None] * 4

    def call(i):
        for _ in range(3):
            got[i] = vocab.lookup(sets[i])

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for i in range(4):
        assert got[i].tolist() == _dict_ids(vocab, sets[i])
