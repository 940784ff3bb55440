"""``Word2VecModel.transform_sentences`` (the upstream ``transform(dataset)``,
ml:428-460) held to the plain reference on the CPU.

The program (``models/word2vec.py``: one fixed-shape program a slide, rows
gathered from the whole-lane form of syn0, a segment mean on the device, slides
overlapped within a call) against ``benchmark/reference/transform_ref.py`` (its
own dictionary, a Python loop in float64) on seeded tables: ragged slides with
out-of-vocabulary tokens, all-OOV and empty sentences, repeated words, one-word
sentences, a 1,000-token sentence, slides over the row capacity, a short last
slide, a call of several slides, four threads at once, the compat wrapper; and
over tables partitioned by rows on meshes of virtual devices (1x4, 1x2, 2x2;
vocabularies that divide and one that does not), against the one-device program
and ``benchmark/reference/sharded_transform_ref.py``. The
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
from reference import sharded_transform_ref as sharded_ref  # noqa: E402
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


def test_a_table_on_a_mesh_builds_the_whole_lane_form_under_its_sharding(tracer):
    """ROADMAP B14 (c), B19 (a) closed: over a table partitioned by rows the
    whole-lane form IS made, once, shard by shard under the table's own
    sharding (its padding rows with it), the slide runs under ``shard_map``
    over it, and the answers are the one-device program's."""
    from glint_word2vec_tpu.ops.scan import _row_shards
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one, sharded = make_model(v=1203), make_model(v=1203, plan=make_mesh(1, 4))
    sents = [[f"w{(i * 13 + j) % 1203}" for j in range(i % 9)] + ["zz"]
             for i in range(70)]
    sharded.find_synonyms("w1", 3)
    assert sharded._lanes is None           # a scan never makes it, here either
    got = sharded.transform_sentences(sents, batch_size=32)
    lanes = sharded._lanes
    assert lanes.shape == (sharded._full0.shape[0], 128) and lanes.shape[0] > 1203
    assert lanes.sharding.is_equivalent_to(sharded._full0.sharding, 2)
    assert _row_shards(lanes) is not None and len(lanes.sharding.device_set) == 4
    assert not np.asarray(lanes[:, D:]).any() and not np.asarray(lanes[1203:]).any()
    assert np.abs(got - one.transform_sentences(sents)).max() <= MESH_TOL
    assert np.array_equal(sharded.pull([5, 1202]), one.pull([5, 1202]))
    assert sharded._lanes is lanes
    made = [e["args"] for e in tracer.setup_events() if e["name"] == "model.row_table"]
    assert made[-2:] == [{"rows": lanes.shape[0], "shards": 4}, {"rows": 1203}]
    one.stop()
    sharded.stop()
    assert sharded._lanes is None and lanes.is_deleted()


# a float32 sum of the same rows in another association (a chip's partial sums,
# then the four partials): each within F32_TOL of the float64 sum, so the two
# programs within twice that of each other
MESH_TOL = 2 * F32_TOL
MESHES = {"1x4": (1, 4), "1x2": (1, 2), "2x2": (2, 2)}


def mesh_sentences(v: int, n: int = 150) -> list:
    """Ragged sentences over ``v`` words with what a partition could lose: a
    sentence whose tokens all lie in the last shard's rows, the vocabulary's
    last word, an all-OOV and an empty sentence, a 300-token sentence, and
    out-of-vocabulary tokens throughout."""
    rng = np.random.default_rng(v)
    out = [[f"w{int(r)}" for r in rng.integers(0, v, int(rng.integers(0, 40)))]
           + (["zz"] if i % 3 == 0 else []) for i in range(n)]
    out[7] = [f"w{v - 1 - i}" for i in range(8)]
    out[8] = [f"w{v - 1}"]
    out[9] = ["nope", "never"]
    out[10] = []
    out[11] = [f"w{int(r)}" for r in rng.integers(0, v, 300)]
    return out


@pytest.fixture(scope="module", params=[1203, 2000], ids=["v1203", "v2000"])
def on_one_device(request):
    v = request.param
    one = make_model(v=v)
    yield v, one, ref.dictionary(v)
    one.stop()


@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_the_sharded_slide_answers_as_one_device_and_as_the_reference(
        on_one_device, mesh, tracer):
    """1,203 rows do not divide over the mesh (the last shards hold padding
    rows, the fill id is the PADDED row count); 2,000 do. A call of five slides
    and a short last one."""
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    v, one, index = on_one_device
    sharded = make_model(v=v, plan=make_mesh(*MESHES[mesh]))
    sents = mesh_sentences(v)
    got = sharded.transform_sentences(sents, batch_size=32)
    said = [e["args"] for e in tracer.events() if e["name"] == "transform.enqueue"]
    assert got.shape == (150, D) and got.dtype == np.float32
    assert np.abs(got - one.transform_sentences(sents, batch_size=32)).max() <= MESH_TOL
    want = sharded_ref.sentence_vectors(sents, index, ROWS_FN, D)
    assert np.abs(got - want).max() <= F32_TOL
    assert not got[9].any() and not got[10].any() and got[7].any() and got[8].any()
    shards = MESHES[mesh][1]
    per = sharded._full0.shape[0] // shards
    assert [a["shards"] for a in said] == [shards] * 5
    for lo, a in zip(range(0, 150, 32), said):
        ids = np.array([index[w] for s in sents[lo:lo + 32] for w in s if w in index])
        assert a["rows"] == len(ids) and a["passes"] == 1
        assert a["owned_max"] == np.bincount(ids // per, minlength=shards).max()
    sharded.stop()


@pytest.mark.parametrize("passes", [2, 3])
def test_a_sharded_slide_over_the_row_capacity_carries_its_sums(
        on_one_device, passes, monkeypatch, tracer):
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    v, one, index = on_one_device
    sents = mesh_sentences(v, 64)
    live = sum(w in index for s in sents for w in s)
    want = one.transform_sentences(sents)
    monkeypatch.setattr(w2v, "_TRANSFORM_MAX_ROWS", -(-live // passes))
    sharded = make_model(v=v, plan=make_mesh(1, 4))
    tracer.clear()
    got = sharded.transform_sentences(sents)
    assert np.abs(got - want).max() <= MESH_TOL
    assert np.abs(got - sharded_ref.sentence_vectors(sents, index, ROWS_FN, D)
                  ).max() <= F32_TOL
    said = [e["args"] for e in tracer.events() if e["name"] == "transform.enqueue"]
    assert [a["passes"] for a in said] == [passes] and said[0]["shards"] == 4
    sharded.stop()


@pytest.mark.parametrize("v", [1203, 2000])
def test_row_reads_on_a_mesh_are_the_one_device_rows_bit_for_bit(v):
    """``pull``, ``transform_words`` and ``transform`` over a partitioned
    table read the whole-lane form's owner rows: a row is one addend that is
    not zero, so the psum returns its bits."""
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one, sharded = make_model(v=v), make_model(v=v, plan=make_mesh(1, 4))
    ids = [0, 5, v // 4 - 1, v // 4, v // 2 + 3, v - 1, 5, -1]
    assert np.array_equal(sharded.pull(ids), one.pull(ids))
    assert sharded.pull(ids).shape == (len(ids), D)
    words = [f"w{i}" for i in range(0, v, 7)]
    assert np.array_equal(np.stack(list(sharded.transform_words(words, batch_size=100))),
                          np.stack(list(one.transform_words(words, batch_size=100))))
    assert np.array_equal(sharded.transform(f"w{v - 1}"), one.transform(f"w{v - 1}"))
    with pytest.raises(KeyError, match="zz"):
        list(sharded.transform_words(["w1", "zz"]))
    assert sharded._lanes is not None
    one.stop()
    sharded.stop()


def test_a_bfloat16_table_on_a_mesh_is_read_in_its_own_dtype():
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one = make_model(dtype=jnp.bfloat16)
    sharded = make_model(dtype=jnp.bfloat16, plan=make_mesh(1, 4))
    assert sharded.pull([3, V - 1]).dtype == one.pull([3, V - 1]).dtype
    assert np.array_equal(sharded.pull([3, V - 1]), one.pull([3, V - 1]))
    sents = mesh_sentences(V, 40)
    assert np.abs(sharded.transform_sentences(sents)
                  - one.transform_sentences(sents)).max() <= MESH_TOL
    one.stop()
    sharded.stop()


def test_the_sharded_slide_is_one_program_with_one_collective():
    """The slide's program over a 1x4 mesh of virtual devices: exactly one
    collective, an all-reduce of the ``[segments, lanes]`` float32 partial
    sums, and nothing else crosses a device (the described v5e's compile,
    with the in-place gather, is tests/test_scan_inplace_tpu.py's)."""
    import re

    from glint_word2vec_tpu.ops.scan import _row_shards
    from glint_word2vec_tpu.ops.transform import _segment_means
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    sharded = make_model(plan=make_mesh(1, 4))
    table = sharded._row_table()
    ids, seg = np.zeros(256, np.int32), np.zeros(256, np.int32)
    for counts, carried in ((np.ones(64, np.int32), None),
                            (None, jnp.zeros((64, 128), jnp.float32))):
        text = _segment_means.lower(table, ids, seg, counts, carried, 64, D,
                                    _row_shards(table)).compile().as_text()
        found = re.findall(r"= (\S+?) (all-reduce|all-gather|all-to-all|reduce-scatter|"
                           r"collective-permute)(?:-start)?\(", text)
        assert [(s.split("{")[0], op) for s, op in found] == [("f32[64,128]", "all-reduce")]
    sharded.stop()


# the lowered text of the one-device slide programs at [1733, 128] rows, 256
# ids, 24 sentences, dim 100, as the parent of PR 59 (1b8b1e8) lowers them:
# sha256, first 16 hex digits. Keys: the program, then for _segment_means
# (counts handed over, sums carried), for _sentence_means (lists handed over).
# _sentence_means' two are PR 60's: its scale is the slide's own [256] values
# (the parent's gathered them from a [1733] vector: 8136661ce6afaa06,
# d13a69d88f70720b), one gather fewer each, nothing else of the text changed
PARENT_SLIDE_TEXT = {
    ("segment", True, False): "3f52cd2a93f111ea",
    ("segment", False, False): "4841ed7d9e6465ec",
    ("segment", True, True): "69ee0d0ae1cba0bd",
    ("sentence", False): "baa1e0d217175ae7",
    ("sentence", True): "e2a577cb4a007380"}


@pytest.mark.parametrize("case", sorted(PARENT_SLIDE_TEXT, key=str), ids=str)
def test_a_slide_on_one_device_lowers_to_the_parents_text(case):
    """A table that is not partitioned runs the programs it ran before there
    was a sharded slide: no operand, no instruction more
    (sgns-transform-3m-300's and subword-sentvec-2.5m-300's cells)."""
    import hashlib

    from glint_word2vec_tpu.ops import transform as ops
    spec = jax.ShapeDtypeStruct
    table, ids = spec((1733, 128), jnp.float32), spec((256,), jnp.int32)
    counts = spec((24,), jnp.int32)
    if case[0] == "segment":
        _, last, carried = case
        text = ops._segment_means.lower(
            table, ids, ids, counts if last else None,
            spec((24, 128), jnp.float32) if carried else None, 24, 100).as_text()
    else:
        lists = (spec((500, 128), jnp.float32), spec((384,), jnp.int32),
                 spec((384,), jnp.int32), spec((64,), jnp.int32))
        text = ops._sentence_means.lower(
            table, spec((256,), jnp.float32), ids, ids, lists if case[1] else None,
            counts, None, 24, 100).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_SLIDE_TEXT[case]


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


def _dict_sentences(vocab, slide):
    """What ``lookup_sentences`` owes, from ``dict.get`` a token: the live
    ids, every sentence's count of them, the tokens that have none."""
    ids = [[vocab.get(t) for t in sentence] for sentence in slide]
    live = [[i for i in s if i >= 0] for s in ids]
    return (sum(live, []), [len(s) for s in live],
            sum(map(len, ids)) - sum(map(len, live)))


def _assert_sentences(vocab, slide, native=None):
    ids, counts, missing, by_objects = vocab.lookup_sentences(slide)
    want_ids, want_counts, want_missing = _dict_sentences(vocab, slide)
    assert ids.dtype == np.int32 and counts.dtype == np.int32
    assert ids.tolist() == want_ids and counts.tolist() == want_counts
    assert missing == want_missing and isinstance(missing, int)
    if native is not None:
        assert by_objects is native


@pytest.fixture()
def small_batches_go_native(monkeypatch):
    from glint_word2vec_tpu.data import vocab as vocab_module
    if vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here: dict.get answers")
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)


@pytest.fixture()
def odd_vocab():
    """Words of every width of UTF-8, the empty string, one that holds a line
    break, one held twice (the last position is its id)."""
    words = ["a", "é", "", "a", "b c", "ab", "日本語", "w" * 300, "x\ny", "😀", "𝔘𝔫"]
    return Vocabulary.from_words_and_counts(words, np.ones(len(words), np.int64))


def test_the_native_table_answers_as_the_dict(small_batches_go_native, odd_vocab):
    tokens = ["a", "é", "", "zz", "b c", "b", "ab", "abc", "日本語", "日本", "w" * 300,
              "w" * 299, "A", "x\ny", "x", "\n", "😀", "😀😀", "𝔘𝔫", "𝔘"]
    got = odd_vocab.lookup(tokens)
    assert odd_vocab._native.handle is not None
    assert got.dtype == np.int32 and got.tolist() == _dict_ids(odd_vocab, tokens)
    assert odd_vocab.get("a") == 3          # a word twice keeps its last position
    assert odd_vocab.get("x\ny") == 8 and got[13] == 8     # no separator: no rule for it
    rng = np.random.default_rng(3)
    many = [f"w{int(i)}" for i in rng.integers(0, 2 * V, 100_000)]
    big = Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64))
    assert big.lookup(many).tolist() == _dict_ids(big, many)    # several threads' parts
    assert big.lookup(["w1"] * 70_000 + [""] * 3).tolist() == [1] * 70_000 + [-1] * 3
    assert big.lookup(tuple(many[:100])).tolist() == _dict_ids(big, many[:100])


SLIDES = {
    # empty and all-OOV sentences at both ends and side by side
    "ragged": lambda: [[], ["a", "zz", "é"], [], [], ["zz"], ["zz", "nope"], ["ab"] * 5, []],
    "a_line_break_in_a_token": lambda: [["x\ny", "x", "y"], ["\n"], ["a\n", "a"]],
    "the_empty_string": lambda: [[""], ["", "", "zz"], []],
    "non_ascii_and_four_byte_code_points": lambda: [
        ["日本語", "日本", "é"], ["😀", "😀😀", "𝔘𝔫", "𝔘"], ["é" * 3]],
    "a_word_the_vocabulary_holds_twice": lambda: [["a"], ["a", "a", "b c"]],
    "one_long_sentence": lambda: [["w" * 300, "w" * 299] * 500],
    "tuples_arrays_and_a_tuple_of_them": lambda: (
        ("a", "zz"), np.array(["é", "zz", "ab"]), ["a"], (), np.array([], dtype=str)),
    "a_str_is_its_characters": lambda: ["ab", "", "aé"],
    "a_list_subclass_goes_by_its_own_iterator": lambda: [_Backwards(["a", "zz", "é"]), ["ab"]],
}


class _Backwards(list):
    def __iter__(self):
        return reversed(list(super().__iter__()))


@pytest.mark.parametrize("case", sorted(SLIDES))
def test_the_nested_form_answers_as_the_dict(small_batches_go_native, odd_vocab, case):
    _assert_sentences(odd_vocab, SLIDES[case](), native=True)


def test_a_ragged_seeded_slide_in_the_nested_form(small_batches_go_native, model):
    slide = sentences(71, 400, oov_share=0.2, empty_share=0.1)
    assert any(not s for s in slide)
    _assert_sentences(model.vocab, slide, native=True)


def test_under_the_threshold_the_dict_answers(model):
    _assert_sentences(model.vocab, sentences(72, 20), native=False)
    ids, counts, missing, by_objects = model.vocab.lookup_sentences([])
    assert ids.shape == (0,) and counts.shape == (0,) and missing == 0 and not by_objects


@pytest.mark.parametrize("tokens", [
    ["w1", "w2\nw3", "w4"],             # no separator any more: the table's own answer
    ["w1", "\udc80", "w2"],             # a lone surrogate does not encode
    ["w1", b"w2", "w3"],                # not a string: the dict's own answer
    ["w1", None, 7, ("w2",)],           # nor these, and all of them hash
], ids=["line_break", "surrogate", "bytes", "none_int_tuple"])
@pytest.mark.parametrize("nested", [False, True], ids=["flat", "nested"])
def test_what_the_native_table_cannot_answer_goes_to_the_dict(
        small_batches_go_native, model, tokens, nested):
    if not nested:
        assert model.vocab.lookup(tokens).tolist() == _dict_ids(model.vocab, tokens)
        return
    slide = [["w5"], tokens, [], tokens[::-1]]
    _assert_sentences(model.vocab, slide,
                      native=all(isinstance(t, str) and t.isprintable() or t == "w2\nw3"
                                 for t in tokens))


@pytest.mark.parametrize("slide", [
    lambda: [["w1"], [["w2"]]],                 # a token that does not hash
    lambda: [["w1"], None],                     # a sentence with no length
    lambda: [["w1"], 7],
    lambda: [["w1"], (t for t in ["w2"])],      # nor has a generator: no sequence
], ids=["unhashable_token", "none_sentence", "int_sentence", "generator_sentence"])
@pytest.mark.parametrize("native", [False, True], ids=["dict", "native"])
def test_what_nothing_can_answer_raises_as_the_dict_does(
        monkeypatch, model, slide, native):
    from glint_word2vec_tpu.data import vocab as vocab_module
    if native and vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here")
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1 if native else 1 << 30)
    with pytest.raises(TypeError):
        model.vocab.lookup_sentences(slide())


def test_a_set_is_no_sequence_and_the_dict_takes_it_as_it_did(
        small_batches_go_native, model):
    _assert_sentences(model.vocab, [["w1"], {"w2"}, {"w3": 1}], native=False)


def test_a_sentence_that_changes_the_slide_under_the_walk(small_batches_go_native, model):
    """Making a list of an odd sentence runs its code, which may shrink the
    slide: the walk asks the slide anew and hands the batch to the dict."""
    slide = [["w1", "w2"], None, ["w3"], ["w4"]]

    class Shrinks(tuple):
        def __iter__(self):
            del slide[2:]
            return iter(["w9"])

    slide[1] = Shrinks(["w9"])
    # the dict's route then trips over its own count of a slide that shrank
    with pytest.raises((ValueError, IndexError)):
        model.vocab.lookup_sentences(slide)
    assert len(slide) == 2
    assert model.vocab.lookup_sentences([["w1"], ["w2", "zz"]])[0].tolist() == [1, 2]


@pytest.mark.parametrize("how", ["no_library", "disabled_by_environment", "no_compiler"])
def test_lookup_without_the_native_library(monkeypatch, model, how):
    from glint_word2vec_tpu.data import native as native_module
    from glint_word2vec_tpu.data import vocab as vocab_module
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)
    if how == "no_library":
        monkeypatch.setattr(vocab_module, "_load_native", lambda: None)
    else:       # the loader itself, from nothing, finds no library to load
        monkeypatch.setattr(vocab_module, "_lib", None)
        monkeypatch.setattr(vocab_module, "_lib_failed", False)
        if how == "disabled_by_environment":
            monkeypatch.setenv("GLINT_DISABLE_NATIVE", "1")
        else:
            monkeypatch.setattr(native_module, "build_or_reload", lambda *a, **k: None)
        assert vocab_module._load_native() is None
    tokens = ["w5", "nope", "w0"] * 10
    assert model.vocab.lookup(tokens).tolist() == _dict_ids(model.vocab, tokens)
    _assert_sentences(model.vocab, [tokens, [], ["nope"], tokens[:4]], native=False)
    got = model.transform_sentences([["w5", "nope"], ["nope"]])
    assert np.abs(got - expected([["w5", "nope"], ["nope"]])).max() <= F32_TOL


def test_an_interpreter_without_the_entry_points_takes_the_dict(monkeypatch, model):
    import ctypes

    from glint_word2vec_tpu.data import vocab as vocab_module
    if vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here")
    monkeypatch.setattr(vocab_module, "_lib", None)
    monkeypatch.setattr(vocab_module, "_lib_failed", False)
    monkeypatch.setattr(vocab_module, "_INTERPRETER_SYMBOLS",
                        vocab_module._INTERPRETER_SYMBOLS[:-1] + ("PyNo_SuchEntryPoint",))
    assert vocab_module._load_native() is None
    assert not hasattr(ctypes.pythonapi, "PyNo_SuchEntryPoint")
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", 1)
    _assert_sentences(model.vocab, [["w5", "nope"], ["w1"]], native=False)


@pytest.mark.parametrize("nested", [False, True], ids=["flat", "nested"])
def test_four_threads_look_up_at_once(small_batches_go_native, nested):
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64))
    rng = np.random.default_rng(5)
    sets = [[f"w{int(i)}" for i in rng.integers(0, 2 * V, 50_000)] for _ in range(4)]
    if nested:      # four ragged slides, each thread its own
        sets = [[s[a:a + n] for a, n in zip(range(0, 50_000, 50), [0, 50, 7, 50, 1] * 200)]
                for s in sets]
    got = [None] * 4

    def call(i):
        for _ in range(3):
            got[i] = vocab.lookup_sentences(sets[i]) if nested else vocab.lookup(sets[i])

    threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for i in range(4):
        if nested:
            want_ids, want_counts, want_missing = _dict_sentences(vocab, sets[i])
            assert got[i][0].tolist() == want_ids and got[i][1].tolist() == want_counts
            assert got[i][2:] == (want_missing, True)
        else:
            assert got[i].tolist() == _dict_ids(vocab, sets[i])


def _encode_slide_as_it_was(vocab, slide):
    """``_encode_slide`` before the nested form: lengths, the flattened list,
    one lookup, the drop and the counts in numpy."""
    lengths = np.fromiter(map(len, slide), np.int64, count=len(slide))
    ids = np.asarray([vocab.get(t) for s in slide for t in s], np.int32)
    live = ids >= 0
    before = np.concatenate([[0], np.cumsum(live)])
    ends = np.cumsum(lengths)
    counts = (before[ends] - before[ends - lengths]).astype(np.int32)
    ids = ids[live]
    return ids, counts, int(live.shape[0] - ids.shape[0])


@pytest.mark.parametrize("route", ["native", "dict"])
def test_encode_slide_gives_what_it_gave(monkeypatch, model, route):
    from glint_word2vec_tpu.data import vocab as vocab_module
    if route == "native" and vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here")
    monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS",
                        1 if route == "native" else 1 << 30)
    slide = sentences(2**31 + 51, 500, oov_share=0.15, empty_share=0.1)
    ids, counts, oov, by_objects = model._encode_slide(slide)
    want_ids, want_counts, want_oov = _encode_slide_as_it_was(model.vocab, slide)
    assert ids.dtype == want_ids.dtype and np.array_equal(ids, want_ids)
    assert counts.dtype == want_counts.dtype and np.array_equal(counts, want_counts)
    assert oov == want_oov and by_objects is (route == "native")


def test_the_walk_is_a_child_span_and_the_encode_says_which_way(
        small_batches_go_native, model, tracer):
    model.transform_sentences(sentences(81, 40), batch_size=32)
    events = tracer.events()
    encodes = [e for e in events if e["name"] == "transform.encode"]
    walks = [e for e in events if e["name"] == "transform.encode.walk"]
    assert [e["args"] for e in encodes] == [{"by_objects": 1}] * 2
    assert [w["parent"] for w in walks] == [e["id"] for e in encodes]
    for walk, encode in zip(walks, encodes):
        assert encode["ts_s"] <= walk["ts_s"]
        assert walk["ts_s"] + walk["dur_s"] <= encode["ts_s"] + encode["dur_s"]


def test_under_the_threshold_no_walk_is_recorded(model, tracer):
    model.transform_sentences(sentences(82, 10))
    events = tracer.events()
    assert [e["args"] for e in events if e["name"] == "transform.encode"] == [
        {"by_objects": 0}]
    assert not [e for e in events if e["name"] == "transform.encode.walk"]
