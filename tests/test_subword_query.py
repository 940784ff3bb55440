"""A subword model's neighbour queries (fastText's ``nn``): strings the
vocabulary lacks answered from their n-grams inside the scan's one program, held
to the plain reference (benchmark/reference/subword_query_ref.py) on seeded
tables; the composed table built from the two parts of syn0 as they arrive."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import weights, words  # noqa: E402
from reference import subword_query_ref as ref  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data import subword as data_sw  # noqa: E402
from glint_word2vec_tpu.data.vocab import Vocabulary  # noqa: E402
from glint_word2vec_tpu.models.word2vec import Word2VecModel  # noqa: E402
from glint_word2vec_tpu.obs.spans import default_tracer  # noqa: E402
from glint_word2vec_tpu.ops import scan  # noqa: E402
from glint_word2vec_tpu.ops import subword as ops_sw  # noqa: E402

V, K, D, NUM = 3000, 500, 24, 5
LONG = "thequickbrownfoxjumpsoverit"          # more n-grams than the capacity


class World:
    """A seeded subword model and the reference's view of the same tables."""

    def __init__(self):
        self.strings = words.make_words(5, V)
        self.vocab = Vocabulary.from_words_and_counts(
            self.strings, np.arange(V, 0, -1).astype(np.int64) + 5)
        self.table = np.asarray(weights.rows_uniform(
            weights.seed32(9), 0, jnp.arange(V + K), D, D, 0.3))
        self.config = Word2VecConfig(
            vector_size=D, min_count=1, subword=True, subword_min_n=3,
            subword_max_n=6, subword_buckets=K)
        self.model = Word2VecModel(
            self.vocab, jnp.asarray(self.table[:V]), jnp.asarray(self.table[:V]),
            config=self.config, subword_buckets=jnp.asarray(self.table[V:]))
        self.lists = ref.bucket_lists(self.strings, K)
        self.composed = ref.composed_table(self.rows_fn, self.lists, V, block=1024)

    def rows_fn(self, ids):
        return jnp.asarray(self.table)[ids]

    def want(self, query, num=NUM):
        """The reference's reply to one query: word ids and their scores."""
        if isinstance(query, str):
            wid = self.vocab.get(query)
            wid = None if wid < 0 else wid
            h = ref.vector(self.rows_fn, query, wid, V, K)
        else:
            wid, h = None, np.asarray(query, np.float32)
        scores = ref.cosine_scores(self.composed, h[None])[0]
        ids = ref.reply(scores, num, wid)
        return ids, scores[ids]

    def holds(self, query, reply, num=NUM):
        ids, scores = self.want(query, num)
        assert [self.vocab.get(w) for w, _ in reply] == ids
        np.testing.assert_allclose([s for _, s in reply], scores, rtol=0, atol=1e-5)


@pytest.fixture(scope="module")
def world():
    w = World()
    yield w
    w.model.stop()


@pytest.fixture
def spans():
    tracer = default_tracer()
    tracer.configure(True)
    tracer.clear()
    yield lambda name: [e for e in tracer.events() if e["name"] == name]
    tracer.configure(False)
    tracer.clear()


QUERIES = {
    "a_word": lambda w: w.strings[3],
    "a_rare_word": lambda w: w.strings[V - 1],
    "an_unseen_string": lambda w: "zzqx",
    "a_typo_of_a_word": lambda w: w.strings[100] + "q",
    "a_single_ngram": lambda w: "q",
    "a_vector": lambda w: np.asarray(w.composed[5]) * 3.0,
    "over_the_list_capacity": lambda w: LONG,
}


def test_the_reference_hasher_is_the_papers_loop_on_every_word(world):
    assert ref.hasher_mismatches(world.strings, world.lists, range(V), V, K) == 0
    np.testing.assert_allclose(np.asarray(world.model.syn0), np.asarray(world.composed),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_one_query_is_answered_as_the_reference_answers_it(world, kind):
    query = QUERIES[kind](world)
    if isinstance(query, str) and kind not in ("a_word", "a_rare_word"):
        assert world.vocab.get(query) < 0
    world.holds(query, world.model.find_synonyms(query, NUM))


def test_a_mixed_chunk_is_one_call_and_every_reply_the_references(world, spans):
    queries = [make(world) for _, make in sorted(QUERIES.items())]
    before = dict(world.model.query_counts)
    replies = world.model.find_synonyms_batch(queries, NUM)
    for query, reply in zip(queries, replies):
        world.holds(query, reply)
    # the word is left out of its own reply; an unseen string is no row
    assert world.strings[3] not in [w for w, _ in replies[sorted(QUERIES).index("a_word")]]
    fetch, = spans("serve.row_fetch")
    hashed, = spans("serve.ngram_hash")
    assert hashed["parent"] == fetch["id"] and hashed["args"] == {"strings": 4}
    n_single = len(data_sw.ngram_buckets("q", 3, 6, K))
    rows = sum(len(data_sw.ngram_buckets(q, 3, 6, K)) for q in
               ("zzqx", world.strings[100] + "q", "q"))
    assert n_single == 1
    assert fetch["args"] == {"ops": 3, "unseen": 3, "list_rows": rows, "overflow": 1}
    got = {k: world.model.query_counts[k] - before[k] for k in before}
    assert got == {"unseen": 3, "list_rows": rows, "overflow": 1}


@pytest.mark.parametrize("mix", ["words", "unseen", "mixed_with_a_vector_and_overflow"])
def test_the_two_halves_are_the_batch_call_bit_for_bit_on_a_subword_model(world, mix):
    """finish(begin(q, k)) == find_synonyms_batch(q, k), the second half on
    another thread, over words, strings the vocabulary lacks, a vector and a
    string over the list capacity; the counters count a batch once."""
    import threading
    queries = {"words": world.strings[3:9],
               "unseen": ["zzqx", world.strings[100] + "q", "q", world.strings[7]],
               "mixed_with_a_vector_and_overflow":
                   [make(world) for _, make in sorted(QUERIES.items())]}[mix]
    before = dict(world.model.query_counts)
    want = world.model.find_synonyms_batch(queries, NUM)
    once = {k: world.model.query_counts[k] - before[k] for k in before}
    pending = world.model.find_synonyms_begin(queries, NUM)
    out = []
    t = threading.Thread(
        target=lambda: out.append(world.model.find_synonyms_finish(pending)))
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and out == [want]
    assert {k: world.model.query_counts[k] - before[k] for k in before} == {
        k: 2 * n for k, n in once.items()}


@pytest.mark.parametrize("mix, ops", [
    (("a_word", "a_rare_word"), 1),
    (("a_word", "an_unseen_string", "a_single_ngram"), 2),
    (("an_unseen_string",), 2),
    (("a_word", "a_vector"), 2),
    (("a_word", "a_vector", "a_typo_of_a_word"), 3),
    (("over_the_list_capacity", "a_word"), 2),
], ids=["words", "words_and_lists", "lists", "words_and_vector", "all_three",
        "overflow_goes_as_a_vector"])
def test_device_operations_of_a_chunk_do_not_grow_with_its_queries(world, spans, mix, ops):
    """One put per host array the program is handed: the ids, the list block
    where the chunk holds an unseen string, the vector block where it holds a
    vector; the same for one of a kind as for twenty."""
    for times in (1, 20):
        world.model.find_synonyms_batch([QUERIES[k](world) for k in mix] * times, NUM)
    assert [e["args"]["ops"] for e in spans("serve.row_fetch")] == [ops, ops]
    assert len(spans("serve.scan_enqueue")) == 2


def test_eight_mixed_batch_sizes_of_one_tile_share_one_program(world, monkeypatch):
    """On a TPU a chunk is handed over in whole tiles of 8 query rows, the list
    block with it: sizes 9 to 16, each with unseen strings, are one program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = [world.strings[i] for i in range(40, 56)]
    before = scan._gather_topk_batch._cache_size()
    for size in range(9, 17):
        queries = base[:size - 2] + ["zzqx", world.strings[size] + "x"]
        for query, reply in zip(queries, world.model.find_synonyms_batch(queries, NUM)):
            world.holds(query, reply)
    assert scan._gather_topk_batch._cache_size() == before + 1


def test_the_list_capacity_is_derived_and_a_longer_list_goes_round(world):
    longest = max(map(len, world.strings))
    cap = data_sw.list_capacity(longest, 3, 6)
    assert world.model._list_cap == cap and cap % data_sw.GROUP == 0
    # one character more than the longest word (a misspelling inserts one) fits
    assert len(data_sw.ngram_buckets("x" * (longest + 1), 3, 6, K)) <= cap
    assert len(data_sw.ngram_buckets(LONG, 3, 6, K)) > cap
    lists, over = data_sw.ngram_lists(["zzqx", LONG, "q", ""], 3, 6, K, cap)
    assert over == [1] and lists.shape == (4, cap) and lists.dtype == np.int32
    for row, string in zip(lists, ("zzqx", None, "q", "")):
        live = row[row != data_sw.NO_ROW].tolist()
        assert live == ([] if string is None else data_sw.ngram_buckets(string, 3, 6, K))
    # at the published vocabulary's longest word (11 characters): 48 slots
    assert data_sw.list_capacity(11, 3, 6) == 48


def test_a_string_without_an_ngram_scores_zero_everywhere(world):
    reply = world.model.find_synonyms("", NUM)
    assert len(reply) == NUM and all(score == 0.0 for _, score in reply)


def test_a_model_without_buckets_still_refuses_an_unseen_string(world):
    plain = Word2VecModel(world.vocab, jnp.asarray(world.table[:V]))
    assert not plain.composes_unseen and world.model.composes_unseen
    with pytest.raises(KeyError):
        plain.find_synonyms_batch([world.strings[3], "zzqx"], NUM)
    plain.stop()


@pytest.mark.parametrize("subword", [True, False], ids=["subword_model", "plain_model"])
def test_the_service_answers_or_fails_an_unseen_string_per_caller(world, subword):
    """Through ``EmbeddingService``: a subword model's unseen string is passed
    on and answered; on any other model it fails ITS caller and the word
    beside it in the batch is answered all the same."""
    from glint_word2vec_tpu.serve.service import EmbeddingService
    model = (Word2VecModel(world.vocab, jnp.asarray(world.table[:V]),
                           config=world.config,
                           subword_buckets=jnp.asarray(world.table[V:]))
             if subword else Word2VecModel(world.vocab, jnp.asarray(world.table[:V])))
    service = EmbeddingService(model=model, ann=False)
    try:
        tickets = [service.synonyms_async(q, NUM) for q in (world.strings[7], "zzqx")]
        word_reply = service.wait_result(tickets[0])
        assert len(word_reply) == NUM
        if subword:
            world.holds(world.strings[7], word_reply)
            world.holds("zzqx", service.wait_result(tickets[1]))
            np.testing.assert_allclose(
                service.vector("zzqx"), ref.vector(world.rows_fn, "zzqx", None, V, K),
                rtol=1e-5, atol=1e-7)
        else:
            with pytest.raises(KeyError):
                service.wait_result(tickets[1])
    finally:
        service.close()
        model.stop()


def test_the_approximate_arm_answers_an_unseen_string_too(world):
    from glint_word2vec_tpu.serve.ann import build_ivf
    world.model.attach_ann(build_ivf(np.asarray(world.model.syn0)))
    try:
        reply, = world.model.find_synonyms_batch(["zzqx"], NUM, ann=True, nprobe=10**6)
    finally:
        world.model.attach_ann(None)
    assert len(reply) == NUM and world.want("zzqx")[0][0] == world.vocab.get(reply[0][0])


def _row_table(strings):
    """The vocabulary's row table and, on the device, the step's form of it."""
    rows = data_sw.build_subword_table(strings, 3, 6, K)
    return rows, ops_sw.SubwordTable(jnp.asarray(rows.offsets), jnp.asarray(rows.rows),
                                     jnp.asarray(rows.counts))


def _parents_compose(table, tab, num_words, max_groups):
    """``compose_vectors`` as it was before PR 40: one gather from the
    concatenated [V + K, D] table, the list summed in slot order."""
    listed, inv = ops_sw._lists(jnp.arange(num_words, dtype=jnp.int32), tab, max_groups)
    return (jnp.asarray(table).at[listed].get(mode="fill", fill_value=0)
            .astype(jnp.float32).sum(axis=1) * inv[:, None])


@pytest.mark.parametrize("block", [1 << 13, 1024, 999], ids=["one_block", "whole", "ragged"])
def test_composing_from_the_two_tables_is_the_parents_on_a_concatenated_one(world, block):
    """To a float32 bound: the words' own row is added after the bucket rows'
    sum, where the parent summed the list in slot order (own row first): the
    same terms in another order, 2 units in the last place of a mean."""
    rows, tab = _row_table(world.strings)
    got = ops_sw.compose_vectors(
        jnp.asarray(world.table[:V]), ops_sw.lane_padded(jnp.asarray(world.table[V:])),
        tab, rows.max_groups, block)
    want = _parents_compose(world.table, tab, V, rows.max_groups)
    assert got.shape == (V, D) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2.5e-7)
    if block == 1 << 13:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(world.model.syn0))


def test_the_composed_tables_build_makes_no_array_of_v_plus_k_rows(world):
    """A shape walk over the jaxpr of one block's program: nothing in it has
    V + K rows (the parent concatenated the two tables first), and the only
    arrays of V or K rows are the operands and the result written in place."""
    rows, tab = _row_table(world.strings)
    closed = jax.make_jaxpr(
        lambda out, raw0, buckets, tab, lo: ops_sw._compose_block(
            out, raw0, buckets, tab, lo, max_groups=rows.max_groups, block=512))(
        jnp.zeros((V, D)), jnp.asarray(world.table[:V]),
        ops_sw.lane_padded(jnp.asarray(world.table[V:])), tab, jnp.int32(0))

    def made(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield eqn.primitive.name, tuple(getattr(var.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from made(sub)

    seen = list(made(closed.jaxpr))
    assert seen and not [s for _, s in seen if s and s[0] >= V + K]
    # of V or K rows: the result, updated in place (and the call that wraps it)
    big = {(name, s) for name, s in seen if len(s) == 2 and s[0] in (V, K)}
    assert {s for _, s in big} == {(V, D)}
    assert {name for name, _ in big} - {"jit", "pjit"} == {"dynamic_update_slice"}


def test_the_models_bucket_rows_are_kept_at_whole_lanes_and_saved_as_trained(world, tmp_path):
    model = world.model
    assert model._buckets.shape == (K, 128) and model.subword_buckets.shape == (K, D)
    assert not np.asarray(model._buckets[:, D:]).any()
    assert model.compose_time > 0
    # handed over already at whole lanes (a trainer's own): taken as it is
    lanes = ops_sw.lane_padded(jnp.asarray(world.table[V:]))
    again = Word2VecModel(world.vocab, jnp.asarray(world.table[:V]),
                          config=world.config, subword_buckets=lanes)
    assert again._buckets is lanes
    np.testing.assert_array_equal(np.asarray(again.syn0), np.asarray(model.syn0))
    path = str(tmp_path / "model")
    again.save(path)
    loaded = Word2VecModel.load(path)
    np.testing.assert_array_equal(np.asarray(loaded.subword_buckets), world.table[V:])
    np.testing.assert_array_equal(np.asarray(loaded._raw0), world.table[:V])
    world.holds("zzqx", loaded.find_synonyms("zzqx", NUM))
    with pytest.raises(ValueError, match="wide"):
        Word2VecModel(world.vocab, jnp.asarray(world.table[:V]), config=world.config,
                      subword_buckets=jnp.zeros((K, D + 1)))
    again.stop()
    loaded.stop()


def test_the_estimators_model_answers_an_unseen_string():
    """After a fit, through the public estimator: the trainer's row table is
    handed to the model, and a string the corpus never held has neighbours
    that share its n-grams."""
    from glint_word2vec_tpu.models.estimator import Word2Vec
    rng = np.random.default_rng(5)
    topics = (["walk", "walked", "walking", "walker"], ["blue", "bluer", "bluest", "blueish"])
    sentences = [[str(w) for w in rng.choice(topics[i % 2], 12)] for i in range(400)]
    model = Word2Vec(vector_size=24, window=3, negatives=5, min_count=1,
                     pairs_per_batch=128, negative_pool=16, learning_rate=0.02,
                     num_iterations=3, seed=1, subsample_ratio=0.0,
                     steps_per_dispatch=2, heartbeat_every_steps=4, subword=True,
                     subword_buckets=500).fit(sentences)
    assert "walks" not in model.vocab
    best = model.find_synonyms("walks", 4)
    assert {w for w, _ in best} == set(topics[0])
    assert model.query_counts["unseen"] == 1 and model.query_counts["overflow"] == 0
    model.stop()


# the lowered text of the scan's program at [1733, 16], k = 11, as the parent
# of PR 40 (5c308ff) lowers it: sha256, first 16 hex digits
PARENT_SCAN_TEXT = {(8, False): "cfeecdbc07d91c48", (1, False): "bdfccc833f1793fe",
                    (8, True): "470de86217bd1461"}


@pytest.mark.parametrize("queries, vectors", sorted(PARENT_SCAN_TEXT))
def test_a_chunk_without_lists_lowers_to_the_parents_text(queries, vectors):
    """A batch that holds no unseen string runs the program it ran before there
    were lists: no operand, no instruction more (sgns-3m-300.query-closed64)."""
    import hashlib
    spec = jax.ShapeDtypeStruct
    text = scan._gather_topk_batch.lower(
        spec((1733, 16), jnp.float32), spec((1733,), jnp.float32),
        spec((queries,), jnp.int32),
        spec((queries, 16), jnp.float32) if vectors else None, 11, 1733, False).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_SCAN_TEXT[queries, vectors]
    mixed = scan._gather_topk_batch.lower(
        spec((1733, 16), jnp.float32), spec((1733,), jnp.float32),
        spec((queries,), jnp.int32), None, 11, 1733, False,
        spec((500, 128), jnp.float32), spec((queries, 32), jnp.int32)).as_text()
    assert mixed != text and "scan.compose" not in text
