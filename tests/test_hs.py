"""Skip-gram under hierarchical softmax (config.loss="hs") on the CPU at a small
size.

The tree against an independent heap construction on seeded counts with and
without ties, and word for word against the plain reference's own loop; the
step against ``hs_ref``'s ``jax.grad`` updates in float32 on the pair feed's
own batches, through every form and every capacity it can overflow, both
twins; the rule for a node many pairs share at M infinite and finite; a fit
through the estimator, save, load and resume; the span and the heartbeat's
args; every refusal.
"""

import heapq
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from harness import zipf  # noqa: E402
from reference import hs_ref  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data.huffman import (  # noqa: E402
    build_path_table, decode_path, huffman_parents)
from glint_word2vec_tpu.data.subword import GROUP, NO_ROW  # noqa: E402
from glint_word2vec_tpu.ops import hs  # noqa: E402
from glint_word2vec_tpu.ops.hs import HsShape, hs_step_core  # noqa: E402
from glint_word2vec_tpu.ops.sgns import EmbeddingPair  # noqa: E402
from glint_word2vec_tpu.ops.subword import SubwordTable  # noqa: E402


# -- the tree ----------------------------------------------------------------------


def _counts(kind: str, v: int) -> np.ndarray:
    rng = np.random.default_rng(v)
    if kind == "zipf":          # the benchmark's: a long tail tied at the floor
        return zipf.zipf_counts(v).astype(np.int64)
    if kind == "distinct":
        return np.sort(rng.choice(10 * v, v, replace=False) + 1)[::-1]
    if kind == "all_tied":
        return np.full(v, 7, np.int64)
    return np.sort(rng.integers(1, 12, v))[::-1]            # "many_ties"


def _heap_cost(counts) -> int:
    """Σ count · code length of AN optimal prefix code (a heap's)."""
    heap = [int(c) for c in counts]
    heapq.heapify(heap)
    total = 0
    while len(heap) > 1:
        merged = heapq.heappop(heap) + heapq.heappop(heap)
        total += merged
        heapq.heappush(heap, merged)
    return total


@pytest.mark.parametrize("v", [2, 3, 6, 257, 3000])
@pytest.mark.parametrize("kind", ["zipf", "distinct", "many_ties", "all_tied"])
def test_tree_is_optimal_prefix_free_and_the_references(kind, v):
    counts = _counts(kind, v)
    table = build_path_table(counts)
    lengths = table.counts[:v].astype(np.int64)
    assert table.counts[v] == 0 and table.slots == lengths.sum()
    assert int((counts * lengths).sum()) == _heap_cost(counts)
    # Kraft: a full binary tree's code lengths sum to exactly one
    assert sum(2 ** int(lengths.max() - n) for n in lengths) == 2 ** int(lengths.max())
    tree = hs_ref.create_binary_tree(counts)
    codes = set()
    for w in range(v):
        points, bits = decode_path(table.rows_of(w))
        want_points, want_bits = hs_ref.word_path(tree, w)
        assert points.tolist() == want_points and bits.tolist() == want_bits
        assert points[0] == v - 2 and ((0 <= points) & (points < v - 1)).all()
        codes.add("".join(map(str, bits)))
    assert len(codes) == v
    if v <= 257:        # prefix-free, pair by pair (Kraft's sum says it for all)
        assert not any(a != b and b.startswith(a) for a in codes for b in codes)
    # the table's padding is out of bounds of any table, in whole groups
    assert table.max_groups == -(-int(lengths.max()) // GROUP)
    assert (table.rows.reshape(-1) == NO_ROW).sum() == table.rows.size - table.slots


def test_tree_of_six_words_by_hand():
    # 9 7 5 5 2 1: (1, 2) -> 3; (3, 5) -> 8; (5, 7) -> 12; (8, 9) -> 17; (12, 17)
    parent, binary = huffman_parents(np.array([9, 7, 5, 5, 2, 1]))
    assert parent.tolist()[:10] == [9, 8, 8, 7, 6, 6, 7, 9, 10, 10]
    assert binary.tolist()[:10] == [1, 1, 0, 1, 1, 0, 0, 0, 0, 1]
    table = build_path_table(np.array([9, 7, 5, 5, 2, 1]))
    paths = [tuple(map(list, map(np.ndarray.tolist, decode_path(table.rows_of(w)))))
             for w in range(6)]
    assert paths == [([4, 3], [1, 1]), ([4, 2], [0, 1]), ([4, 2], [0, 0]),
                     ([4, 3, 1], [1, 0, 1]), ([4, 3, 1, 0], [1, 0, 0, 1]),
                     ([4, 3, 1, 0], [1, 0, 0, 0])]


def test_tree_refuses_counts_out_of_order_or_one_word():
    with pytest.raises(ValueError, match="sorted descending"):
        build_path_table(np.array([3, 5, 1]))
    with pytest.raises(ValueError, match="two words"):
        build_path_table(np.array([3]))


# -- the step ----------------------------------------------------------------------

V, D, B, STEPS = 3000, 24, 2048, 3


def _feed_case():
    """A small vocabulary with Zipf counts, its path table, the pair feed's
    own first batches over a seeded corpus, and the reference's paths."""
    from glint_word2vec_tpu.data.pipeline import epoch_batches
    from glint_word2vec_tpu.data.vocab import Vocabulary

    counts = zipf.zipf_counts(V).astype(np.int64)
    vocab = Vocabulary.from_words_and_counts(zipf.words_of(V), counts)
    tokens = zipf.draw(np.random.default_rng(3), V, 60_000)
    sentences = [tokens[i:i + 40] for i in range(0, tokens.shape[0], 40)]
    centers, contexts = [], []
    for batch in epoch_batches(sentences, vocab, pairs_per_batch=B, window=5,
                               subsample_ratio=1e-3, seed=1, iteration=1):
        if batch.num_real_pairs == B:
            centers.append(np.array(batch.centers, np.int32))
            contexts.append(np.array(batch.contexts, np.int32))
        if len(centers) == STEPS:
            break
    table = build_path_table(counts)
    tree = hs_ref.create_binary_tree(counts)
    longest = table.max_groups * GROUP
    points = np.zeros((V, longest), np.int32)
    codes = np.zeros((V, longest), np.int32)
    for w in range(V):
        p, c = hs_ref.word_path(tree, w)
        points[w, :len(p)], codes[w, :len(c)] = p, c
    return table, np.stack(centers), np.stack(contexts), points, codes


CASE = {}


def _case():
    if not CASE:
        CASE["v"] = _feed_case()
    return CASE["v"]


def _device(table):
    return SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                        jnp.asarray(table.counts))


def _tables(seed=5):
    rng = np.random.default_rng(seed)
    return (rng.uniform(-0.3, 0.3, (V, D)).astype(np.float32),
            rng.uniform(-0.3, 0.3, (V, D)).astype(np.float32))


# (max_run, word_cap, slot_cap): every form and every capacity a batch can be
# over. A feed batch of 2,048 pairs holds ~560 pieces of 8 pairs and ~7,000
# live slots
FORMS = {
    "per_pair": (1, 0, 0),
    "per_word_piece": (8, 768, 768 * 16),
    "per_word_piece_cut": (8, 768, 8192),
    "pieces_of_2": (2, 1536, 1536 * 16),
    "over_word_cap": (8, 64, 8192),
    "over_slot_cap": (8, 768, 2048),
}


def _step(shape, with_metrics=True, compute_dtype=jnp.float32, real=B):
    table, centers, contexts, _, _ = _case()
    dev = _device(table)
    mask = jnp.asarray((np.arange(B) < real).astype(np.float32))
    fn = jax.jit(lambda p, c, x: hs_step_core(
        p, c, x, mask, jnp.float32(0.05), dev, shape, "exact", compute_dtype,
        with_metrics, center_runs=(10, 1024)))
    return fn, centers, contexts


def _assert_follows(got, want, start):
    """To 4e-6 of the largest element or move of the step (the root's row
    takes the sum of every pair's update, in another order: its elements are
    several units large, and a float32 unit there is 5e-7)."""
    got, want, start = (np.asarray(x) for x in (got, want, start))
    scale = max(1.0, float(np.abs(want).max()), float(np.abs(want - start).max()))
    assert float(np.abs(got - want).max()) <= 4e-6 * scale


def _reference(syn0, syn1, centers, contexts, real=B, max_node_pairs="the program's"):
    _, _, _, points, codes = _case()
    if max_node_pairs == "the program's":
        max_node_pairs = hs.MAX_NODE_PAIRS
    lengths = _case()[0].counts[contexts[:real]]
    return hs_ref.hs_step(
        jnp.asarray(syn0), jnp.asarray(syn1), jnp.asarray(centers[:real]),
        jnp.asarray(points[contexts[:real]]), jnp.asarray(codes[contexts[:real]]),
        jnp.asarray(lengths), 0.05, max_node_pairs)


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
@pytest.mark.parametrize("form", list(FORMS))
def test_step_follows_the_reference_on_feed_batches(form, with_metrics):
    table = _case()[0]
    shape = HsShape(table.max_groups, *FORMS[form])
    fn, centers, contexts = _step(shape, with_metrics)
    syn0, syn1 = _tables()
    params = EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1))
    want0, want1 = jnp.asarray(syn0), jnp.asarray(syn1)
    for s in range(STEPS):
        params, metrics = fn(params, jnp.asarray(centers[s]), jnp.asarray(contexts[s]))
        start0, start1 = want0, want1
        want0, want1, loss = _reference(want0, want1, centers[s], contexts[s])
        _assert_follows(params.syn0, want0, start0)
        _assert_follows(params.syn1, want1, start1)
        # both twins count the live (pair, node) terms exactly
        assert float(metrics.hs_nodes) == table.counts[contexts[s]].sum()
        assert float(metrics.pairs) == B
        if with_metrics:
            assert abs(float(metrics.loss) - loss) < 2e-6 * loss
        else:
            assert float(metrics.loss) == 0.0
        # which form ran: the rows syn1's scatter was handed
        live = float(metrics.syn1_rows)
        nodes = float(metrics.hs_nodes)
        if form == "per_pair" or form.startswith("over_"):
            assert live == nodes
        else:
            assert 0.15 * nodes < live < 0.6 * nodes


def test_a_capacity_between_two_batches_takes_each_form_once():
    """One program, two batches: the first inside the word capacity, the
    second (its contexts all different) over it."""
    table, centers, contexts, _, _ = _case()
    pieces = [len(np.unique(x)) for x in contexts]
    shape = HsShape(table.max_groups, 8, 1024, 1024 * 16)
    fn, _, _ = _step(shape)
    flat = np.arange(B, dtype=np.int32) % V                 # 2,048 distinct words
    assert max(pieces) < 1024 < len(np.unique(flat))
    syn0, syn1 = _tables()
    for x, per_pair in ((contexts[0], False), (flat, True)):
        params, metrics = fn(EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1)),
                             jnp.asarray(centers[0]), jnp.asarray(x))
        want0, want1, _ = _reference(syn0, syn1, centers[0], x)
        _assert_follows(params.syn0, want0, syn0)
        _assert_follows(params.syn1, want1, syn1)
        assert (float(metrics.syn1_rows) == float(metrics.hs_nodes)) == per_pair
    assert fn._cache_size() == 1


@pytest.mark.parametrize("form", ["per_pair", "per_word_piece_cut"])
def test_masked_pairs_move_nothing(form):
    table = _case()[0]
    real = B - 300
    fn, centers, contexts = _step(HsShape(table.max_groups, *FORMS[form]), real=real)
    syn0, syn1 = _tables()
    c, x = centers[0].copy(), contexts[0].copy()
    c[real:], x[real:] = 0, 0               # the feed's placeholder for a masked pair
    params, metrics = fn(EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1)),
                         jnp.asarray(c), jnp.asarray(x))
    want0, want1, loss = _reference(syn0, syn1, c, x, real=real)
    _assert_follows(params.syn0, want0, syn0)
    _assert_follows(params.syn1, want1, syn1)
    assert float(metrics.pairs) == real
    assert float(metrics.hs_nodes) == table.counts[x[:real]].sum()
    assert abs(float(metrics.loss) - loss) < 2e-6 * loss


@pytest.mark.parametrize("form", ["per_pair", "per_word_piece_cut", "pieces_of_2"])
@pytest.mark.parametrize("m", [None, 64, 700])
def test_the_rule_for_a_node_many_pairs_share(form, m, monkeypatch):
    """M infinite is the plain sum; a finite M scales node j's summed update
    by min(1, M / m_j) and leaves syn0's side alone."""
    monkeypatch.setattr(hs, "MAX_NODE_PAIRS", m)
    table = _case()[0]
    fn, centers, contexts = _step(HsShape(table.max_groups, *FORMS[form]))
    syn0, syn1 = _tables()
    params, _ = fn(EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1)),
                   jnp.asarray(centers[0]), jnp.asarray(contexts[0]))
    want0, want1, _ = _reference(syn0, syn1, centers[0], contexts[0], max_node_pairs=m)
    _assert_follows(params.syn0, want0, syn0)
    _assert_follows(params.syn1, want1, syn1)
    plain0, plain1, _ = _reference(syn0, syn1, centers[0], contexts[0],
                                   max_node_pairs=None)
    np.testing.assert_allclose(want0, plain0, atol=1e-7, rtol=0)    # never scaled
    root = V - 2
    moved = np.linalg.norm(np.asarray(want1)[root] - syn1[root])
    plain = np.linalg.norm(np.asarray(plain1)[root] - syn1[root])
    if m is None:
        assert moved == plain
    else:
        np.testing.assert_allclose(moved, plain * m / B, rtol=1e-4)  # the root: m_j = B


def test_bfloat16_compute_stays_near_the_reference():
    table = _case()[0]
    fn, centers, contexts = _step(HsShape(table.max_groups, *FORMS["per_word_piece_cut"]),
                                  compute_dtype=jnp.bfloat16)
    syn0, syn1 = _tables()
    params, metrics = fn(EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1)),
                         jnp.asarray(centers[0]), jnp.asarray(contexts[0]))
    want0, want1, loss = _reference(syn0, syn1, centers[0], contexts[0])
    for got, want, init in ((params.syn0, want0, syn0), (params.syn1, want1, syn1)):
        change = np.linalg.norm(np.asarray(want) - init)
        assert np.linalg.norm(np.asarray(got) - np.asarray(want)) < 0.02 * change
    assert abs(float(metrics.loss) - loss) < 1e-3 * loss


# -- the capacities ----------------------------------------------------------------


def test_capacities_hold_the_feed_batches_with_room():
    from glint_word2vec_tpu.train.trainer import _HS_MAX_RUN, _hs_caps
    table, _, contexts, _, _ = _case()
    counts = zipf.zipf_counts(V).astype(np.int64)
    word_cap, slot_cap = _hs_caps(counts, int(counts.sum()), 1e-3, 5, B,
                                  table.counts, table.max_groups * GROUP)
    for x in contexts:
        words, times = np.unique(x, return_counts=True)
        pieces = -(-times // _HS_MAX_RUN)
        assert pieces.sum() <= word_cap <= 1.6 * pieces.sum()
        live = (pieces * table.counts[words]).sum()
        assert live <= slot_cap <= 1.6 * live
    assert word_cap % (B // 32) == 0 and slot_cap <= word_cap * table.max_groups * GROUP
    # contexts that hardly repeat (a flat vocabulary under window 2, where a
    # kept token is in half a pair): nothing to build
    flat = np.full(50_000, 5, np.int64)
    assert _hs_caps(flat, int(flat.sum()), 1.0, 2, B,
                    build_path_table(flat).counts, 16) == (0, 0)


# -- through the trainer and the estimator -----------------------------------------

FIT = dict(vector_size=24, learning_rate=0.05, window=3, loss="hs", negatives=0,
           min_count=1, pairs_per_batch=128, seed=1, subsample_ratio=0.0,
           num_iterations=20, steps_per_dispatch=4, heartbeat_every_steps=8)


def _sentences():
    rng = np.random.default_rng(0)
    topics = (["a", "b", "c", "d"], ["x", "y", "z", "w"])
    return [[topics[i % 2][j] for j in rng.integers(0, 4, 12)] for i in range(800)]


@pytest.fixture(scope="module")
def fitted():
    from glint_word2vec_tpu.models.estimator import Word2Vec
    return Word2Vec(**FIT).fit(_sentences())


def test_a_fit_learns_the_two_topics(fitted):
    vectors = fitted.get_vectors()
    unit = {w: v / np.linalg.norm(v) for w, v in vectors.items()}
    within = np.mean([unit[a] @ unit[b] for a, b in (("a", "b"), ("c", "d"), ("x", "y"),
                                                      ("z", "w"), ("a", "d"))])
    across = np.mean([unit[a] @ unit[b] for a, b in (("a", "x"), ("b", "y"), ("c", "z"),
                                                      ("d", "w"))])
    assert within > 0.9 and within > across + 0.5, (within, across)
    assert [w for w, _ in fitted.find_synonyms("a", 3)] and set(
        w for w, _ in fitted.find_synonyms("a", 3)) <= {"b", "c", "d"}


def test_save_load_and_resume_carry_the_loss_and_rebuild_the_tree(fitted, tmp_path):
    from glint_word2vec_tpu.models.estimator import Word2Vec
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.train.trainer import Trainer
    path = str(tmp_path / "model")
    fitted.save(path)
    loaded = Word2VecModel.load(path)
    assert loaded.config.loss == "hs" and loaded.config.negatives == 0
    np.testing.assert_array_equal(loaded.syn0, fitted.syn0)
    assert loaded.find_synonyms("x", 2) == fitted.find_synonyms("x", 2)
    # no file holds the tree: a trainer over the loaded vocabulary builds it again
    assert not [f for f in os.listdir(path) if "tree" in f or "path" in f or "huff" in f]
    first = Trainer(fitted.config, fitted.vocab)
    again = Trainer(loaded.config, loaded.vocab)
    for a, b in zip(first._step_extra, again._step_extra):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert first._hs_shape == again._hs_shape
    ck = str(tmp_path / "ck")
    whole = Word2Vec(**FIT).fit(_sentences(), checkpoint_path=ck)
    resumed = Word2Vec.resume(ck, _sentences())     # finished: loads, trains no more
    np.testing.assert_array_equal(resumed.syn0, whole.syn0)


def test_trainer_builds_no_sampler_and_the_heartbeat_reports_the_paths(tmp_path):
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.obs.spans import default_tracer
    from glint_word2vec_tpu.train.trainer import Trainer

    sentences = _sentences()
    cfg = Word2VecConfig(**FIT, telemetry_path=str(tmp_path / "run.jsonl"))
    vocab = build_vocab(sentences, 1)
    trainer = Trainer(cfg, vocab)
    assert trainer.table is None and trainer._sampler_args == ()
    assert trainer.hs_tree_time > 0 and len(trainer._step_extra) == 3
    # the groups' count, a shape of the step's arguments, is a round number
    assert trainer._step_extra[1].shape[0] % (1 << 20) == 0
    built = [e for e in default_tracer().events() if e["name"] == "vocab.huffman_tree"]
    table = build_path_table(vocab.counts)
    assert built and built[-1]["args"] == dict(
        words=vocab.size, nodes=vocab.size - 1, slots=table.slots,
        max_code_len=int(table.counts.max()))
    trainer.fit(encode_sentences(sentences, vocab, 1000))
    blocks = [e["args"] for e in default_tracer().events() if e["name"] == "device_block"]
    assert blocks and all({"hs_path_nodes_per_pair", "syn1_rows_per_pair",
                           "syn0_rows_per_pair"} <= set(a) for a in blocks)
    longest, shortest = int(table.counts[:-1].max()), int(table.counts[:-1].min())
    assert all(shortest <= a["hs_path_nodes_per_pair"] <= longest for a in blocks)
    assert trainer._step_fn._cache_size() == 1 and trainer._step_fn_fast._cache_size() == 1
    assert trainer._step_fn is not trainer._step_fn_fast
    assert all(np.isfinite(h.loss) for h in trainer.heartbeats)


# -- what is refused ---------------------------------------------------------------


@pytest.mark.parametrize("beside, says", [
    (dict(negatives=5), "negatives=0"),
    (dict(negative_pool=64), "no negative pool"),
    (dict(cbow=True), "cbow"),
    (dict(cbow=True, cbow_update="banded"), "cbow"),
    (dict(subword=True), "subword"),
    (dict(device_pairgen=True), "device_pairgen"),
    (dict(step_lowering="shard_map"), "shard_map"),
    (dict(num_model_shards=4), "one device"),
    (dict(mesh_shape=(2, 1)), "one device"),
    (dict(duplicate_scaling=True), "duplicate_scaling"),
    (dict(sharded_checkpoint=True), "sharded_checkpoint"),
    (dict(fused_logits=True), "fused_logits"),
    (dict(bf16_chain=True, compute_dtype="bfloat16"), "bf16_chain"),
    (dict(max_row_norm=10.0), "max_row_norm"),
    (dict(row_l2=1e-4), "row_l2"),
    (dict(update_clip=0.5), "update_clip"),
    (dict(norm_watch="recover"), "recover"),
    (dict(loss="nce"), "'ns' or 'hs'"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_what_hs_is_refused_beside(beside, says):
    base = dict(loss="hs", negatives=0, pairs_per_batch=8192)
    cfg = Word2VecConfig(**base)
    assert cfg.loss == "hs" and cfg.negative_pool == 0
    assert cfg.replace(seed=7).negative_pool == 0
    assert Word2VecConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match=says):
        Word2VecConfig(**{**base, **beside})
    # without hs the same options are legal or refused for their own reasons
    if "loss" not in beside and beside != dict(negatives=5):
        try:
            Word2VecConfig(**{"pairs_per_batch": 8192, **beside})
        except ValueError as e:
            assert "hs" not in str(e)


def test_negative_sampling_still_needs_negatives():
    with pytest.raises(ValueError, match="negatives must be positive"):
        Word2VecConfig(negatives=0)


def test_a_plan_over_several_devices_is_refused_at_the_trainer():
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    vocab = build_vocab(_sentences(), 1)
    with pytest.raises(ValueError, match="one device"):
        Trainer(Word2VecConfig(**FIT), vocab, plan=make_mesh(1, 2))
