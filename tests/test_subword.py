"""Subword skip-gram (config.subword; fastText) on the CPU at a small size.

The n-gram function against hand-written cases and the vectorised row table
against the plain reference's loop; the step against ``subword_ref``'s
``jax.grad`` updates on the pair feed's own batches, through the per-word
branch, the per-run branch it overflows to and the plain fallback, both twins; the lowered step of a configuration
that is not subword against the parent's text; the model's composed vectors and
a string the vocabulary has never seen; save and load; every refusal.
"""

import hashlib
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

from harness import words as bench_words  # noqa: E402
from reference import subword_ref  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data import subword as data_subword  # noqa: E402
from glint_word2vec_tpu.data.subword import (  # noqa: E402
    GROUP, NO_ROW, build_subword_table, ngram_buckets)
from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core  # noqa: E402
from glint_word2vec_tpu.ops.subword import SubwordShape, SubwordTable  # noqa: E402

K = 50_000


# -- the n-gram function -----------------------------------------------------------


def test_the_by_the_papers_rule():
    """"<the>": every substring of 3 to 6 characters, the whole "<the>" among
    them (it is that short), by start then by length."""
    assert subword_ref.ngrams("the") == ["<th", "<the", "<the>", "the", "the>", "he>"]
    assert ngram_buckets("the", 3, 6, K) == [
        subword_ref.fnv1a(g.encode()) % K for g in subword_ref.ngrams("the")]
    # a word of one letter is one n-gram, "<a>"; seven letters give no whole word
    assert subword_ref.ngrams("a") == ["<a>"]
    assert "<letters>" not in subword_ref.ngrams("letters")
    assert len(subword_ref.ngrams("letters")) == 7 + 6 + 5 + 4


def test_hash_is_fnv1a_over_utf8_bytes_sign_extended():
    assert subword_ref.fnv1a(b"a") == data_subword.fnv1a(b"a") == 0xE40C292C
    # "é" is the bytes C3 A9: fastText xors int8_t(byte), i.e. 0xFFFFFFC3
    h = 2166136261
    for byte in (0xC3, 0xA9):
        h = ((h ^ (byte | 0xFFFFFF00)) * 16777619) & 0xFFFFFFFF
    plain = 2166136261
    for byte in (0xC3, 0xA9):
        plain = ((plain ^ byte) * 16777619) & 0xFFFFFFFF
    assert subword_ref.fnv1a("é".encode()) == data_subword.fnv1a("é".encode()) == h
    assert h != plain
    # characters are code points: "<hé" is three of them and four bytes
    grams = subword_ref.ngrams("héllo")
    assert grams[0] == "<hé" and len(grams[0].encode()) == 4
    assert ngram_buckets("héllo", 3, 6, K) == [
        subword_ref.fnv1a(g.encode()) % K for g in grams]


def test_a_bucket_two_ngrams_share_is_listed_twice():
    table = build_subword_table(["letters", "a"], 3, 6, 3)    # 3 buckets, 22 n-grams
    rows = table.rows_of(0)
    assert rows.shape[0] == 23 == table.counts[0] and rows[0] == 0
    assert len(set(rows.tolist())) <= 4
    assert rows.tolist() == subword_ref.word_rows("letters", 0, 2, 3)
    # padding slots are out of bounds of any table, and word V has no list
    group = table.rows[table.offsets[0]:table.offsets[1]].reshape(-1)
    assert (group[23:] == NO_ROW).all() and group.shape[0] == 24
    assert table.offsets[2] == table.offsets[3] and table.counts[2] == 0


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
def test_row_table_is_the_reference_loop_on_2000_seeded_words(native):
    vocab = bench_words.make_words(2**31 + 11, 1994) + [
        "héllo", "日本語", "naïve", "<>", "x" * 30, "ß"]
    v = len(vocab)
    table = build_subword_table(vocab, 3, 6, K, native=native)
    if native and data_subword._load_native() is None:
        pytest.skip("native/subword.cpp did not build here")
    assert table.max_groups == -(-int(table.counts.max()) // GROUP)
    for w, string in enumerate(vocab):
        assert table.rows_of(w).tolist() == subword_ref.word_rows(string, w, v, K), string
    assert table.slots == sum(1 + len(subword_ref.ngrams(s)) for s in vocab)


def test_seeded_words_are_distinct_and_the_same_again():
    first = bench_words.make_words(2**31 + 5, 30_000)
    assert first == bench_words.make_words(2**31 + 5, 30_000)
    assert first != bench_words.make_words(2**31 + 6, 30_000)
    assert len(set(first)) == 30_000 and all(w.isalpha() and w.islower() for w in first)
    lengths = np.array([len(w) for w in first])
    assert lengths[:6].max() <= 4 and lengths.max() <= 1 + 5 + 3   # short words first


# -- the step ----------------------------------------------------------------------

V, BUCKETS, D, B, P, NEG, STEPS = 3000, 2000, 24, 2048, 64, 5, 3


def _feed_case():
    """A small vocabulary of seeded strings with Zipf counts, its row table,
    and the native-or-NumPy pair feed's own first batches over a seeded corpus."""
    from harness import zipf

    from glint_word2vec_tpu.data.pipeline import epoch_batches
    from glint_word2vec_tpu.data.vocab import Vocabulary

    strings = bench_words.make_words(7, V)
    vocab = Vocabulary.from_words_and_counts(
        strings, zipf.zipf_counts(V).astype(np.int64))
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
    return strings, build_subword_table(strings, 3, 6, BUCKETS), \
        np.stack(centers), np.stack(contexts)


CASE = {}


def _case():
    if not CASE:
        CASE["v"] = _feed_case()
    return CASE["v"]


# (head_cap, word_run, word_cap). head_cap: roomy (a head's list once a center
# run), or too few heads for the batch (the plain form: every pair its own
# list). word_cap: not built, roomy (a word's list once a batch; with pieces of
# two heads a frequent word has several, each listing the same rows), or a few
# heads under a roomy head_cap (the per-run form)
BRANCHES = {"per_run": (1024, 1, 0), "over_head_cap": (16, 1, 0),
            "per_word": (1024, 8, 768), "per_word_pieces_of_2": (1024, 2, 768),
            "over_word_cap": (1024, 8, 16), "over_both_caps": (16, 8, 8)}


def _run_head_words(centers, max_run=10):
    """The word of every center run's head, a run cut every ``max_run``
    pairs, in NumPy."""
    at = np.arange(centers.shape[0])
    start = np.maximum.accumulate(
        np.where(np.diff(centers, prepend=-1) != 0, at, 0))
    return centers[(at - start) % max_run == 0]


def _word_pieces_of(centers, word_run):
    """The words of a batch's run heads and how many pieces of ``word_run``
    heads each makes."""
    words, count = np.unique(_run_head_words(centers), return_counts=True)
    return words, -(-count // word_run)


def _reference_lists(strings, centers, table):
    """Every pair's center as the reference sees it: the rows ITS n-gram
    function lists, [steps, B, longest] with the count."""
    lists = np.zeros(centers.shape + (table.max_groups * GROUP,), np.int32)
    nrows = np.zeros(centers.shape, np.int32)
    for k in range(centers.shape[0]):
        for i, w in enumerate(centers[k]):
            rows = subword_ref.word_rows(strings[w], w, V, BUCKETS)
            lists[k, i, :len(rows)], nrows[k, i] = rows, len(rows)
    return jnp.asarray(lists), jnp.asarray(nrows)


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
@pytest.mark.parametrize("branch", list(BRANCHES))
def test_step_follows_the_reference_on_feed_batches(branch, with_metrics):
    strings, table, centers, contexts = _case()
    rng = np.random.default_rng(17)
    syn0 = jnp.asarray(rng.uniform(-0.3, 0.3, (V + BUCKETS, D)), jnp.float32)
    syn1 = jnp.asarray(rng.uniform(-0.3, 0.3, (V, D)), jnp.float32)
    negatives = rng.integers(0, V, (STEPS, P)).astype(np.int32)
    head_cap, word_run, word_cap = BRANCHES[branch]
    shape = SubwordShape(table.max_groups, 10, head_cap, word_run, word_cap)
    dev = SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                       jnp.asarray(table.counts))
    heads = _run_head_words(centers[0])
    assert heads.shape[0] < B // 2                # the feed emits center runs
    words, pieces = _word_pieces_of(centers[0], word_run)
    assert words.shape[0] < 0.8 * heads.shape[0]  # and its centers repeat their words

    @jax.jit
    def step(params, dev, c, x, n):
        with jax.default_matmul_precision("highest"):
            return sgns_step_shared_core(
                params, c, x, jnp.ones(B, jnp.float32), n, jnp.float32(0.05), NEG,
                "exact", jnp.float32, with_metrics=with_metrics,
                context_runs=(6, B // 2), subword=(dev, shape))

    params, losses, handed = EmbeddingPair(syn0, syn1), [], []
    for k in range(STEPS):
        params, metrics = step(params, dev, centers[k], contexts[k], negatives[k])
        losses.append(float(metrics.loss))
        handed.append((float(metrics.syn0_rows), float(metrics.subword_rows)))

    lists, nrows = _reference_lists(strings, centers, table)
    ref = subword_ref.follow_steps(
        syn0, syn1, lists, nrows, jnp.asarray(contexts),
        jnp.asarray(negatives), [0.05] * STEPS, NEG, np.arange(V + BUCKETS) < V)
    # float32 on both sides, sums in another order
    np.testing.assert_allclose(params.syn0, ref["syn0"], rtol=2e-5, atol=2e-7)
    np.testing.assert_allclose(params.syn1, ref["syn1"], rtol=2e-5, atol=2e-7)
    assert not np.allclose(params.syn0[V:], syn0[V:])      # bucket rows moved
    if with_metrics:
        np.testing.assert_allclose(losses, ref["losses"], rtol=1e-6)
    # the counter says which branch ran: a word's list once a piece, a head's
    # once a run, or every pair's
    if branch.startswith("per_word"):
        assert 16 < pieces.sum() <= word_cap
        # a word with more heads than a piece holds lists its rows once a piece
        assert (pieces.max() > 1) == (word_run == 2)
        assert handed[0] == (pieces.sum(), (table.counts[words] * pieces).sum())
    elif branch in ("per_run", "over_word_cap"):
        assert handed[0] == (heads.shape[0], table.counts[heads].sum())
    else:
        assert handed[0] == (B, table.counts[centers[0]].sum())


def _live_slots(centers, table, word_run=8):
    """Live slots of the per-word block of one batch: a piece lists its
    word's rows once."""
    words, pieces = _word_pieces_of(centers, word_run)
    return int((table.counts[words] * pieces).sum())


def _slot_capacity(table, centers, kind):
    """A slot capacity of the per-word block [768, max_groups · 8] that every
    one of the three batches is ``under``, every one is ``over`` (the whole
    form), or that falls between two of them (``straddled``)."""
    live = sorted(_live_slots(c, table) for c in centers)
    assert live[0] < live[1]
    cap = {"under": live[2] + 64, "over": live[0] - 64,
           "straddled": (live[0] + live[1]) // 2}[kind]
    assert cap < 768 * table.max_groups * GROUP
    return cap


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("kind", ["under", "over", "straddled"])
def test_word_slot_capacity_gives_the_same_rows_and_sums(kind, dtype):
    """The per-word form's scatter under ``slot_cap`` (PR 47): the block's
    slots sorted by row and the first ``slot_cap`` of them scattered, a batch
    with more live ones whole. Against the reference in the tables' own dtype
    (a live slot the cut dropped, or a padding slot it kept, is a row's whole
    update: a hundred thousand times what float64 holds), and bit for bit
    against the form without a capacity: the cut is a prefix of the same
    sorted order, and the slots it leaves out were out of bounds."""
    strings, table, centers, contexts = _case()
    cap = _slot_capacity(table, centers, kind)
    rng = np.random.default_rng(17)
    init0 = rng.uniform(-0.3, 0.3, (V + BUCKETS, D))
    init1 = rng.uniform(-0.3, 0.3, (V, D))
    negatives = rng.integers(0, V, (STEPS, P)).astype(np.int32)
    # float64: 1 / |G| is still a float32 (plan_centers' default)
    tol = dict(rtol=2e-5, atol=2e-7) if dtype == "float32" else dict(rtol=1e-7, atol=2e-8)

    with jax.enable_x64(dtype == "float64"):
        dt = jnp.dtype(dtype)
        dev = SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                           jnp.asarray(table.counts))

        def run(slot_cap):
            shape = SubwordShape(table.max_groups, 10, 1024, 8, 768, slot_cap=slot_cap)

            @jax.jit
            def step(params, dev, c, x, n):
                with jax.default_matmul_precision("highest"):
                    return sgns_step_shared_core(
                        params, c, x, jnp.ones(B, jnp.float32), n, jnp.float32(0.05),
                        NEG, "exact", dt, context_runs=(6, B // 2),
                        subword=(dev, shape))

            params, slots = EmbeddingPair(jnp.asarray(init0, dt), jnp.asarray(init1, dt)), []
            for k in range(STEPS):
                params, metrics = step(params, dev, centers[k], contexts[k], negatives[k])
                assert float(metrics.syn0_rows) == _word_pieces_of(centers[k], 8)[1].sum()
                assert float(metrics.subword_rows) == _live_slots(centers[k], table)
                slots.append(float(metrics.subword_slots))
            return params, slots

        got, slots = run(cap)
        whole, whole_slots = run(0)
        s0, s1 = jnp.asarray(init0, dt), jnp.asarray(init1, dt)
        lists, nrows = _reference_lists(strings, centers, table)
        for k in range(STEPS):
            s0, s1, _ = subword_ref.subword_step(
                s0, s1, lists[k], nrows[k], jnp.asarray(contexts[k]),
                jnp.asarray(negatives[k]), jnp.float32(0.05), NEG)
        np.testing.assert_allclose(got.syn0, s0, **tol)
        np.testing.assert_allclose(got.syn1, s1, **tol)
        np.testing.assert_array_equal(np.asarray(got.syn0), np.asarray(whole.syn0))
        np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(whole.syn1))
    # the counter says what the scatter was handed: the capacity, or every
    # slot of the word heads' block (a batch over it; a shape without one)
    block = 768 * table.max_groups * GROUP
    fits = [_live_slots(c, table) <= cap for c in centers]
    assert sorted(set(fits)) == {"under": [True], "over": [False],
                                 "straddled": [False, True]}[kind]
    assert slots == [cap if f else block for f in fits]
    assert whole_slots == [block] * STEPS


# seeds of the benchmark's runs, a large one among them
@pytest.mark.parametrize("seed", [7, 2147483653])
def test_word_slot_cap_is_derived_from_the_counts_and_the_lists(seed):
    """The capacity of the per-word block's list scatter
    (train/trainer.py ``_word_slot_cap``): at ``subword-sgns-2.5m-300``'s
    counts, strings and resolved subsample every seed's strings give 34 units
    of 8,192 of the block's 491,520 slots, 1.1 to 1.3 times what the pair
    feed's batches hold; lists that fill their groups, a shape without a word
    level and a vocabulary subsampling keeps nothing of build none."""
    from harness import zipf

    from glint_word2vec_tpu.data.pipeline import epoch_batches
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.train.trainer import (
        _center_run_cap, _last_rung, _word_cap, _word_pieces_by_word, _word_slot_cap)

    v, b, window, ratio = 2_519_370, 65536, 5, 6.54e-4
    counts = zipf.zipf_counts(v).astype(np.int64)
    total = int(counts.sum())
    kept = (counts, total, ratio, window, b)
    strings = bench_words.make_words(seed, v)
    table = build_subword_table(strings, 3, 6, 2_000_000)
    word_cap = _word_cap(*kept, _last_rung(_center_run_cap(window, b)))
    slots = word_cap * table.max_groups * GROUP
    cap = _word_slot_cap(*kept, table.counts, slots)
    assert (word_cap, slots, cap) == (12288, 491_520, 34 * 8192)
    expected = float(_word_pieces_by_word(*kept) @ table.counts[:v])
    assert 1.15 * expected < cap < 1.25 * expected

    vocab = Vocabulary.from_words_and_counts(strings, counts)
    tokens = zipf.draw(np.random.default_rng(11), v, 1_200_000)
    held = []
    for batch in epoch_batches([tokens[i:i + 40] for i in range(0, tokens.shape[0], 40)],
                               vocab, pairs_per_batch=b, window=window,
                               subsample_ratio=ratio, seed=1, iteration=1):
        if batch.num_real_pairs == b:
            held.append(_live_slots(np.asarray(batch.centers), table))
        if len(held) == 3:
            break
    assert len(held) == 3 and all(1.1 * live < cap < 1.3 * live for live in held), held

    # a block of another size takes the same share of its slots
    for n in (4096, 16384):
        small_cap = _word_cap(counts, total, ratio, window, n,
                              _last_rung(_center_run_cap(window, n)))
        small = _word_slot_cap(counts, total, ratio, window, n, table.counts,
                               small_cap * 40)
        assert 0 < small < 0.8 * small_cap * 40
        assert small % (1 << ((small_cap * 40 // 32).bit_length() - 1)) == 0
    # lists that fill their groups: the cut saves under a fifth, not built
    assert _word_slot_cap(*kept, np.full(v + 1, 40, np.int32), slots) == 0
    assert _word_slot_cap(*kept, np.full(v + 1, 36, np.int32), slots) == 0
    assert _word_slot_cap(*kept, np.full(v + 1, 30, np.int32), slots) == 44 * 8192
    # no word level (a data axis, a flat vocabulary), or nothing kept: not built
    assert _word_slot_cap(*kept, table.counts, 0) == 0
    assert _word_slot_cap(np.zeros(8, np.int64), 0, 0.0, window, b,
                          np.ones(9, np.int32), 512) == 0


def test_slot_capacity_beside_a_word_capacity_is_the_per_word_forms_alone():
    """A batch over the word capacity takes the per-run form's plain scatter
    whole, with or without a slot capacity in the shape (the capacity is the
    per-word block's; the per-run form has one of its own only in a shape
    without a word level, a CBOW token block's), and the counter reads the
    run heads' block."""
    _, table, centers, contexts = _case()
    rng = np.random.default_rng(17)
    syn0 = jnp.asarray(rng.uniform(-0.3, 0.3, (V + BUCKETS, D)), jnp.float32)
    syn1 = jnp.asarray(rng.uniform(-0.3, 0.3, (V, D)), jnp.float32)
    negatives = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    dev = SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                       jnp.asarray(table.counts))
    out = {}
    for slot_cap in (0, 64):
        shape = SubwordShape(table.max_groups, 10, 1024, 8, 16, slot_cap=slot_cap)
        out[slot_cap] = jax.jit(lambda p, dev, c, x, n: sgns_step_shared_core(
            p, c, x, jnp.ones(B, jnp.float32), n, jnp.float32(0.05), NEG, "exact",
            jnp.float32, subword=(dev, shape)))(
            EmbeddingPair(syn0, syn1), dev, centers[0], contexts[0], negatives)
    (with_cap, m), (without, _) = out[64], out[0]
    np.testing.assert_array_equal(np.asarray(with_cap.syn0), np.asarray(without.syn0))
    assert float(m.subword_slots) == 1024 * table.max_groups * GROUP
    assert float(m.subword_rows) == table.counts[_run_head_words(centers[0])].sum()


@pytest.mark.parametrize("branch", ["per_run", "per_word"])
def test_masked_pairs_and_the_lane_padding_stay_zero(branch):
    """A batch whose tail is masked (centers 0, mask 0) moves nothing for it,
    and zero columns stay exactly zero."""
    strings, table, centers, contexts = _case()
    rng = np.random.default_rng(23)
    pad = 8
    syn0 = jnp.pad(jnp.asarray(rng.uniform(-0.3, 0.3, (V + BUCKETS, D)), jnp.float32),
                   ((0, 0), (0, pad)))
    syn1 = jnp.pad(jnp.asarray(rng.uniform(-0.3, 0.3, (V, D)), jnp.float32),
                   ((0, 0), (0, pad)))
    real = B // 3
    c = np.where(np.arange(B) < real, centers[0], 0).astype(np.int32)
    x = np.where(np.arange(B) < real, contexts[0], 0).astype(np.int32)
    mask = (np.arange(B) < real).astype(np.float32)
    dev = SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                       jnp.asarray(table.counts))
    head_cap, word_run, word_cap = BRANCHES[branch]
    shape = SubwordShape(table.max_groups, 10, head_cap, word_run, word_cap)
    negatives = jnp.asarray(rng.integers(0, V, P), jnp.int32)

    def run(c, x, mask):
        return sgns_step_shared_core(
            EmbeddingPair(syn0, syn1), jnp.asarray(c), jnp.asarray(x),
            jnp.asarray(mask), negatives, jnp.float32(0.05), NEG, "exact",
            jnp.bfloat16, logits_dtype=jnp.bfloat16, subword=(dev, shape))

    (got0, got1, _), metrics = run(c, x, mask)
    assert float(metrics.pairs) == real
    # the masked tail is one long run of word 0, cut into heads of its own
    assert float(metrics.syn0_rows) == (
        _word_pieces_of(c, word_run)[1].sum() if word_cap
        else _run_head_words(c).shape[0])
    assert not np.asarray(got0[:, D:]).any() and not np.asarray(got1[:, D:]).any()
    # rows that only the masked tail's word lists would touch did not move
    touched = np.unique(np.concatenate([table.rows_of(w) for w in np.unique(c[:real])]))
    still = np.setdiff1d(np.arange(V + BUCKETS), touched)
    np.testing.assert_array_equal(got0[still], syn0[still])


# -- a configuration that is not subword compiles the parent's step ---------------

# sha256[:16] of the lowered (StableHLO) text of both step twins of the
# `sgns-3m-300.train` cell at its `tiny` sizes, taken at the parent commit of
# PR 31 (4960b0f) and equal on PR 31's tree: the row source adds no op and no
# argument where the model is not subword. A later PR that changes the SGNS
# step on purpose takes new digests from its own tree (the failure prints them):
# PR 58 did (each coalesced scatter under a ladder of two caps and the counters
# of the slots it was handed; tests/test_cbow_subword.py holds the same two).
PARENT_STEP_TEXT = {"_step_fn": "8ce8095e605b956f", "_step_fn_fast": "2f1ac1ca7cdea1fb"}


@pytest.mark.parametrize("twin", list(PARENT_STEP_TEXT))
def test_step_of_a_plain_configuration_lowers_to_the_parents_text(twin):
    from harness import loader
    from kinds import train as train_kind

    from glint_word2vec_tpu.parallel.distributed import put_global

    cell = loader.resolve(loader.load_manifest(ROOT), "sgns-3m-300.train", ROOT)
    trainer, _, _ = train_kind.build_trainer(cell, 0, tiny=True)
    assert trainer._step_extra == () and trainer._subword_shape is None
    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    staged = put_global(trainer._chunk_shardings,
                        {"pairs": np.zeros((k, 2, b), trainer._pair_dtype)})
    meta, base = trainer._stage_dispatch_meta(np.zeros((2, k), np.float32), 0)
    text = getattr(trainer, twin).lower(
        trainer.params, staged, meta, base, trainer._table_prob,
        trainer._table_alias).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_STEP_TEXT[twin]


# -- the model ---------------------------------------------------------------------

FIT = dict(vector_size=24, window=3, negatives=5, min_count=1, pairs_per_batch=128,
           negative_pool=16, learning_rate=0.02, num_iterations=3, seed=1,
           subsample_ratio=0.0, steps_per_dispatch=2, heartbeat_every_steps=4,
           subword=True, subword_buckets=500)


def _sentences():
    rng = np.random.default_rng(5)
    topics = (["walk", "walked", "walking", "walker"], ["blue", "bluer", "bluest", "blueish"])
    return [[str(w) for w in rng.choice(topics[i % 2], 12)] for i in range(400)]


@pytest.fixture(scope="module")
def fitted():
    from glint_word2vec_tpu.models.estimator import Word2Vec
    return Word2Vec(**FIT).fit(_sentences())


def _trained_table(model):
    return np.concatenate([np.asarray(model._raw0), np.asarray(model.subword_buckets)])


def test_model_answers_with_composed_vectors(fitted):
    model = fitted
    v, k = model.vocab.size, FIT["subword_buckets"]
    assert model.subword_buckets.shape == (k, 24) and model.syn0.shape == (v, 24)
    table = _trained_table(model)
    assert np.abs(table[v:]).max() > 0                    # bucket rows trained
    for word in model.vocab.words:
        want = subword_ref.word_vector(table, word, model.vocab.get(word), v, k)
        np.testing.assert_allclose(model.transform(word), want, rtol=1e-5, atol=1e-7)
    # the scan is over the composed table: a word's nearest neighbours by it
    query = "walked"
    composed = np.stack([subword_ref.word_vector(table, w, i, v, k)
                         for i, w in enumerate(model.vocab.words)])
    unit = composed / np.linalg.norm(composed, axis=1, keepdims=True)
    cosines = unit @ unit[model.vocab.get(query)]
    best = [model.vocab.words[i] for i in np.argsort(-cosines)
            if model.vocab.words[i] != query][:3]
    assert [w for w, _ in model.find_synonyms(query, 3)] == best


def test_a_string_the_vocabulary_never_saw_has_a_vector(fitted):
    model = fitted
    v, k = model.vocab.size, FIT["subword_buckets"]
    assert "walks" not in model.vocab
    want = subword_ref.word_vector(_trained_table(model), "walks", None, v, k)
    np.testing.assert_allclose(model.transform("walks"), want, rtol=1e-5, atol=1e-7)
    assert np.abs(want).max() > 0
    # a model that is not subword still refuses it, as the reference does
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    plain = Word2VecModel(model.vocab, np.asarray(model._raw0))
    with pytest.raises(KeyError):
        plain.transform("walks")


def test_save_and_load_carry_the_bucket_rows(fitted, tmp_path):
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    path = str(tmp_path / "model")
    fitted.save(path)
    assert os.path.exists(os.path.join(path, "syn0_buckets.npy"))
    loaded = Word2VecModel.load(path)
    cfg = loaded.config
    assert (cfg.subword, cfg.subword_min_n, cfg.subword_max_n, cfg.subword_buckets) == (
        True, 3, 6, FIT["subword_buckets"])
    np.testing.assert_array_equal(loaded._raw0, fitted._raw0)
    np.testing.assert_array_equal(loaded._buckets, fitted._buckets)
    np.testing.assert_array_equal(loaded.syn0, fitted.syn0)
    np.testing.assert_array_equal(loaded.transform("bluenesses"),
                                  fitted.transform("bluenesses"))


def test_checkpoint_resume_restores_both_kinds_of_rows(tmp_path):
    from glint_word2vec_tpu.models.estimator import Word2Vec
    path = str(tmp_path / "ck")
    first = Word2Vec(**FIT).fit(_sentences(), checkpoint_path=path)
    again = Word2Vec.resume(path, _sentences())       # finished: loads, trains no more
    np.testing.assert_array_equal(again._raw0, first._raw0)
    np.testing.assert_array_equal(again._buckets, first._buckets)


def test_heartbeat_reports_the_rows_and_the_span_the_table(tmp_path):
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.obs.spans import default_tracer
    from glint_word2vec_tpu.train.trainer import Trainer

    sentences = _sentences()
    cfg = Word2VecConfig(**FIT, telemetry_path=str(tmp_path / "run.jsonl"))
    vocab = build_vocab(sentences, 1)
    trainer = Trainer(cfg, vocab)
    assert trainer.subword_table_time > 0 and len(trainer._step_extra) == 3
    # the groups' count, a shape of the step's arguments, is a round number
    assert trainer._step_extra[1].shape[0] % (1 << 20) == 0
    assert trainer.params.syn0.shape[0] >= vocab.size + FIT["subword_buckets"]
    assert trainer.params.syn1.shape[0] < trainer.params.syn0.shape[0]
    # the span is taken inside Trainer(); a fit's run_start clears the ring
    built = [e for e in default_tracer().events() if e["name"] == "vocab.subword_table"]
    assert built and built[-1]["args"]["words"] == vocab.size
    assert built[-1]["args"]["slots"] == sum(
        1 + len(subword_ref.ngrams(w)) for w in vocab.words)
    trainer.fit(encode_sentences(sentences, vocab, 1000))
    rows = [e["args"]["subword_rows_per_pair"] for e in default_tracer().events()
            if e["name"] == "device_block" and "subword_rows_per_pair" in e["args"]]
    assert rows and all(r > 1.0 for r in rows)


# -- what is refused ---------------------------------------------------------------


@pytest.mark.parametrize("beside, says", [
    (dict(cbow=True), "needs cbow_update='banded'"),    # the scatter forms have no lists
    (dict(negative_pool=0), "shared-pool"),
    (dict(pairs_per_batch=128), "shared-pool"),             # AUTO resolves the pool to 0
    (dict(step_lowering="shard_map"), "shard_map"),
    (dict(num_model_shards=4), "one device"),
    (dict(mesh_shape=(2, 1)), "one device"),
    (dict(device_pairgen=True), "device_pairgen"),
    (dict(duplicate_scaling=True), "duplicate_scaling"),
    (dict(sharded_checkpoint=True), "sharded_checkpoint"),
    (dict(max_row_norm=10.0), "max_row_norm"),
    (dict(row_l2=1e-4), "row_l2"),
    (dict(norm_watch="recover"), "recover"),
    (dict(subword_min_n=4, subword_max_n=3), "subword_min_n"),
    (dict(subword_buckets=0), "subword_buckets"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_what_subword_is_refused_beside(beside, says):
    base = dict(subword=True, pairs_per_batch=8192)
    assert Word2VecConfig(**base).subword
    with pytest.raises(ValueError, match=says):
        Word2VecConfig(**{**base, **beside})
    # the same options without subword are legal or refused for their own reasons
    if "subword_min_n" not in beside and "subword_buckets" not in beside:
        try:
            Word2VecConfig(**{"pairs_per_batch": 8192, **beside})
        except ValueError as e:
            assert "subword" not in str(e)


def test_a_plan_over_several_devices_is_refused_at_the_trainer():
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    if len(jax.devices()) < 2:
        pytest.skip("one device")
    with pytest.raises(ValueError, match="one device"):
        Trainer(Word2VecConfig(**FIT), build_vocab(_sentences(), 1), plan=make_mesh(1, 2))
