"""syn0's update coalesced by center run, syn1's by context run
(ops/sgns.scatter_add_by_runs).

The pair feed emits a center's pairs consecutively, so the shared-pool SGNS
step sums each run of equal neighbouring centers first and hands the scatter
one row a run, where a batch holds at most ``cap`` runs; a batch with more
takes the plain scatter, bit for bit. Contexts come in no order: the helper
sorts them inside the step (``sort=True``) and does the same with their runs
(the ``context_*`` cases). Every case runs in float32 and in the benchmark
cell's bfloat16 compute dtype (the tables stay float32).

Where the tables' rows lie over a model axis the step's forward gathers go by
the same runs (``gather_by_runs``, ``assemble_by_runs``: one gathered row a
piece, expanded by piece id): the same rows, so the step is the step without
it bit for bit, on one device and on a 1x4 mesh (the ``assembly_*`` cases).
"""

import os
import shutil
import sys
import tempfile
from dataclasses import replace as dc_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import epoch_batches
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    Stabilizers,
    gather_by_runs,
    plan_runs,
    run_positions,
    scatter_add_by_runs,
    sgns_step_shared_core,
)
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train import trainer as trainer_mod
from glint_word2vec_tpu.train.trainer import (
    _CONTEXT_MAX_RUN,
    _TOKEN_MAX_RUN,
    Trainer,
    _WORD_MAX_RUN,
    _center_run_cap,
    _context_run_cap,
    _last_rung,
    _slot_cap,
    _tail_cap,
    _token_run_caps,
    _word_cap,
    _word_pieces,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import sgns_ref  # noqa: E402

V, D, B, P, WINDOW = 2000, 24, 1024, 32, 5
MAX_RUN = 2 * WINDOW
# the ladder a trainer derives for syn0's scatter, and its last (roomy) rung:
# the one cap of the cases that came before the ladder
LADDER = _center_run_cap(WINDOW, B)
CAP = LADDER[-1]
ALPHA, NEG = 0.05, 5
COUNTS = np.maximum(1e6 / (np.arange(V) + 10.0) ** 1.07, 5.0).astype(np.int64)
# the context side's pair, as a trainer over this vocabulary derives it
CTX_RUN = _CONTEXT_MAX_RUN
CTX_LADDER = _context_run_cap(COUNTS, int(COUNTS.sum()), 0.0, WINDOW, B)
CTX_CAP = CTX_LADDER[-1]


def _vocab_and_sentences(seed=0, n_tokens=40_000):
    counts = COUNTS.astype(np.float64)
    vocab = Vocabulary.from_words_and_counts([f"w{i}" for i in range(V)], COUNTS)
    rng = np.random.default_rng(seed)
    toks = rng.choice(V, n_tokens, p=counts / counts.sum()).astype(np.int32)
    return vocab, [toks[i:i + 40] for i in range(0, n_tokens, 40)]


def feed_batches(steps=3):
    """Batches as the trainer gets them: the pair feed (native where it
    builds) over a Zipf corpus at window 5, never shuffled below sentences."""
    vocab, sents = _vocab_and_sentences()
    out = []
    for b in epoch_batches(sents, vocab, pairs_per_batch=B, window=WINDOW,
                           subsample_ratio=0.0, seed=1, iteration=1):
        out.append((b.centers.astype(np.int32), b.contexts.astype(np.int32)))
        if len(out) == steps:
            return out
    raise AssertionError("corpus too small")


def _tables(seed=5):
    rng = np.random.default_rng(seed)
    return EmbeddingPair(jnp.asarray(rng.uniform(-0.3, 0.3, (V, D)), jnp.float32),
                         jnp.asarray(rng.uniform(-0.3, 0.3, (V, D)), jnp.float32))


def _rows(dtype, seed=9):
    return jnp.asarray(np.random.default_rng(seed).normal(0, 0.01, (B, D)), dtype)


def _add_at(idx, rows):
    want = np.zeros((V, D), np.float64)
    np.add.at(want, np.asarray(idx), np.asarray(rows, np.float64))
    return want


def _helper_against_add_at(idx, dtype, expect_rows=None, by_context=False):
    """scatter_add_by_runs on a zero table against np.add.at in float64: by
    the runs ``idx`` comes in, or (``by_context``) by those its own sort makes."""
    idx = jnp.asarray(idx, jnp.int32)
    rows = _rows(dtype)
    runs = (CTX_RUN, CTX_CAP, True) if by_context else (MAX_RUN, CAP, False)
    got, handed, _ = jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5))(
        jnp.zeros((V, D), jnp.float32), idx, rows, *runs)
    want = _add_at(idx, rows)
    # float32 sums of up to B rows: to rounding of the largest entry
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6,
                               atol=2e-6 * np.abs(want).max())
    if expect_rows is not None:
        assert int(handed) == expect_rows
    return int(handed)


def _step(params, c, x, mask, dtype, runs, **kw):
    negs = jnp.asarray(np.random.default_rng(11).integers(0, V, P), jnp.int32)
    fn = jax.jit(lambda p: sgns_step_shared_core(
        p, jnp.asarray(c), jnp.asarray(x), jnp.asarray(mask, jnp.float32), negs,
        jnp.float32(ALPHA), NEG, "exact", dtype, logits_dtype=dtype,
        center_runs=runs, **kw))
    # strict bfloat16: by default XLA may keep a fused bfloat16 value in float32
    # ("excess precision"), and the two programs fuse differently, so their
    # update rows would differ by a rounding that neither step asks for
    strict = fn.lower(params).compile(
        compiler_options={"xla_allow_excess_precision": False})
    return strict(params)


def _both(c, x, mask, dtype, by_context=False, **kw):
    """The plain step against the step with syn0's update coalesced, or
    (``by_context``) with both tables' updates coalesced."""
    params = _tables()
    plain, m0 = _step(params, c, x, mask, dtype, None, **kw)
    if by_context:
        kw = dict(kw, context_runs=(CTX_RUN, CTX_CAP))
    runs, m1 = _step(params, c, x, mask, dtype, (MAX_RUN, CAP), **kw)
    return params, plain, m0, runs, m1


def _close_to_plain(params, plain, runs):
    """The coalesced update against the plain one: the same per-pair terms,
    float32 additions in another order. syn1 is not touched by the change."""
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(plain.syn1))
    want = np.asarray(plain.syn0) - np.asarray(params.syn0)
    got = np.asarray(runs.syn0) - np.asarray(params.syn0)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


# ---- the cases ------------------------------------------------------------------------

def case_native_feed_against_add_at(dtype):
    for c, _ in feed_batches():
        heads = 1 + int((c[1:] != c[:-1]).sum())
        assert heads < 0.4 * B          # the feed does emit runs
        handed = _helper_against_add_at(c, dtype)
        assert heads <= handed <= CAP   # forced heads may add a few


def case_native_feed_against_reference(dtype):
    """Three steps on feed batches against benchmark/reference/sgns_ref on the
    touched rows, as ``correct`` compares them: float32 to rounding, bfloat16
    compute by the change norms."""
    batches = feed_batches()
    rng = np.random.default_rng(11)
    negs = rng.integers(0, V, (len(batches), P)).astype(np.int32)
    params = _tables()
    init = params
    step = jax.jit(lambda p, c, x, n: sgns_step_shared_core(
        p, c, x, jnp.ones(B, jnp.float32), n, jnp.float32(ALPHA), NEG, "exact",
        dtype, logits_dtype=dtype, center_runs=(MAX_RUN, CAP)))
    with jax.default_matmul_precision("highest"):
        for (c, x), n in zip(batches, negs):
            params, metrics = step(params, jnp.asarray(c), jnp.asarray(x), jnp.asarray(n))
            assert float(metrics.syn0_rows) < 0.4 * B
    ref = sgns_ref.follow_steps(
        init.syn0, init.syn1, [jnp.asarray(c) for c, _ in batches],
        [jnp.asarray(x) for _, x in batches], jnp.asarray(negs),
        [ALPHA] * len(batches), NEG)
    got = (sgns_ref.leaf_norm(params.syn0 - init.syn0),
           sgns_ref.leaf_norm(params.syn1 - init.syn1))
    limit = 1e-5 if dtype == jnp.float32 else 5e-3
    for g, w in zip(got, ref["change_norm"]):
        assert abs(g - w) / w < limit


def case_native_feed_step_against_plain(dtype):
    for c, x in feed_batches():
        params, plain, m0, runs, m1 = _both(c, x, np.ones(B), dtype)
        _close_to_plain(params, plain, runs)
        assert float(m0.syn0_rows) == B and float(m1.syn0_rows) < 0.4 * B
        assert float(m0.loss) == float(m1.loss) and float(m1.pairs) == B


def case_runs_longer_than_max_run(dtype):
    # 25 pairs a center: every run is cut at 10 and 20, three heads a run
    idx = np.repeat(np.arange(100, 100 + B // 25 + 1), 25)[:B]
    pos = np.asarray(run_positions(jnp.asarray(idx, jnp.int32), MAX_RUN))
    assert pos.max() == MAX_RUN - 1
    _helper_against_add_at(idx, dtype, expect_rows=int((pos == 0).sum()))
    assert int((pos == 0).sum()) == 3 * (B // 25) + 3


def case_equal_neighbouring_words(dtype):
    # "a a b": the two a's pairs merge into one run of 8, b's stay apart
    idx = np.tile(np.repeat([7, 7, 9], 4), B // 12 + 1)[:B]
    _helper_against_add_at(idx, dtype)


def case_run_cut_by_batch_start_and_end(dtype):
    # the batch opens inside a run and closes inside another: both are summed
    # from what the batch holds, and nothing wraps from the end to the front
    idx = np.concatenate([np.full(3, 41), np.repeat(np.arange(200, 200 + 254), 4),
                          np.full(5, 41)])
    assert idx.shape[0] == B
    _helper_against_add_at(idx, dtype, expect_rows=256)


def case_masked_tail(dtype):
    c, x = feed_batches(1)[0]
    real = 700
    c, x = c.copy(), x.copy()
    c[real:], x[real:] = 0, 0             # the batcher pads with zeros
    mask = (np.arange(B) < real).astype(np.float32)
    params, plain, m0, runs, m1 = _both(c, x, mask, dtype)
    _close_to_plain(params, plain, runs)
    assert float(m1.pairs) == real
    # the tail is one run of row 0, cut every MAX_RUN, with zero updates
    assert float(m1.syn0_rows) < 0.4 * real + (B - real) / MAX_RUN + 2


def case_every_pair_masked(dtype):
    z = np.zeros(B, np.int32)
    params, plain, _, runs, m1 = _both(z, z, np.zeros(B), dtype)
    np.testing.assert_array_equal(np.asarray(runs.syn0), np.asarray(params.syn0))
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(params.syn1))
    assert float(m1.pairs) == 0 and float(m1.syn0_rows) == -(-B // MAX_RUN)


def case_all_rows_different_bit_equal(dtype):
    # the benchmark's check batches: a head on every row, the plain scatter taken
    rng = np.random.default_rng(3)
    c, x = rng.permutation(V)[:B], rng.permutation(V)[:B]
    _, plain, _, runs, m1 = _both(c.astype(np.int32), x.astype(np.int32),
                                     np.ones(B), dtype)
    np.testing.assert_array_equal(np.asarray(runs.syn0), np.asarray(plain.syn0))
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(plain.syn1))
    assert float(m1.syn0_rows) == B


def case_one_word_in_every_slot(dtype):
    idx = np.full(B, 17)
    _helper_against_add_at(idx, dtype, expect_rows=-(-B // MAX_RUN))


def case_heads_over_cap_bit_equal(dtype):
    # runs of two: B/2 heads > cap, so today's scatter runs, bit for bit
    idx = jnp.asarray(np.repeat(np.random.default_rng(4).permutation(V)[:B // 2], 2),
                      jnp.int32)
    assert B // 2 > CAP
    rows = _rows(dtype)
    table = _tables().syn0
    got, handed, slots = jax.jit(scatter_add_by_runs, static_argnums=(3, 4))(
        table, idx, rows, MAX_RUN, CAP)
    want = jax.jit(lambda t: t.at[idx].add(rows.astype(t.dtype)))(table)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(handed) == B == int(slots)


def case_update_clip_and_duplicate_scaling(dtype):
    # both act per pair, ahead of the run sum
    c, x = feed_batches(1)[0]
    for kw in (dict(stabilizers=Stabilizers(update_clip=0.002)),
               dict(duplicate_scaling=True),
               dict(stabilizers=Stabilizers(update_clip=0.002, max_row_norm=2.0),
                    duplicate_scaling=True)):
        params, plain, _, runs, m1 = _both(c, x, np.ones(B), dtype, **kw)
        got = np.asarray(runs.syn0) - np.asarray(params.syn0)
        want = np.asarray(plain.syn0) - np.asarray(params.syn0)
        assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)
        np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(plain.syn1))
        assert float(m1.syn0_rows) < 0.4 * B


# ---- syn1 by context run: the helper sorts inside the step ------------------------------

def _ctx_heads(x):
    """Heads the step counts in a batch's contexts: one per CTX_RUN of a word."""
    xs = jnp.sort(jnp.asarray(x, jnp.int32))
    return int((np.asarray(run_positions(xs, CTX_RUN)) == 0).sum())


def _both_tables_close(params, plain, runs, limit=1e-5):
    for got, want, start in zip(runs[:2], plain[:2], params[:2]):
        want = np.asarray(want) - np.asarray(start)
        got = np.asarray(got) - np.asarray(start)
        assert np.linalg.norm(got - want) <= limit * np.linalg.norm(want)


def case_context_feed_against_add_at(dtype):
    """The helper with its own sort on the feed's contexts, as they come."""
    for _, x in feed_batches():
        heads = _ctx_heads(x)
        assert len(np.unique(x)) <= heads <= CTX_CAP < 0.4 * B
        _helper_against_add_at(x, dtype, expect_rows=heads, by_context=True)


def case_context_feed_against_reference(dtype):
    """Three steps on feed batches with BOTH updates coalesced against
    benchmark/reference/sgns_ref: syn0's and syn1's change norms."""
    batches = feed_batches()
    negs = np.random.default_rng(11).integers(0, V, (len(batches), P)).astype(np.int32)
    params = init = _tables()
    step = jax.jit(lambda p, c, x, n: sgns_step_shared_core(
        p, c, x, jnp.ones(B, jnp.float32), n, jnp.float32(ALPHA), NEG, "exact",
        dtype, logits_dtype=dtype, center_runs=(MAX_RUN, CAP),
        context_runs=(CTX_RUN, CTX_CAP)))
    with jax.default_matmul_precision("highest"):
        for (c, x), n in zip(batches, negs):
            params, metrics = step(params, jnp.asarray(c), jnp.asarray(x), jnp.asarray(n))
            assert float(metrics.syn0_rows) < 0.4 * B
            assert float(metrics.syn1_rows) == _ctx_heads(x)
    ref = sgns_ref.follow_steps(
        init.syn0, init.syn1, [jnp.asarray(c) for c, _ in batches],
        [jnp.asarray(x) for _, x in batches], jnp.asarray(negs),
        [ALPHA] * len(batches), NEG)
    got = (sgns_ref.leaf_norm(params.syn0 - init.syn0),
           sgns_ref.leaf_norm(params.syn1 - init.syn1))
    limit = 1e-5 if dtype == jnp.float32 else 5e-3
    for g, w in zip(got, ref["change_norm"]):
        assert abs(g - w) / w < limit


def case_context_feed_step_against_plain(dtype):
    for c, x in feed_batches():
        params, plain, m0, runs, m1 = _both(c, x, np.ones(B), dtype, True)
        _both_tables_close(params, plain, runs)
        assert float(m0.syn1_rows) == B and float(m1.syn1_rows) == _ctx_heads(x)
        assert float(m0.loss) == float(m1.loss) and float(m1.pairs) == B


def case_context_runs_longer_than_max_run(dtype):
    # 25 pairs a context, scattered over the batch: sorted, every run is cut
    # into five pieces (6 + 6 + 6 + 6 + 1)
    words = np.arange(100, 100 + B // 25 + 1)
    x = np.random.default_rng(6).permutation(np.repeat(words, 25)[:B])
    pieces = sum(-(-int(n) // CTX_RUN) for n in np.bincount(x)[100:])
    assert pieces <= CTX_CAP
    _helper_against_add_at(x, dtype, expect_rows=pieces, by_context=True)


def case_context_masked_tail(dtype):
    c, x = feed_batches(1)[0]
    real = 700
    c, x = c.copy(), x.copy()
    c[real:], x[real:] = 0, 0
    mask = (np.arange(B) < real).astype(np.float32)
    params, plain, _, runs, m1 = _both(c, x, mask, dtype, True)
    _both_tables_close(params, plain, runs)
    assert float(m1.pairs) == real
    # the masked slots sort to the front as one run of row 0 with zero updates
    assert float(m1.syn1_rows) == _ctx_heads(x)
    assert _ctx_heads(x) >= -(-(B - real) // CTX_RUN)


def case_context_every_pair_masked(dtype):
    z = np.zeros(B, np.int32)
    params, _, _, runs, m1 = _both(z, z, np.zeros(B), dtype, True)
    np.testing.assert_array_equal(np.asarray(runs.syn0), np.asarray(params.syn0))
    # the pool rows' scatter adds zeros too: no pair is valid
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(params.syn1))
    assert float(m1.pairs) == 0 and float(m1.syn1_rows) == -(-B // CTX_RUN)


def case_context_all_rows_different_bit_equal(dtype):
    # the benchmark's check batches: both updates take their plain scatter
    rng = np.random.default_rng(3)
    c, x = rng.permutation(V)[:B], rng.permutation(V)[:B]
    _, plain, _, runs, m1 = _both(c.astype(np.int32), x.astype(np.int32),
                                     np.ones(B), dtype, True)
    np.testing.assert_array_equal(np.asarray(runs.syn0), np.asarray(plain.syn0))
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(plain.syn1))
    assert float(m1.syn0_rows) == B and float(m1.syn1_rows) == B


def case_context_heads_over_cap_bit_equal(dtype):
    # every context twice, far apart: B/2 heads > cap, the parent's scatter on
    # the parent's unsorted rows
    half = np.random.default_rng(4).permutation(V)[:B // 2]
    x = np.concatenate([half, half[::-1]]).astype(np.int32)
    c = feed_batches(1)[0][0]
    assert B // 2 > CTX_CAP
    _, plain, _, runs, m1 = _both(c, x, np.ones(B), dtype, True)
    np.testing.assert_array_equal(np.asarray(runs.syn1), np.asarray(plain.syn1))
    assert float(m1.syn1_rows) == B and float(m1.syn0_rows) < 0.4 * B


def case_context_update_clip_and_duplicate_scaling(dtype):
    # both act per pair, ahead of the sort and the run sums; the post-pass
    # and duplicate_scaling's counts read contexts, not the order
    c, x = feed_batches(1)[0]
    for kw in (dict(stabilizers=Stabilizers(update_clip=0.002)),
               dict(duplicate_scaling=True),
               dict(stabilizers=Stabilizers(update_clip=0.002, max_row_norm=2.0),
                    duplicate_scaling=True)):
        params, plain, _, runs, m1 = _both(c, x, np.ones(B), dtype, True, **kw)
        # float32 sums in another order, at the table's magnitude (rows of
        # norm ~1.5 against a change of 0.06): a few ulps of the row
        _both_tables_close(params, plain, runs, limit=3e-5)
        assert float(m1.syn1_rows) == _ctx_heads(x)


def _kept_against_add_at(x, dtype, cap):
    """The helper with ``keep`` (ops/cbow_banded.py ``token_runs``, syn1's
    side): the entries it leaves out carry zero rows by construction."""
    idx = jnp.asarray(x, jnp.int32)
    keep = jnp.asarray(np.random.default_rng(13).random(B) < 0.8)
    rows = _rows(dtype) * keep[:, None].astype(dtype)
    got, handed, _ = jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5))(
        jnp.zeros((V, D), jnp.float32), idx, rows, CTX_RUN, cap, True, keep)
    return idx, keep, rows, got, int(handed)


def case_kept_entries_against_add_at(dtype):
    _, x = feed_batches(1)[0]
    idx, keep, rows, got, handed = _kept_against_add_at(x, dtype, CTX_CAP)
    # the runs of the kept entries alone, fewer than the batch's
    assert handed == _ctx_heads(x[np.asarray(keep)]) < _ctx_heads(x)
    want = _add_at(idx, rows)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6,
                               atol=2e-6 * np.abs(want).max())


def case_kept_entries_over_cap_bit_equal(dtype):
    _, x = feed_batches(1)[0]
    heads = _ctx_heads(x[np.asarray(np.random.default_rng(13).random(B) < 0.8)])
    idx, keep, rows, got, handed = _kept_against_add_at(x, dtype, heads - 1)
    assert handed == B
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(jnp.zeros((V, D), jnp.float32).at[idx].add(rows.astype(jnp.float32))))


def case_nothing_kept_hands_over_nothing(dtype):
    idx = jnp.asarray(feed_batches(1)[0][1], jnp.int32)
    got, handed, _ = jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5))(
        jnp.ones((V, D), jnp.float32), idx, jnp.zeros((B, D), dtype), CTX_RUN,
        CTX_CAP, True, jnp.zeros(B, bool))
    assert int(handed) == 0 and (np.asarray(got) == 1.0).all()


def _fit_with_and_without(dtype, cap_fn, arg):
    """Through Trainer.fit: both twins coalesce, compile once, report the share
    on the heartbeat's device_block span as ``arg``, and train what the step
    trains with ``cap_fn`` (the trainer's derivation of that cap) giving 0."""
    vocab, sents = _vocab_and_sentences(n_tokens=30_000)
    cfg = Word2VecConfig(
        vector_size=D, window=WINDOW, negatives=NEG, min_count=1,
        compute_dtype=jnp.dtype(dtype).name, logits_dtype=jnp.dtype(dtype).name,
        pairs_per_batch=B, steps_per_dispatch=2, heartbeat_every_steps=4,
        negative_pool=P, subsample_ratio=0.0, num_iterations=1, seed=1)

    def fit(coalesce):
        # spans are recorded under run telemetry; each fit clears the ring
        run_dir = tempfile.mkdtemp(prefix="coalesce_")
        cap = getattr(trainer_mod, cap_fn)
        if not coalesce:
            setattr(trainer_mod, cap_fn, lambda *a: ())
        try:
            t = Trainer(dc_replace(cfg, telemetry_path=os.path.join(run_dir, "run.jsonl")),
                        vocab)
        finally:
            setattr(trainer_mod, cap_fn, cap)
        try:
            t.fit(sents)
            shares = [e["args"][arg] for e in t._tracer.events()
                      if e["name"] == "device_block" and e.get("args")]
        finally:
            shutil.rmtree(run_dir, ignore_errors=True)
        return t, shares

    start = jax.device_get(Trainer(cfg, vocab).params)
    (on, on_shares), (off, off_shares) = fit(True), fit(False)
    assert on._step_fn_fast is not on._step_fn
    assert on._step_fn._cache_size() == 1 and on._step_fn_fast._cache_size() == 1
    assert on.global_step == off.global_step and on.global_step >= 8
    assert on_shares and max(on_shares) < 0.4
    assert off_shares and min(off_shares) >= 1.0
    # bfloat16 on the CPU: XLA keeps a fused bfloat16 update row in float32 in
    # one program and rounds it in the other (see _step), a rounding apart
    limit = 1e-4 if dtype == jnp.float32 else 5e-3
    for a, b, s in zip(on.params[:2], off.params[:2], start[:2]):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= limit * np.linalg.norm(b - s)


def case_both_step_twins_one_program_each(dtype):
    _fit_with_and_without(dtype, "_center_run_cap", "syn0_rows_per_pair")


def case_context_both_step_twins_one_program_each(dtype):
    _fit_with_and_without(dtype, "_context_run_cap", "syn1_rows_per_pair")


# ---- the forward gathers by the same runs (gather_by_runs, assemble_by_runs) --------------

def _gathered(idx, dtype, sort, went_by_runs):
    """gather_by_runs of one table against ``mat[idx]``, bit for bit, by the
    runs ``idx`` comes in or (``sort``) by those the plan's sort makes."""
    idx = jnp.asarray(idx, jnp.int32)
    max_run, cap = (CTX_RUN, CTX_CAP) if sort else (MAX_RUN, CAP)
    table = _tables().syn1 if sort else _tables().syn0

    def both(mat):
        plan = plan_runs(idx, max_run, sort=sort)
        (got,), handed = gather_by_runs(((mat, idx, plan, cap),), dtype)
        return got, handed, mat[idx].astype(dtype)

    got, handed, want = jax.jit(both)(table)
    assert float(handed) == (cap if went_by_runs else B)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def case_gather_by_runs_against_indexing(dtype):
    for c, x in feed_batches():
        _gathered(c, dtype, False, True)
        _gathered(x, dtype, True, True)      # the pieces' ids back in feed order
    # pieces of a cut run gather their row again; a batch of one word
    _gathered(np.repeat(np.arange(100, 100 + B // 25 + 1), 25)[:B], dtype, False, True)
    _gathered(np.full(B, 17), dtype, True, True)
    # more pieces than the cap: the plain gather
    rng = np.random.default_rng(3)
    _gathered(rng.permutation(V)[:B], dtype, False, False)
    _gathered(rng.permutation(V)[:B], dtype, True, False)


def _assembled(c, x, mask, dtype, went_by_runs, model_axis=False, **kw):
    """The step with both updates coalesced against the same step with its
    forward gathers by the same runs: both tables, the loss and the pairs bit
    for bit; ``assembly_rows`` says which branch the batch took. On one
    device, or (``model_axis``) with the tables' rows over a 1x4 mesh, where
    the gathered rows are assembled by an all-reduce."""
    params = _tables()
    if model_axis:
        params = jax.device_put(params, make_mesh(1, 4).embedding)
    kw = dict(kw, context_runs=(CTX_RUN, CTX_CAP))
    want, m0 = _step(params, c, x, mask, dtype, (MAX_RUN, CAP), **kw)
    got, m1 = _step(params, c, x, mask, dtype, (MAX_RUN, CAP),
                    assemble_by_runs=True, **kw)
    np.testing.assert_array_equal(np.asarray(got.syn0), np.asarray(want.syn0))
    np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(want.syn1))
    assert float(m1.loss) == float(m0.loss) and float(m1.pairs) == float(m0.pairs)
    assert float(m1.mean_f_pos) == float(m0.mean_f_pos)
    assert (float(m1.syn0_rows), float(m1.syn1_rows)) == (
        float(m0.syn0_rows), float(m0.syn1_rows))
    assert m0.assembly_rows is None
    assert float(m1.assembly_rows) == (
        CAP + CTX_CAP + P if went_by_runs else 2 * B + P)
    return m1


def _assembly_batches():
    """(centers, contexts, mask, whether both tables fit their caps)."""
    (c, x), (c2, x2) = feed_batches(2)
    ones = np.ones(B, np.float32)
    yield c, x, ones, True
    yield c2, x2, ones, True
    # a masked tail: the batcher pads with zeros, one run of row 0
    real = 700
    ct, xt = c.copy(), x.copy()
    ct[real:], xt[real:] = 0, 0
    yield ct, xt, (np.arange(B) < real).astype(np.float32), True
    # runs longer than max_run on both sides: 25 pairs a center, and one
    # context in every 8th pair (cut every CTX_RUN of the sorted batch)
    long_c = np.repeat(np.arange(100, 100 + B // 25 + 1), 25)[:B].astype(np.int32)
    long_x = np.where(np.arange(B) % 8 == 0, 5, x).astype(np.int32)
    yield long_c, long_x, ones, True
    # over syn0's cap (runs of two), then over syn1's (contexts that all
    # differ): the plain gathers for both tables
    rng = np.random.default_rng(4)
    pairs_c = np.repeat(rng.permutation(V)[:B // 2], 2).astype(np.int32)
    assert B // 2 > CAP
    yield pairs_c, x, ones, False
    yield c, rng.permutation(V)[:B].astype(np.int32), ones, False


def case_assembly_by_runs_bit_equal(dtype):
    for c, x, mask, fits in _assembly_batches():
        _assembled(c, x, mask, dtype, fits)


def case_assembly_by_runs_on_a_model_axis_bit_equal(dtype):
    for c, x, mask, fits in _assembly_batches():
        _assembled(c, x, mask, dtype, fits, model_axis=True)


def case_assembly_by_runs_with_stabilizers_and_duplicate_scaling(dtype):
    # both read the per-pair rows after the gathers: nothing of them changes
    c, x = feed_batches(1)[0]
    for kw in (dict(stabilizers=Stabilizers(update_clip=0.002, max_row_norm=2.0)),
               dict(duplicate_scaling=True)):
        _assembled(c, x, np.ones(B, np.float32), dtype, True, **kw)


def case_assembly_needs_both_runs(dtype):
    c, x = feed_batches(1)[0]
    for runs, kw in ((None, dict(context_runs=(CTX_RUN, CTX_CAP))),
                     ((MAX_RUN, CAP), {})):
        with pytest.raises(ValueError, match="needs center_runs and context_runs"):
            _step(_tables(), c, x, np.ones(B), dtype, runs,
                  assemble_by_runs=True, **kw)


# ---- a ladder of caps: the step takes the first rung that holds its batch -----------------

# (tight, roomy) and batches whose heads fall under the first rung, between
# the two, and over both (the plain scatter)
RUNGS = (200, 400)


def _ladder_batch(heads, sort):
    """B entries in ``heads`` pieces: runs of equal neighbours as they come,
    or (``sort``) the same words scattered over the batch, where the helper's
    own sort makes the runs (cut every CTX_RUN)."""
    max_run = CTX_RUN if sort else MAX_RUN
    words = np.sort(np.random.default_rng(heads).permutation(V)[:heads])
    idx = words[np.arange(B) * heads // B]
    # one run a word, none longer than a piece
    assert -(-B // heads) <= max_run and len(np.unique(idx)) == heads
    return np.random.default_rng(1).permutation(idx) if sort else idx


def _ladder_against_add_at(dtype, sort, with_keep):
    for heads, slots in ((180, RUNGS[0]), (200, RUNGS[0]), (201, RUNGS[1]),
                         (300, RUNGS[1]), (400, RUNGS[1]), (401, B), (600, B)):
        idx = jnp.asarray(_ladder_batch(heads, sort), jnp.int32)
        rows = _rows(dtype)
        keep = None
        if with_keep:
            # whole words left out, so the kept entries' pieces are counted
            keep = jnp.asarray(np.asarray(idx) % 5 != 0)
            rows = rows * keep[:, None].astype(dtype)
        max_run = CTX_RUN if sort else MAX_RUN
        got, handed, took = jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5))(
            jnp.zeros((V, D), jnp.float32), idx, rows, max_run, RUNGS, sort, keep)
        want = _add_at(idx, rows)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-6,
                                   atol=2e-6 * np.abs(want).max())
        if with_keep:
            heads = len(np.unique(np.asarray(idx)[np.asarray(keep)]))
            slots = RUNGS[0] if heads <= RUNGS[0] else RUNGS[1] if heads <= RUNGS[1] else B
        # the live rows do not move with the rung; the slots are the rung's
        assert int(handed) == (heads if slots < B else B) and int(took) == slots


def case_ladder_runs_as_they_come_against_add_at(dtype):
    _ladder_against_add_at(dtype, False, False)


def case_ladder_sorted_against_add_at(dtype):
    _ladder_against_add_at(dtype, True, False)


def case_ladder_sorted_kept_entries_against_add_at(dtype):
    _ladder_against_add_at(dtype, True, True)


def case_ladder_rungs_hand_a_row_the_same_sums_bit_equal(dtype):
    """Whatever the padding behind them: the table under the ladder is the
    table under the rung's cap alone, and over both rungs the plain scatter's."""
    table = _tables().syn0
    rows = _rows(dtype)
    for heads, cap in ((180, RUNGS[0]), (300, RUNGS[1]), (600, RUNGS[1])):
        for sort in (False, True):
            idx = jnp.asarray(_ladder_batch(heads, sort), jnp.int32)
            max_run = CTX_RUN if sort else MAX_RUN
            fn = jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5))
            got = fn(table, idx, rows, max_run, RUNGS, sort)
            want = fn(table, idx, rows, max_run, cap, sort)
            np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
            assert float(got[1]) == float(want[1])
    # the benchmark's check batches (rows that all differ) take the LAST entry
    idx = jnp.asarray(np.random.default_rng(3).permutation(V)[:B], jnp.int32)
    got, handed, took = jax.jit(scatter_add_by_runs, static_argnums=(3, 4))(
        table, idx, rows, MAX_RUN, RUNGS)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(table.at[idx].add(rows.astype(table.dtype))))
    assert int(handed) == B == int(took)


def case_ladder_of_one_rung_is_the_ints_program(dtype):
    idx = jnp.asarray(feed_batches(1)[0][0], jnp.int32)
    args = (_tables().syn0, idx, _rows(dtype), MAX_RUN)
    for sort in (False, True):
        texts = {jax.jit(scatter_add_by_runs, static_argnums=(3, 4, 5)).lower(
            *args, cap, sort).as_text() for cap in (CAP, (CAP,))}
        assert len(texts) == 1
    two = jax.jit(scatter_add_by_runs, static_argnums=(3, 4)).lower(*args, RUNGS).as_text()
    # ONE flat switch of three entries, and one sort of the heads' places
    assert two.count("stablehlo.case") == 1 and two.count("stablehlo.sort") == 1
    assert len(texts | {two}) == 2


def case_ladder_gather_by_runs_against_indexing(dtype):
    """One switch for both tables: the batch takes the LARGER of their rungs,
    a shorter ladder repeats its last, and the rows are ``mat[idx]``'s."""
    tables = _tables()

    def both(mats, c, x, caps0, caps1):
        plan0, plan1 = plan_runs(c, MAX_RUN), plan_runs(x, CTX_RUN, sort=True)
        got, handed = gather_by_runs(
            ((mats[0], c, plan0, caps0), (mats[1], x, plan1, caps1)), dtype)
        return got, handed, (mats[0][c].astype(dtype), mats[1][x].astype(dtype))

    for heads0, heads1, caps0, caps1, crossed in (
            (180, 180, RUNGS, RUNGS, 2 * RUNGS[0]),
            (180, 300, RUNGS, RUNGS, 2 * RUNGS[1]),     # syn1's rung for both
            (300, 180, RUNGS, RUNGS, 2 * RUNGS[1]),
            (180, 600, RUNGS, RUNGS, 2 * B),            # over both: plain
            (180, 350, RUNGS, (350,), RUNGS[0] + 350),  # one rung beside two
            (300, 350, RUNGS, (350,), RUNGS[1] + 350),
            (300, 351, RUNGS, (350,), 2 * B),
            (180, 180, RUNGS[1], RUNGS[1], 2 * RUNGS[1])):     # ints
        c = jnp.asarray(_ladder_batch(heads0, False), jnp.int32)
        x = jnp.asarray(_ladder_batch(heads1, True), jnp.int32)
        got, handed, want = jax.jit(both, static_argnums=(3, 4))(
            tables[:2], c, x, caps0, caps1)
        assert float(handed) == crossed
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


def _ladder_step_against_roomy(dtype, model_axis=False, **kw):
    """The step under the trainer's ladders against the step under their last
    rungs alone (the parent's): tables, loss and counters bit for bit, and the
    slots say which rung each table's batch took."""
    params = _tables()
    if model_axis:
        params = jax.device_put(params, make_mesh(1, 4).embedding)
        kw = dict(kw, assemble_by_runs=True)
    assert len(LADDER) == 2 and len(CTX_LADDER) == 2
    slots = []
    for c, x, mask, _ in _assembly_batches():
        want, m0 = _step(params, c, x, mask, dtype, (MAX_RUN, CAP),
                         context_runs=(CTX_RUN, CTX_CAP), **kw)
        got, m1 = _step(params, c, x, mask, dtype, (MAX_RUN, LADDER),
                        context_runs=(CTX_RUN, CTX_LADDER), **kw)
        np.testing.assert_array_equal(np.asarray(got.syn0), np.asarray(want.syn0))
        np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(want.syn1))
        assert float(m1.loss) == float(m0.loss) and float(m1.pairs) == float(m0.pairs)
        rows = float(m1.syn0_rows), float(m1.syn1_rows)
        assert rows == (float(m0.syn0_rows), float(m0.syn1_rows))
        took = float(m1.syn0_slots), float(m1.syn1_slots)
        for live, slot, ladder in zip(rows, took, (LADDER, CTX_LADDER)):
            assert slot == next((cap for cap in ladder if live <= cap), B)
        # under one cap the counter reads that cap, or the batch
        assert float(m0.syn0_slots) == (CAP if rows[0] <= CAP else B)
        assert float(m0.syn1_slots) == (CTX_CAP if rows[1] <= CTX_CAP else B)
        if model_axis:
            rung = max(ladder.index(slot) if slot < B else 2
                       for slot, ladder in zip(took, (LADDER, CTX_LADDER)))
            assert float(m1.assembly_rows) == (
                (LADDER + (B,))[rung] + (CTX_LADDER + (B,))[rung] + P)
        slots.append(took)
    # the feed's batches take the tight rung of both tables; the batches made
    # to overflow take the roomy one or the plain scatter
    assert slots[0] == slots[1] == (LADDER[0], CTX_LADDER[0])
    assert {s[0] for s in slots} >= {LADDER[0], B} and (LADDER[0], B) in slots
    return slots


def case_ladder_step_is_the_roomy_steps_bit_equal(dtype):
    _ladder_step_against_roomy(dtype)


def case_ladder_step_on_a_model_axis_bit_equal(dtype):
    _ladder_step_against_roomy(dtype, model_axis=True)


def case_step_without_runs_hands_over_the_batch(dtype):
    c, x = feed_batches(1)[0]
    _, m = _step(_tables(), c, x, np.ones(B), dtype, None)
    assert float(m.syn0_slots) == B == float(m.syn1_slots)


CASES = {name[len("case_"):]: fn for name, fn in sorted(globals().items())
         if name.startswith("case_")}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_coalesced_update(case, dtype):
    CASES[case](dtype)


# (window, batch) -> (tight, roomy). The roomy rung is what the rule gave as
# its one cap before the ladder (0.25 runs a pair at window 5 with 40% of
# room, in eighths of the batch); the tight one the same expectation with 15%
# to the nearest 32nd (a power of two); () = a run of two pairs or fewer, or a
# batch of a few pairs: not built; one rung where the batch has no 32nds
@pytest.mark.parametrize("window, batch, ladder", [
    (5, 65536, (18432, 24576)),
    (5, 2048, (576, 768)),
    (5, B, (288, 384)),
    (1, 65536, ()),
    (2, 65536, ()),
    (3, 65536, (36864, 49152)),
    (10, 65536, (8192, 16384)),
    (5, 16, (6,)),
    (5, 4, ()),
])
def test_cap_is_derived_from_the_window(window, batch, ladder):
    assert _center_run_cap(window, batch) == ladder
    assert list(ladder) == sorted(set(ladder)) and all(c < batch for c in ladder)


def test_word_cap_is_derived_from_the_counts():
    """The subword row source's second level (ops/subword.py): at
    ``subword-sgns-2.5m-300``'s counts and resolved subsample the estimate is
    within 10% of what the pair feed's batches hold."""
    from harness import zipf

    v, b, ratio = 2_519_370, 65536, 6.5e-4
    counts = zipf.zipf_counts(v).astype(np.int64)
    total, run_cap = int(counts.sum()), _last_rung(_center_run_cap(WINDOW, b))
    pieces = _word_pieces(counts, total, ratio, WINDOW, b)
    cap = _word_cap(counts, total, ratio, WINDOW, b, run_cap)
    assert cap == 12288 and cap % (b // 32) == 0 and pieces < cap <= 0.8 * run_cap

    vocab = Vocabulary.from_words_and_counts(zipf.words_of(v), counts)
    tokens = zipf.draw(np.random.default_rng(11), v, 1_200_000)
    held = []
    for batch in epoch_batches([tokens[i:i + 40] for i in range(0, tokens.shape[0], 40)],
                               vocab, pairs_per_batch=b, window=WINDOW,
                               subsample_ratio=ratio, seed=1, iteration=1):
        if batch.num_real_pairs < b:
            continue
        c = np.asarray(batch.centers)
        _, heads = np.unique(c[np.diff(c, prepend=-1) != 0], return_counts=True)
        held.append((heads.shape[0], (-(-heads // _WORD_MAX_RUN)).sum()))
        if len(held) == 3:
            break
    assert len(held) == 3
    for distinct, in_pieces in held:
        assert distinct <= in_pieces <= cap
        assert abs(pieces - in_pieces) < 0.1 * in_pieces, (pieces, held)

    # more pairs a batch, more words; a smaller share of a larger batch
    caps = [_word_cap(counts, total, ratio, WINDOW, n, _last_rung(_center_run_cap(WINDOW, n)))
            for n in (4096, 16384, 65536)]
    assert caps == sorted(caps) and all(c % (n // 32) == 0
                                        for c, n in zip(caps, (4096, 16384, 65536)))
    # a flat vocabulary: every center another word, nothing to save, not built
    flat = np.full(1_000_000, 5, np.int64)
    assert _word_pieces(flat, int(flat.sum()), 0.0, WINDOW, b) > 0.6 * run_cap
    assert _word_cap(flat, int(flat.sum()), 0.0, WINDOW, b, run_cap) == 0
    # no center runs (window <= 2, a data axis), or a batch of a few pairs: not built
    assert _word_cap(counts, total, ratio, WINDOW, b, 0) == 0
    assert _word_cap(counts, total, ratio, WINDOW, 16, 6) == 0


def test_slot_cap_is_derived_from_the_counts():
    """The capacity of a CBOW token block's list scatter (ops/subword.py): at
    ``cbow-subword-2m-300``'s counts, strings and resolved subsample it holds
    1.1 to 1.35 times the live slots expected of a block and under half the
    block's slots, and blocks of kept tokens hold what was expected."""
    from harness import words, zipf

    from glint_word2vec_tpu.data.pipeline import keep_probabilities
    from glint_word2vec_tpu.data.subword import build_subword_table

    v, t, ratio = 2_000_000, 65546, 6.45e-4
    counts = zipf.zipf_counts(v).astype(np.int64)
    total = int(counts.sum())
    table = build_subword_table(words.make_words(7, v), 5, 5, 2_000_000)
    slots = t * table.max_groups * 8
    cap = _slot_cap(counts, total, ratio, table.counts, t, slots)
    kept = counts * keep_probabilities(counts, total, ratio)
    p = kept / kept.sum()
    expected = t * float(p @ table.counts[:v])
    assert slots == 1_048_736 and cap == 393_216 and cap % (1 << 15) == 0
    assert 1.1 * expected < cap < 1.35 * expected and cap < 0.5 * slots
    rng = np.random.default_rng(11)
    for _ in range(3):
        live = int(table.counts[rng.choice(v, t, p=p)].sum())
        assert abs(live - expected) < 0.02 * expected and live <= cap

    # a block of another size takes the same share of its slots
    for n in (4096, 16384):
        small = _slot_cap(counts, total, ratio, table.counts, n, n * 16)
        assert 1.1 * expected * n / t < small < 1.35 * expected * n / t
        assert small % (1 << ((n * 16 // 32).bit_length() - 1)) == 0
    # lists that fill their groups: the cut saves nothing, not built
    full = np.full(v + 1, 16, np.int32)
    assert _slot_cap(counts, total, ratio, full, t, slots) == 0
    assert _slot_cap(counts, total, ratio, full * 3 // 4, t, slots) == 0   # saves a tenth
    assert _slot_cap(counts, total, ratio, full // 2, t, slots) == 19 << 15    # 0.59 of them
    # a vocabulary subsampling keeps nothing of, or a block of a few slots
    assert _slot_cap(np.zeros(8, np.int64), 0, 0.0, np.ones(9, np.int32), 64, 512) == 0
    assert _slot_cap(counts, total, ratio, table.counts, 1, 16) == 0


# (seed of the strings, tiny): seeds of the benchmark's runs, a large one among
# them, at ``cbow-subword-2m-300``'s shape and at its ``--tiny`` sizes
@pytest.mark.parametrize("seed, tiny", [(7, False), (11, False), (2147483653, False),
                                        (7, True), (2147483653, True)])
def test_tail_cap_is_derived_from_the_counts(seed, tiny):
    """The capacity of a CBOW token block's tail gather (ops/subword.py): at
    ``cbow-subword-2m-300``'s counts, strings and resolved subsample every
    seed's strings give 4,096 (1.15 to 1.3 times the tail tokens expected of a
    block, a sixteenth of its tokens), and blocks of kept tokens hold what was
    expected; at the ``--tiny`` sizes (20,000 ranks: words of 9 letters at
    most, one group) nothing is built."""
    from harness import words, zipf

    from glint_word2vec_tpu.data.pipeline import keep_probabilities
    from glint_word2vec_tpu.data.subword import build_subword_table

    v, buckets, t, ratio = ((20_000, 10_000, 2058, 1e-3) if tiny
                            else (2_000_000, 2_000_000, 65546, 6.45e-4))
    counts = zipf.zipf_counts(v).astype(np.int64)
    total = int(counts.sum())
    table = build_subword_table(words.make_words(seed, v), 5, 5, buckets)
    cap = _tail_cap(counts, total, ratio, table.counts, t)
    if tiny:
        assert table.max_groups == 1 and cap == 0
        return
    kept = counts * keep_probabilities(counts, total, ratio)
    p = kept / kept.sum()
    expected = t * float(p @ (table.counts[:v] > 8))
    assert table.max_groups == 2 and cap == 4096 == 2 * (t // 32)
    assert 1.15 * expected < cap < 1.3 * expected
    rng = np.random.default_rng(11)
    for _ in range(3):
        tails = int((table.counts[rng.choice(v, t, p=p)] > 8).sum())
        assert abs(tails - expected) < 0.08 * expected and tails <= cap
    # a block of another size takes the same share of its tokens
    for n in (4096, 16384):
        assert _tail_cap(counts, total, ratio, table.counts, n) == 2 * (n // 32)


def test_tail_cap_builds_nothing_where_it_saves_nothing():
    v, t = 50_000, 65546
    counts = np.full(v, 100, np.int64)
    total = int(counts.sum())
    rows = np.full(v + 1, 6, np.int32)
    # lists of one group: no tails
    assert _tail_cap(counts, total, 0.0, rows, t) == 0
    # a word in 200 with a second group: the nearest unit, and never under one
    rows[:v:200] = 12
    assert _tail_cap(counts, total, 0.0, rows, t) == 2048
    rows[:v:20] = 12
    assert _tail_cap(counts, total, 0.0, rows, t) == 4096        # 3,277 expected
    # up to a quarter of the tokens with a tail it is built, past it not
    rows[:v:5] = 12
    assert _tail_cap(counts, total, 0.0, rows, t) == 16384       # 13,109 expected
    rows[:v:3] = 12
    assert _tail_cap(counts, total, 0.0, rows, t) == 0
    assert _tail_cap(counts, total, 0.0, np.full(v + 1, 16, np.int32), t) == 0
    # a vocabulary subsampling keeps nothing of, or a block of a few tokens
    assert _tail_cap(np.zeros(8, np.int64), 0, 0.0, np.full(9, 12, np.int32), 64) == 0
    assert _tail_cap(counts, total, 0.0, rows, 16) == 0


FLAT = np.full(1_000_000, 5, np.int64)


# (counts, subsample, window, batch) -> (tight, roomy): the roomy rung is the
# estimate with 20% of room in sixteenths of the batch, as the one cap was
@pytest.mark.parametrize("counts, ratio, window, batch, ladder", [
    (COUNTS, 0.0, WINDOW, 256, (80, 96)),
    (COUNTS, 0.0, WINDOW, B, (288, 320)),
    (COUNTS, 0.0, WINDOW, 4096, (1024, 1280)),
    (COUNTS, 0.0, WINDOW, 16384, (3584, 4096)),
    (COUNTS, 0.0, WINDOW, 65536, (14336, 16384)),
    # a flat vocabulary: every kept token another word, B / 3.2 of them at
    # window 5, with the room
    (FLAT, 0.0, WINDOW, B, (352, 384)),
    # where the estimate passes half the batch nothing is built: windows with
    # a pair a token or fewer; nor for a batch of a few pairs
    (FLAT, 0.0, 2, B, ()),
    (COUNTS, 0.0, 1, B, ()),
    (COUNTS, 0.0, WINDOW, 8, ()),
], ids=lambda v: "counts" if isinstance(v, np.ndarray) else None)
def test_context_cap_is_derived_from_the_counts(counts, ratio, window, batch, ladder):
    got = _context_run_cap(counts, int(counts.sum()), ratio, window, batch)
    assert got == ladder, got
    if ladder:
        # ascending, the last in sixteenths, the first in 32nds (a power of two)
        assert list(got) == sorted(set(got)) and got[-1] % (batch // 16) == 0
        assert got[0] % (1 << ((batch // 32).bit_length() - 1)) == 0


def test_context_cap_grows_with_the_batch_and_the_subsampling():
    total = int(COUNTS.sum())
    sizes = (256, 1024, 4096, 16384, 65536)
    caps = [_context_run_cap(COUNTS, total, 0.0, WINDOW, b) for b in sizes]
    # more pairs a batch, more distinct contexts and more cut pieces; a
    # smaller share of a larger batch, on every rung
    for rung in (0, -1):
        rungs = [c[rung] for c in caps]
        assert rungs == sorted(rungs) and rungs[0] / 256 > rungs[-1] / 65536
    assert CTX_LADDER == caps[1]
    # stronger subsampling flattens the kept tokens: more distinct words
    assert _context_run_cap(COUNTS, total, 1e-4, WINDOW, B)[-1] >= CTX_CAP


# the two SGNS cells' shapes: what the benchmark's steps are compiled with
@pytest.mark.parametrize("v, ratio", [(3_000_000, 6.606e-4), (10_000_000, 7.018e-4)])
def test_ladders_at_the_benchmarks_shapes(v, ratio):
    from harness import zipf

    counts = zipf.zipf_counts(v).astype(np.int64)
    ladder = _context_run_cap(counts, int(counts.sum()), ratio, 5, 65536)
    assert ladder == (18432, 20480) and _center_run_cap(5, 65536) == (18432, 24576)
    # the subsample a little off (another corpus size): the same program
    assert _context_run_cap(counts, int(counts.sum()), 8e-4, 5, 65536) == ladder


def test_token_caps_are_derived_from_the_counts():
    """The banded CBOW block's rule at ``cbow-3m-300``'s shape: the benchmark's
    Zipf counts, the AUTO subsample the trainer resolves there (6.606e-4) and
    a block of 65,536 + 2 x 5 kept tokens, every slot a token of its own and
    four fifths of them an example (window 5)."""
    from harness import zipf

    tokens = 65536 + 2 * 5
    counts = zipf.zipf_counts(3_000_000).astype(np.int64)
    total = int(counts.sum())
    estimates = [trainer_mod._expected_heads(counts, total, 6.606e-4, n, n, _TOKEN_MAX_RUN)
                 for n in (tokens, 0.8 * tokens)]
    # 550 feed blocks of five seeds hold 34,555-35,246 pieces at a run length
    # of 6 (30,314-31,132 distinct words), 28,187-28,851 of them of slots that
    # train an example: my CPU count, PERF.md section 6, PR 46
    assert _TOKEN_MAX_RUN == 6
    assert 35_250 < estimates[0] < 35_450 and 28_950 < estimates[1] < 29_150
    caps = _token_run_caps(counts, total, 6.606e-4, tokens, 5)
    # 15% of room to the nearest 32nd of the block, one program for every
    # seed: at least 12% above the largest block counted, and far under the
    # break-even (0.75 of the block)
    assert caps == (40960, 32768) == (20 * (tokens // 32), 16 * (tokens // 32))
    assert caps[0] >= 1.12 * 35_246 and caps[1] >= 1.12 * 28_851
    # the subsample a little off (another corpus size) moves the estimates by
    # under half a unit: the same program
    assert {_token_run_caps(counts, total, s, tokens, 5) for s in (6e-4, 8e-4)} == {caps}
    # cc.en.300's 2M words: fewer distinct words a block, a unit fewer for syn0
    counts2 = zipf.zipf_counts(2_000_000).astype(np.int64)
    assert _token_run_caps(counts2, int(counts2.sum()), 6.606e-4, tokens, 5) == (38912, 32768)
    # a flat vocabulary: every token another word, nothing to sum, not built
    flat = np.full(1_000_000, 5, np.int64)
    assert _token_run_caps(flat, int(flat.sum()), 0.0, tokens, 5) == (0, 0)
    # a vocabulary subsampling keeps nothing of, or a block of a few tokens
    assert _token_run_caps(np.zeros(8, np.int64), 0, 0.0, tokens, 5) == (0, 0)
    assert _token_run_caps(counts, total, 6.606e-4, 16, 5) == (0, 0)
    # the harness's tiny sizes build them too (a rehearsal runs the branches)
    tiny = zipf.zipf_counts(20_000).astype(np.int64)
    cap0, cap1 = _token_run_caps(tiny, int(tiny.sum()), 1e-3, 2048 + 10, 5)
    assert 0 < cap1 < cap0 <= 0.75 * 2058
