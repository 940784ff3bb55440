"""Equivalence suite for the banded CBOW step (ops/cbow_banded.py).

The banded path must produce the SAME update as the shipped scatter step
``cbow_step_shared_core`` on the same example set — it is a perf restructuring,
not a new estimator. The suite pins that across the cases the formulation could
get wrong: dynamic per-position windows, sentence boundaries inside a block,
subsampled (kept) streams, padded tails, and — the banded-only hazard — examples
whose windows cross a chunk cut (the ±window halo must make them exact).

Float64 runs (via jax.enable_x64) hold the two formulations to
~1e-12: at that tolerance any dropped/double-counted context link or off-by-one
interval endpoint is a hard failure, not noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.data.hashrng import (
    STREAM_SUBSAMPLE,
    STREAM_WINDOW,
    hash_u01_at,
    stream_base,
)
from glint_word2vec_tpu.data.pipeline import (
    _subsample_and_window,
    pack_halo_token_blocks,
)
from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core, cumsum_rows
from glint_word2vec_tpu.ops.pairgen import device_cbow_windows
from glint_word2vec_tpu.ops.sgns import EmbeddingPair, cbow_step_shared_core

SEED, IT, SHARD = 7, 1, 0


# ---------------------------------------------------------------------------
# corpus / reference helpers
# ---------------------------------------------------------------------------


def _kept_stream(rng, vocab, n_sentences, max_len, subsample=0.0):
    """A random-sentence corpus reduced to its kept-token stream exactly like
    the trainer's packer (_device_seg_blocks): raw-ordinal-keyed hash
    subsample, sentence-start flags on the kept stream."""
    lens = rng.integers(1, max_len, n_sentences)
    toks = rng.integers(0, vocab, lens.sum()).astype(np.int32)
    sids = np.repeat(np.arange(n_sentences), lens)
    if subsample > 0:
        sub_base = stream_base(SEED, STREAM_SUBSAMPLE, IT, SHARD)
        # a crude keep curve is enough — the test only needs SOME tokens gone
        keep = np.minimum(
            0.2 + 0.8 * rng.random(vocab), 1.0).astype(np.float32)
        u = hash_u01_at(sub_base, np.arange(toks.shape[0], dtype=np.uint64))
        m = u <= keep[toks]
        toks, sids = toks[m], sids[m]
    if toks.shape[0] == 0:
        return toks, np.zeros(0, bool)
    starts = np.empty(toks.shape[0], bool)
    starts[0] = True
    starts[1:] = sids[1:] != sids[:-1]
    return toks, starts


def _host_windows(ktoks, starts, window):
    """(left, right) per kept position — the host mirror the device derivation
    must match: pipeline._subsample_and_window on the kept stream (keep ≡ 1,
    ordinals = kept ordinals, the presubsampled-feed keying)."""
    lens = np.diff(np.concatenate(
        [np.flatnonzero(starts), [ktoks.shape[0]]])).astype(np.int64)
    out = _subsample_and_window(
        ktoks, lens, np.ones(int(ktoks.max()) + 1, np.float32), window,
        SEED, IT, SHARD, 0, True)
    toks2, left, total, nk = out
    np.testing.assert_array_equal(toks2, ktoks)
    return left.astype(np.int64), (total - left).astype(np.int64)


def _scatter_reference(params, ktoks, left, right, sel, negatives, alpha,
                       num_negatives, window, dtype):
    """One cbow_step_shared_core step over the stream positions in ``sel``.
    The batch is padded with masked rows to a multiple of 16: the step runs
    eagerly, so every distinct batch length would recompile each of its ops
    (the multi-block cases feed a dozen lengths); a masked row adds exact
    zeros to row 0."""
    C = 2 * window
    nb = len(sel)
    padded = -(-nb // 16) * 16
    centers = np.zeros(padded, np.int32)
    centers[:nb] = ktoks[sel]
    ctx = np.zeros((padded, C), np.int32)
    ctxm = np.zeros((padded, C), np.float32)
    for i, b in enumerate(sel):
        idx = (list(range(b - left[b], b))
               + list(range(b + 1, b + right[b] + 1)))
        ctx[i, :len(idx)] = ktoks[idx]
        ctxm[i, :len(idx)] = 1.0
    mask = (np.arange(padded) < nb).astype(np.float32)
    return cbow_step_shared_core(
        params, jnp.asarray(centers), jnp.asarray(ctx), jnp.asarray(ctxm),
        jnp.asarray(mask), negatives, alpha, num_negatives, "exact", dtype)


def _banded_blocks(ktoks, starts, T, window):
    """Halo blocks + device window derivation for each, as the trainer feeds
    them (win_base keyed like the presubsampled device feed)."""
    win_base = stream_base(SEED, STREAM_WINDOW, IT, SHARD)
    out = []
    for tb, bits, nv, ob, nc in pack_halo_token_blocks(
            [(ktoks, starts)], T, window, np.int32):
        band = device_cbow_windows(
            jnp.asarray(tb), jnp.asarray(bits), jnp.int32(nv),
            jnp.uint32(ob & 0xFFFFFFFF), jnp.uint32(ob >> 32),
            jnp.uint32(win_base), window=window, halo=window)
        out.append((tb, band, nc))
    return out


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def test_cumsum_rows_matches_numpy():
    rng = np.random.default_rng(0)
    for T, D in ((1, 3), (127, 8), (128, 8), (300, 7), (1000, 5)):
        x = rng.normal(size=(T, D)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(cumsum_rows(jnp.asarray(x))), np.cumsum(x, axis=0),
            rtol=1e-5, atol=1e-5)


def test_prefix_matmuls_ask_the_mxu_for_what_they_need():
    """The CPU computes a float32 matmul exactly and cannot show what the TPU
    does at the default precision (bfloat16 operands: the banded backward's
    rounded difference array then never cancels; seen on the v5e, PR 27). What
    can be held here is what the lowered matmuls ask for: HIGHEST, but for the
    forward prefix over rows already rounded to bfloat16, which is one pass."""
    x = jnp.zeros((300, 8), jnp.float32)

    def precisions(fn, *args):
        text = jax.jit(fn).lower(*args).as_text()
        return ["HIGHEST" in l for l in text.splitlines() if "dot_general" in l]

    assert precisions(cumsum_rows, x) == [True]
    assert precisions(lambda a: cumsum_rows(a, one_pass=True), x) == [False]
    tokens = jnp.arange(40, dtype=jnp.int32)
    ones, one = jnp.ones(40, jnp.float32), jnp.ones(40, jnp.int32)
    table = jnp.zeros((64, 8), jnp.float32)

    def step(compute_dtype):
        return lambda t: cbow_step_banded_core(
            EmbeddingPair(t, t), tokens, one, 0 * one, ones, ones, tokens[:4],
            jnp.float32(0.1), 5, 3, compute_dtype=compute_dtype,
            logits_dtype=compute_dtype)

    # the two prefix sums are the step's only [128, 128] matmuls
    for compute_dtype, want in ((jnp.bfloat16, [False, True]),
                                (jnp.float32, [True, True])):
        text = jax.jit(step(compute_dtype)).lower(table).as_text()
        got = ["HIGHEST" in l for l in text.splitlines()
               if "dot_general" in l and "128x128" in l]
        assert got == want, (compute_dtype, got)
        assert ("reduce_precision" in text) == (compute_dtype == jnp.bfloat16)


def test_halo_blocks_cover_every_token_once():
    rng = np.random.default_rng(1)
    ktoks, starts = _kept_stream(rng, 50, 30, 12)
    L = ktoks.shape[0]
    H, T = 4, 20
    Tc = T - 2 * H
    blocks = list(pack_halo_token_blocks([(ktoks, starts)], T, H, np.int32))
    assert sum(b[4] for b in blocks) == L          # every token a core once
    covered = 0
    for tb, bits, nv, ob, nc in blocks:
        # the core slots hold exactly the next nc stream tokens
        np.testing.assert_array_equal(
            tb[H:H + nc], ktoks[covered:covered + nc])
        assert nc <= Tc
        assert nv <= T
        # ordinal base points H before the first core slot's stream position
        assert (ob - ((covered - H) & 0xFFFFFFFFFFFFFFFF)
                ) % (1 << 64) == 0
        covered += nc
    # streams shorter than one block still emit their cores
    short = list(pack_halo_token_blocks(
        [(ktoks[:3], starts[:3])], T, H, np.int32))
    assert sum(b[4] for b in short) == 3
    # empty stream emits nothing
    assert list(pack_halo_token_blocks([], T, H, np.int32)) == []


def test_device_windows_match_host_across_blocks():
    """The chunk-edge case: device-derived (left, right) of every CORE slot —
    including slots whose window crosses a block cut and lives in the halo —
    must equal the host pipeline's sentence-clamped extents."""
    rng = np.random.default_rng(2)
    W = 4
    ktoks, starts = _kept_stream(rng, 60, 40, 14)
    left_h, right_h = _host_windows(ktoks, starts, W)
    covered = 0
    for tb, band, nc in _banded_blocks(ktoks, starts, 3 * W + 9, W):
        lb, rb = np.asarray(band.left), np.asarray(band.right)
        cm = np.asarray(band.center)
        core = slice(W, W + nc)
        np.testing.assert_array_equal(
            lb[core], left_h[covered:covered + nc])
        np.testing.assert_array_equal(
            rb[core], right_h[covered:covered + nc])
        assert cm[core].all()
        assert cm[:W].sum() == 0 and cm[W + nc:].sum() == 0
        covered += nc
    assert covered == ktoks.shape[0]


# ---------------------------------------------------------------------------
# step equivalence vs the scatter oracle
# ---------------------------------------------------------------------------


def _equivalence_case(dtype, rtol, atol, subsample):
    rng = np.random.default_rng(3)
    V, D, P, W, NEG = 120, 16, 32, 3, 4
    ktoks, starts = _kept_stream(rng, 40, 15, V, subsample=subsample)
    left_h, right_h = _host_windows(ktoks, starts, W)
    live = np.flatnonzero(left_h + right_h > 0)
    assert live.size > 20   # the case actually exercises dynamic windows

    params0 = EmbeddingPair(
        jnp.asarray(rng.normal(0, 0.1, (V, D)), dtype),
        jnp.asarray(rng.normal(0, 0.05, (V, D)), dtype))
    negs = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    alpha = jnp.asarray(0.05, dtype)

    # --- single block: everything (sentences, padding tail) in one step -----
    T = ktoks.shape[0] + 2 * W + 5
    ((tb, band, nc),) = _banded_blocks(ktoks, starts, T, W)
    p_band, m_band = cbow_step_banded_core(
        params0, jnp.asarray(tb), band.left, band.right, band.center,
        band.token, negs, alpha, NEG, W, "exact", dtype)
    p_ref, m_ref = _scatter_reference(
        params0, ktoks, left_h, right_h, live, negs, alpha, NEG, W, dtype)
    np.testing.assert_allclose(
        np.asarray(p_band.syn0), np.asarray(p_ref.syn0), rtol=rtol, atol=atol)
    np.testing.assert_allclose(
        np.asarray(p_band.syn1), np.asarray(p_ref.syn1), rtol=rtol, atol=atol)
    np.testing.assert_allclose(
        float(m_band.loss), float(m_ref.loss), rtol=max(rtol, 1e-6))
    assert float(m_band.pairs) == float(m_ref.pairs) == live.size

    # --- multi block: sequential steps, windows crossing every cut ----------
    p_cur = params0
    p_refc = params0
    covered = 0
    for tb, band, nc in _banded_blocks(ktoks, starts, 4 * W + 6, W):
        p_cur, _ = cbow_step_banded_core(
            p_cur, jnp.asarray(tb), band.left, band.right, band.center,
            band.token, negs, alpha, NEG, W, "exact", dtype)
        sel = live[(live >= covered) & (live < covered + nc)]
        covered += nc
        if sel.size:
            p_refc, _ = _scatter_reference(
                p_refc, ktoks, left_h, right_h, sel, negs, alpha, NEG, W,
                dtype)
    np.testing.assert_allclose(
        np.asarray(p_cur.syn0), np.asarray(p_refc.syn0),
        rtol=rtol * 5, atol=atol * 5)
    np.testing.assert_allclose(
        np.asarray(p_cur.syn1), np.asarray(p_refc.syn1),
        rtol=rtol * 5, atol=atol * 5)


def test_banded_equals_scatter_float32():
    _equivalence_case(jnp.float32, 2e-5, 2e-6, subsample=0.0)


def test_banded_equals_scatter_float32_subsampled():
    _equivalence_case(jnp.float32, 2e-5, 2e-6, subsample=1e-1)


def test_banded_equals_scatter_float64_tight():
    """float64 on CPU: any structural mismatch (lost/duplicated context link,
    off-by-one interval) is far above 1e-12 — this is the exactness pin."""
    with jax.enable_x64():
        _equivalence_case(jnp.float64, 1e-12, 1e-14, subsample=0.0)


def test_banded_metrics_elided_twin_bit_identical():
    """with_metrics=False must change ONLY the loss side-channel — trained
    params bit-identical (the trainer's fast-twin contract)."""
    rng = np.random.default_rng(5)
    V, D, P, W, NEG = 80, 8, 16, 3, 3
    ktoks, starts = _kept_stream(rng, 20, 12, V)
    T = ktoks.shape[0] + 2 * W + 3
    ((tb, band, nc),) = _banded_blocks(ktoks, starts, T, W)
    params0 = EmbeddingPair(
        jnp.asarray(rng.normal(0, 0.1, (V, D)), jnp.float32),
        jnp.asarray(rng.normal(0, 0.05, (V, D)), jnp.float32))
    negs = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    args = (params0, jnp.asarray(tb), band.left, band.right, band.center,
            band.token, negs, jnp.float32(0.05), NEG, W, "exact", jnp.float32,
            jnp.float32)
    p_full, m_full = cbow_step_banded_core(*args, with_metrics=True)
    p_fast, m_fast = cbow_step_banded_core(*args, with_metrics=False)
    np.testing.assert_array_equal(np.asarray(p_full.syn0),
                                  np.asarray(p_fast.syn0))
    np.testing.assert_array_equal(np.asarray(p_full.syn1),
                                  np.asarray(p_fast.syn1))
    assert float(m_fast.loss) == 0.0
    assert float(m_fast.pairs) == float(m_full.pairs)


def test_banded_scatter_fallback_for_large_windows():
    """windows past the shifted-add unroll bound take the 2T-row scatter form
    of the endpoint accumulation — same update either way."""
    from glint_word2vec_tpu.ops import cbow_banded

    rng = np.random.default_rng(6)
    V, D, P, W, NEG = 100, 8, 16, 4, 3
    ktoks, starts = _kept_stream(rng, 20, 20, V)
    T = ktoks.shape[0] + 2 * W + 1
    ((tb, band, nc),) = _banded_blocks(ktoks, starts, T, W)
    params0 = EmbeddingPair(
        jnp.asarray(rng.normal(0, 0.1, (V, D)), jnp.float32),
        jnp.asarray(rng.normal(0, 0.05, (V, D)), jnp.float32))
    negs = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    args = (params0, jnp.asarray(tb), band.left, band.right, band.center,
            band.token, negs, jnp.float32(0.05), NEG, W)
    p_shift, _ = cbow_step_banded_core(*args)
    orig = cbow_banded._SHIFT_UNROLL_MAX_WINDOW
    try:
        cbow_banded._SHIFT_UNROLL_MAX_WINDOW = 0  # force the scatter form
        p_scat, _ = cbow_step_banded_core(*args)
    finally:
        cbow_banded._SHIFT_UNROLL_MAX_WINDOW = orig
    np.testing.assert_allclose(np.asarray(p_shift.syn0),
                               np.asarray(p_scat.syn0), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(p_shift.syn1),
                               np.asarray(p_scat.syn1), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# the two token scatters by runs of the block's tokens sorted once (token_runs)
# ---------------------------------------------------------------------------

RUN_V, RUN_D, RUN_P, RUN_W, RUN_NEG, RUN_T = 300, 16, 32, 3, 4, 256


def _run_block(tokens, real=None, seed=0):
    """One block of RUN_T slots holding ``tokens`` (the rest masked), in
    sentences of 12, with the device's own window draws."""
    real = len(tokens) if real is None else real
    tb = np.zeros(RUN_T, np.int32)
    tb[:len(tokens)] = tokens
    bits = np.packbits(np.arange(RUN_T) % 12 == 0, bitorder="little")
    band = device_cbow_windows(
        jnp.asarray(tb), jnp.asarray(bits), jnp.int32(real), jnp.uint32(seed),
        jnp.uint32(0), jnp.uint32(stream_base(SEED, STREAM_WINDOW, IT, SHARD)),
        window=RUN_W, halo=RUN_W)
    return jnp.asarray(tb), band


def _run_params(dtype=jnp.float32, seed=4):
    rng = np.random.default_rng(seed)
    return EmbeddingPair(jnp.asarray(rng.normal(0, 0.1, (RUN_V, RUN_D)), dtype),
                         jnp.asarray(rng.normal(0, 0.05, (RUN_V, RUN_D)), dtype))


def _run_step(params, tb, band, token_runs, compute_dtype=jnp.float32, **kw):
    negs = jnp.asarray(np.random.default_rng(8).integers(0, RUN_V, RUN_P), jnp.int32)
    fn = jax.jit(lambda p: cbow_step_banded_core(
        p, tb, band.left, band.right, band.center, band.token, negs,
        jnp.asarray(0.05, p.syn0.dtype), RUN_NEG, RUN_W, "exact", compute_dtype,
        compute_dtype, token_runs=token_runs, **kw))
    # strict bfloat16, as tests/test_coalesce_runs.py: the two programs fuse
    # differently, and XLA may keep a fused bfloat16 value in float32 in one
    return fn.lower(params).compile(
        compiler_options={"xla_allow_excess_precision": False})(params)


def _pieces(tokens, max_run, keep=None):
    """Pieces of the runs of ``tokens`` sorted by word and cut every
    ``max_run``; of the slots ``keep`` marks alone where given."""
    tokens = np.asarray(tokens)
    if keep is not None:
        tokens = tokens[np.asarray(keep) > 0]
    _, counts = np.unique(tokens, return_counts=True)
    return int((-(-counts // max_run)).sum())


def _live(band):
    return np.asarray(band.center) * ((np.asarray(band.left) + np.asarray(band.right)) > 0)


def _zipf_tokens(n, seed):
    rng = np.random.default_rng(seed)
    p = 1.0 / (np.arange(RUN_V) + 3.0)
    return rng.choice(RUN_V, n, p=p / p.sum()).astype(np.int32)


# name -> (tokens of the block, real slots, (max_run, syn0's cap, syn1's cap));
# every block has RUN_T slots, the slots past ``real`` are masked and hold word 0
TOKEN_RUN_CASES = {
    # a Zipf block: about half as many words as slots, runs of every length
    "zipf_block": (_zipf_tokens(RUN_T, 1), RUN_T, (4, 224, 192)),
    # runs longer than max_run are cut: a word in several pieces
    "a_word_in_several_pieces": (
        np.concatenate([np.full(37, 7), _zipf_tokens(RUN_T - 37, 2)]).astype(np.int32),
        RUN_T, (4, 224, 192)),
    # one piece a word exactly at max_run, one more at max_run + 1
    "pieces_cut_at_max_run": (
        np.concatenate([np.full(4, 5), np.full(5, 9), np.arange(20, 20 + RUN_T - 9)]
                       ).astype(np.int32), RUN_T, (4, RUN_T, RUN_T)),
    # the masked tail rides in word 0's run with zero rows
    "masked_tail": (_zipf_tokens(150, 3), 150, (4, 224, 192)),
    "all_masked": (np.zeros(0, np.int32), 0, (4, 224, 192)),
    # no word twice: as many heads as slots, under a cap that holds them
    "every_token_another_word": (np.arange(RUN_T, dtype=np.int32) + 10, RUN_T,
                                 (4, RUN_T, RUN_T)),
}


@pytest.mark.parametrize("compute_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(TOKEN_RUN_CASES))
def test_token_runs_against_the_plain_banded_step(case, compute_dtype):
    tokens, real, runs = TOKEN_RUN_CASES[case]
    tb, band = _run_block(tokens, real)
    params = _run_params()
    plain, m0 = _run_step(params, tb, band, None, compute_dtype)
    got, m1 = _run_step(params, tb, band, runs, compute_dtype)
    heads0, heads1 = _pieces(tb, runs[0]), _pieces(tb, runs[0], _live(band))
    assert heads0 <= runs[1] and heads1 <= runs[2] and heads1 <= heads0
    # syn0's scatter was handed one row a piece of a word's run, masked slots
    # (word 0's) among them; syn1's the pieces of the slots that train an
    # example alone; the plain step counts nothing
    assert (float(m1.syn0_rows), float(m1.syn1_rows)) == (heads0, heads1)
    assert m0.syn0_rows is None and m0.syn1_rows is None
    assert float(m1.pairs) == float(m0.pairs) and float(m1.loss) == float(m0.loss)
    for new, old, start in zip(got[:2], plain[:2], params[:2]):
        want = np.asarray(old) - np.asarray(start)
        diff = np.asarray(new) - np.asarray(old)
        # the same rows summed in another order: float32 roundings of a row
        assert np.linalg.norm(diff) <= 1e-5 * max(np.linalg.norm(want), 1e-30)
    # a word the block does not hold keeps its syn0 row (syn1 also moves at
    # the pool's rows)
    untouched = np.setdiff1d(np.arange(RUN_V), np.asarray(tb))
    np.testing.assert_array_equal(np.asarray(got.syn0)[untouched],
                                  np.asarray(params.syn0)[untouched])


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
@pytest.mark.parametrize("compute_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_block_over_the_token_cap_is_the_parents_step_bit_for_bit(compute_dtype,
                                                                   with_metrics):
    tb, band = _run_block(_zipf_tokens(RUN_T, 1))
    params = _run_params()
    heads0, heads1 = _pieces(tb, 4), _pieces(tb, 4, _live(band))
    plain, _ = _run_step(params, tb, band, None, compute_dtype,
                         with_metrics=with_metrics)
    got, m = _run_step(params, tb, band, (4, heads0 - 1, heads1 - 1), compute_dtype,
                       with_metrics=with_metrics)
    assert float(m.syn0_rows) == float(m.syn1_rows) == RUN_T
    np.testing.assert_array_equal(np.asarray(got.syn0), np.asarray(plain.syn0))
    np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(plain.syn1))
    # each table decides for itself, and at its cap exactly it coalesces
    got, m = _run_step(params, tb, band, (4, heads0, heads1 - 1), compute_dtype,
                       with_metrics=with_metrics)
    assert (float(m.syn0_rows), float(m.syn1_rows)) == (heads0, RUN_T)
    np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(plain.syn1))
    _, m = _run_step(params, tb, band, (4, heads0 - 1, heads1), compute_dtype,
                     with_metrics=with_metrics)
    assert (float(m.syn0_rows), float(m.syn1_rows)) == (RUN_T, heads1)


def _three_steps(dtype, compute_dtype, token_runs, rtol, atol):
    """Three sequential blocks of one kept stream (windows crossing the cuts)
    through the banded step with ``token_runs`` against the scatter oracle."""
    rng = np.random.default_rng(3)
    V, D, P, W, NEG = 60, 16, 32, 3, 4
    ktoks, starts = _kept_stream(rng, 36, 15, V)
    left_h, right_h = _host_windows(ktoks, starts, W)
    live = np.flatnonzero(left_h + right_h > 0)
    T = -(-ktoks.shape[0] // 3) + 2 * W
    blocks = _banded_blocks(ktoks, starts, T, W)
    assert len(blocks) == 3
    p_cur = p_ref = EmbeddingPair(
        jnp.asarray(rng.normal(0, 0.1, (V, D)), dtype),
        jnp.asarray(rng.normal(0, 0.05, (V, D)), dtype))
    negs = jnp.asarray(rng.integers(0, V, P), jnp.int32)
    alpha = jnp.asarray(0.05, dtype)
    covered, handed = 0, []
    for tb, band, nc in blocks:
        p_cur, m = cbow_step_banded_core(
            p_cur, jnp.asarray(tb), band.left, band.right, band.center, band.token,
            negs, alpha, NEG, W, "exact", compute_dtype, token_runs=token_runs)
        handed.append((float(m.syn0_rows), _pieces(tb, token_runs[0])))
        assert float(m.syn1_rows) == _pieces(tb, token_runs[0], _live(band))
        sel = live[(live >= covered) & (live < covered + nc)]
        covered += nc
        p_ref, _ = _scatter_reference(p_ref, ktoks, left_h, right_h, sel, negs,
                                      alpha, NEG, W, compute_dtype)
    # every block held repeats and coalesced
    assert all(got == want < T for got, want in handed), handed
    for got, want in zip(p_cur[:2], p_ref[:2]):
        np.testing.assert_allclose(np.asarray(got, np.float64),
                                   np.asarray(want, np.float64), rtol=rtol, atol=atol)


def test_token_runs_three_steps_against_the_float64_reference():
    with jax.enable_x64():
        _three_steps(jnp.float64, jnp.float64, (3, 64, 64), 5e-12, 5e-14)


@pytest.mark.parametrize("compute_dtype, rtol, atol",
                         [(jnp.float32, 1e-4, 1e-5), (jnp.bfloat16, 5e-2, 5e-3)],
                         ids=["float32", "bfloat16"])
def test_token_runs_three_steps_in_both_compute_dtypes(compute_dtype, rtol, atol):
    _three_steps(jnp.float32, compute_dtype, (3, 64, 64), rtol, atol)


def test_token_runs_leave_the_stabilizers_and_the_pool_rows_alone():
    """update_clip and the post-pass read ``tokens`` and the masks, not the
    order: with them on the coalesced step is the plain one to rounding."""
    from glint_word2vec_tpu.ops.sgns import Stabilizers

    tb, band = _run_block(_zipf_tokens(200, 5), 200)
    params = _run_params()
    stab = Stabilizers(max_row_norm=0.35, update_clip=0.01, row_l2=1e-3)
    plain, _ = _run_step(params, tb, band, None, stabilizers=stab)
    got, m = _run_step(params, tb, band, (4, 224, 192), stabilizers=stab)
    assert float(m.syn1_rows) < RUN_T
    for new, old in zip(got[:2], plain[:2]):
        np.testing.assert_allclose(np.asarray(new), np.asarray(old), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# trainer integration + config matrix
# ---------------------------------------------------------------------------


def _toy_fit(cbow_update):
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer

    rng = np.random.default_rng(3)
    words = [f"w{i}" for i in range(60)]
    sentences = [[words[j] for j in rng.integers(0, 60, 15)]
                 for _ in range(150)]
    vocab = build_vocab(sentences, min_count=1)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=256, num_iterations=2,
        window=3, negatives=3, cbow=True, cbow_update=cbow_update,
        negative_pool=128, steps_per_dispatch=2, seed=2,
        subsample_ratio=1e-2, heartbeat_every_steps=4)
    t = Trainer(cfg, vocab)
    before = np.asarray(t.params.syn0).copy()
    t.fit(encode_sentences(sentences, vocab, 1000))
    return t, before


def test_trainer_fit_banded_smoke():
    t, before = _toy_fit("banded")
    after = np.asarray(t.params.syn0)
    assert np.isfinite(after).all()
    assert not np.allclose(before, after)
    assert t.pairs_trained > 0
    assert t.heartbeats and np.isfinite(t.heartbeats[-1].loss)
    # the metrics-elided fast twin is actually wired for this path
    assert t._step_fn_fast is not t._step_fn


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_trainer_fit_both_twins_coalesce_the_token_scatters(compute_dtype):
    """Through Trainer.fit: the rule derives a cap from the vocabulary's
    counts, both twins take it and compile once, every heartbeat's
    ``device_block`` span says how many rows each token scatter was handed
    over the step's live examples, and the fit trains what the plain step
    trains (the rule giving 0)."""
    import os
    import shutil
    import tempfile
    from dataclasses import replace as dc_replace

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.train import trainer as trainer_mod
    from glint_word2vec_tpu.train.trainer import _TOKEN_MAX_RUN, Trainer

    V = 2000
    counts = np.maximum(1e6 / (np.arange(V) + 10.0) ** 1.07, 5.0).astype(np.int64)
    vocab = Vocabulary.from_words_and_counts([f"w{i}" for i in range(V)], counts)
    rng = np.random.default_rng(0)
    toks = rng.choice(V, 30_000, p=counts / counts.sum()).astype(np.int32)
    sents = [toks[i:i + 40] for i in range(0, toks.shape[0], 40)]
    cfg = Word2VecConfig(
        vector_size=24, window=5, negatives=5, min_count=1, cbow=True,
        cbow_update="banded", compute_dtype=compute_dtype, logits_dtype=compute_dtype,
        pairs_per_batch=1024, steps_per_dispatch=2, heartbeat_every_steps=4,
        negative_pool=32, subsample_ratio=0.0, num_iterations=1, seed=1)

    def fit(coalesce):
        run_dir = tempfile.mkdtemp(prefix="token_runs_")
        rule = trainer_mod._token_run_caps
        if not coalesce:
            trainer_mod._token_run_caps = lambda *a: (0, 0)
        try:
            t = Trainer(dc_replace(cfg, telemetry_path=os.path.join(run_dir, "run.jsonl")),
                        vocab)
        finally:
            trainer_mod._token_run_caps = rule
        try:
            t.fit(sents)
            spans = [e.get("args") or {} for e in t._tracer.events()
                     if e["name"] == "device_block"]
        finally:
            shutil.rmtree(run_dir, ignore_errors=True)
        return t, spans

    start = jax.device_get(Trainer(cfg, vocab).params)
    (on, on_spans), (off, off_spans) = fit(True), fit(False)
    tokens = on._tokens_per_step
    cap0, cap1 = on._token_caps
    assert tokens == 1024 + 2 * 5 and 0 < cap1 < cap0 <= 0.75 * tokens
    assert cap0 % (tokens // 32) == cap1 % (tokens // 32) == 0
    assert off._token_caps == (0, 0)
    assert on._step_fn_fast is not on._step_fn
    assert on._step_fn._cache_size() == 1 and on._step_fn_fast._cache_size() == 1
    assert on.global_step == off.global_step and on.global_step >= 8
    # heads over live examples: a block of 1,034 tokens over 2,000 Zipf words
    # holds ~560 pieces, ~470 of them of its ~820 live examples' slots; plain
    # would read 1,034 / 820 for both
    shares0 = [a["syn0_rows_per_pair"] for a in on_spans]
    shares1 = [a["syn1_rows_per_pair"] for a in on_spans]
    assert shares0 and max(shares0) < 0.9 and min(shares1) > 0.3
    assert all(s1 < s0 for s0, s1 in zip(shares0, shares1))
    assert off_spans and not any("syn1_rows_per_pair" in a or "syn0_rows_per_pair" in a
                                 for a in off_spans)
    assert _TOKEN_MAX_RUN >= 2
    limit = 1e-4 if compute_dtype == "float32" else 5e-3
    for a, b, s in zip(on.params[:2], off.params[:2], start[:2]):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= limit * np.linalg.norm(b - s)


def test_trainer_fit_banded_deterministic():
    t1, _ = _toy_fit("banded")
    t2, _ = _toy_fit("banded")
    np.testing.assert_array_equal(np.asarray(t1.params.syn0),
                                  np.asarray(t2.params.syn0))


def test_config_selection_matrix_errors():
    from glint_word2vec_tpu.config import Word2VecConfig

    for kw, msg in [
        (dict(cbow_update="banded"), "requires cbow=True"),
        (dict(cbow=True, cbow_update="banded", duplicate_scaling=True),
         "duplicate_scaling"),
        (dict(cbow=True, cbow_update="banded", negative_pool=0),
         "shared-pool"),
        (dict(cbow=True, cbow_update="banded", tokens_per_step=64),
         "tokens_per_step"),
        (dict(cbow=True, cbow_update="banded", window=1), "window"),
        (dict(cbow=True, cbow_update="bogus"), "cbow_update"),
        (dict(cbow=True, duplicate_scaling=True, negative_pool=256),
         "per-example"),
    ]:
        with pytest.raises(ValueError, match=msg.replace("(", "\\(")):
            Word2VecConfig(**kw)
    # AUTO pool resolutions around the matrix
    assert Word2VecConfig(cbow=True, duplicate_scaling=True).negative_pool == 0
    assert Word2VecConfig(cbow=True, cbow_update="banded",
                          pairs_per_batch=256).negative_pool > 0
    # scatter stays the default
    assert Word2VecConfig(cbow=True).cbow_update == "scatter"


def test_trainer_banded_config_roundtrip():
    """cbow_update survives to_dict/from_dict (checkpoint metadata)."""
    from glint_word2vec_tpu.config import Word2VecConfig

    cfg = Word2VecConfig(cbow=True, cbow_update="banded")
    d = cfg.to_dict(auto_markers=False)
    assert d["cbow_update"] == "banded"
    assert Word2VecConfig.from_dict(d).cbow_update == "banded"


def test_from_dict_normalizes_legacy_ignored_pool():
    """Pre-selection-matrix checkpoints could store cbow + duplicate_scaling +
    a RESOLVED auto pool (the old trainer warn-ignored it and sampled
    per-example). from_dict must normalize that to pool=0 — the semantics the
    model actually trained with — instead of refusing to load the checkpoint."""
    from glint_word2vec_tpu.config import Word2VecConfig

    legacy = Word2VecConfig(cbow=True, pairs_per_batch=65536).to_dict(
        auto_markers=False)
    assert legacy["negative_pool"] > 0          # the resolved auto pool
    legacy["duplicate_scaling"] = True          # the pre-change combination
    cfg = Word2VecConfig.from_dict(legacy)
    assert cfg.negative_pool == 0
    # but a banded checkpoint keeps its pool (banded never ignored it), and
    # banded+duplicate_scaling still refuses (it never existed to preserve)
    banded = Word2VecConfig(cbow=True, cbow_update="banded").to_dict(
        auto_markers=False)
    assert Word2VecConfig.from_dict(banded).negative_pool > 0


def test_replace_rederives_auto_pool_across_path_switches():
    """replace() must re-run the AUTO pool rule when the update path changes,
    not freeze the previously resolved value into a refused combination."""
    from glint_word2vec_tpu.config import Word2VecConfig

    cfg = Word2VecConfig(cbow=True, pairs_per_batch=65536)
    assert cfg.negative_pool > 0
    assert cfg.replace(duplicate_scaling=True).negative_pool == 0
    small = Word2VecConfig(cbow=True, pairs_per_batch=128)
    assert small.negative_pool == 0
    assert small.replace(cbow_update="banded").negative_pool > 0
    # an EXPLICIT pool is never silently rewritten — the refusal stands
    explicit = Word2VecConfig(cbow=True, negative_pool=256,
                              pairs_per_batch=65536)
    with pytest.raises(ValueError, match="per-example"):
        explicit.replace(duplicate_scaling=True)
