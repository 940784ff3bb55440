"""Subword CBOW with position weights (config.subword beside cbow_update="banded",
config.cbow_position_weights; fastText's cc.*.300 recipe) on the CPU at a small
size.

The banded step with {row source on, off} x {position weights on, off} against
``cbow_subword_ref``'s ``jax.grad`` updates on token blocks with repeated words,
real sentence ends and a halo, in float32 and (the taps' equivalence) in
float64; the row source's forms giving the same sums, the lists' scatter under a
slot capacity and a block over it among them; masked slots and the lane
padding; the lowered steps of the configurations that use neither against the
parent's text; the third leaf through a fit, a save, a load and a resume; the
model's composed vectors and a string the vocabulary has never seen; the
config's new legal rows and each refusal.
"""

import hashlib
import json
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
from harness import zipf  # noqa: E402
from kinds import train_cbow_subword as kind  # noqa: E402
from reference import cbow_subword_ref as ref_model  # noqa: E402
from reference import subword_ref  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data.hashrng import STREAM_WINDOW, stream_base  # noqa: E402
from glint_word2vec_tpu.data.pipeline import pack_halo_token_blocks  # noqa: E402
from glint_word2vec_tpu.data.subword import build_subword_table  # noqa: E402
from glint_word2vec_tpu.ops.cbow_banded import (  # noqa: E402
    cbow_step_banded_core, position_taps)
from glint_word2vec_tpu.ops.pairgen import device_cbow_windows  # noqa: E402
from glint_word2vec_tpu.ops.sgns import EmbeddingPair  # noqa: E402
from glint_word2vec_tpu.ops.subword import SubwordShape, SubwordTable  # noqa: E402

V, BUCKETS, D, T, W, P, NEG, STEPS = 3000, 2000, 24, 1034, 5, 64, 10, 3

CASES = {}


def _case(long_words=False):
    """A small vocabulary of seeded strings, its row table at n-grams of 5, and
    the halo packer's own first blocks over a seeded Zipf corpus in sentences of
    40 with the device's own window draws: repeated words, sentence ends, empty
    windows, halo slots that are no centers. The seeded strings of 3,000 ranks
    have 8 letters at most, 7 rows, one group; ``long_words`` draws every
    eighth rank's out to 10-20 letters (a word of L letters lists L - 1 rows),
    so that lists have a second and a third group and every block tail tokens
    (133 / 130 / 135 of 1,034 in the three blocks)."""
    CASE = CASES.setdefault(long_words, {})
    if CASE:
        return CASE
    strings = bench_words.make_words(7, V)
    if long_words:
        letters = np.random.default_rng(23).choice(list("etaoinshr"), (V, 20))
        for r in range(3, V, 8):
            strings[r] += "".join(letters[r, :10 + (r // 8) % 11 - len(strings[r])])
        assert len(set(strings)) == V
    tokens = zipf.draw(np.random.default_rng(3), V, 8 * T).astype(np.int32)
    starts = np.arange(tokens.shape[0]) % 40 == 0
    win_base = stream_base(1, STREAM_WINDOW, 1, 0)
    blocks, bands = [], []
    for tb, bits, nv, ob, _ in pack_halo_token_blocks([(tokens, starts)], T, W, np.int32):
        band = device_cbow_windows(
            jnp.asarray(tb), jnp.asarray(bits), jnp.int32(nv),
            jnp.uint32(ob & 0xFFFFFFFF), jnp.uint32(ob >> 32), jnp.uint32(win_base),
            window=W, halo=W)
        blocks.append(np.asarray(tb, np.int32))
        bands.append(band)
        if len(blocks) == STEPS:
            break
    tokens = np.stack(blocks)
    assert np.unique(tokens[0]).shape[0] < 0.8 * T      # words repeat within a block
    assert any((np.asarray(b.left) + np.asarray(b.right) == 0)[W:-W].any() for b in bands)
    rng = np.random.default_rng(17)
    table = build_subword_table(strings, 5, 5, BUCKETS)
    assert table.max_groups == (3 if long_words else 1)
    CASE.update(
        strings=strings, table=table,
        tokens=tokens, bands=bands,
        negatives=rng.integers(0, V, (STEPS, P)).astype(np.int32),
        syn0=rng.uniform(-0.3, 0.3, (V + BUCKETS, D)), syn1=rng.uniform(-0.3, 0.3, (V, D)),
        pos=rng.uniform(0.5, 1.5, (2 * W, D)))
    return CASE


def _device_table(table):
    return SubwordTable(jnp.asarray(table.offsets), jnp.asarray(table.rows),
                        jnp.asarray(table.counts))


def _run_program(case, subword, positions, dtype, shape=None, with_metrics=True,
                 compute_dtype=None, steps=STEPS, token_runs=None):
    """``steps`` steps of the banded core from the case's tables: the params
    and each step's metrics."""
    table = case["table"]
    shape = shape or SubwordShape(table.max_groups, 1, T)
    dev = _device_table(table) if subword else None
    params = EmbeddingPair(
        jnp.asarray(case["syn0"] if subword else case["syn0"][:V], dtype),
        jnp.asarray(case["syn1"], dtype),
        jnp.asarray(case["pos"], dtype) if positions else None)

    @jax.jit
    def step(params, dev, tokens, band, negatives):
        with jax.default_matmul_precision("highest"):
            return cbow_step_banded_core(
                params, tokens, band.left, band.right, band.center, band.token,
                negatives, jnp.asarray(0.05, dtype), NEG, W, "exact",
                compute_dtype or dtype, compute_dtype or dtype, with_metrics,
                subword=None if dev is None else (dev, shape), token_runs=token_runs)

    out = []
    for k in range(steps):
        params, metrics = step(params, dev, jnp.asarray(case["tokens"][k]),
                               case["bands"][k], jnp.asarray(case["negatives"][k]))
        out.append(metrics)
    return params, out


def _run_reference(case, subword, positions, dtype=jnp.float32):
    sizes = dict(v=V, k=BUCKETS, strings=case["strings"], ngram=(5, 5))
    lists = kind.reference_lists(sizes, case["tokens"], subword=subword)
    nctx, slots, rows = (np.stack(x) for x in zip(*(
        kind.window_examples(np.asarray(b.left), np.asarray(b.right),
                             np.asarray(b.center), W) for b in case["bands"])))
    ctx_word = np.stack([lists["slot_word"][k][slots[k]] for k in range(STEPS)])
    syn0 = case["syn0"] if subword else case["syn0"][:V]
    d_init = case["pos"] if positions else np.ones((2 * W, D))
    return ref_model.follow_steps(
        jnp.asarray(syn0, dtype), jnp.asarray(case["syn1"], dtype),
        jnp.asarray(d_init, dtype), jnp.asarray(case["tokens"]), jnp.asarray(ctx_word),
        jnp.asarray(rows), jnp.asarray(nctx), jnp.asarray(lists["lists"]),
        jnp.asarray(lists["nrows"]), jnp.asarray(case["negatives"]), [0.05] * STEPS,
        NEG, np.arange(syn0.shape[0]) < V, train_positions=positions), nctx


# -- the step against the reference -------------------------------------------------

@pytest.mark.parametrize("positions", [True, False], ids=["position_weights", "plain_window"])
@pytest.mark.parametrize("subword", [True, False], ids=["row_source", "one_row"])
def test_step_follows_the_reference(subword, positions):
    case = _case()
    params, metrics = _run_program(case, subword, positions, jnp.float32)
    ref, nctx = _run_reference(case, subword, positions)
    # float32 on both sides, sums in another order
    np.testing.assert_allclose(params.syn0, ref["syn0"], rtol=3e-5, atol=3e-7)
    np.testing.assert_allclose(params.syn1, ref["syn1"], rtol=3e-5, atol=3e-7)
    np.testing.assert_allclose([float(m.loss) for m in metrics], ref["losses"], rtol=2e-6)
    assert [float(m.pairs) for m in metrics] == (nctx > 0).sum(axis=1).tolist()
    if positions:
        np.testing.assert_allclose(params.pos, ref["d"], rtol=3e-6, atol=1e-7)
        assert np.abs(np.asarray(params.pos) - case["pos"]).max() > 1e-6   # it trains
    else:
        assert params.pos is None
    if subword:
        assert not np.allclose(params.syn0[V:], case["syn0"][V:])      # bucket rows moved
        assert float(metrics[0].subword_rows) == case["table"].counts[case["tokens"][0]].sum()
    else:
        assert metrics[0].subword_rows is None


@pytest.mark.parametrize("subword", [True, False], ids=["row_source", "one_row"])
def test_step_follows_the_reference_in_float64(subword):
    """Float64 tables and sums on both sides: what is left is the step's own
    float32 logits and coefficients (as in the plain banded step), ~1e-8, forty
    times under what the float32 case can hold."""
    case = _case()
    with jax.enable_x64():
        params, metrics = _run_program(case, subword, True, jnp.float64)
        ref, _ = _run_reference(case, subword, True, jnp.float64)
        for got, want in ((params.syn0, ref["syn0"]), (params.syn1, ref["syn1"]),
                          (params.pos, ref["d"])):
            np.testing.assert_allclose(got, want, rtol=1e-7, atol=2e-8)
        np.testing.assert_allclose([float(m.loss) for m in metrics], ref["losses"],
                                   rtol=1e-7)


def test_taps_are_the_written_sums_in_float64():
    """The float64 equivalence tests/test_cbow_banded.py holds the prefix sums
    to, for the taps, against loops written from the equations: at 1e-12 a
    position mirrored, dropped, or masked one slot off is a hard failure."""
    case = _case()
    band = case["bands"][0]
    left, right = np.asarray(band.left), np.asarray(band.right)
    rng = np.random.default_rng(9)
    x, g = rng.normal(size=(T, D)), rng.normal(size=(T, D))
    live = (rng.random(T) < 0.7).astype(np.float64)
    w = case["pos"]
    fwd, bwd = np.zeros((T, D)), np.zeros((T, D))
    sums, having = np.zeros((2 * W, D)), np.zeros(2 * W)
    for t in range(T):
        for p in list(range(-left[t], 0)) + list(range(1, right[t] + 1)):
            row = ref_model.position_row(p, W)
            fwd[t] += w[row] * x[t + p]
            bwd[t + p] += w[row] * g[t]
            sums[row] += g[t] * x[t + p]
            having[row] += live[t]
    with jax.enable_x64():
        from glint_word2vec_tpu.ops.cbow_banded import position_weight_sums
        args = (band.left, band.right, W)
        np.testing.assert_allclose(position_taps(jnp.asarray(x), jnp.asarray(w), *args),
                                   fwd, rtol=1e-12, atol=1e-13)
        np.testing.assert_allclose(
            position_taps(jnp.asarray(g), jnp.asarray(w), *args, transpose=True),
            bwd, rtol=1e-12, atol=1e-13)
        got_sums, got_having = position_weight_sums(
            jnp.asarray(g), jnp.asarray(x), jnp.asarray(live), *args)
        np.testing.assert_allclose(got_sums, sums, rtol=1e-11, atol=1e-12)
        np.testing.assert_array_equal(got_having, having)


def test_weights_of_one_are_the_interval_sum_in_float64():
    """Position weights that all equal one make the window the prefix-sum
    difference the plain banded step takes: one step moves the tables alike to
    1e-11 (the weights train too, which moves no table within the step)."""
    case = dict(_case(), pos=np.ones((2 * W, D)))
    with jax.enable_x64():
        got, _ = _run_program(case, False, True, jnp.float64, steps=1)
        want, _ = _run_program(case, False, False, jnp.float64, steps=1)
        np.testing.assert_allclose(got.syn0, want.syn0, rtol=1e-10, atol=1e-12)
        np.testing.assert_allclose(got.syn1, want.syn1, rtol=1e-10, atol=1e-12)


def test_taps_transpose_is_the_adjoint():
    """<taps(x), y> = <x, taps^T(y)> for every drawn window: the backward pass
    spreads exactly what the forward pass gathered."""
    case = _case()
    band = case["bands"][0]
    rng = np.random.default_rng(5)
    with jax.enable_x64():
        x, y = (jnp.asarray(rng.normal(size=(T, D))) for _ in range(2))
        w = jnp.asarray(case["pos"])
        fwd = position_taps(x, w, band.left, band.right, W)
        bwd = position_taps(y, w, band.left, band.right, W, transpose=True)
        np.testing.assert_allclose(jnp.sum(fwd * y), jnp.sum(x * bwd), rtol=1e-12)


# -- the row source's forms ----------------------------------------------------------

# (max_run, head_cap, slot_cap, tail_cap): every token slot the head of its own
# list, with room for all of them (one branch, known while tracing), and with
# too few heads (the chunked plain form, chosen by the step); under the first,
# the lists' slots sorted by row and cut to a capacity over every block's live
# slots (what the trainer builds where the counts promise one), to one that
# every block overflows (the whole form, chosen by the step), and to one
# between the blocks' 3,531 / 3,636 / 3,592 live slots (a form a step). The
# ``tail`` forms run on the table with long words (three groups a list, 4,807 /
# 4,902 / 4,888 live slots a block): every token's first group gathered densely
# and the later groups of the blocks' 133 / 130 / 135 tail tokens in passes of
# a capacity over them all (one pass, beside the slot capacity: what the trainer
# builds), of one that every block overflows (three passes), and of one
# between them (one pass or two, chosen by the step)
FORMS = {"capacity_held": (1, T, 0, 0), "capacity_overflowed": (1, 16, 0, 0),
         "slots_cut": (1, T, 4096, 0), "slot_capacity_overflowed": (1, T, 2048, 0),
         "slot_capacity_straddled": (1, T, 3600, 0),
         "tails_cut": (1, T, 8192, 192), "tail_capacity_overflowed": (1, T, 0, 64),
         "tail_capacity_straddled": (1, T, 0, 132)}


def _form_case(form):
    return _case(long_words=form.startswith("tail"))


def _form_shape(table, form):
    max_run, head_cap, slot_cap, tail_cap = FORMS[form]
    return SubwordShape(table.max_groups, max_run, head_cap, slot_cap=slot_cap,
                        tail_cap=tail_cap)


def _slots_handed(case, form, step=0):
    """What ``subword_slots`` must read: the capacity where the block's live
    slots fit it, else every slot of the block."""
    table, cap = case["table"], FORMS[form][2]
    live = table.counts[case["tokens"][step]].sum()
    return cap if cap and live <= cap else T * table.max_groups * 8


def _tail_tokens(case, step):
    return int((case["table"].counts[case["tokens"][step]] > 8).sum())


def _gather_slots_handed(case, form, step=0):
    """What ``subword_gather_slots`` must read: every slot of the block, or,
    under a tail capacity, every token's first group and the capacity's later
    groups a pass of the loop over the block's tail tokens."""
    groups, cap = case["table"].max_groups, FORMS[form][3]
    if cap:
        return T * 8 + -(-_tail_tokens(case, step) // cap) * cap * (groups - 1) * 8
    return T * groups * 8


@pytest.mark.parametrize("subword", [True, False], ids=["token_lists", "words_alone"])
@pytest.mark.parametrize("cap1", [T, 64], ids=["coalesced", "over_the_cap"])
def test_token_runs_beside_the_row_source_give_the_same_sums(cap1, subword):
    """``token_runs`` (PR 46): beside the token row source syn1's scatter alone
    goes by runs of the tokens that train an example, and syn0's update stays
    the lists' (no ``syn0_rows``); without it both tables' do. To the
    reference either way, position weights on."""
    case = _case()
    want, _ = _run_reference(case, subword, True)
    got, metrics = _run_program(case, subword, True, jnp.float32,
                                token_runs=(4, cap1, cap1))
    v = V + BUCKETS if subword else V
    np.testing.assert_allclose(got.syn0, want["syn0"][:v], rtol=3e-5, atol=3e-7)
    np.testing.assert_allclose(got.syn1, want["syn1"], rtol=3e-5, atol=3e-7)
    plain, _ = _run_program(case, subword, True, jnp.float32)
    if cap1 < T:      # a plain branch is the step's own scatter, bit for bit
        np.testing.assert_array_equal(np.asarray(got.syn0), np.asarray(plain.syn0))
        np.testing.assert_array_equal(np.asarray(got.syn1), np.asarray(plain.syn1))
    for m, tokens, band in zip(metrics, case["tokens"], case["bands"]):
        live = np.asarray(band.center) * (
            (np.asarray(band.left) + np.asarray(band.right)) > 0)
        pieces = [int((-(-np.unique(t, return_counts=True)[1] // 4)).sum())
                  for t in (tokens, tokens[live > 0])]
        assert 64 < pieces[1] < pieces[0] < T
        assert float(m.syn1_rows) == (pieces[1] if cap1 == T else T)
        assert (m.syn0_rows is None) if subword else (
            float(m.syn0_rows) == (pieces[0] if cap1 == T else T))


@pytest.mark.parametrize("form", list(FORMS))
def test_row_source_branches_give_the_same_sums(form):
    case = _form_case(form)
    table = case["table"]
    want, _ = _run_reference(case, True, True)
    got, metrics = _run_program(case, True, True, jnp.float32,
                                shape=_form_shape(table, form))
    np.testing.assert_allclose(got.syn0, want["syn0"], rtol=3e-5, atol=3e-7)
    np.testing.assert_allclose(got.syn1, want["syn1"], rtol=3e-5, atol=3e-7)
    np.testing.assert_allclose(got.pos, want["d"], rtol=3e-6, atol=1e-7)
    # either way every token's list reaches the scatter once
    live = [table.counts[tokens].sum() for tokens in case["tokens"]]
    assert [float(m.subword_rows) for m in metrics] == live
    assert [float(m.subword_slots) for m in metrics] == [
        _slots_handed(case, form, k) for k in range(STEPS)]
    assert [float(m.subword_gather_slots) for m in metrics] == [
        _gather_slots_handed(case, form, k) for k in range(STEPS)]
    tails = [_tail_tokens(case, k) for k in range(STEPS)]
    if form == "tails_cut":
        assert 0 < min(tails) and max(tails) <= FORMS[form][3] < T // 4
        assert max(live) <= FORMS[form][2]
    if form == "tail_capacity_overflowed":
        assert min(tails) > FORMS[form][3]
    if form == "tail_capacity_straddled":
        assert min(tails) <= FORMS[form][3] < max(tails)
    if form == "slots_cut":
        assert max(live) <= FORMS[form][2] < 0.5 * T * table.max_groups * 8
    if form == "slot_capacity_overflowed":
        assert min(live) > FORMS[form][2]
    if form == "slot_capacity_straddled":
        assert min(live) <= FORMS[form][2] < max(live)


@pytest.mark.parametrize("form", ["slots_cut", "slot_capacity_overflowed",
                                  "tails_cut", "tail_capacity_overflowed"])
def test_slot_capacity_branches_give_the_same_sums_in_float64(form):
    """The cut and its overflow, the gather's two parts and theirs, against the
    reference with float64 tables and sums: a live slot the cut dropped, a
    padding slot it kept, a tail group dropped or read twice, is a row's whole
    update or vector, a million times what this can hold."""
    case = _form_case(form)
    with jax.enable_x64():
        params, metrics = _run_program(case, True, True, jnp.float64,
                                       shape=_form_shape(case["table"], form))
        ref, _ = _run_reference(case, True, True, jnp.float64)
        for got, want in ((params.syn0, ref["syn0"]), (params.syn1, ref["syn1"]),
                          (params.pos, ref["d"])):
            np.testing.assert_allclose(got, want, rtol=1e-7, atol=2e-8)
        assert float(metrics[0].subword_slots) == _slots_handed(case, form)
        assert float(metrics[0].subword_gather_slots) == _gather_slots_handed(case, form)


def test_the_trainers_row_source_builds_one_branch():
    """With room for every token slot and no capacity the step holds no
    conditional (the head capacity is known while tracing) and no loop; with
    the slot capacity it holds exactly one conditional, around the lists'
    scatter, and the gather stays outside any; the tail capacity adds no
    conditional, alone or beside the slot capacity (what the trainer builds
    where the counts promise both), but the one loop over the passes of the
    tail tokens' later groups: a block over the capacity has no form of its
    own."""
    case = _case(long_words=True)
    table = case["table"]
    band = case["bands"][0]

    def lowered(cap, slot_cap=0, tail_cap=0):
        return jax.jit(lambda p, dev, tk, n: cbow_step_banded_core(
            p, tk, band.left, band.right, band.center, band.token, n,
            jnp.float32(0.05), NEG, W, subword=(dev, SubwordShape(
                table.max_groups, 1, cap, slot_cap=slot_cap, tail_cap=tail_cap)))).lower(
            EmbeddingPair(jnp.asarray(case["syn0"], jnp.float32),
                          jnp.asarray(case["syn1"], jnp.float32),
                          jnp.asarray(case["pos"], jnp.float32)),
            _device_table(table), jnp.asarray(case["tokens"][0]),
            jnp.asarray(case["negatives"][0])).as_text()

    def conditionals(text):
        return text.count("stablehlo.case") + text.count("stablehlo.if")

    def sorts(text):
        return text.count("stablehlo.sort")

    def loops(text):
        return text.count("stablehlo.while")

    _, _, slot_cap, tail_cap = FORMS["tails_cut"]
    neither = lowered(T)
    assert (conditionals(neither), loops(neither)) == (0, 0)
    assert conditionals(lowered(16)) >= 1
    cut = lowered(T, slot_cap)
    assert (conditionals(cut), loops(cut)) == (1, 0)
    # one sort more than the program without the capacity: the cut branch's own
    assert sorts(cut) == sorts(neither) + 1
    tails = lowered(T, tail_cap=tail_cap)
    assert (conditionals(tails), loops(tails)) == (0, 1)
    both = lowered(T, slot_cap, tail_cap)
    # (the tail tokens' positions are compacted by the sort the heads' are: one
    # function of the lowered text, called twice)
    assert (conditionals(both), loops(both), sorts(both)) == (1, 1, sorts(cut))


@pytest.mark.parametrize("slot_cap, tail_cap", [(0, 0), (2048, 0), (2048, 64)],
                         ids=["every_slot", "slots_cut", "tail_cap"])
def test_masked_slots_and_the_lane_padding_stay_zero(slot_cap, tail_cap):
    """A block whose tail is not valid (token_mask 0) lists nothing and moves
    nothing for it, and zero columns stay exactly zero, the weights' too: with
    every slot of the block handed to the gather and the scatter, with the
    slots sorted by row and cut (a third of the block is live: its slots fit
    2,048), and with the gather in its two parts besides (on the table with
    long words: 49 tail tokens and 1,627 slots of the block's third are live)."""
    case = _case(long_words=bool(tail_cap))
    table, pad, real = case["table"], 8, T // 3
    tokens = np.where(np.arange(T) < real, case["tokens"][0], 0).astype(np.int32)
    starts = np.packbits(np.arange(T) % 40 == 0, bitorder="little")
    band = device_cbow_windows(
        jnp.asarray(tokens), jnp.asarray(starts), jnp.int32(real), jnp.uint32(0),
        jnp.uint32(0), jnp.uint32(stream_base(1, STREAM_WINDOW, 1, 0)),
        window=W, halo=W)
    assert float(jnp.sum(band.token)) == real

    def padded(x):
        return jnp.pad(jnp.asarray(x, jnp.float32), ((0, 0), (0, pad)))

    params = EmbeddingPair(padded(case["syn0"]), padded(case["syn1"]), padded(case["pos"]))
    got, metrics = cbow_step_banded_core(
        params, jnp.asarray(tokens), band.left, band.right, band.center, band.token,
        jnp.asarray(case["negatives"][0]), jnp.float32(0.05), NEG, W, "exact",
        jnp.bfloat16, jnp.bfloat16,
        subword=(_device_table(table), SubwordShape(
            table.max_groups, 1, T, slot_cap=slot_cap, tail_cap=tail_cap)))
    for leaf in got:
        assert not np.asarray(leaf[:, D:]).any()
    groups = table.max_groups
    assert float(metrics.subword_rows) == table.counts[tokens[:real]].sum()
    assert float(metrics.subword_slots) == (slot_cap or T * groups * 8)
    if tail_cap:
        # a masked slot is no word and has no tail: the live third's fit one pass
        assert 0 < (table.counts[tokens[:real]] > 8).sum() <= tail_cap
    assert float(metrics.subword_gather_slots) == (
        T * 8 + tail_cap * (groups - 1) * 8 if tail_cap else T * groups * 8)
    touched = np.unique(np.concatenate([table.rows_of(w) for w in np.unique(tokens[:real])]))
    still = np.setdiff1d(np.arange(V + BUCKETS), touched)
    np.testing.assert_array_equal(got.syn0[still], params.syn0[still])


# -- configurations that use neither compile the parent's step ----------------------

# sha256[:16] of the lowered (StableHLO) text of both step twins of the
# `cbow-3m-300.train` and `subword-sgns-2.5m-300.train` cells at their `tiny`
# sizes, taken at the parent commit of PR 33 (4bf51b6) and equal on PR 33's
# tree: the token row source, the taps and the third leaf add no op and no
# argument where the model uses none of them. A later PR that changes either
# step on purpose takes new digests from its own tree (the failure prints them).
# PR 34 did, for the subword skip-gram step: its row source plans one list per
# distinct center word where the trainer's rule derives a word cap (it does at
# the tiny sizes), so those two digests are PR 34's tree's. The token block's
# row source shares `plan_centers` and took none of it: `cbow-subword-2m-300.train`'s
# two were the parent commit of PR 34's (1b95d5f) until PR 36, which changed that
# step on purpose (its lists' scatter under a slot capacity, which the trainer's
# rule derives at the tiny sizes too: 10,240 of 16,464 slots), and PR 43, which
# changed it again (its lists' gather in two parts under a tail capacity; at the
# tiny sizes the strings' law gives words of 9 letters at most, one group of 8
# rows, so the rule derives none and the program gathers its 2,058 x 8 slots
# whole: what the digests see of PR 43 is the step's new counter,
# `StepMetrics.subword_gather_slots`). PR 46 changed both banded steps on
# purpose (the token scatters by runs of the block's tokens sorted inside the
# step, whose caps the trainer's rule derives at the tiny sizes too: 1,536 and
# 1,280 of 2,058 slots; beside the token row source syn1's alone): those four
# are PR 46's tree's. The SGNS and hierarchical-softmax steps' four, which share
# `ops/sgns.scatter_add_by_runs` with the banded step, are the parent commit of
# PR 46's (9decdaa): the helper's new `keep` adds no op where it is not given.
# PR 47 changed the subword skip-gram step on purpose (its per-word scatter cut
# to a slot capacity, which the trainer's rule derives at the tiny sizes too:
# 8,192 of 16,384 slots, a fourth entry of the scatter's switch; and the
# counter `StepMetrics.subword_slots`): those two are PR 47's tree's. The token
# block's row source shares `spread_sorted` and `scatter_slots` and took none
# of it: the other eight are as they were. PR 58 changed the shared-pool SGNS
# step on purpose (each coalesced scatter under a ladder of two caps, one flat
# switch of three entries, which the trainer's rule derives at the tiny sizes
# too: (576, 768) and (640, 768) of 2,048 pairs; and the counters
# `StepMetrics.syn0_slots` / `.syn1_slots`): `sgns-3m-300.train`'s two are PR
# 58's tree's. The CBOW, subword, subword-CBOW and hierarchical-softmax steps
# are handed one cap a scatter as before and share the helper
# (`scatter_add_by_runs` returns the slots beside the rows; unread, they leave
# no op): the other eight are as they were.
PARENT_STEP_TEXT = {
    ("cbow-3m-300.train", "train_cbow", "_step_fn"): "b004263a353a0230",
    ("cbow-3m-300.train", "train_cbow", "_step_fn_fast"): "93b9ca55222cf02a",
    ("subword-sgns-2.5m-300.train", "train_subword", "_step_fn"): "07bf3255d9a6288a",
    ("subword-sgns-2.5m-300.train", "train_subword", "_step_fn_fast"): "22b45970317cbaaf",
    ("cbow-subword-2m-300.train", "train_cbow_subword", "_step_fn"): "3652dd4987fcefa7",
    ("cbow-subword-2m-300.train", "train_cbow_subword", "_step_fn_fast"): "b4492ee4d2d96c50",
    ("sgns-3m-300.train", "train", "_step_fn"): "8ce8095e605b956f",
    ("sgns-3m-300.train", "train", "_step_fn_fast"): "2f1ac1ca7cdea1fb",
    ("skipgram-hs-3m-300.train", "train_hs", "_step_fn"): "1ca84c1f23489d27",
    ("skipgram-hs-3m-300.train", "train_hs", "_step_fn_fast"): "6841e75acfc947f2",
}


@pytest.mark.parametrize("cell_name, kind_name, twin", list(PARENT_STEP_TEXT),
                         ids=lambda v: v.split(".")[0] if "." in v else v)
def test_steps_without_the_new_parts_lower_to_the_parents_text(cell_name, kind_name, twin):
    import importlib

    from harness import loader

    from glint_word2vec_tpu.parallel.distributed import put_global

    cell = loader.resolve(loader.load_manifest(ROOT), cell_name, ROOT)
    trainer, _, _ = importlib.import_module("kinds." + kind_name).build_trainer(
        cell, 0, tiny=True)
    shape = trainer._subword_shape
    if kind_name == "train_cbow_subword":
        # every token slot its own list, no second level under them, the
        # lists' scatter under a slot capacity, and no tail capacity for lists
        # of one group
        assert (shape.max_run, shape.head_cap, shape.word_cap) == (
            1, trainer._tokens_per_step, 0)
        assert 0 < shape.slot_cap < trainer._tokens_per_step * shape.max_groups * 8
        assert (shape.max_groups, shape.tail_cap) == (1, 0)
    else:
        assert trainer.params.pos is None
        # the subword skip-gram step: a word level, under it the slot
        # capacity of the word heads' block (8,192 of 16,384 slots), no tails
        assert shape is None or (
            0 < shape.slot_cap < shape.word_cap * shape.max_groups * 8
            and shape.tail_cap == 0)
    # the banded steps' token scatters coalesce at the tiny sizes too
    assert all(trainer._token_caps) == trainer._banded_cbow
    cfg = trainer.config
    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    zeros = np.zeros((2, k), np.float32)
    if trainer._banded_cbow:
        t = trainer._tokens_per_step
        staged = put_global(trainer._chunk_shardings, {
            "tokens": np.zeros((k, 1, t), trainer._pair_dtype),
            "starts": np.zeros((k, 1, -(-t // 8)), np.uint8),
            "obase": np.zeros((k, 1, 2), np.int32)})
        meta, base, sub, win = trainer._stage_dispatch_meta(
            zeros, 0, np.zeros(1, np.uint32), np.zeros(1, np.uint32))
        args = (staged, meta, base, trainer._table_prob, trainer._table_alias,
                trainer._keep_prob_dev, sub, win, *trainer._step_extra)
    else:
        staged = put_global(trainer._chunk_shardings,
                            {"pairs": np.zeros((k, 2, b), trainer._pair_dtype)})
        meta, base = trainer._stage_dispatch_meta(zeros, 0)
        # the sampler's tables (none under hierarchical softmax), then the row
        # table's or the path table's arrays
        args = (staged, meta, base, *trainer._sampler_args, *trainer._step_extra)
    text = getattr(trainer, twin).lower(trainer.params, *args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_STEP_TEXT[cell_name, kind_name, twin]


# -- the normal path: Trainer.fit, the model, the checkpoint ------------------------

FIT = dict(vector_size=24, window=3, negatives=10, min_count=1, pairs_per_batch=128,
           negative_pool=16, learning_rate=0.02, num_iterations=3, seed=1,
           subsample_ratio=0.0, steps_per_dispatch=2, heartbeat_every_steps=4,
           cbow=True, cbow_update="banded", cbow_position_weights=True,
           subword=True, subword_min_n=5, subword_max_n=5, subword_buckets=500)


def _sentences():
    strings = bench_words.make_words(11, 60)
    rng = np.random.default_rng(2)
    return [[strings[i] for i in rng.integers(0, 60, 12)] for _ in range(400)]


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    from glint_word2vec_tpu import Word2Vec
    path = str(tmp_path_factory.mktemp("fit") / "telemetry.jsonl")
    model = Word2Vec(telemetry_path=path, **FIT).fit(_sentences())
    return model, path


def test_fit_trains_all_three_leaves(fitted):
    model, _ = fitted
    assert model.position_weights.shape == (6, 24)
    assert np.isfinite(model.position_weights).all()
    assert np.abs(model.position_weights - 1.0).max() > 1e-4
    assert np.abs(np.asarray(model._buckets)).max() > 0.5 / 24     # bucket rows moved


def test_model_composes_the_fitted_vectors_and_answers_an_unseen_string(fitted):
    model, _ = fitted
    table = np.concatenate([np.asarray(model._raw0), np.asarray(model.subword_buckets)])
    v = model.vocab.size
    for word in model.vocab.words[:5]:
        want = subword_ref.word_vector(table, word, model.vocab.index[word], v,
                                       500, 5, 5)
        np.testing.assert_allclose(model.transform(word), want, rtol=1e-5, atol=1e-7)
    unseen = model.vocab.words[0] + model.vocab.words[1]
    assert unseen not in model.vocab.index
    np.testing.assert_allclose(
        model.transform(unseen),
        subword_ref.word_vector(table, unseen, None, v, 500, 5, 5), rtol=1e-5, atol=1e-7)
    assert len(model.find_synonyms(model.vocab.words[0], 3)) == 3


def test_position_weights_are_saved_restored_and_not_exported(fitted, tmp_path):
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    model, _ = fitted
    path = str(tmp_path / "model")
    model.save(path)
    digests = json.load(open(os.path.join(path, "metadata.json")))["digests"]
    assert "position_weights.npy" in digests
    back = Word2VecModel.load(path)
    np.testing.assert_array_equal(back.position_weights, model.position_weights)
    text = str(tmp_path / "vectors.txt")
    model.export_word2vec(text)
    header = open(text).readline().split()
    assert header == [str(model.vocab.size), "24"]      # words' vectors alone
    # a file of another shape is refused, and so is a missing one
    np.save(os.path.join(path, "position_weights.npy"), np.ones((4, 24), np.float32))
    with pytest.raises(ValueError, match="position weights of shape"):
        Word2VecModel.load(path, verify=False)
    os.remove(os.path.join(path, "position_weights.npy"))
    with pytest.raises(ValueError, match="position weights of shape None"):
        Word2VecModel.load(path, verify=False)


def test_checkpoint_resume_restores_the_third_leaf(tmp_path):
    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.train.checkpoint import load_model
    ck = str(tmp_path / "ck")
    first = Word2Vec(**FIT).fit(_sentences(), checkpoint_path=ck)
    saved = load_model(ck)
    np.testing.assert_array_equal(saved["position_weights"], first.position_weights)
    resumed = Word2Vec.resume(ck, _sentences())       # finished: loads, trains nothing
    np.testing.assert_array_equal(resumed.position_weights, first.position_weights)
    np.testing.assert_array_equal(np.asarray(resumed._buckets), np.asarray(first._buckets))


def test_heartbeat_reports_the_rows_and_the_drift(fitted):
    _, path = fitted
    from glint_word2vec_tpu.obs.spans import default_tracer
    blocks = [e for e in default_tracer().events() if e["name"] == "device_block"
              and "position_drift" in e.get("args", {})]
    assert blocks, "device_block carries position_drift on this model"
    assert all(0 < e["args"]["position_drift"] < 0.5 for e in blocks)
    assert all(1.0 < e["args"]["subword_rows_per_pair"] < 40 for e in blocks)
    # slots handed to the lists' scatter, live or padding: never fewer than the
    # live rows, and at most every slot of a block over the live examples
    assert all(e["args"]["subword_rows_per_pair"] <= e["args"]["subword_slots_per_pair"]
               < 200 for e in blocks)
    # and to their gather: every slot of a block of one-group lists
    assert all(e["args"]["subword_rows_per_pair"] <= e["args"]["subword_gather_slots_per_pair"]
               < 200 for e in blocks)
    assert any(json.loads(line).get("event", json.loads(line).get("kind")) for line in open(path))


def test_the_health_probe_reads_the_third_leaf():
    from glint_word2vec_tpu.obs.probe import make_health_probe
    probe = make_health_probe(4, 10.0)
    table = jnp.ones((4, 8), jnp.float32)
    assert bool(probe(EmbeddingPair(table, table, jnp.ones((6, 8)))).finite)
    assert not bool(probe(EmbeddingPair(
        table, table, jnp.ones((6, 8)).at[2, 3].set(jnp.nan))).finite)
    assert bool(probe(EmbeddingPair(table, table)).finite)


# -- the config's rows and refusals ------------------------------------------------

BANDED = dict(cbow=True, cbow_update="banded", pairs_per_batch=4096)


@pytest.mark.parametrize("row", [
    dict(BANDED, subword=True, subword_min_n=5, subword_max_n=5,
         cbow_position_weights=True, negatives=10),
    dict(BANDED, subword=True),
    dict(BANDED, cbow_position_weights=True),
    dict(BANDED, cbow_position_weights=True, num_data_shards=2),
], ids=["both", "row_source_alone", "position_weights_alone", "position_weights_on_a_mesh"])
def test_legal_rows_construct(row):
    cfg = Word2VecConfig(**row)
    assert cfg.negative_pool > 0
    assert Word2VecConfig.from_dict(cfg.to_dict()).cbow_position_weights == \
        cfg.cbow_position_weights


@pytest.mark.parametrize("beside, says", [
    (dict(cbow=True, subword=True), "needs cbow_update='banded'"),
    (dict(cbow=True, cbow_update="scatter", subword=True, negative_pool=64),
     "needs cbow_update='banded'"),
    (dict(cbow_position_weights=True), "requires cbow=True with cbow_update='banded'"),
    (dict(cbow=True, cbow_position_weights=True),
     "requires cbow=True with cbow_update='banded'"),
    (dict(BANDED, cbow_position_weights=True, sharded_checkpoint=True),
     "does not support sharded_checkpoint"),
    (dict(BANDED, subword=True, num_model_shards=2), "trains on one device"),
    (dict(BANDED, subword=True, sharded_checkpoint=True),
     "does not support sharded_checkpoint"),
    (dict(BANDED, subword=True, max_row_norm=5.0), "does not support max_row_norm"),
    (dict(BANDED, subword=True, negative_pool=0), "requires the shared-pool"),
], ids=lambda v: "-".join(f"{k}" for k in v) if isinstance(v, dict) else None)
def test_what_the_new_rows_are_refused_beside(beside, says):
    with pytest.raises(ValueError, match=says):
        Word2VecConfig(**beside)


def test_params_of_another_shape_are_refused_at_the_trainer():
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.train.trainer import Trainer
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(50)], np.arange(50, 0, -1).astype(np.int64) * 10)
    cfg = Word2VecConfig(vector_size=8, window=3, negative_pool=16, pairs_per_batch=64,
                         min_count=1, **{k: v for k, v in BANDED.items()
                                         if k != "pairs_per_batch"},
                         cbow_position_weights=True)
    tables = (jnp.zeros((50, 8)), jnp.zeros((50, 8)))
    with pytest.raises(ValueError, match="needs position weights of 6 rows"):
        Trainer(cfg, vocab, params=EmbeddingPair(*tables, jnp.ones((10, 8))))
    with pytest.raises(ValueError, match="needs position weights"):
        Trainer(cfg.replace(cbow_position_weights=False), vocab,
                params=EmbeddingPair(*tables, jnp.ones((6, 8))))
    trainer = Trainer(cfg, vocab)
    assert trainer.params.pos.shape == (6, trainer.padded_dim)
    assert float(trainer.params.pos[:, :8].min()) == 1.0
    assert not np.asarray(trainer.params.pos[:, 8:]).any()
