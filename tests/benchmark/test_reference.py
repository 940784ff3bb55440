"""The plain reference against the program at a small size on the CPU; the
lower-precision control failing ``correct``'s limits; and a run whose timed
path is broken underneath coming out not correct."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader, weights  # noqa: E402
from kinds import query as query_kind  # noqa: E402
from kinds import train as train_kind  # noqa: E402
from reference import sgns_ref  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
TRAIN_CELLS = [w["name"] for w in MANIFEST["workloads"]
               if w["chips"] == 1 and loader.resolve(MANIFEST, w["name"])["kind"] == "train"]
QUERY_CELLS = [w["name"] for w in MANIFEST["workloads"]
               if loader.resolve(MANIFEST, w["name"])["kind"] == "query"]


def test_reference_step_matches_the_programs_float32_step():
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core

    rng = np.random.default_rng(3)
    v, d, b, p = 500, 24, 128, 16
    syn0 = jnp.asarray(rng.normal(0, 0.1, (v, d)), jnp.float32)
    syn1 = jnp.asarray(rng.normal(0, 0.1, (v, d)), jnp.float32)
    c = jnp.asarray(rng.integers(0, v, b), jnp.int32)       # duplicates included
    x = jnp.asarray(rng.integers(0, v, b), jnp.int32)
    negs = jnp.asarray(rng.integers(0, v, p), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, metrics = sgns_step_shared_core(
            EmbeddingPair(syn0, syn1), c, x, jnp.ones(b), negs, jnp.float32(0.05), 5)
    got0, got1, loss = sgns_ref.sgns_step(syn0, syn1, c, x, negs, jnp.float32(0.05), 5)
    np.testing.assert_allclose(got0, want.syn0, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got1, want.syn1, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(loss, metrics.loss, rtol=1e-6)
    assert not np.allclose(got0, syn0)


def test_reference_cosine_matches_find_synonyms_batch():
    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    v, d, k = 3000, 20, 10
    table = weights.make_table(9, 0, v, d, d, 0.5, jnp.float32)
    vocab = Vocabulary.from_words_and_counts([f"w{i}" for i in range(v)], np.ones(v))
    model = Word2VecModel(vocab, table)
    qids = np.asarray([0, 7, 1234, 2999], np.int32)
    served = model.find_synonyms_batch([f"w{i}" for i in qids], k)
    scores = sgns_ref.cosine_scores(
        lambda ids: weights.rows_uniform(weights.seed32(9), 0, ids, d, d, 0.5), v,
        table[jnp.asarray(qids)])
    for row, wid, reply in zip(scores, qids, served):
        row = row.copy()
        row[wid] = -np.inf
        best = np.argsort(-row)[:k]
        assert [int(w[1:]) for w, _ in reply] == best.tolist()
        np.testing.assert_allclose([s for _, s in reply], row[best], atol=1e-6)


def test_tables_are_a_function_of_seed_and_row_alone():
    import jax.numpy as jnp

    full = weights.make_table(2**31 + 5, 0, 64, 5, 8, 0.5, jnp.float32)
    rows = jnp.asarray([3, 60, 11], jnp.int32)
    some = weights.rows_uniform(weights.seed32(2**31 + 5), 0, rows, 5, 8, 0.5)
    np.testing.assert_array_equal(full[rows], some)
    assert float(jnp.abs(full[:, 5:]).max()) == 0.0 and float(jnp.abs(full).max()) <= 0.5
    other = weights.make_table(2**31 + 6, 0, 64, 5, 8, 0.5, jnp.float32)
    assert not np.allclose(full, other)


@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
@pytest.mark.parametrize("control", [False, True])
def test_train_limits_pass_the_program_and_fail_bfloat16_tables(cell_name, control):
    cell = loader.resolve(MANIFEST, cell_name)
    limits = loader.sizes(cell["config"], True)["check"]["train"]
    got, = train_kind.check_readings(cell, [41], control=control, tiny=True)
    over = [n for n in got if got[n] > limits.get(n.removeprefix("fast_"), 0.0)]
    assert bool(over) == control, (got, limits)


@pytest.mark.parametrize("cell_name", QUERY_CELLS)
@pytest.mark.parametrize("control", [False, True])
def test_query_limits_pass_the_program_and_fail_bfloat16_tables(cell_name, control):
    cell = loader.resolve(MANIFEST, cell_name)
    limits = loader.sizes(cell["config"], True)["check"]["query"]
    got, = query_kind.check_readings(cell, [43], control=control, tiny=True)
    over = [n for n in limits if got[n] > limits[n]]
    assert bool(over) == control, (got, limits)


def _state_unchanged(real, params, args):
    return params, real(params, *args)[1]


def _syn0_never_moves(real, params, args):
    moved, metrics = real(params, *args)
    return moved._replace(syn0=params.syn0), metrics


def _syn1_never_moves(real, params, args):
    moved, metrics = real(params, *args)
    return moved._replace(syn1=params.syn1), metrics


def _fast_twin_drops_half_the_batch(real, params, args):
    with_metrics = args[10]
    if with_metrics:
        return real(params, *args)
    mask = args[2]
    half = mask * (np.arange(mask.shape[0]) % 2)
    return real(params, *args[:2], half, *args[3:])


@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "change_norm_gap"),
    (_syn0_never_moves, "first_gradient_norm_gap"),
    (_syn1_never_moves, "first_gradient_norm_gap"),
    (_fast_twin_drops_half_the_batch, "fast_change_norm_gap"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_step_broken_underneath_is_not_correct(cell_name, fault, caught_by,
                                                 monkeypatch, capsys):
    """The rest of a run, past the harness's look for a chip, with the timed path
    broken underneath: a step that moves nothing, that moves one table only, or
    whose metrics-elided twin (half of the window's dispatches) loses pairs."""
    from glint_word2vec_tpu.train import trainer as trainer_module

    real = trainer_module.sgns_step_shared_core
    monkeypatch.setattr(trainer_module, "sgns_step_shared_core",
                        lambda params, *args, **kw: fault(
                            lambda p, *a: real(p, *a, **kw), params, args))
    cell = loader.resolve(MANIFEST, cell_name)
    out = train_kind.run(cell, seed=45, seconds=1.0, trace=False, tiny=True,
                         t_start=time.perf_counter())
    assert out["correct"] is False and out["attempted"] > 0
    failed = [l.split()[1].rstrip(":") for l in capsys.readouterr().out.splitlines()
              if l.startswith("check ") and l.endswith("FAILED")]
    assert caught_by in failed, failed
    if fault is _fast_twin_drops_half_the_batch:
        assert not [n for n in failed if not n.startswith("fast_")], failed


@pytest.mark.parametrize("cell_name", QUERY_CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell_name, monkeypatch):
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    real = Word2VecModel.find_synonyms_batch

    def altered(self, queries, num, **kw):
        rows = real(self, queries, num, **kw)
        return [[("w1", 0.5)] + row[1:] if row[0][0] != "w1" else row for row in rows]

    monkeypatch.setattr(Word2VecModel, "find_synonyms_batch", altered)
    cell = loader.resolve(MANIFEST, cell_name)
    out = query_kind.run(cell, seed=47, seconds=1.0, trace=False, tiny=True,
                         t_start=time.perf_counter())
    assert out["correct"] is False and out["failed"] == 0


def test_limits_are_stated_for_every_number_a_kind_compares():
    for cfg in MANIFEST["configs"]:
        check = json.load(open(os.path.join(ROOT, cfg["file"])))["check"]
        if "train" in check:
            assert set(check["train"]) == {"loss_rel_gap", "first_gradient_norm_gap",
                                           "change_norm_gap"}
        if "query" in check:
            assert set(check["query"]) == {"score_abs_err_mean", "score_abs_err_max",
                                           "rank_gap_max"}
