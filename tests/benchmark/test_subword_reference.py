"""The plain subword reference against the program's step at a small size on
the CPU; a rehearsal of kind ``train_subword``; the lower-precision control
failing every norm limit of ``correct``, the bucket rows' among them; and a run
whose timed path is broken underneath coming out not correct."""

import importlib
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader  # noqa: E402
from kinds import train_subword as kind  # noqa: E402
from reference import subword_ref  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "subword-sgns-2.5m-300.train"


def _cell() -> dict:
    return loader.resolve(MANIFEST, CELL)


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "reference", "subword_ref.py")).read()
    assert "glint_word2vec_tpu" not in source.split('"""', 2)[2]
    assert "jax.value_and_grad" in source and '"highest"' in source


def test_reference_word_vector_for_a_seen_and_an_unseen_string():
    rng = np.random.default_rng(3)
    v, k = 5, 40
    table = rng.normal(size=(v + k, 6)).astype(np.float32)
    rows = subword_ref.word_rows("the", 2, v, k)
    assert rows[0] == 2 and len(rows) == 7 and all(v <= r < v + k for r in rows[1:])
    np.testing.assert_allclose(subword_ref.word_vector(table, "the", 2, v, k),
                               table[rows].mean(axis=0), rtol=1e-6)
    np.testing.assert_allclose(subword_ref.word_vector(table, "the", None, v, k),
                               table[rows[1:]].mean(axis=0), rtol=1e-6)


def test_tiny_rehearsal_of_the_kind_is_correct(capsys):
    out = kind.run(_cell(), seed=2**31 + 49, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    printed = capsys.readouterr().out
    assert "row table" in printed and "check row_table_mismatches: 0" in printed
    assert out["counters"]["subword_table_s"] > 0
    assert set(out["end_to_end"]) == {"train_pairs_per_s", "train_loss_at_budget",
                                      "setup_s"}
    # the check's batches are the feed's: a head's list once a center run
    assert 2.0 < out["shapes"]["subword_rows_per_pair"] < 10.0
    cost = importlib.import_module("costs.subword_step").cost(**out["shapes"])
    assert cost["bytes"] > 0 and cost["flops"] > 0


@pytest.mark.parametrize("control", [False, True], ids=["program", "bfloat16_tables"])
def test_limits_pass_the_program_and_fail_bfloat16_tables(control):
    cell = _cell()
    limits = loader.sizes(cell["config"], True)["check"]["train"]
    got, = kind.check_readings(cell, [41], control=control, tiny=True)

    def limit(name):
        return limits.get(name.removeprefix("fast_").split(".")[0], 0.0)

    over = {n for n in got if got[n] > limit(n)}
    if not control:
        assert not over, (got, limits)
    else:
        # every norm limit fails, each leaf's: the bucket rows' too
        norms = {n for n in got if "norm_gap" in n}
        assert len(norms) == 12 and norms <= over, (sorted(norms - over), got)


def _v(params):
    return params.syn1.shape[0]


def _state_unchanged(real, params, args, kw):
    return params, real(params, *args, **kw)[1]


def _bucket_rows_never_move(real, params, args, kw):
    moved, metrics = real(params, *args, **kw)
    v = _v(params)
    return moved._replace(syn0=moved.syn0.at[v:].set(params.syn0[v:])), metrics


def _word_rows_never_move(real, params, args, kw):
    moved, metrics = real(params, *args, **kw)
    v = _v(params)
    return moved._replace(syn0=moved.syn0.at[:v].set(params.syn0[:v])), metrics


def _fast_twin_drops_half_the_batch(real, params, args, kw):
    if args[10]:        # with_metrics: the twin that reports the loss stays whole
        return real(params, *args, **kw)
    half = args[2] * (np.arange(args[2].shape[0]) % 2)
    return real(params, *args[:2], half, *args[3:], **kw)


def _gradient_not_divided(real, syn0, centers, d_in, table, shape, plan):
    """fastText's convention: every row of G(w) gets the whole d_h."""
    import jax.numpy as jnp
    return real(syn0, centers, d_in, table, shape,
                plan._replace(inv=jnp.where(plan.inv > 0, 1.0, 0.0)))


@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "change_norm_gap.syn1"),
    (_bucket_rows_never_move, "first_gradient_norm_gap.bucket_rows"),
    (_word_rows_never_move, "first_gradient_norm_gap.word_rows"),
    (_gradient_not_divided, "first_gradient_norm_gap.bucket_rows"),
    (_fast_twin_drops_half_the_batch, "fast_change_norm_gap.bucket_rows"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_step_broken_underneath_is_not_correct(fault, caught_by, monkeypatch, capsys):
    """The rest of a run, past the harness's look for a chip, with the timed
    path broken underneath: a step that moves nothing, whose bucket rows or
    word rows never move, that spreads the center's gradient by the other
    convention, or whose metrics-elided twin loses pairs."""
    from glint_word2vec_tpu.ops import subword as ops_subword
    from glint_word2vec_tpu.train import trainer as trainer_module

    if fault is _gradient_not_divided:
        real = ops_subword.scatter_center_updates
        monkeypatch.setattr(ops_subword, "scatter_center_updates",
                            lambda *a: fault(real, *a))
    else:
        real = trainer_module.sgns_step_shared_core
        monkeypatch.setattr(trainer_module, "sgns_step_shared_core",
                            lambda params, *args, **kw: fault(real, params, args, kw))
    out = kind.run(_cell(), seed=45, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is False and out["attempted"] > 0
    failed = [l.split()[1].rstrip(":") for l in capsys.readouterr().out.splitlines()
              if l.startswith("check ") and l.endswith("FAILED")]
    assert caught_by in failed, failed
    if fault is _fast_twin_drops_half_the_batch:
        assert all(n.startswith("fast_") for n in failed), failed
    if fault is _bucket_rows_never_move:
        # the first step's other leaves are right; later steps read the stale rows
        assert not [n for n in failed if "first_gradient" in n
                    and "bucket_rows" not in n], failed
