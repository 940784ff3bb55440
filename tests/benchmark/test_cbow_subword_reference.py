"""The plain subword-CBOW reference against the program's step at a small size
on the CPU; a rehearsal of kind ``train_cbow_subword``; the lower-precision
control failing every norm limit of ``correct``, the position weights' among
them; and a run whose timed path is broken underneath coming out not correct."""

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
from kinds import train_cbow_subword as kind  # noqa: E402
from reference import cbow_subword_ref as ref_model  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "cbow-subword-2m-300.train"


def _cell() -> dict:
    return loader.resolve(MANIFEST, CELL)


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "reference", "cbow_subword_ref.py")).read()
    code = source.split('"""', 2)[2]
    assert "glint_word2vec_tpu" not in code and "data.subword" not in code
    assert "jax.value_and_grad" in source and '"highest"' in source
    assert "cumsum" not in code            # no prefix sums: the window is written out


def test_position_rows_are_negative_positions_first():
    assert [ref_model.position_row(p, 5) for p in (-5, -1, 1, 5)] == [0, 4, 5, 9]


def test_window_examples_are_the_draws_left_packed():
    left, right = np.array([0, 1, 2, 0]), np.array([1, 0, 1, 2])
    nctx, slot, row = kind.window_examples(left, right, np.array([1, 1, 1, 0]), 2)
    assert nctx.tolist() == [1, 1, 3, 0]                 # the last slot is no center
    assert slot[0, 0] == 1 and row[0, 0] == ref_model.position_row(1, 2)
    assert slot[1, 0] == 0 and row[1, 0] == ref_model.position_row(-1, 2)
    assert slot[2, :3].tolist() == [0, 1, 3]
    assert row[2, :3].tolist() == [ref_model.position_row(p, 2) for p in (-2, -1, 1)]


def test_tiny_rehearsal_of_the_kind_is_correct(capsys):
    out = kind.run(_cell(), seed=2**31 + 49, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    printed = capsys.readouterr().out
    assert "row table" in printed and "check row_table_mismatches: 0" in printed
    assert "check examples_abs_gap: 0" in printed
    assert out["counters"]["subword_table_s"] > 0
    assert 0.5 < out["counters"]["examples"] / out["counters"]["slots"] <= 1.0
    assert set(out["end_to_end"]) == {"train_pairs_per_s", "train_loss_at_budget",
                                      "setup_s"}
    # the check's blocks are the feed's: every token slot hands over its list
    per_token = out["shapes"]["subword_rows_per_block"] / out["shapes"]["tokens_per_block"]
    assert 2.0 < per_token < 12.0
    cost = importlib.import_module("costs.cbow_subword_step").cost(**out["shapes"])
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
        # a norm limit fails on every leaf, the position weights' too (their
        # change is small beside what bfloat16 rounds off their start)
        norms = {n for n in got if "norm_gap" in n}
        assert len(norms) == 16
        for leaf in kind.LEAVES:
            assert {n for n in over if n.endswith(leaf)}, (leaf, got)
        assert {n for n in norms if "first_gradient" in n} <= over, got


def _v(params):
    return params.syn1.shape[0]


def _state_unchanged(real, params, args, kw):
    return params, real(params, *args, **kw)[1]


def _bucket_rows_never_move(real, params, args, kw):
    moved, metrics = real(params, *args, **kw)
    v = _v(params)
    return moved._replace(syn0=moved.syn0.at[v:].set(params.syn0[v:])), metrics


def _positions_never_move(real, params, args, kw):
    moved, metrics = real(params, *args, **kw)
    return moved._replace(pos=params.pos), metrics


def _positions_applied_mirrored(real, params, args, kw):
    """d_{-p} where d_p belongs: the rows of ``d`` read and written in reverse."""
    moved, metrics = real(params._replace(pos=params.pos[::-1]), *args, **kw)
    return moved._replace(pos=moved.pos[::-1]), metrics


def _fast_twin_drops_half_its_block(real, params, args, kw):
    if args[12]:        # with_metrics: the twin that reports the loss stays whole
        return real(params, *args, **kw)
    half = args[3] * (np.arange(args[3].shape[0]) % 2)      # center_mask
    return real(params, *args[:3], half, *args[4:], **kw)


def _list_gradient_not_divided(real, syn0, words, d_ctx, table, shape, plan):
    """fastText's convention: every row of G(w) gets the whole gradient."""
    import jax.numpy as jnp
    return real(syn0, words, d_ctx, table, shape,
                plan._replace(inv=jnp.where(plan.inv > 0, 1.0, 0.0)))


def _window_not_divided(real, x, weights, left, right, window, transpose=False):
    """word2vec.c's convention on the way in: the window's sum, not its mean
    (the step divides what this returns by n_t, so hand it n_t times as much)."""
    import jax.numpy as jnp
    out = real(x, weights, left, right, window, transpose)
    return out if transpose else out * jnp.maximum(left + right, 1)[:, None]


@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "change_norm_gap.syn1"),
    (_bucket_rows_never_move, "first_gradient_norm_gap.bucket_rows"),
    (_positions_never_move, "first_gradient_norm_gap.positions"),
    (_positions_applied_mirrored, "first_gradient_norm_gap.positions"),
    (_list_gradient_not_divided, "first_gradient_norm_gap.bucket_rows"),
    (_window_not_divided, "loss_rel_gap"),
    (_fast_twin_drops_half_its_block, "fast_change_norm_gap.syn1"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_step_broken_underneath_is_not_correct(fault, caught_by, monkeypatch, capsys):
    """The rest of a run, past the harness's look for a chip, with the timed
    path broken underneath: a step that moves nothing, whose bucket rows or
    position weights never move, that reads the weights mirrored, that spreads
    a token's gradient or sums a window by the other convention, or whose
    metrics-elided twin loses examples."""
    from glint_word2vec_tpu.ops import cbow_banded
    from glint_word2vec_tpu.ops import subword as ops_subword

    if fault is _list_gradient_not_divided:
        real = ops_subword.scatter_center_updates
        monkeypatch.setattr(ops_subword, "scatter_center_updates",
                            lambda *a: fault(real, *a))
    elif fault is _window_not_divided:
        real = cbow_banded.position_taps
        monkeypatch.setattr(cbow_banded, "position_taps",
                            lambda *a, **kw: fault(real, *a, **kw))
    else:
        real = cbow_banded.cbow_step_banded_core
        monkeypatch.setattr(cbow_banded, "cbow_step_banded_core",
                            lambda params, *args, **kw: fault(real, params, args, kw))
    out = kind.run(_cell(), seed=45, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is False and out["attempted"] > 0
    failed = [l.split()[1].rstrip(":") for l in capsys.readouterr().out.splitlines()
              if l.startswith("check ") and l.endswith("FAILED")]
    assert caught_by in failed, failed
    if fault is _fast_twin_drops_half_its_block:
        # the twin that reports the loss is whole; the count of examples is one
        # check over both twins
        assert all(n.startswith("fast_") or n == "examples_abs_gap" for n in failed), failed
        assert "examples_abs_gap" in failed
    if fault is _positions_never_move:
        # the first step's other leaves are right; later steps read stale weights
        assert not [n for n in failed if "first_gradient" in n
                    and "positions" not in n], failed
