"""The plain CBOW reference against both of the program's CBOW step forms at a
small size on the CPU; a rehearsal of kind ``train_cbow`` on each form; the
lower-precision control failing ``correct``'s limits; and a run whose timed path
is broken underneath coming out not correct."""

import copy
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
from kinds import train_cbow as kind  # noqa: E402
from reference import cbow_ref  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "cbow-3m-300.train"
FORMS = ["banded", "scatter"]


def _cell(form: str) -> dict:
    cell = copy.deepcopy(loader.resolve(MANIFEST, CELL))
    cell["config"]["cbow_update"] = form
    return cell


def _block(rng, v, t, sentence):
    """A sentence-contiguous block of t tokens (duplicates included) with the
    window extents a draw could give: inside the sentence, some of them empty."""
    tokens = rng.integers(0, v, t).astype(np.int32)
    pos = np.arange(t) % sentence
    b = rng.integers(0, 5, t)
    left = np.minimum(b, pos).astype(np.int32)
    right = np.clip(np.minimum(b - 1, sentence - 1 - pos), 0, None).astype(np.int32)
    right = np.minimum(right, t - 1 - np.arange(t)).astype(np.int32)
    left[3], right[3] = 0, 0            # an example with no context at all
    return tokens, left, right


def _examples(tokens, left, right, width):
    t = tokens.shape[0]
    slot = np.arange(t)[:, None]
    j = np.arange(width)[None, :]
    at = np.where(j < left[:, None], slot - left[:, None] + j, slot + j - left[:, None] + 1)
    nctx = left + right
    contexts = np.where(j < nctx[:, None], tokens[np.clip(at, 0, t - 1)], 0)
    return contexts.astype(np.int32), nctx.astype(np.int32)


# rtol 1e-5: both sides are float32 with matmul precision "highest" and differ
# in the order of their sums alone (autodiff's scatter-adds against the
# program's, and for the banded form a prefix-sum difference over the block
# against a direct sum of at most 8 rows); at these sizes that is a few units in
# the last place of a float32, where a bfloat16 path would be off by 1e-2
@pytest.mark.parametrize("form", FORMS)
def test_reference_step_matches_the_programs_float32_step(form):
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair, cbow_step_shared_core

    rng = np.random.default_rng(5)
    v, d, t, p = 300, 24, 160, 16        # t > v / 2: words repeat within the block
    syn0 = jnp.asarray(rng.normal(0, 0.1, (v, d)), jnp.float32)
    syn1 = jnp.asarray(rng.normal(0, 0.1, (v, d)), jnp.float32)
    tokens, left, right = _block(rng, v, t, sentence=20)
    contexts, nctx = _examples(tokens, left, right, 8)
    negs = jnp.asarray(rng.integers(0, v, p), jnp.int32)
    alpha = jnp.float32(0.05)
    with jax.default_matmul_precision("highest"):
        if form == "scatter":
            ctx_mask = (np.arange(8)[None, :] < nctx[:, None]).astype(np.float32)
            want, metrics = cbow_step_shared_core(
                EmbeddingPair(syn0, syn1), jnp.asarray(tokens), jnp.asarray(contexts),
                jnp.asarray(ctx_mask), jnp.ones(t), negs, alpha, 5)
        else:
            want, metrics = cbow_step_banded_core(
                EmbeddingPair(syn0, syn1), jnp.asarray(tokens), jnp.asarray(left),
                jnp.asarray(right), jnp.ones(t), jnp.ones(t), negs, alpha, 5, 5)
    got0, got1, loss = cbow_ref.cbow_step(
        syn0, syn1, jnp.asarray(tokens), jnp.asarray(contexts), jnp.asarray(nctx),
        negs, alpha, 5)
    assert int(metrics.pairs) == int((nctx > 0).sum()) < t
    np.testing.assert_allclose(got0, want.syn0, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got1, want.syn1, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(loss, metrics.loss, rtol=1e-6)
    assert not np.allclose(got0, syn0) and not np.allclose(got1, syn1)


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "reference", "cbow_ref.py")).read()
    assert "glint_word2vec_tpu" not in source.split('"""', 2)[2]
    assert "jax.value_and_grad" in source and '"highest"' in source


@pytest.mark.parametrize("form", FORMS)
def test_tiny_rehearsal_of_the_kind_is_correct(form, capsys):
    out = kind.run(_cell(form), seed=2**31 + 49, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert f"cbow_update={form}" in capsys.readouterr().out
    counters = out["counters"]
    assert 0 < counters["examples"] <= counters["slots"]
    assert set(out["end_to_end"]) == {"train_pairs_per_s", "train_loss_at_budget",
                                      "setup_s"}
    cost = importlib.import_module("costs.cbow_step").cost(**out["shapes"])
    assert cost["bytes"] > 0 and cost["flops"] > 0


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("control", [False, True])
def test_limits_pass_the_program_and_fail_bfloat16_tables(form, control):
    cell = _cell(form)
    limits = loader.sizes(cell["config"], True)["check"]["train"]
    got, = kind.check_readings(cell, [41], control=control, tiny=True)
    over = [n for n in got if got[n] > limits.get(n.removeprefix("fast_"), 0.0)]
    assert bool(over) == control, (got, limits)


# what a step function gets after ``params``, in either form: [3] is the mask
# of the slots that train (``mask`` / ``center_mask``), and the last positional
# is ``with_metrics``; an example's context count is args[2]'s row sum (scatter:
# ctx_mask) or args[1] + args[2] (banded: left + right)
def _nctx(form, args):
    return args[2].sum(axis=-1) if form == "scatter" else args[1] + args[2]


def _state_unchanged(form, real, params, args):
    return params, real(params, *args)[1]


def _syn0_never_moves(form, real, params, args):
    moved, metrics = real(params, *args)
    return moved._replace(syn0=params.syn0), metrics


def _syn1_never_moves(form, real, params, args):
    moved, metrics = real(params, *args)
    return moved._replace(syn1=params.syn1), metrics


def _context_gradient_not_divided(form, real, params, args):
    """``word2vec.c``'s convention: every context row gets the whole d_hidden.
    An example's d_hidden depends on the tables before the step alone, so the
    examples with k context words move syn0 by 1/k of what that convention
    would: the step is run on each k's examples and its change scaled by k."""
    moved, metrics = real(params, *args)
    nctx = _nctx(form, args)
    syn0 = params.syn0
    for k in range(1, 2 * 5 + 1):
        only = args[3] * (nctx == k)
        part = real(params, *args[:3], only, *args[4:])[0].syn0
        syn0 = syn0 + k * (part - params.syn0)
    return moved._replace(syn0=syn0), metrics


def _fast_twin_drops_half_the_batch(form, real, params, args):
    if args[-1]:        # with_metrics: the twin that reports the loss stays whole
        return real(params, *args)
    half = args[3] * (np.arange(args[3].shape[0]) % 2)
    return real(params, *args[:3], half, *args[4:])


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "change_norm_gap"),
    (_syn0_never_moves, "first_gradient_norm_gap"),
    (_syn1_never_moves, "first_gradient_norm_gap"),
    (_context_gradient_not_divided, "first_gradient_norm_gap"),
    (_fast_twin_drops_half_the_batch, "fast_change_norm_gap"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_step_broken_underneath_is_not_correct(form, fault, caught_by, monkeypatch,
                                                 capsys):
    """The rest of a run, past the harness's look for a chip, with the timed path
    broken underneath: a step that moves nothing, that moves one table only,
    that spreads the context gradient by the other convention, or whose
    metrics-elided twin (half of the window's dispatches) loses examples."""
    from glint_word2vec_tpu.ops import cbow_banded
    from glint_word2vec_tpu.train import trainer as trainer_module

    module, name = ((trainer_module, "cbow_step_shared_core") if form == "scatter"
                    else (cbow_banded, "cbow_step_banded_core"))
    real = getattr(module, name)
    monkeypatch.setattr(module, name, lambda params, *args, **kw: fault(
        form, lambda p, *a: real(p, *a, **kw), params, args))
    out = kind.run(_cell(form), seed=45, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is False and out["attempted"] > 0
    failed = [l.split()[1].rstrip(":") for l in capsys.readouterr().out.splitlines()
              if l.startswith("check ") and l.endswith("FAILED")]
    assert caught_by in failed, failed
    if fault is _fast_twin_drops_half_the_batch:
        assert not [n for n in failed
                    if not n.startswith("fast_") and n != "examples_abs_gap"], failed
