"""Numerical-equivalence tests for the fused Pallas SGNS kernel (interpret mode).

The kernel (ops/pallas/sgns_kernel.py) must produce the same update as the XLA
reference implementation ``sgns_step_shared`` (ops/sgns.py) given the same PRNG key,
wherever their concurrency semantics coincide: batches whose centers are distinct
among themselves and whose contexts are distinct among themselves (in-tile duplicates
are last-wins in the kernel vs accumulated by XLA scatter-add — documented divergence,
sgns_kernel.py module docstring).

Replaces-the-reference note: these cover the G3 dotprod + G4 adjust server kernels
(mllib:419-425) at the numerical level the reference never tested (SURVEY §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops.pallas.sgns_kernel import make_pallas_sgns_step
from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    init_embeddings,
    sgns_step_shared_core,
)

V, D, P, N = 512, 128, 64, 5


def _setup(seed=0):
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, 50, V)
    table = build_alias_table(counts, 0.75)
    params = init_embeddings(V, D, jax.random.key(1))
    # nonzero syn1 so negative-branch math is exercised
    syn1 = jnp.asarray(rng.normal(0.0, 0.05, (V, D)), jnp.float32)
    return table, EmbeddingPair(params.syn0, syn1), rng


def _distinct_batch(rng, B):
    centers = rng.permutation(V)[:B].astype(np.int32)
    contexts = rng.permutation(V)[:B].astype(np.int32)
    mask = np.ones(B, np.float32)
    return centers, contexts, mask


def _run_both(table, params, centers, contexts, mask, tile, alpha=0.025):
    negatives = sample_negatives(table, jax.random.key(7), (P,))
    batch = {
        "centers": jnp.asarray(centers),
        "contexts": jnp.asarray(contexts),
        "mask": jnp.asarray(mask),
    }
    pallas_inner = make_pallas_sgns_step(
        N, P, "exact", jnp.float32, tile=tile, interpret=True)
    got_params, got_metrics = pallas_inner(
        params, batch, negatives, jnp.float32(alpha))
    want_params, want_metrics = sgns_step_shared_core(
        params, batch["centers"], batch["contexts"], batch["mask"],
        negatives, jnp.float32(alpha), N, "exact", jnp.float32)
    return got_params, got_metrics, want_params, want_metrics


def test_single_tile_equivalence():
    table, params, rng = _setup()
    centers, contexts, mask = _distinct_batch(rng, 256)
    got_p, got_m, want_p, want_m = _run_both(table, params, centers, contexts, mask, 256)
    np.testing.assert_allclose(got_p.syn0, want_p.syn0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p.syn1, want_p.syn1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_m.loss), float(want_m.loss), rtol=1e-5)
    np.testing.assert_allclose(
        float(got_m.mean_f_pos), float(want_m.mean_f_pos), rtol=1e-5, atol=1e-7)
    assert float(got_m.pairs) == float(want_m.pairs)


def test_multi_tile_equivalence():
    # rows globally distinct → the kernel's sequential-tile semantics coincide with
    # XLA's batch-start-value semantics even across tiles
    table, params, rng = _setup(seed=3)
    centers, contexts, mask = _distinct_batch(rng, 256)
    got_p, got_m, want_p, want_m = _run_both(table, params, centers, contexts, mask, 64)
    np.testing.assert_allclose(got_p.syn0, want_p.syn0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p.syn1, want_p.syn1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_m.loss), float(want_m.loss), rtol=1e-5)


def test_masked_rows_do_not_clobber_row0():
    """The ADVICE finding: flush-padded entries have centers/contexts == 0; their
    writeback must be skipped or a stale row-0 value can overwrite a real row-0
    update made earlier in the same tile."""
    table, params, rng = _setup(seed=5)
    B = 64
    centers, contexts, mask = _distinct_batch(rng, B)
    # a real pair touching row 0 early in the tile...
    centers[3] = 0
    contexts[5] = 0
    # ...and masked padding (centers/contexts = 0) at the end of the same tile
    centers[B - 8:] = 0
    contexts[B - 8:] = 0
    mask[B - 8:] = 0.0
    got_p, got_m, want_p, want_m = _run_both(table, params, centers, contexts, mask, B)
    np.testing.assert_allclose(got_p.syn0, want_p.syn0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_p.syn1, want_p.syn1, rtol=1e-5, atol=1e-6)
    # row 0 actually moved (the hazard scenario is exercised, not vacuous)
    assert not np.allclose(np.asarray(want_p.syn0[0]), np.asarray(params.syn0[0]))
    np.testing.assert_allclose(float(got_m.pairs), float(want_m.pairs))


def test_trainer_smoke_use_pallas():
    """use_pallas=True constructs and trains end-to-end (the round-1 wiring bug made
    this raise TypeError before the first step)."""
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    rng = np.random.default_rng(11)
    words = [f"w{i}" for i in range(40)]
    sentences = [[words[j] for j in rng.integers(0, 40, 12)] for _ in range(60)]
    vocab = build_vocab(sentences, min_count=1)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=128, num_iterations=1,
        window=3, negatives=3, negative_pool=16, use_pallas=True,
        steps_per_dispatch=2, seed=2, subsample_ratio=0.0)
    plan = make_mesh(1, 1, devices=jax.devices()[:1])
    trainer = Trainer(cfg, vocab, plan=plan)
    before = np.asarray(trainer.params.syn0).copy()
    trainer.fit(encode_sentences(sentences, vocab, cfg.max_sentence_length))
    after = np.asarray(trainer.params.syn0)
    assert np.isfinite(after).all()
    assert not np.allclose(before, after)


def test_pallas_rejects_multi_device_plan():
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(16)], np.full(16, 5))
    cfg = Word2VecConfig(vector_size=8, min_count=1, use_pallas=True)
    plan = make_mesh(1, 2, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="single-device"):
        Trainer(cfg, vocab, plan=plan)
