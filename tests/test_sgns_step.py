"""Property tests for the fused SGNS/CBOW step.

The key property: the manual scatter-update step equals SGD-via-autodiff on the SGNS loss
(with the same pre-drawn negatives) — the reference could never test this (async Hogwild
races, SURVEY §4); synchronous training makes it exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    alpha_schedule,
    cbow_step,
    init_embeddings,
    sgns_loss,
    sgns_step,
)

V, D, B, N = 50, 16, 32, 5


@pytest.fixture(scope="module")
def setup():
    key = jax.random.key(0)
    params = init_embeddings(V, D, key)
    # make syn1 nonzero so gradients flow everywhere
    params = EmbeddingPair(
        syn0=params.syn0,
        syn1=jax.random.normal(jax.random.key(1), (V, D)) * 0.1,
    )
    counts = np.arange(V, 0, -1) ** 2
    table = build_alias_table(counts)
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.integers(0, V, B), jnp.int32)
    contexts = jnp.asarray(rng.integers(0, V, B), jnp.int32)
    mask = jnp.ones(B, jnp.float32)
    return params, table, centers, contexts, mask


def test_manual_step_matches_autodiff_sgd(setup):
    params, table, centers, contexts, mask = setup
    alpha = 0.05
    step_key = jax.random.key(42)
    new_params, metrics = sgns_step(
        params, centers, contexts, mask, step_key, alpha, table, N,
        duplicate_scaling=False)

    negatives = sample_negatives(table, step_key, (B, N))
    denom = jnp.maximum(mask.sum(), 1.0)
    grads = jax.grad(
        lambda p: sgns_loss(p, centers, contexts, negatives, mask) * denom)(params)
    exp_syn0 = params.syn0 - alpha * grads.syn0
    exp_syn1 = params.syn1 - alpha * grads.syn1
    np.testing.assert_allclose(np.asarray(new_params.syn0), np.asarray(exp_syn0),
                               atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_params.syn1), np.asarray(exp_syn1),
                               atol=1e-6, rtol=1e-5)
    assert float(metrics.pairs) == B


def test_masked_pairs_do_not_update(setup):
    params, table, centers, contexts, _ = setup
    mask = jnp.zeros(B, jnp.float32)
    new_params, metrics = sgns_step(
        params, centers, contexts, mask, jax.random.key(0), 0.1, table, N)
    np.testing.assert_array_equal(np.asarray(new_params.syn0), np.asarray(params.syn0))
    np.testing.assert_array_equal(np.asarray(new_params.syn1), np.asarray(params.syn1))
    assert float(metrics.pairs) == 0.0


def test_partial_mask_matches_smaller_batch(setup):
    params, table, centers, contexts, _ = setup
    # Batch with the last half masked == batch of just the first half, with the caveat that
    # negatives are drawn per-slot; use the same key and compare only syn0 rows untouched by
    # negatives' e_in scatter — simplest exact check: masked-slot contributions are zero, so
    # rows appearing ONLY in masked slots are unchanged.
    mask = jnp.concatenate([jnp.ones(B // 2), jnp.zeros(B // 2)]).astype(jnp.float32)
    new_params, _ = sgns_step(
        params, centers, contexts, mask, jax.random.key(3), 0.1, table, N)
    live = set(np.asarray(centers[: B // 2]).tolist())
    dead = set(np.asarray(centers[B // 2:]).tolist()) - live
    for row in dead:
        np.testing.assert_array_equal(
            np.asarray(new_params.syn0[row]), np.asarray(params.syn0[row]))


def test_duplicate_indices_accumulate(setup):
    params, table, *_ = setup
    centers = jnp.zeros(B, jnp.int32)  # every pair hits row 0
    contexts = jnp.ones(B, jnp.int32)
    mask = jnp.ones(B, jnp.float32)
    new_params, _ = sgns_step(
        params, centers, contexts, mask, jax.random.key(5), 0.05, table, N,
        duplicate_scaling=False)
    # update to row 0 must equal B times the single-pair update (same context, same e values
    # pre-update, negatives differ per slot — so compare against per-slot sum via autodiff)
    negatives = sample_negatives(table, jax.random.key(5), (B, N))
    grads = jax.grad(
        lambda p: sgns_loss(p, centers, contexts, negatives, mask) * B)(params)
    np.testing.assert_allclose(
        np.asarray(new_params.syn0[0]),
        np.asarray(params.syn0[0] - 0.05 * grads.syn0[0]), atol=1e-6, rtol=1e-5)


def test_clipped_sigmoid_saturates(setup):
    _, table, centers, contexts, mask = setup
    # Huge positive dots → σ=1 → zero positive gradient under "clipped" mode (reference LUT
    # behavior, mllib:292-302).
    big = EmbeddingPair(
        syn0=jnp.ones((V, D)) * 10.0,
        syn1=jnp.ones((V, D)) * 10.0,
    )
    new_params, _ = sgns_step(
        big, centers, contexts, mask, jax.random.key(0), 0.1, table, N,
        sigmoid_mode="clipped")
    # positive grad is exactly 0; negative grad is exactly -1·α (σ clipped to 1 for f>6)
    # so syn1[context] rows get only the positive-side update = 0 + possible negative hits.
    # Check f_pos path: rows used only as centers changed solely via negative coefficients;
    # with all-equal embeddings every update direction is identical — simply assert finite
    # and that clipped mode differs from exact mode.
    exact_params, _ = sgns_step(
        big, centers, contexts, mask, jax.random.key(0), 0.1, table, N,
        sigmoid_mode="exact")
    assert np.all(np.isfinite(np.asarray(new_params.syn0)))
    # σ_exact(200) ≈ 1 to float precision too, so exact vs clipped agree at saturation
    np.testing.assert_allclose(np.asarray(new_params.syn0),
                               np.asarray(exact_params.syn0), atol=1e-4)


def test_negatives_colliding_with_positive_are_skipped():
    # Vocab of 1: every negative == the context word → all negative grads masked out.
    params = EmbeddingPair(syn0=jnp.ones((1, 4)) * 0.1, syn1=jnp.ones((1, 4)) * 0.1)
    table = build_alias_table(np.array([10]))
    centers = jnp.zeros(8, jnp.int32)
    contexts = jnp.zeros(8, jnp.int32)
    mask = jnp.ones(8, jnp.float32)
    new_params, metrics = sgns_step(
        params, centers, contexts, mask, jax.random.key(0), 0.1, table, 5)
    # only the positive-pair gradient applied; loss = -log σ(f_pos) only
    f = float(jnp.sum(params.syn0[0] * params.syn1[0]))
    expected_loss = -np.log(1.0 / (1.0 + np.exp(-f)))
    np.testing.assert_allclose(float(metrics.loss), expected_loss, rtol=1e-5)


def test_training_reduces_loss(setup):
    params, table, *_ = setup
    rng = np.random.default_rng(1)
    # deterministic corpus: word i co-occurs with i+1 mod 10 within first 10 words
    c = jnp.asarray(rng.integers(0, 10, 256), jnp.int32)
    x = (c + 1) % 10
    mask = jnp.ones(256, jnp.float32)
    step = jax.jit(lambda p, k: sgns_step(p, c, x, mask, k, 0.02, table, N))
    losses = []
    for i in range(60):
        params, m = step(params, jax.random.key(i))
        losses.append(float(m.loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_cbow_step_basics(setup):
    params, table, *_ = setup
    rng = np.random.default_rng(2)
    Bc, C = 64, 6
    centers = jnp.asarray(rng.integers(0, V, Bc), jnp.int32)
    contexts = jnp.asarray(rng.integers(0, V, (Bc, C)), jnp.int32)
    ctx_mask = jnp.asarray(rng.integers(0, 2, (Bc, C)), jnp.float32)
    mask = jnp.ones(Bc, jnp.float32)
    first = last = None
    for i in range(30):
        params, m = cbow_step(
            params, centers, contexts, ctx_mask, mask, jax.random.key(i), 0.1, table, N)
        if first is None:
            first = float(m.loss)
        last = float(m.loss)
    assert np.isfinite(last) and last < first


def test_cbow_masked_batch_no_update(setup):
    params, table, *_ = setup
    centers = jnp.zeros(8, jnp.int32)
    contexts = jnp.zeros((8, 4), jnp.int32)
    ctx_mask = jnp.ones((8, 4), jnp.float32)
    mask = jnp.zeros(8, jnp.float32)
    new_params, _ = cbow_step(
        params, centers, contexts, ctx_mask, mask, jax.random.key(0), 0.1, table, N)
    np.testing.assert_array_equal(np.asarray(new_params.syn0), np.asarray(params.syn0))


def test_cbow_empty_context_no_update(setup):
    params, table, *_ = setup
    centers = jnp.arange(8, dtype=jnp.int32)
    contexts = jnp.zeros((8, 4), jnp.int32)
    ctx_mask = jnp.zeros((8, 4), jnp.float32)  # no context at all
    mask = jnp.ones(8, jnp.float32)
    new_params, m = cbow_step(
        params, centers, contexts, ctx_mask, mask, jax.random.key(0), 0.1, table, N)
    np.testing.assert_array_equal(np.asarray(new_params.syn0), np.asarray(params.syn0))
    np.testing.assert_array_equal(np.asarray(new_params.syn1), np.asarray(params.syn1))
    # loss telemetry must also ignore empty-context rows entirely
    assert float(m.loss) == 0.0


def test_alpha_schedule_reference_semantics():
    # alpha = lr·(1−progress), floor lr·1e-4 (mllib:405-413)
    lr = 0.025
    assert alpha_schedule(0, 1000, lr) == pytest.approx(lr)
    assert alpha_schedule(500, 1000, lr) == pytest.approx(lr * 0.5)
    assert alpha_schedule(2000, 1000, lr) == pytest.approx(lr * 1e-4)
    # jnp path
    a = alpha_schedule(jnp.asarray(500.0), 1000.0, lr)
    np.testing.assert_allclose(float(a), lr * 0.5)


def test_init_embeddings_ranges():
    p = init_embeddings(V, D, jax.random.key(0))
    s0 = np.asarray(p.syn0)
    assert s0.max() <= 0.5 / D and s0.min() >= -0.5 / D
    assert np.all(np.asarray(p.syn1) == 0)


def test_duplicate_scaling_stabilizes_large_batches(setup):
    # Pathological density: vocab 6, batch 512, lr 0.05 — accumulate-semantics diverges,
    # scaled semantics must stay finite and learn (the sync-large-batch design point).
    _, _, *_ = setup
    counts = np.array([100, 90, 80, 70, 60, 50])
    table6 = build_alias_table(counts)
    params = init_embeddings(6, 16, jax.random.key(0))
    params = EmbeddingPair(params.syn0,
                           jax.random.normal(jax.random.key(1), (6, 16)) * 0.05)
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.integers(0, 6, 512), jnp.int32)
    x = (c + 1) % 6
    mask = jnp.ones(512, jnp.float32)
    for i in range(50):
        params, m = sgns_step(
            params, c, x, mask, jax.random.key(i), 0.05, table6, N,
            duplicate_scaling=True)
    assert np.isfinite(float(m.loss))
    assert np.all(np.isfinite(np.asarray(params.syn0)))


def test_shared_negative_step_basics(setup):
    from glint_word2vec_tpu.ops.sgns import sgns_step_shared
    params, table, centers, contexts, mask = setup
    P = 16
    new_params, m = sgns_step_shared(
        params, centers, contexts, mask, jax.random.key(0), 0.05, table, N, P)
    assert np.all(np.isfinite(np.asarray(new_params.syn0)))
    assert float(m.pairs) == B
    # masked batch -> no update, zero loss
    zp, zm = sgns_step_shared(
        params, centers, contexts, jnp.zeros(B, jnp.float32),
        jax.random.key(0), 0.05, table, N, P)
    np.testing.assert_array_equal(np.asarray(zp.syn0), np.asarray(params.syn0))
    np.testing.assert_array_equal(np.asarray(zp.syn1), np.asarray(params.syn1))
    assert float(zm.loss) == 0.0


def test_shared_negative_step_learns(setup):
    from glint_word2vec_tpu.ops.sgns import sgns_step_shared
    params, table, *_ = setup
    rng = np.random.default_rng(3)
    c = jnp.asarray(rng.integers(0, 10, 256), jnp.int32)
    x = (c + 1) % 10
    mask = jnp.ones(256, jnp.float32)
    step = jax.jit(lambda p, k: sgns_step_shared(p, c, x, mask, k, 0.02, table, N, 16))
    losses = []
    for i in range(60):
        params, m = step(params, jax.random.key(i))
        losses.append(float(m.loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_shared_negative_pool_collision_masked():
    # Vocab of 1: the whole pool == every context word -> zero negative gradient.
    from glint_word2vec_tpu.ops.sgns import sgns_step_shared
    params = EmbeddingPair(syn0=jnp.ones((1, 4)) * 0.1, syn1=jnp.ones((1, 4)) * 0.1)
    table = build_alias_table(np.array([10]))
    centers = contexts = jnp.zeros(8, jnp.int32)
    mask = jnp.ones(8, jnp.float32)
    _, m = sgns_step_shared(
        params, centers, contexts, mask, jax.random.key(0), 0.1, table, 5, 4)
    f = float(jnp.sum(params.syn0[0] * params.syn1[0]))
    expected_loss = -np.log(1.0 / (1.0 + np.exp(-f)))
    np.testing.assert_allclose(float(m.loss), expected_loss, rtol=1e-5)


def test_shared_pool_bf16_logits_tracks_f32(setup):
    """logits_dtype="bfloat16" (PERF.md §4: halves the [B, P] chain's bandwidth) must
    produce the same update direction with only half-precision rounding noise: the
    per-row deltas stay within bf16 relative tolerance of the f32-logit step, and the
    CBOW shared path mirrors it."""
    from glint_word2vec_tpu.ops.sgns import (
        cbow_step_shared_core, sgns_step_shared_core)
    params, table, centers, contexts, mask = setup
    negs = jnp.asarray(np.random.default_rng(7).integers(0, V, 16), jnp.int32)
    ref, m_ref = sgns_step_shared_core(
        params, centers, contexts, mask, negs, jnp.float32(0.05), N)
    lo, m_lo = sgns_step_shared_core(
        params, centers, contexts, mask, negs, jnp.float32(0.05), N,
        logits_dtype=jnp.bfloat16)
    d_ref = np.asarray(ref.syn0) - np.asarray(params.syn0)
    d_lo = np.asarray(lo.syn0) - np.asarray(params.syn0)
    # bf16 has ~3 significant digits; deltas are tiny so compare against scale
    np.testing.assert_allclose(d_lo, d_ref, atol=2e-2 * np.abs(d_ref).max())
    np.testing.assert_allclose(float(m_lo.loss), float(m_ref.loss), rtol=2e-2)

    C = 4
    ctx = jnp.asarray(np.random.default_rng(8).integers(0, V, (B, C)), jnp.int32)
    cmask = jnp.ones((B, C), jnp.float32)
    ref_c, mc_ref = cbow_step_shared_core(
        params, centers, ctx, cmask, mask, negs, jnp.float32(0.05), N)
    lo_c, mc_lo = cbow_step_shared_core(
        params, centers, ctx, cmask, mask, negs, jnp.float32(0.05), N,
        logits_dtype=jnp.bfloat16)
    d_ref = np.asarray(ref_c.syn1) - np.asarray(params.syn1)
    d_lo = np.asarray(lo_c.syn1) - np.asarray(params.syn1)
    np.testing.assert_allclose(d_lo, d_ref, atol=2e-2 * np.abs(d_ref).max())
    np.testing.assert_allclose(float(mc_lo.loss), float(mc_ref.loss), rtol=2e-2)


def test_shared_pool_metrics_elision_bit_identical(setup):
    """with_metrics=False (the trainer's fast twin for chunks no heartbeat
    samples, PERF.md §4) must change ONLY the metric side-channel: parameters
    bit-identical, pairs exact, loss/mean_f_pos zeroed."""
    from glint_word2vec_tpu.ops.sgns import sgns_step_shared_core
    params, table, centers, contexts, mask = setup
    negs = jnp.asarray(np.random.default_rng(9).integers(0, V, 16), jnp.int32)
    full, m_full = sgns_step_shared_core(
        params, centers, contexts, mask, negs, jnp.float32(0.05), N)
    fast, m_fast = sgns_step_shared_core(
        params, centers, contexts, mask, negs, jnp.float32(0.05), N,
        with_metrics=False)
    np.testing.assert_array_equal(np.asarray(full.syn0), np.asarray(fast.syn0))
    np.testing.assert_array_equal(np.asarray(full.syn1), np.asarray(fast.syn1))
    assert float(m_fast.pairs) == float(m_full.pairs) == B
    assert float(m_fast.loss) == 0.0 and float(m_full.loss) > 0.0

    # the CBOW shared-pool path has the same twin contract
    from glint_word2vec_tpu.ops.sgns import cbow_step_shared_core
    C = 4
    ctx = jnp.asarray(np.random.default_rng(10).integers(0, V, (B, C)), jnp.int32)
    cmask = jnp.ones((B, C), jnp.float32)
    cf, mcf = cbow_step_shared_core(
        params, centers, ctx, cmask, mask, negs, jnp.float32(0.05), N)
    cq, mcq = cbow_step_shared_core(
        params, centers, ctx, cmask, mask, negs, jnp.float32(0.05), N,
        with_metrics=False)
    np.testing.assert_array_equal(np.asarray(cf.syn0), np.asarray(cq.syn0))
    np.testing.assert_array_equal(np.asarray(cf.syn1), np.asarray(cq.syn1))
    assert float(mcq.pairs) == float(mcf.pairs)
    assert float(mcq.loss) == 0.0 and float(mcf.loss) > 0.0


def test_shared_pool_duplicate_scaling_mean_semantics():
    """With duplicate_scaling=True on the shared-pool path, R identical pairs move
    each row exactly as far as ONE pair does (mean of identical updates), bounding the
    per-row step at any batch size; without it the movement is R-fold (sum)."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core

    V, D, R = 12, 8, 16
    rng = np.random.default_rng(0)
    syn0 = jnp.asarray(rng.normal(0, 0.1, (V, D)), jnp.float32)
    syn1 = jnp.asarray(rng.normal(0, 0.1, (V, D)), jnp.float32)
    pool = jnp.asarray([7, 8, 9, 7], jnp.int32)  # word 7 twice: multiplicity covered
    alpha = jnp.float32(0.1)

    def run(B, scaled):
        centers = jnp.full((B,), 2, jnp.int32)
        contexts = jnp.full((B,), 5, jnp.int32)
        mask = jnp.ones((B,), jnp.float32)
        (s0, s1, _), _ = sgns_step_shared_core(
            EmbeddingPair(syn0, syn1), centers, contexts, mask, pool, alpha,
            num_negatives=2, duplicate_scaling=scaled)
        return np.asarray(s0), np.asarray(s1)

    one0, one1 = run(1, True)
    many0, many1 = run(R, True)
    np.testing.assert_allclose(many0, one0, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(many1, one1, rtol=2e-5, atol=1e-7)

    # sum semantics (default) moves the center row ~R times as far
    sum0, _ = run(R, False)
    d_scaled = np.abs(many0[2] - np.asarray(syn0)[2]).sum()
    d_sum = np.abs(sum0[2] - np.asarray(syn0)[2]).sum()
    assert d_sum > 5 * d_scaled


def test_cbow_shared_pool_learns_and_masks():
    """CBOW shared-pool path (the CBOW TPU fast tier): learns a predictive toy task,
    zero-masked batches are no-ops, and pool==center collisions contribute nothing."""
    import jax

    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, cbow_step_shared_core, init_embeddings)

    V, D, B, C, P = 20, 16, 128, 4, 8
    rng = np.random.default_rng(0)
    params = init_embeddings(V, D, jax.random.key(1))
    params = EmbeddingPair(params.syn0, params.syn0[::-1] * 0.5)
    # predictable structure: center = (first context + 1) % 10
    contexts = jnp.asarray(rng.integers(0, 10, (B, C)), jnp.int32)
    centers = (contexts[:, 0] + 1) % 10
    ctx_mask = jnp.ones((B, C), jnp.float32)
    mask = jnp.ones(B, jnp.float32)

    def step(p, i):
        pool = jnp.asarray(rng.integers(10, V, P), jnp.int32)  # disjoint negatives
        return cbow_step_shared_core(
            p, centers, contexts, ctx_mask, mask, pool, jnp.float32(0.05), 3)

    losses = []
    for i in range(40):
        params, m = jax.jit(step, static_argnums=1)(params, i)
        losses.append(float(m.loss))
    assert np.all(np.isfinite(np.asarray(params.syn0)))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])

    # fully masked batch: params unchanged, zero loss
    zp, zm = cbow_step_shared_core(
        params, centers, contexts, ctx_mask, jnp.zeros(B, jnp.float32),
        jnp.asarray(rng.integers(10, V, P), jnp.int32), jnp.float32(0.05), 3)
    np.testing.assert_array_equal(np.asarray(zp.syn0), np.asarray(params.syn0))
    assert float(zm.loss) == 0.0

    # pool made entirely of the centers themselves -> negative term fully masked:
    # identical update to a pool of valid negatives with zero gradient coefficient
    all_self = jnp.full((P,), int(centers[0]), jnp.int32)
    sp, sm = cbow_step_shared_core(
        params, centers[:1], contexts[:1], ctx_mask[:1], mask[:1],
        all_self, jnp.float32(0.05), 3)
    f = float(sm.mean_f_pos)
    assert np.isfinite(f)
    # loss reduces to the positive term only
    expected = float(np.log1p(np.exp(-f)))
    np.testing.assert_allclose(float(sm.loss), expected, rtol=1e-5)
