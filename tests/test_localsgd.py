"""Local-SGD data parallelism (config.sync_every — ISSUE 17,
docs/sharding.md §Local-SGD).

Five layers, each pinned where it can actually break:

1. DEFAULT IDENTITY — ``sync_every=1`` is byte-for-byte the pre-knob
   shard_map step at every mesh shape: the knob's existence cannot perturb
   the synchronous path.
2. ORACLE — the k-step owner-local window + delta merge against a NumPy
   float64 oracle that replays k steps PER DATA SHARD on that shard's
   disjoint batch/pool slices and then merges the per-shard deltas
   (merged = start + mean(local − start)), stabilizers off and on. The
   mean is exact at the power-of-2 shard counts this repo ships, so the
   bound is ~1e-11, not "close".
3. DEGENERATION — at nd=1 (no data axis) the window is bit-identical to
   running the synchronous step k times: the merge degenerates to identity
   and the owner-local schedule IS the synchronous schedule.
4. DETERMINISM — merged training runs are bit-identical per
   (seed, mesh, sync_every): the disjoint per-shard sample lattices + the
   replica-consistent merge leave nothing order-dependent.
5. REFUSALS — the config selection matrix refuses every combination the
   window has no form for (GSPMD lowering, device_pairgen, a sync_every
   that does not divide steps_per_dispatch), with messages naming the knob.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair, Stabilizers, sgns_step_shared_core)
from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train.trainer import Trainer

# the stabilized shared-pool NumPy oracle both repos' step tests pin against
from test_stabilizers import _np_shared_step

MESHES = [(1, 8), (2, 4), (4, 2), (8, 1)]
NEG = 3


def _inputs(dtype, v=64, d=16, b=32, pool_per_shard=4, k=2, nd=1, seed=0):
    """Window-shaped inputs: batch leaves [k, b], negatives [k, nd·P]
    (each data shard consumes its own disjoint [k, P] slice), alphas [k]."""
    rng = np.random.default_rng(seed)
    params = EmbeddingPair(
        jnp.asarray(rng.standard_normal((v, d)), dtype),
        jnp.asarray(rng.standard_normal((v, d)) * 0.1, dtype))
    batch = {
        "centers": jnp.asarray(rng.integers(0, v, (k, b)), jnp.int32),
        "contexts": jnp.asarray(rng.integers(0, v, (k, b)), jnp.int32),
        "mask": jnp.asarray(rng.random((k, b)) < 0.9, jnp.float32),
    }
    negs = jnp.asarray(
        rng.integers(0, v, (k, nd * pool_per_shard)), jnp.int32)
    alphas = jnp.asarray(np.full(k, 0.025), dtype)
    return params, batch, negs, alphas


@pytest.mark.parametrize("shape", MESHES)
def test_sync_every_one_bit_identity(shape):
    """sync_every=1 returns the existing synchronous step — outputs are
    bit-identical to a factory call that never heard of the knob."""
    plan = make_mesh(*shape)
    params, batch, negs, alphas = _inputs(jnp.float32, k=1)
    sharded = EmbeddingPair(
        jax.device_put(params.syn0, plan.embedding),
        jax.device_put(params.syn1, plan.embedding))
    flat_batch = {kk: vv[0] for kk, vv in batch.items()}
    base = make_shard_map_sgns_step(
        plan.mesh, NEG, "exact", jnp.float32, jnp.float32, True)
    knob = make_shard_map_sgns_step(
        plan.mesh, NEG, "exact", jnp.float32, jnp.float32, True,
        sync_every=1)
    b_out, b_m = jax.jit(base)(sharded, flat_batch, negs[0], alphas[0])
    k_out, k_m = jax.jit(knob)(sharded, flat_batch, negs[0], alphas[0])
    assert np.array_equal(np.asarray(b_out.syn0), np.asarray(k_out.syn0))
    assert np.array_equal(np.asarray(b_out.syn1), np.asarray(k_out.syn1))
    assert float(b_m.loss) == float(k_m.loss)


def _np_window_oracle(params, batch, negs, alphas, nd, k, stab):
    """Replay the window in NumPy float64: each data shard runs k steps on
    its contiguous batch-column slice and disjoint pool slice against its own
    full-view replica, then merged = start + mean over shards of the deltas
    (exact: nd is a power of 2)."""
    syn0 = np.asarray(params.syn0, np.float64)
    syn1 = np.asarray(params.syn1, np.float64)
    b = batch["centers"].shape[1]
    bl = b // nd
    p = negs.shape[1] // nd
    locals_ = []
    for j in range(nd):
        s0, s1 = syn0.copy(), syn1.copy()
        for i in range(k):
            cols = slice(j * bl, (j + 1) * bl)
            s0, s1 = _np_shared_step(
                s0, s1,
                np.asarray(batch["centers"][i, cols]),
                np.asarray(batch["contexts"][i, cols]),
                np.asarray(batch["mask"][i, cols], np.float64),
                np.asarray(negs[i, j * p:(j + 1) * p]),
                float(alphas[i]), NEG, stab)
        locals_.append((s0, s1))
    m0 = syn0 + sum(s0 - syn0 for s0, _ in locals_) / nd
    m1 = syn1 + sum(s1 - syn1 for _, s1 in locals_) / nd
    return m0, m1


@pytest.mark.parametrize("shape", MESHES)
@pytest.mark.parametrize("stab", [
    None,
    Stabilizers(max_row_norm=5.0, update_clip=0.05),
])
def test_window_matches_numpy_oracle_f64(shape, stab):
    """The k-step merged result ≡ the NumPy per-shard replay at f64 ~1e-11,
    every mesh shape, stabilizers off and on (the owner-local clamp pass runs
    on the LOCAL touched set, which is exactly what the per-shard oracle
    replays; the merge preserves the clamp ball by convexity)."""

    nd, nm = shape
    k = 2
    with jax.enable_x64():
        params, batch, negs, alphas = _inputs(
            jnp.float64, k=k, nd=nd, seed=5)
        plan = make_mesh(*shape)
        sharded = EmbeddingPair(
            jax.device_put(params.syn0, plan.embedding),
            jax.device_put(params.syn1, plan.embedding))
        window = make_shard_map_sgns_step(
            plan.mesh, NEG, "exact", jnp.float64, jnp.float64, True,
            stabilizers=stab, sync_every=k)
        got, m = jax.jit(window)(sharded, batch, negs, alphas)
        ref0, ref1 = _np_window_oracle(
            params, batch, negs, alphas, nd, k, stab or Stabilizers())
        # atol 5e-9 for the INDEPENDENT NumPy oracle: XLA's exp differs from
        # libm's in the last ulps (the test_stabilizers oracle documents the
        # same gap at 3e-8 with deliberately blown rows); chaining k steps
        # feeds step 1's ulp drift through step 2's gathers and the merge
        # averages it across shards, landing ~2e-9 here. Any real semantic
        # error — a shard reading another shard's pool slice, a missed merge
        # scale, a stabilizer pass on the wrong touched set — is orders of
        # magnitude larger, and the same-transcendentals replay below pins
        # those at 1e-12.
        np.testing.assert_allclose(
            np.asarray(got.syn0), ref0, rtol=0, atol=5e-9,
            err_msg=f"merged syn0 @ {shape}")
        np.testing.assert_allclose(
            np.asarray(got.syn1), ref1, rtol=0, atol=5e-9,
            err_msg=f"merged syn1 @ {shape}")
        # metrics come back per-step: [k] vectors
        assert np.asarray(m.loss).shape == (k,)
        assert np.asarray(m.pairs).shape == (k,)

        # the ~1e-11-class semantic pin: replay k owner-local steps per
        # shard with the single-device JAX core (same transcendentals, so
        # only SCHEDULE errors can show) and merge in f64 on the host
        if stab is not None:
            return  # the stabilized replay is the NumPy oracle's job above
        b = batch["centers"].shape[1]
        bl, p = b // nd, negs.shape[1] // nd
        start0, start1 = np.asarray(params.syn0), np.asarray(params.syn1)
        d0 = np.zeros_like(start0)
        d1 = np.zeros_like(start1)
        for j in range(nd):
            rp = EmbeddingPair(params.syn0, params.syn1)
            for i in range(k):
                cols = slice(j * bl, (j + 1) * bl)
                rp, _ = sgns_step_shared_core(
                    rp, batch["centers"][i, cols], batch["contexts"][i, cols],
                    batch["mask"][i, cols], negs[i, j * p:(j + 1) * p],
                    alphas[i], NEG, "exact", jnp.float64, False, jnp.float64,
                    True)
            d0 += np.asarray(rp.syn0) - start0
            d1 += np.asarray(rp.syn1) - start1
        np.testing.assert_allclose(
            np.asarray(got.syn0), start0 + d0 / nd, rtol=0, atol=1e-12,
            err_msg=f"replay syn0 @ {shape}")
        np.testing.assert_allclose(
            np.asarray(got.syn1), start1 + d1 / nd, rtol=0, atol=1e-12,
            err_msg=f"replay syn1 @ {shape}")


def test_window_nd1_bit_identical_to_sync_chain():
    """No data axis → the merge is identity and the owner-local schedule IS
    the synchronous schedule: the window equals k chained synchronous steps
    bit-for-bit (f32 — same ops in the same order, not just close)."""
    shape = (1, 8)
    k = 2
    plan = make_mesh(*shape)
    params, batch, negs, alphas = _inputs(jnp.float32, k=k, nd=1, seed=7)
    sharded = EmbeddingPair(
        jax.device_put(params.syn0, plan.embedding),
        jax.device_put(params.syn1, plan.embedding))
    window = make_shard_map_sgns_step(
        plan.mesh, NEG, "exact", jnp.float32, jnp.float32, True,
        sync_every=k)
    w_out, _ = jax.jit(window)(sharded, batch, negs, alphas)
    step = make_shard_map_sgns_step(
        plan.mesh, NEG, "exact", jnp.float32, jnp.float32, True)
    p = sharded
    for i in range(k):
        p, _ = jax.jit(step)(
            p, {kk: vv[i] for kk, vv in batch.items()}, negs[i], alphas[i])
    assert np.array_equal(np.asarray(w_out.syn0), np.asarray(p.syn0))
    assert np.array_equal(np.asarray(w_out.syn1), np.asarray(p.syn1))


def _fit_localsgd(shape, sync_every, seed=11):
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(40)]
    sents = [[words[j] for j in rng.integers(0, 40, 10)] for _ in range(80)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=64,
                         num_iterations=1, window=2, negatives=NEG,
                         negative_pool=16, steps_per_dispatch=2, seed=seed,
                         step_lowering="shard_map", sync_every=sync_every)
    tr = Trainer(cfg, vocab, plan=make_mesh(*shape))
    tr.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    return np.asarray(tr.params.syn0), np.asarray(tr.params.syn1)


def test_trainer_localsgd_deterministic_and_finite():
    """Merged runs are bit-identical per (seed, mesh, sync_every) — the
    determinism contract docs/sharding.md §Local-SGD documents — and train
    to finite params on a mesh with a real data axis."""
    a0, a1 = _fit_localsgd((2, 4), 2)
    b0, b1 = _fit_localsgd((2, 4), 2)
    assert np.array_equal(a0, b0) and np.array_equal(a1, b1), (
        "local-SGD run is not deterministic per (seed, mesh, k)")
    assert np.all(np.isfinite(a0)) and np.all(np.isfinite(a1))


def test_config_refusals_sync_every():
    base = dict(negative_pool=16, steps_per_dispatch=4)
    with pytest.raises(ValueError, match="sync_every.*shard_map"):
        Word2VecConfig(sync_every=2, **base)          # GSPMD has no window
    with pytest.raises(ValueError, match="sync_every.*positive"):
        Word2VecConfig(sync_every=0, **base)
    with pytest.raises(ValueError, match="sync_every.*packed-pair"):
        Word2VecConfig(sync_every=2, step_lowering="shard_map",
                       device_pairgen=True, **base)
    with pytest.raises(ValueError, match="sync_every.*divide"):
        Word2VecConfig(sync_every=3, step_lowering="shard_map", **base)
    # the valid combination constructs
    cfg = Word2VecConfig(sync_every=2, step_lowering="shard_map", **base)
    assert cfg.sync_every == 2
