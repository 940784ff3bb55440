"""ISSUE-14 step restructurings: fused logit chain (config.fused_logits) and
end-to-end bf16 update chain (config.bf16_chain).

Three layers, mirroring the PR-7 stabilizer discipline:

1. ORACLE — the fused coefficient chain against a plain-NumPy float64 oracle
   (masked slots, duplicate indices, pool-collision entries, pool edge sizes
   P=1 / odd / P=B), plus fused ≡ classic and bf16_chain ≡ classic at f64.
2. OFF-IS-BIT-IDENTICAL — the PR-7 contract: both knobs off elide the new
   ops entirely (identical lowered module, bit-identical trained params vs
   a default-constructed trainer).
3. DISPATCH — trainer fits with each knob on, shard_map gets the fused chain
   (cross-lowering f64 equivalence), and the config selection matrix refuses
   every documented illegal combination (graftlint R8 parses the parity;
   graftcheck executes it).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    sgns_step_core,
    sgns_step_shared_core,
)
from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train.trainer import Trainer

NEG = 3


# ---------------------------------------------------------------------------
# 1. NumPy float64 oracle for the fused shared-pool coefficient chain
# ---------------------------------------------------------------------------


def _sig(x):
    out = np.empty_like(x)
    pos = x >= 0
    out[pos] = 1.0 / (1.0 + np.exp(-x[pos]))
    ex = np.exp(x[~pos])
    out[~pos] = ex / (1.0 + ex)
    return out


def _np_shared_step(syn0, syn1, centers, contexts, mask, negs, alpha, n):
    """Plain-NumPy float64 mirror of the (unfused) shared-pool update — the
    same oracle family tests/test_stabilizers.py pins the stabilized step
    against; the fused chain must land on the identical math."""
    e_in, e_pos, Z = syn0[centers], syn1[contexts], syn1[negs]
    P = negs.shape[0]
    f_pos = (e_in * e_pos).sum(-1)
    f_neg = e_in @ Z.T
    neg_valid = (negs[None, :] != contexts[:, None]).astype(np.float64) \
        * mask[:, None]
    g_pos = (1.0 - _sig(f_pos)) * alpha * mask
    g_neg = (0.0 - _sig(f_neg)) * alpha * neg_valid * (n / P)
    d_in = g_pos[:, None] * e_pos + g_neg @ Z
    d_pos = g_pos[:, None] * e_in
    d_Z = g_neg.T @ e_in
    s0, s1 = syn0.copy(), syn1.copy()
    np.add.at(s0, centers, d_in)
    np.add.at(s1, contexts, d_pos)
    np.add.at(s1, negs, d_Z)
    return s0, s1


def _inputs(seed=0, V=60, D=12, B=24, P=8):
    rng = np.random.default_rng(seed)
    syn0 = rng.normal(0, 0.5, (V, D))
    syn1 = rng.normal(0, 0.5, (V, D))
    centers = rng.integers(0, V, B).astype(np.int32)
    contexts = rng.integers(0, V, B).astype(np.int32)
    centers[3] = centers[4] = 2          # duplicates on a (hot-class) row
    contexts[5] = contexts[6] = 1
    mask = (np.arange(B) < B - 4).astype(np.float64)
    # masked tail slots point at real rows: their coefficients must be zero
    centers[B - 1], contexts[B - 1] = 0, 1
    negs = rng.integers(0, V, P).astype(np.int32)
    negs[0] = contexts[0]                # collision -> invalid (pair 0) entry
    if P > 2:
        negs[1] = negs[2]                # duplicate pool entries
    return syn0, syn1, centers, contexts, mask, negs


def _run_shared(params_np, centers, contexts, mask, negs, alpha, **kw):
    with jax.enable_x64():
        got = sgns_step_shared_core(
            EmbeddingPair(jnp.asarray(params_np[0]), jnp.asarray(params_np[1])),
            jnp.asarray(centers), jnp.asarray(contexts),
            jnp.asarray(mask, jnp.float32), jnp.asarray(negs),
            jnp.float64(alpha), NEG, "exact", jnp.float64, False, jnp.float64,
            True, **kw)
    return got


@pytest.mark.parametrize("pool", [1, 3, 8, 24])  # edge sizes incl. P == B
def test_fused_oracle_f64(pool):
    syn0, syn1, centers, contexts, mask, negs = _inputs(P=pool)
    ref0, ref1 = _np_shared_step(
        syn0, syn1, centers, contexts, mask, negs, 0.05, NEG)
    got, _ = _run_shared((syn0, syn1), centers, contexts, mask, negs, 0.05,
                         fused=True)
    np.testing.assert_allclose(np.asarray(got.syn0), ref0, atol=3e-8)
    np.testing.assert_allclose(np.asarray(got.syn1), ref1, atol=3e-8)


@pytest.mark.parametrize("kw", [
    dict(fused=True),
    dict(bf16_chain=True),
    dict(fused=True, bf16_chain=True),
])
def test_fused_and_chain_match_classic_f64(kw):
    """The restructured chains are the SAME math as the classic chain at f64
    (association-only differences, far under 1e-12) — params AND metrics."""
    syn0, syn1, centers, contexts, mask, negs = _inputs()
    base, mb = _run_shared((syn0, syn1), centers, contexts, mask, negs, 0.05)
    got, mg = _run_shared((syn0, syn1), centers, contexts, mask, negs, 0.05,
                          **kw)
    np.testing.assert_allclose(np.asarray(got.syn0), np.asarray(base.syn0),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(got.syn1), np.asarray(base.syn1),
                               atol=1e-12)
    assert abs(float(mg.loss) - float(mb.loss)) < 1e-12
    assert float(mg.pairs) == float(mb.pairs)


def test_perpair_fused_and_chain_match_classic_f64():
    syn0, syn1, centers, contexts, mask, _ = _inputs()
    rng = np.random.default_rng(7)
    pn = rng.integers(0, syn0.shape[0], (centers.shape[0], NEG)).astype(
        np.int32)
    pn[0, 0] = contexts[0]               # negative colliding with positive
    with jax.enable_x64():
        params = EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1))
        args = (jnp.asarray(centers), jnp.asarray(contexts),
                jnp.asarray(mask, jnp.float32), jnp.asarray(pn),
                jnp.float64(0.05), "exact", jnp.float64, False)
        base, mb = sgns_step_core(params, *args)
        got, mg = sgns_step_core(params, *args, fused=True, bf16_chain=True)
    np.testing.assert_allclose(np.asarray(got.syn0), np.asarray(base.syn0),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(got.syn1), np.asarray(base.syn1),
                               atol=1e-12)
    assert abs(float(mg.loss) - float(mb.loss)) < 1e-12


def test_fused_chain_bf16_tracks_f32():
    """The fused bf16 chain stays within the shared-pool coefficient noise
    bound of the f32 chain (the PERF.md §4 tolerance argument, now for the
    fused form)."""
    syn0, syn1, centers, contexts, mask, negs = _inputs(V=40, D=16, B=32, P=8)
    params32 = EmbeddingPair(jnp.asarray(syn0, jnp.float32),
                             jnp.asarray(syn1, jnp.float32))
    args = (jnp.asarray(centers), jnp.asarray(contexts),
            jnp.asarray(mask, jnp.float32), jnp.asarray(negs),
            jnp.float32(0.05), NEG, "exact")
    ref, _ = sgns_step_shared_core(params32, *args, jnp.float32, False,
                                   jnp.float32, True)
    got, _ = sgns_step_shared_core(params32, *args, jnp.bfloat16, False,
                                   jnp.bfloat16, True, fused=True,
                                   bf16_chain=True)
    err = np.abs(np.asarray(got.syn0, np.float32)
                 - np.asarray(ref.syn0, np.float32)).max()
    assert err < 0.02, err


# ---------------------------------------------------------------------------
# 2. Off-is-bit-identical (the PR-7 elision contract)
# ---------------------------------------------------------------------------


def _toy():
    rng = np.random.default_rng(0)
    V = 80
    words = [f"w{i}" for i in range(V)]
    vocab = Vocabulary.from_words_and_counts(
        words, np.sort(rng.integers(5, 100, V))[::-1].copy())
    sents = [[f"w{i}" for i in rng.integers(0, V, 12)] for _ in range(80)]
    return vocab, encode_sentences(sents, vocab, 1000)


def _fit(vocab, enc, **kw):
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=32,
                         num_iterations=1, window=2, steps_per_dispatch=4,
                         prefetch_chunks=0, seed=3, **kw)
    t = Trainer(cfg, vocab, plan=make_mesh(1, 1))
    t.fit(enc)
    return (np.asarray(t.params.syn0.astype(jnp.float32)),
            np.asarray(t.params.syn1.astype(jnp.float32)))


def test_knobs_off_elide_ops_bit_identical():
    """Default config vs explicitly-off knobs: identical LOWERED module (the
    new ops are structurally absent, not just numerically neutral) and
    bit-identical trained params."""
    syn0, syn1, centers, contexts, mask, negs = _inputs()
    params = EmbeddingPair(jnp.asarray(syn0, jnp.float32),
                           jnp.asarray(syn1, jnp.float32))
    args = (jnp.asarray(centers), jnp.asarray(contexts),
            jnp.asarray(mask, jnp.float32), jnp.asarray(negs),
            jnp.float32(0.05), NEG)

    def lower(**kw):
        def step(p, c, x, m, ng):
            return sgns_step_shared_core(p, c, x, m, ng, jnp.float32(0.05),
                                         NEG, **kw)
        return jax.jit(step).lower(params, *args[:4]).as_text()

    assert lower() == lower(fused=False, bf16_chain=False)

    vocab, enc = _toy()
    a = _fit(vocab, enc, negative_pool=16)
    b = _fit(vocab, enc, negative_pool=16, fused_logits=False,
             bf16_chain=False)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


# ---------------------------------------------------------------------------
# 3. Trainer dispatch, shard_map fused, and the refusal matrix
# ---------------------------------------------------------------------------


def test_trainer_fused_and_chain_fit_smoke():
    vocab, enc = _toy()
    base = _fit(vocab, enc, negative_pool=16)
    fus = _fit(vocab, enc, negative_pool=16, fused_logits=True)
    assert np.allclose(base[0], fus[0], atol=2e-6)
    bf = _fit(vocab, enc, negative_pool=16, param_dtype="bfloat16",
              compute_dtype="bfloat16", logits_dtype="bfloat16",
              fused_logits=True, bf16_chain=True)
    assert np.isfinite(bf[0]).all() and np.abs(bf[0]).sum() > 0


def test_shard_map_fused_matches_gspmd_fused_f64():
    """shard_map runs the SAME fused chain through the shared helper —
    cross-lowering equivalence at f64 on a 2x4 mesh."""

    with jax.enable_x64():
        rng = np.random.default_rng(0)
        v, d, b, pool = 64, 16, 32, 8
        params = EmbeddingPair(
            jnp.asarray(rng.standard_normal((v, d)), jnp.float64),
            jnp.asarray(rng.standard_normal((v, d)) * 0.1, jnp.float64))
        batch = {
            "centers": jnp.asarray(rng.integers(0, v, b), jnp.int32),
            "contexts": jnp.asarray(rng.integers(0, v, b), jnp.int32),
            "mask": jnp.asarray(rng.random(b) < 0.9, jnp.float32),
        }
        negs = jnp.asarray(rng.integers(0, v, pool), jnp.int32)
        alpha = jnp.float64(0.025)
        ref, mref = sgns_step_shared_core(
            params, batch["centers"], batch["contexts"], batch["mask"],
            negs, alpha, NEG, "exact", jnp.float64, False, jnp.float64, True,
            fused=True, bf16_chain=True)
        plan = make_mesh(2, 4)
        sharded = EmbeddingPair(
            jax.device_put(params.syn0, plan.embedding),
            jax.device_put(params.syn1, plan.embedding))
        step = make_shard_map_sgns_step(
            plan.mesh, NEG, "exact", jnp.float64, jnp.float64, True,
            fused=True, bf16_chain=True)
        # jitted like the trainer's dispatch: eager shard_map is op-by-op
        got, mgot = jax.jit(step)(sharded, batch, negs, alpha)
        np.testing.assert_allclose(np.asarray(got.syn0),
                                   np.asarray(ref.syn0), atol=1e-11)
        np.testing.assert_allclose(np.asarray(got.syn1),
                                   np.asarray(ref.syn1), atol=1e-11)
        assert abs(float(mgot.loss) - float(mref.loss)) < 1e-9


@pytest.mark.parametrize("kw", [
    dict(fused_logits=True, cbow=True),
    dict(fused_logits=True, duplicate_scaling=True),
    dict(bf16_chain=True),                       # compute f32: no chain
    dict(bf16_chain=True, cbow=True, compute_dtype="bfloat16"),
    dict(bf16_chain=True, compute_dtype="bfloat16", negative_pool=512),
])
def test_config_refusal_matrix(kw):
    with pytest.raises(ValueError):
        Word2VecConfig(**kw)


def test_config_legal_combinations_construct():
    Word2VecConfig(fused_logits=True)
    Word2VecConfig(fused_logits=True, step_lowering="shard_map",
                   pairs_per_batch=8192)
    Word2VecConfig(bf16_chain=True, compute_dtype="bfloat16",
                   logits_dtype="bfloat16")
    Word2VecConfig(bf16_chain=True, compute_dtype="bfloat16",
                   negative_pool=0)
    # round-trip + replace preserve the knobs
    c = Word2VecConfig(fused_logits=True)
    assert Word2VecConfig.from_dict(c.to_dict()).fused_logits
    assert c.replace(seed=5).fused_logits

