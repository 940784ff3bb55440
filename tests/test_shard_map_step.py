"""The explicit shard_map scale-out step (ops/sgns_shard.py, ISSUE 4).

Three contracts, each tested at every 8-device mesh shape (1x8, 2x4, 4x2,
8x1 — the conftest forces the 8-device CPU mesh):

1. EQUIVALENCE — shard_map ≡ GSPMD ≡ single-device step at float64 to ~1e-12
   (params; the loss side-channel reassociates its f32 sums across shards and
   gets a correspondingly looser bound), plus the rows/cols cross-layout loss
   check against the shard_map step.
2. DETERMINISM — ``step_lowering`` changes wall clock only: params are
   bit-identical across repeated runs per lowering, and the two lowerings
   agree to f32 reassociation noise (bit-identity ACROSS lowerings is
   impossible by construction: different reduction orders).
3. SCHEDULE — the compiled shard_map HLO moves ZERO update bytes over the
   model axis (its only model-axis collective is the forward row-assembly
   psum) and fewer total collective bytes than GSPMD on every mesh with a
   data axis — asserted through the real auditor (tools/collectives.py), so
   a regression that re-introduces a dense all-gather/all-reduce into the
   compiled step fails HERE, not on a hardware run. tools/shard_ab.py --smoke
   runs as a subprocess for the same reason (the harness cannot rot).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core
from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
from glint_word2vec_tpu.parallel.mesh import classify_replica_groups, make_mesh
from glint_word2vec_tpu.train.trainer import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MESHES = [(1, 8), (2, 4), (4, 2), (8, 1)]
NEG = 3


def _f64_inputs(v=64, d=16, b=32, pool=8, seed=0):
    rng = np.random.default_rng(seed)
    params = EmbeddingPair(
        jnp.asarray(rng.standard_normal((v, d)), jnp.float64),
        jnp.asarray(rng.standard_normal((v, d)) * 0.1, jnp.float64))
    batch = {
        "centers": jnp.asarray(rng.integers(0, v, b), jnp.int32),
        "contexts": jnp.asarray(rng.integers(0, v, b), jnp.int32),
        # some padded pairs, so masking semantics are exercised
        "mask": jnp.asarray(rng.random(b) < 0.9, jnp.float32),
    }
    negs = jnp.asarray(rng.integers(0, v, pool), jnp.int32)
    return params, batch, negs, jnp.float64(0.025)


@pytest.mark.parametrize("shape", MESHES)
def test_equivalence_f64_all_mesh_shapes(shape):
    """shard_map ≡ GSPMD ≡ single-device at f64 ~1e-12, per mesh shape."""

    with jax.enable_x64():
        params, batch, negs, alpha = _f64_inputs()
        ref, mref = sgns_step_shared_core(
            params, batch["centers"], batch["contexts"], batch["mask"],
            negs, alpha, NEG, "exact", jnp.float64, False, jnp.float64, True)

        plan = make_mesh(*shape)
        sharded = EmbeddingPair(
            jax.device_put(params.syn0, plan.embedding),
            jax.device_put(params.syn1, plan.embedding))

        # GSPMD lowering on this mesh
        def gspmd(p, b_, n_, a_):
            new_p, m = sgns_step_shared_core(
                p, b_["centers"], b_["contexts"], b_["mask"], n_, a_,
                NEG, "exact", jnp.float64, False, jnp.float64, True)
            return jax.lax.with_sharding_constraint(
                new_p, EmbeddingPair(plan.embedding, plan.embedding)), m

        g_out, g_m = jax.jit(gspmd)(sharded, batch, negs, alpha)
        # explicit shard_map lowering on this mesh
        step = make_shard_map_sgns_step(
            plan.mesh, NEG, "exact", jnp.float64, jnp.float64, True)
        s_out, s_m = jax.jit(step)(sharded, batch, negs, alpha)
        assert s_out.syn0.sharding.is_equivalent_to(plan.embedding, 2)

        for out, m, name in ((g_out, g_m, "gspmd"), (s_out, s_m, "shard_map")):
            np.testing.assert_allclose(
                np.asarray(out.syn0), np.asarray(ref.syn0),
                rtol=0, atol=1e-12, err_msg=f"{name} syn0 @ {shape}")
            np.testing.assert_allclose(
                np.asarray(out.syn1), np.asarray(ref.syn1),
                rtol=0, atol=1e-12, err_msg=f"{name} syn1 @ {shape}")
            assert float(m.pairs) == float(mref.pairs)
            # the loss numerators are f32 by production choice
            # (shared_pool_coeffs casts f_pos to f32), so cross-shard
            # reassociation bounds the side-channel at f32 resolution
            assert abs(float(m.loss) - float(mref.loss)) < 1e-5


def test_cross_layout_loss_rows_vs_cols():
    """The CIKM'16 column layout (GSPMD, embedding_partition='cols') and the
    explicit rows schedule compute the same loss — the dryrun's cross-layout
    check extended to the shard_map step (f64)."""

    with jax.enable_x64():
        params, batch, negs, alpha = _f64_inputs(v=64, d=32, b=32, pool=8)
        plan = make_mesh(2, 4)
        rows_p = EmbeddingPair(
            jax.device_put(params.syn0, plan.embedding),
            jax.device_put(params.syn1, plan.embedding))
        cols_p = EmbeddingPair(
            jax.device_put(params.syn0, plan.embedding_cols),
            jax.device_put(params.syn1, plan.embedding_cols))

        step = make_shard_map_sgns_step(
            plan.mesh, NEG, "exact", jnp.float64, jnp.float64, True)
        _, m_rows = jax.jit(step)(rows_p, batch, negs, alpha)

        def cols(p, b_, n_, a_):
            new_p, m = sgns_step_shared_core(
                p, b_["centers"], b_["contexts"], b_["mask"], n_, a_,
                NEG, "exact", jnp.float64, False, jnp.float64, True)
            return jax.lax.with_sharding_constraint(
                new_p, EmbeddingPair(plan.embedding_cols,
                                     plan.embedding_cols)), m

        _, m_cols = jax.jit(cols)(cols_p, batch, negs, alpha)
        assert abs(float(m_rows.loss) - float(m_cols.loss)) < 1e-5


def _fit(lowering, shape, vocab, sents, seed=3):
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=64,
                         num_iterations=1, window=2, negatives=NEG,
                         negative_pool=16, steps_per_dispatch=2, seed=seed,
                         step_lowering=lowering)
    tr = Trainer(cfg, vocab, plan=make_mesh(*shape))
    tr.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    return np.asarray(tr.params.syn0), np.asarray(tr.params.syn1)


def test_step_lowering_wall_clock_only():
    """Repeated runs are bit-identical PER lowering; the two lowerings agree
    to f32 reassociation noise (different reduction orders — cross-lowering
    bit-identity is not claimed, docs/sharding.md)."""
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(40)]
    sents = [[words[j] for j in rng.integers(0, 40, 10)] for _ in range(80)]
    vocab = build_vocab(sents, min_count=1)

    runs = {low: [_fit(low, (2, 4), vocab, sents) for _ in range(2)]
            for low in ("gspmd", "shard_map")}
    for low, ((a0, a1), (b0, b1)) in runs.items():
        assert np.array_equal(a0, b0) and np.array_equal(a1, b1), (
            f"{low} lowering is not run-to-run deterministic")
    np.testing.assert_allclose(runs["gspmd"][0][0], runs["shard_map"][0][0],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(runs["gspmd"][0][1], runs["shard_map"][0][1],
                               rtol=1e-4, atol=1e-6)


def test_trainer_shard_map_trains_and_stays_sharded():
    rng = np.random.default_rng(1)
    words = [f"w{i}" for i in range(50)]
    sents = [[words[j] for j in rng.integers(0, 50, 12)] for _ in range(60)]
    vocab = build_vocab(sents, min_count=1)
    plan = make_mesh(2, 4)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=64,
                         num_iterations=2, window=3, negative_pool=16,
                         step_lowering="shard_map")
    tr = Trainer(cfg, vocab, plan=plan)
    tr.fit(encode_sentences(sents, vocab))
    assert tr.params.syn0.sharding.is_equivalent_to(plan.embedding, 2)
    assert np.all(np.isfinite(np.asarray(tr.unpadded_params().syn0)))


def test_shard_map_device_pairgen_smoke():
    """The shard_map inner composes with the on-device pair generator feed."""
    rng = np.random.default_rng(2)
    words = [f"w{i}" for i in range(50)]
    sents = [[words[j] for j in rng.integers(0, 50, 12)] for _ in range(60)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=64,
                         num_iterations=1, window=3, negative_pool=16,
                         device_pairgen=True, step_lowering="shard_map")
    tr = Trainer(cfg, vocab, plan=make_mesh(2, 4))
    tr.fit(encode_sentences(sents, vocab))
    assert np.all(np.isfinite(np.asarray(tr.unpadded_params().syn0)))


# -- config selection matrix ---------------------------------------------------------


def test_config_refusals():
    for kw in (dict(cbow=True), dict(duplicate_scaling=True),
               dict(negative_pool=0),
               dict(embedding_partition="cols")):
        with pytest.raises(ValueError, match="shard_map|lowering"):
            Word2VecConfig(step_lowering="shard_map", **kw)
    with pytest.raises(ValueError, match="step_lowering"):
        Word2VecConfig(step_lowering="banana")
    # AUTO pool resolves to a real pool (not 0) under shard_map even at
    # small batches — the schedule requires the shared-pool estimator
    cfg = Word2VecConfig(step_lowering="shard_map", pairs_per_batch=256)
    assert cfg.negative_pool > 0


def test_trainer_refuses_indivisible_batch():
    sents = [["a", "b", "c"]] * 10
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=65,
                         negative_pool=16, step_lowering="shard_map")
    with pytest.raises(ValueError, match="divisible"):
        Trainer(cfg, vocab, plan=make_mesh(2, 4))


# -- replica-group classifier (the audit's mesh bridge) ------------------------------


def test_classify_replica_groups():
    assert classify_replica_groups(2, 4, [[0, 1, 2, 3], [4, 5, 6, 7]]) == "model"
    assert classify_replica_groups(
        2, 4, [[0, 4], [1, 5], [2, 6], [3, 7]]) == "data"
    assert classify_replica_groups(2, 4, [range(8)]) == "all"
    assert classify_replica_groups(2, 4, [[0, 1], [2, 3], [4, 5], [6, 7]]) == "other"
    # order inside a group must not matter (XLA orders ids arbitrarily)
    assert classify_replica_groups(2, 4, [[3, 1, 0, 2], [7, 5, 6, 4]]) == "model"
    assert classify_replica_groups(4, 2, [[0, 1], [2, 3], [4, 5], [6, 7]]) == "model"
    assert classify_replica_groups(4, 2, [[0, 2, 4, 6], [1, 3, 5, 7]]) == "data"


# -- the audited schedule + the A/B harness cannot rot -------------------------------


def test_collective_audit_smoke_schedule_holds():
    """Compile both lowerings at the smoke geometry on every mesh shape and
    assert the shard_map schedule facts from the HLO: zero model-axis update
    bytes, and (on every mesh with a data axis) fewer total bytes than
    GSPMD. This is the regression tripwire the ISSUE asks for: a change that
    re-introduces a dense all-gather/all-reduce into the compiled step fails
    this test, not a hardware run."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "collectives.py"),
         "--smoke", "--mesh", "all"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(result["meshes"]) == 4
    for mesh in result["meshes"]:
        nd, nm = mesh["mesh"]
        sm = mesh["shard_map"]
        assert sm["model_axis_update_bytes"] == 0, (nd, nm, sm)
        if nm > 1:
            # the one forward-assembly psum was found and matched
            assert sm["forward_assembly_bytes"] > 0, (nd, nm, sm)
        assert "other" not in sm["bytes_by_axis"], sm
        if nd > 1:
            # with a data axis, GSPMD pays the dense [Vs, D] delta psum;
            # the explicit schedule must move strictly fewer bytes
            assert sm["total_bytes"] < mesh["gspmd"]["total_bytes"], (nd, nm)
        # the local-SGD window (config.sync_every — ISSUE 17): still zero
        # model-axis update bytes, all k per-step assembly psums visible to
        # the text audit (the Python-unrolled-loop contract), and per-window
        # data bytes within the priced bound of the k=1 GSPMD schedule
        ls = mesh.get("localsgd")
        assert ls is not None and ls["sync_every"] > 1, mesh.keys()
        assert ls["model_axis_update_bytes"] == 0, (nd, nm, ls)
        if nm > 1:
            assert ls["forward_assembly_count"] == ls["sync_every"], ls
        if nd > 1:
            assert ls["window_data_over_gspmd_k1_schedule"] <= 0.2, (nd, nm, ls)


def test_shard_ab_smoke_tier():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shard_ab.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(result["meshes"]) == 4
    for mesh in result["meshes"]:
        assert mesh["gspmd_ms"] > 0 and mesh["shard_map_ms"] > 0
        # f32 agreement: reassociation noise only, relative to param scale
        assert mesh["max_abs_diff"] <= 1e-4 * max(mesh["param_abs_max"], 1e-3)
    # the sync_every interleaved arm (ISSUE 17): every arm timed, and the
    # sync_every=1 arm is the synchronous baseline — zero divergence from
    # itself, positive divergence recorded (not asserted — it IS the
    # staleness measurement) for the local arms
    for mesh in result["localsgd_meshes"]:
        arms = mesh["arms"]
        assert "1" in arms and len(arms) >= 2
        for a in arms.values():
            assert a["ms_per_step"] > 0
        assert arms["1"]["max_abs_diff_vs_sync"] == 0.0
