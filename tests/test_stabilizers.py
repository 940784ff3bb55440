"""The self-stabilizing runtime (ISSUE 7): in-step stabilizers + the
norm-watchdog recovery ladder.

Four layers, each pinned where it can actually break:

1. ORACLE — the stabilized shared-pool SGNS update (update_clip → scatter →
   per-touched-row decay+clamp) against a plain-NumPy float64 oracle: clamp
   engaged and not engaged, masked batch slots excluded from the touched set,
   never-touched (padding-class) rows bit-untouched, and the all-off state
   bit-identical to the pre-stabilizer step.
2. CROSS-LOWERING — GSPMD single-program ≡ shard_map owner-local at f64
   ~1e-11 with stabilizers ON (every mesh shape), and banded CBOW ≡ scatter
   CBOW with the clamp+clip engaged.
3. ESCALATION LADDER — watchdog `recover` policy units (would_fire purity,
   one recovery per firing probe, budget decrement, exhaustion degrades to
   the halt contract with the telemetry record emitted BEFORE the raise) and
   the snapshot-ring arming fix (the previously-dead norm_watch='recover' +
   nonfinite_policy='halt' combination).
4. VOCAB-SCALED AUTO POOL — the trainer re-resolves a still-AUTO pool into
   the measured large-vocab safe band (load <= 160 past 500k vocab), never
   touches explicit pools, and keeps replace() re-resolution semantics.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import Vocabulary, build_vocab
from glint_word2vec_tpu.obs.watch import NormWatchdog
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    Stabilizers,
    sgns_step_shared_core,
)
from glint_word2vec_tpu.train import faults
from glint_word2vec_tpu.train.faults import NormBlowupError
from glint_word2vec_tpu.train.trainer import Trainer

# ---------------------------------------------------------------------------
# 1. NumPy float64 oracle for the stabilized shared-pool step
# ---------------------------------------------------------------------------


def _np_shared_step(syn0, syn1, centers, contexts, mask, negs, alpha, n,
                    stab: Stabilizers):
    """Plain-NumPy mirror of sgns_step_shared_core + stabilizers (float64)."""
    e_in, e_pos, Z = syn0[centers], syn1[contexts], syn1[negs]
    P = negs.shape[0]

    def sig(x):
        # the numerically-stable two-branch expit, matching jax.nn.sigmoid
        # to the ulp (the naive 1/(1+exp(-x)) loses precision for x < 0,
        # which the blown-row dot products amplify past the tolerance)
        out = np.empty_like(x)
        pos = x >= 0
        out[pos] = 1.0 / (1.0 + np.exp(-x[pos]))
        ex = np.exp(x[~pos])
        out[~pos] = ex / (1.0 + ex)
        return out
    f_pos = (e_in * e_pos).sum(-1)
    f_neg = e_in @ Z.T
    neg_valid = (negs[None, :] != contexts[:, None]).astype(np.float64) \
        * mask[:, None]
    g_pos = (1.0 - sig(f_pos)) * alpha * mask
    g_neg = (0.0 - sig(f_neg)) * alpha * neg_valid * (n / P)
    d_in = g_pos[:, None] * e_pos + g_neg @ Z
    d_pos = g_pos[:, None] * e_in
    d_Z = g_neg.T @ e_in
    if stab.update_clip:
        def clip(d):
            nrm = np.linalg.norm(d, axis=-1, keepdims=True)
            return d * np.minimum(1.0, stab.update_clip / np.maximum(
                nrm, 1e-30))
        d_in, d_pos = clip(d_in), clip(d_pos)
    s0, s1 = syn0.copy(), syn1.copy()
    np.add.at(s0, centers, d_in)
    np.add.at(s1, contexts, d_pos)
    np.add.at(s1, negs, d_Z)
    if (stab.max_row_norm or stab.row_l2) and mask.sum() > 0:
        t0 = np.unique(centers[mask > 0])
        t1 = np.unique(np.concatenate([contexts[mask > 0], negs]))
        for mat, idx in ((s0, t0), (s1, t1)):
            rows = mat[idx]
            scale = np.ones(len(idx))
            if stab.row_l2:
                scale = scale * (1.0 - alpha * stab.row_l2)
            if stab.max_row_norm:
                nrm = np.linalg.norm(rows, axis=-1) * scale
                scale = scale * np.minimum(
                    1.0, stab.max_row_norm / np.maximum(nrm, 1e-30))
            mat[idx] = rows * scale[:, None]
    return s0, s1


def _oracle_inputs(seed=0, V=60, D=12, B=24, P=8):
    rng = np.random.default_rng(seed)
    syn0 = rng.normal(0, 0.5, (V, D))
    syn1 = rng.normal(0, 0.5, (V, D))
    syn0[40] *= 300.0          # a blown row the clamp must catch when touched
    syn1[41] *= 300.0
    syn0[V - 2] *= 500.0       # NEVER touched — must stay bit-identical
    centers = rng.integers(0, 38, B).astype(np.int32)
    contexts = rng.integers(0, 38, B).astype(np.int32)
    centers[0], contexts[1] = 40, 41          # blown rows get touched
    # masked tail slots deliberately point at the blown rows: the sentinel
    # gating must keep them OUT of the clamp/decay pass
    mask = (np.arange(B) < B - 4).astype(np.float64)
    centers[B - 1], contexts[B - 1] = 40, 41
    negs = rng.integers(0, 38, P).astype(np.int32)
    return syn0, syn1, centers, contexts, mask, negs


@pytest.mark.parametrize("stab", [
    Stabilizers(),                                        # all off
    Stabilizers(max_row_norm=5.0),                        # clamp only
    Stabilizers(update_clip=0.05),                        # clip only
    Stabilizers(row_l2=1e-3),                             # decay only
    Stabilizers(max_row_norm=5.0, update_clip=0.05, row_l2=1e-3),
    Stabilizers(max_row_norm=1e6),                        # present, no row hit
])
def test_shared_pool_oracle_f64(stab):
    syn0, syn1, centers, contexts, mask, negs = _oracle_inputs()
    n = 3
    alpha = 0.05
    ref0, ref1 = _np_shared_step(
        syn0, syn1, centers, contexts, mask, negs, alpha, n, stab)
    with jax.enable_x64():
        got, _ = sgns_step_shared_core(
            EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1)),
            jnp.asarray(centers), jnp.asarray(contexts),
            jnp.asarray(mask, jnp.float32), jnp.asarray(negs),
            jnp.float64(alpha), n, "exact", jnp.float64, False, jnp.float64,
            True, stabilizers=stab if stab.enabled else None)
    # atol 3e-8, not 1e-11: XLA's exp differs from libm's in the last ulps,
    # and the deliberately 300x-blown rows amplify that through the sigmoid
    # chain; any real semantic error (dropped clamp, double decay, wrong
    # touched set) is orders of magnitude larger
    np.testing.assert_allclose(np.asarray(got.syn0), ref0, atol=3e-8)
    np.testing.assert_allclose(np.asarray(got.syn1), ref1, atol=3e-8)
    # the never-touched blown row is BIT-identical — no dense renorm pass
    assert np.array_equal(np.asarray(got.syn0)[syn0.shape[0] - 2],
                          syn0[syn0.shape[0] - 2])
    if stab.max_row_norm:
        norms0 = np.linalg.norm(np.asarray(got.syn0), axis=1)
        assert norms0[40] <= stab.max_row_norm * (1 + 1e-9)


def test_off_state_bit_identical():
    """stabilizers=None, all-zero Stabilizers, and the pre-stabilizer call
    signature produce the bit-identical compiled step."""
    syn0, syn1, centers, contexts, mask, negs = _oracle_inputs()
    params = EmbeddingPair(jnp.asarray(syn0, jnp.float32),
                           jnp.asarray(syn1, jnp.float32))
    args = (jnp.asarray(centers), jnp.asarray(contexts),
            jnp.asarray(mask, jnp.float32), jnp.asarray(negs),
            jnp.float32(0.05), 3)
    base, _ = sgns_step_shared_core(params, *args)
    none_, _ = sgns_step_shared_core(params, *args, stabilizers=None)
    zero, _ = sgns_step_shared_core(params, *args,
                                    stabilizers=Stabilizers())
    for other in (none_, zero):
        assert np.array_equal(np.asarray(base.syn0), np.asarray(other.syn0))
        assert np.array_equal(np.asarray(base.syn1), np.asarray(other.syn1))


def test_update_clip_bounds_single_pair_delta():
    """With no duplicates, clamp/decay off: ||new_row − old_row|| <= clip."""
    rng = np.random.default_rng(1)
    V, D = 20, 8
    syn0 = rng.normal(0, 5.0, (V, D)).astype(np.float32)
    syn1 = rng.normal(0, 5.0, (V, D)).astype(np.float32)
    params = EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1))
    got, _ = sgns_step_shared_core(
        params, jnp.asarray([3], jnp.int32), jnp.asarray([7], jnp.int32),
        jnp.ones(1, jnp.float32), jnp.asarray([11, 12], jnp.int32),
        jnp.float32(5.0),  # absurd lr so the unclipped delta is huge
        3, stabilizers=Stabilizers(update_clip=0.25))
    d_center = np.linalg.norm(np.asarray(got.syn0)[3] - syn0[3])
    d_ctx = np.linalg.norm(np.asarray(got.syn1)[7] - syn1[7])
    assert d_center <= 0.25 * (1 + 1e-5)
    assert d_ctx <= 0.25 * (1 + 1e-5)


# ---------------------------------------------------------------------------
# 2. cross-lowering agreement with stabilizers ON
# ---------------------------------------------------------------------------

MESHES = [(1, 8), (2, 4), (8, 1)]


@pytest.mark.parametrize("shape", MESHES)
def test_shard_map_stabilized_equivalence_f64(shape):
    from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest)")
    with jax.enable_x64():
        syn0, syn1, centers, contexts, mask, negs = _oracle_inputs(
            seed=2, V=64, D=16, B=16, P=8)
        stab = Stabilizers(max_row_norm=5.0, update_clip=0.1, row_l2=1e-3)
        params = EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1))
        batch = {"centers": jnp.asarray(centers),
                 "contexts": jnp.asarray(contexts),
                 "mask": jnp.asarray(mask, jnp.float32)}
        alpha = jnp.float64(0.025)
        plan = make_mesh(*shape)
        sharded = EmbeddingPair(jax.device_put(params.syn0, plan.embedding),
                                jax.device_put(params.syn1, plan.embedding))
        step = make_shard_map_sgns_step(
            plan.mesh, 3, compute_dtype=jnp.float64,
            logits_dtype=jnp.float64, stabilizers=stab)
        # jitted like the trainer's dispatch (and tests/test_shard_map_step.py):
        # an eager shard_map runs op by op across the 8 devices, ~40 s here
        ps, _ = jax.jit(step)(sharded, batch, jnp.asarray(negs), alpha)
        pr, _ = sgns_step_shared_core(
            params, batch["centers"], batch["contexts"], batch["mask"],
            jnp.asarray(negs), alpha, 3, "exact", jnp.float64, False,
            jnp.float64, True, stabilizers=stab)
        np.testing.assert_allclose(np.asarray(ps.syn0), np.asarray(pr.syn0),
                                   atol=1e-11)
        np.testing.assert_allclose(np.asarray(ps.syn1), np.asarray(pr.syn1),
                                   atol=1e-11)


def test_banded_scatter_stabilized_equivalence_f64():
    """Banded CBOW ≡ scatter CBOW with clamp+clip engaged (row_l2 stays off
    here: the two formulations' touched SETS differ on context-less tokens —
    documented in cbow_step_banded_core — so decay is pinned by the oracle
    and SGNS lowering tests instead)."""

    from test_cbow_banded import _banded_blocks, _host_windows, _kept_stream

    from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core

    with jax.enable_x64():
        rng = np.random.default_rng(3)
        V, D, P, W, NEG = 120, 16, 16, 3, 4
        ktoks, starts = _kept_stream(rng, 40, 15, V)
        left_h, right_h = _host_windows(ktoks, starts, W)
        live = np.flatnonzero(left_h + right_h > 0)
        assert live.size > 20

        syn0 = rng.normal(0, 0.1, (V, D))
        syn1 = rng.normal(0, 0.05, (V, D))
        # blow a row that IS a live context/center and a pool row — the clamp
        # must catch them identically in both formulations
        blown = int(ktoks[live[3]])
        syn0[blown] *= 400.0
        negs = rng.integers(0, V, P).astype(np.int32)
        syn1[negs[0]] *= 400.0
        params0 = EmbeddingPair(jnp.asarray(syn0), jnp.asarray(syn1))
        alpha = jnp.float64(0.05)
        stab = Stabilizers(max_row_norm=2.0, update_clip=0.05)

        T = ktoks.shape[0] + 2 * W + 5
        ((tb, band, nc),) = _banded_blocks(ktoks, starts, T, W)
        p_band, _ = cbow_step_banded_core(
            params0, jnp.asarray(tb), band.left, band.right, band.center,
            band.token, jnp.asarray(negs), alpha, NEG, W, "exact",
            jnp.float64, jnp.float64, True, stabilizers=stab)
        # scatter reference over the same live example set + stabilizers
        from glint_word2vec_tpu.ops.sgns import cbow_step_shared_core
        C = 2 * W
        nb = len(live)
        ctx = np.zeros((nb, C), np.int32)
        ctxm = np.zeros((nb, C), np.float32)
        for i, b in enumerate(live):
            idx = (list(range(b - left_h[b], b))
                   + list(range(b + 1, b + right_h[b] + 1)))
            ctx[i, :len(idx)] = ktoks[idx]
            ctxm[i, :len(idx)] = 1.0
        p_ref, _ = cbow_step_shared_core(
            params0, jnp.asarray(ktoks[live].astype(np.int32)),
            jnp.asarray(ctx), jnp.asarray(ctxm),
            jnp.ones(nb, jnp.float32), jnp.asarray(negs), alpha, NEG,
            "exact", jnp.float64, jnp.float64, True, stabilizers=stab)
        np.testing.assert_allclose(
            np.asarray(p_band.syn0), np.asarray(p_ref.syn0), atol=1e-10)
        np.testing.assert_allclose(
            np.asarray(p_band.syn1), np.asarray(p_ref.syn1), atol=1e-10)
        # the clamp actually engaged
        assert np.linalg.norm(np.asarray(p_band.syn0)[blown]) <= 2.0 + 1e-9


# ---------------------------------------------------------------------------
# 3. escalation ladder
# ---------------------------------------------------------------------------


def _channels(max_norm=1.0, frac=0.0):
    m = {"max_norm": max_norm, "mean_norm": 1.0, "p99_norm": 1.0,
         "frac_over": frac}
    return {"finite": True, "syn0": dict(m), "syn1": dict(m)}


def test_watchdog_would_fire_is_pure():
    wd = NormWatchdog("recover", threshold=100.0, max_norm=1000.0, frac=0.01)
    assert wd.would_fire(_channels()) is None
    assert wd.would_fire(_channels(max_norm=5000.0))
    assert wd.fires == 0 and wd.last_reason is None  # no state touched


def test_watchdog_recover_policy_returns_reason_no_raise():
    wd = NormWatchdog("recover", 100.0, 1000.0, 0.01)
    reason = wd.check(_channels(frac=0.5), step=10)
    assert reason and "exceed norm" in reason
    assert wd.fires == 1


def _toy_sentences(n=200, seed=2):
    rng = np.random.default_rng(seed)
    return [[f"w{i}" for i in rng.integers(0, 30, 20)] for _ in range(n)]


def _toy_cfg(**kw):
    return Word2VecConfig(
        vector_size=8, pairs_per_batch=128, window=3, num_iterations=2,
        steps_per_dispatch=2, heartbeat_every_steps=2, subsample_ratio=0.0,
        prefetch_chunks=0, seed=1, **kw)


def _toy_trainer(**kw):
    sents = _toy_sentences()
    vocab = build_vocab(sents, min_count=1)
    enc = encode_sentences(sents, vocab, 1000)
    return Trainer(_toy_cfg(**kw), vocab), enc


def test_snapshot_ring_arms_for_recover_without_rollback_policy():
    """The arming bugfix: pre-round-12 the ring seeded only under
    nonfinite_policy='rollback', so norm_watch='recover' beside
    nonfinite_policy='halt' found it empty on first firing."""
    trainer, _ = _toy_trainer(norm_watch="recover", nonfinite_policy="halt")
    assert trainer._needs_snapshot_ring
    trainer._start_run_bookkeeping()
    assert len(trainer._snapshot_ring) == 1
    # and the rollback-only arming still works
    trainer2, _ = _toy_trainer(nonfinite_policy="rollback")
    trainer2._start_run_bookkeeping()
    assert len(trainer2._snapshot_ring) == 1
    # while a consumer-less config pays nothing
    trainer3, _ = _toy_trainer(nonfinite_policy="halt")
    assert not trainer3._needs_snapshot_ring
    trainer3._start_run_bookkeeping()
    assert len(trainer3._snapshot_ring) == 0


def test_recover_ladder_end_to_end(tmp_path):
    """One injected finite blowup under the previously-dead combination:
    recover fires ONCE, rolls back, backs lr off, engages the clamp, and the
    fit FINISHES finite — with schema-valid watchdog + recovery records."""
    run_log = str(tmp_path / "run.jsonl")
    faults.configure(scale_params_at_step=8)
    try:
        trainer, enc = _toy_trainer(
            norm_watch="recover", nonfinite_policy="halt",
            telemetry_path=run_log)
        trainer.fit(enc)
    finally:
        faults.reset()
    assert trainer.recoveries_performed == 1   # one recovery per firing probe
    assert trainer.norm_watchdog.fires == 1
    assert trainer._lr_scale == pytest.approx(0.5)
    assert trainer._stabilizers.max_row_norm == pytest.approx(
        trainer.config.norm_watch_threshold)
    assert np.isfinite(np.asarray(trainer.params.syn0)).all()
    norms = np.linalg.norm(np.asarray(trainer.params.syn0, np.float64),
                           axis=1)
    assert norms.max() <= trainer.config.norm_watch_threshold * 1.001

    from glint_word2vec_tpu.obs.schema import validate_file
    summary = validate_file(run_log)
    assert summary["ok"], summary["errors"]
    assert summary["kinds"].get("recovery") == 1
    assert summary["kinds"].get("watchdog", 0) >= 1
    recs = [json.loads(line) for line in open(run_log)
            if '"kind": "recovery"' in line]
    assert recs[0]["action"] == "rollback"
    assert recs[0]["recoveries_performed"] == 1
    assert recs[0]["lr_scale"] == pytest.approx(0.5)
    assert recs[0]["max_row_norm"] == pytest.approx(
        trainer.config.norm_watch_threshold)
    # run_end carries the recovery outcome
    ends = [json.loads(line) for line in open(run_log)
            if '"kind": "run_end"' in line]
    assert ends[-1]["recoveries"] == 1 and ends[-1]["status"] == "ok"


def test_recover_budget_decrements_then_halts(tmp_path):
    """A repeatedly-reblowing run: the budget decrements one recovery per
    firing, and exhaustion degrades to the halt contract — with the halt
    recovery record emitted BEFORE the raise."""
    run_log = str(tmp_path / "run.jsonl")
    faults.configure(scale_params_at_step=8, scale_params_times=99)
    trainer = None
    try:
        trainer, enc = _toy_trainer(
            norm_watch="recover", nonfinite_policy="halt",
            max_recoveries=2, telemetry_path=run_log)
        with pytest.raises(NormBlowupError, match="budget exhausted"):
            trainer.fit(enc)
    finally:
        faults.reset()
    assert trainer.recoveries_performed == 2
    # lr backoff compounds per recovery
    assert trainer._lr_scale == pytest.approx(0.25)
    recs = [json.loads(line) for line in open(run_log)
            if '"kind": "recovery"' in line]
    assert [r["action"] for r in recs] == ["rollback", "rollback", "halt"]
    assert recs[-1]["snapshot_step"] == -1
    ends = [json.loads(line) for line in open(run_log)
            if '"kind": "run_end"' in line]
    assert ends[-1]["status"] == "error"
    from glint_word2vec_tpu.obs.schema import validate_file
    assert validate_file(run_log)["ok"]


def test_recover_lr_backoff_scales_dispatched_alphas():
    trainer, _ = _toy_trainer(norm_watch="recover", nonfinite_policy="halt")
    trainer._lr_scale = 0.25
    meta = np.stack([np.full(4, 0.02, np.float32), np.ones(4, np.float32)])
    meta_dev, _ = trainer._stage_dispatch_meta(meta, 1)
    np.testing.assert_allclose(np.asarray(meta_dev)[0],
                               0.25 * meta[0], rtol=1e-6)
    assert meta[0][0] == np.float32(0.02)  # producer's array not mutated


def test_maybe_snapshot_skips_states_the_watchdog_flags():
    trainer, _ = _toy_trainer(norm_watch="recover", nonfinite_policy="halt")
    trainer._snapshot_ring.clear()
    trainer._maybe_snapshot(_channels(max_norm=5000.0))   # would fire
    assert len(trainer._snapshot_ring) == 0
    trainer._maybe_snapshot(_channels())                  # healthy
    assert len(trainer._snapshot_ring) == 1


# ---------------------------------------------------------------------------
# 4. vocab-scaled AUTO pool
# ---------------------------------------------------------------------------


def _big_vocab(size):
    rng = np.random.default_rng(0)
    counts = rng.integers(5, 50, size).astype(np.int64)
    return Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(size)], counts)


def _large_vocab_trainer(**kw):
    cfg = Word2VecConfig(
        vector_size=8, pad_vector_to_lanes=False, pairs_per_batch=65536,
        subsample_ratio=1e-4, prefetch_chunks=0, **kw)
    return Trainer(cfg, _big_vocab(600_001))


def test_auto_pool_scales_with_vocab():
    trainer = _large_vocab_trainer()
    cfg = trainer.config
    load = cfg.pairs_per_batch * cfg.negatives / cfg.negative_pool
    assert load <= Trainer._LARGE_VOCAB_SAFE_LOAD
    assert cfg.negative_pool % 128 == 0
    assert getattr(cfg, "_auto_pool", False)   # re-resolution kept AUTO-ness
    # replace() re-derives from -1 (the from_dict/replace semantics intact):
    # a geometry change re-runs the config-time rule, not the frozen value
    derived = cfg.replace(pairs_per_batch=8192)
    assert getattr(derived, "_auto_pool", False)
    assert derived.negative_pool == Word2VecConfig(
        pairs_per_batch=8192).negative_pool


def test_explicit_pool_never_rescaled():
    trainer = _large_vocab_trainer(negative_pool=640)
    assert trainer.config.negative_pool == 640
    assert not getattr(trainer.config, "_auto_pool", True)


def test_to_dict_round_trip_preserves_pool_autoness():
    """The worker-transport round trip (to_dict with auto markers →
    from_dict) must keep an AUTO pool AUTO, or the receiving trainer's
    vocab-scaled safety re-resolution silently never runs."""
    cfg = Word2VecConfig(pairs_per_batch=65536)
    assert getattr(cfg, "_auto_pool", False)
    rt = Word2VecConfig.from_dict(cfg.to_dict())
    assert getattr(rt, "_auto_pool", False)
    assert rt.negative_pool == cfg.negative_pool  # same resolved value
    # checkpoints pin the RESOLVED value instead (trained semantics)
    assert cfg.to_dict(auto_markers=False)["negative_pool"] \
        == cfg.negative_pool
    # an explicit pool stays explicit through the round trip
    ex = Word2VecConfig(pairs_per_batch=65536, negative_pool=640)
    rt2 = Word2VecConfig.from_dict(ex.to_dict())
    assert rt2.negative_pool == 640
    assert not getattr(rt2, "_auto_pool", True)


def test_small_vocab_auto_pool_unchanged():
    """Below the boundary the config-time resolution stands untouched."""
    sents = _toy_sentences()
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(pairs_per_batch=65536, vector_size=8,
                         subsample_ratio=1e-3)
    trainer = Trainer(cfg, vocab)
    assert trainer.config.negative_pool == Word2VecConfig(
        pairs_per_batch=65536).negative_pool


# ---------------------------------------------------------------------------
# trainer-level stabilized smoke: every step path accepts the knobs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(),                                           # shared pool resolves 0
    dict(negative_pool=64),                           # shared pool
    dict(cbow=True),                                  # per-example CBOW
    dict(cbow=True, negative_pool=64),                # shared-pool CBOW
    dict(cbow=True, negative_pool=64, cbow_update="banded"),
    dict(device_pairgen=True),                        # device feed
])
def test_stabilized_fit_smoke_all_paths(kw):
    sents = _toy_sentences(60)
    vocab = build_vocab(sents, min_count=1)
    enc = encode_sentences(sents, vocab, 1000)
    cfg = _toy_cfg(max_row_norm=50.0, update_clip=0.5, row_l2=1e-4, **kw)
    trainer = Trainer(cfg, vocab)
    assert trainer._stabilizers.enabled
    trainer.fit(enc)
    emb = np.asarray(trainer.params.syn0, np.float64)
    assert np.isfinite(emb).all()
    assert np.linalg.norm(emb, axis=1).max() <= 50.0 * 1.001


def test_default_config_stabilizers_off():
    trainer, _ = _toy_trainer()
    assert not trainer._stabilizers.enabled
