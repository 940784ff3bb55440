"""End-to-end estimator tests on a small synthetic corpus: fit → model ops → save/load →
resume; compat layer surface; CBOW path; trainer heartbeats."""

import warnings

import numpy as np
import pytest

from glint_word2vec_tpu import (
    ServerSideGlintWord2Vec,
    ServerSideGlintWord2VecModel,
    Word2Vec,
    Word2VecConfig,
)
from glint_word2vec_tpu.train.checkpoint import load_model


def two_topic_corpus(n=300, seed=0):
    """Two disjoint co-occurrence clusters: {a,b,c} and {x,y,z}."""
    rng = np.random.default_rng(seed)
    sents = []
    for _ in range(n):
        ws = ["a", "b", "c"] if rng.integers(0, 2) == 0 else ["x", "y", "z"]
        sents.append([ws[i] for i in rng.integers(0, 3, 10)])
    return sents


CFG = dict(vector_size=16, window=3, negatives=5, min_count=1, num_iterations=3,
           learning_rate=0.025, pairs_per_batch=128, subsample_ratio=0.0, seed=1)


@pytest.fixture(scope="module")
def fitted():
    sents = two_topic_corpus()
    model = Word2Vec(**CFG).fit(sents)
    return model, sents


def test_fit_produces_valid_model(fitted):
    # NOTE: semantic-quality gates live in test_integration_toy.py on the real corpus —
    # micro-vocab synthetic corpora do not yield separated cosine geometry even for
    # textbook sequential word2vec (verified against a numpy reference implementation).
    model, _ = fitted
    assert model.num_words == 6
    mat = np.asarray(model.syn0)
    assert np.all(np.isfinite(mat)) and np.abs(mat).sum() > 0
    syns = model.find_synonyms("a", 5)
    assert len(syns) == 5 and all(np.isfinite(s) for _, s in syns)


def test_fit_deterministic_per_seed():
    sents = two_topic_corpus(50)
    m1 = Word2Vec(**CFG).fit(sents)
    m2 = Word2Vec(**CFG).fit(sents)
    np.testing.assert_array_equal(np.asarray(m1.syn0), np.asarray(m2.syn0))
    cfg3 = dict(CFG); cfg3["seed"] = 9
    m3 = Word2Vec(**cfg3).fit(sents)
    assert not np.array_equal(np.asarray(m1.syn0), np.asarray(m3.syn0))


def test_heartbeats_recorded(fitted):
    model, _ = fitted
    # alpha decays over training (reference schedule mllib:405-413)
    assert model.train_state.finished
    assert model.train_state.words_processed > 0


def test_heartbeats_sample_real_loss_despite_fast_twin():
    """The trainer dispatches a metrics-elided step twin for chunks no
    heartbeat samples (PERF.md §4). Heartbeat rows must still carry the REAL
    loss — a 0.0 loss in a heartbeat means the elision prediction missed."""
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer

    sents = two_topic_corpus(400)
    vocab = build_vocab(sents, 1)
    cfg = Word2VecConfig(vector_size=16, window=3, negatives=3, min_count=1,
                         num_iterations=4, pairs_per_batch=128, negative_pool=16,
                         steps_per_dispatch=2, heartbeat_every_steps=8,
                         subsample_ratio=0.0, seed=1)
    t = Trainer(cfg, vocab)
    assert t._step_fn_fast is not t._step_fn  # shared-pool path builds the twin
    # count twin usage: the elision must actually run (a regression that always
    # picks the full twin would otherwise pass every assertion below)
    used = {"fast": 0, "full": 0}
    fast, full = t._step_fn_fast, t._step_fn

    def fast_counting(*a, **kw):
        used["fast"] += 1
        return fast(*a, **kw)

    def full_counting(*a, **kw):
        used["full"] += 1
        return full(*a, **kw)

    t._step_fn_fast, t._step_fn = fast_counting, full_counting
    t.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    assert t.heartbeats, "cadence 8 over hundreds of steps must fire"
    assert all(np.isfinite(h.loss) and h.loss > 0.0 for h in t.heartbeats)
    assert used["fast"] > 0 and used["full"] > 0, used

    # and the twins really are interchangeable: the same fit with elision
    # disabled (fast twin never used) lands on bit-identical params
    t2 = Trainer(cfg, vocab)
    t2._step_fn_fast = t2._step_fn
    t2.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    np.testing.assert_array_equal(np.asarray(t.params.syn0),
                                  np.asarray(t2.params.syn0))
    np.testing.assert_array_equal(np.asarray(t.params.syn1),
                                  np.asarray(t2.params.syn1))


def test_save_load_resume(tmp_path, fitted):
    model, sents = fitted
    path = str(tmp_path / "m")
    model.save(path)
    data = load_model(path)
    assert data["train_state"].finished
    loaded = ServerSideGlintWord2VecModel.load(path)
    np.testing.assert_allclose(
        loaded.inner.transform("a"), model.transform("a"), rtol=1e-6)


def test_mid_training_checkpoint_and_resume(tmp_path):
    sents = two_topic_corpus(100)
    path = str(tmp_path / "ckpt")
    cfg = dict(CFG)
    cfg["num_iterations"] = 2
    Word2Vec(**cfg).fit(sents, checkpoint_path=path, checkpoint_every_steps=2)
    data = load_model(path)
    assert data["syn1"] is not None  # trainable state present
    resumed = Word2Vec.resume(path, sents)
    assert resumed.train_state.finished


def test_compat_builder_surface():
    sents = two_topic_corpus(150)
    w2v = (ServerSideGlintWord2Vec()
           .setVectorSize(12)
           .setLearningRate(0.05)
           .setNumIterations(2)
           .setWindowSize(3)
           .setMinCount(1)
           .setSubsampleRatio(1.0)
           .setBatchSize(50)
           .setN(5)
           .setSeed(3)
           .setNumParameterServers(2)
           .setMaxSentenceLength(100)
           .setUnigramTableSize(10 ** 6)
           .setNumPartitions(1))
    model = w2v.fit(sents)
    vecs = model.getVectors()
    assert set(vecs) == {"a", "b", "c", "x", "y", "z"}
    assert vecs["a"].shape == (12,)
    # single word transform (mllib path) and sentence transform (ml path)
    assert model.transform("a").shape == (12,)
    out = model.transform([["a", "b"], ["x"]])
    assert out.shape == (2, 12)
    arr = model.findSynonymsArray("a", 2)
    assert len(arr) == 2
    words, mat = model.toLocal()
    assert len(words) == 6 and mat.shape == (6, 12)
    model.stop(terminateOtherClients=True)


def test_compat_ps_knobs_warn():
    w2v = ServerSideGlintWord2Vec()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        w2v.setParameterServerHost("10.0.0.1")
        w2v.setParameterServerConfig({"glint.master.port": 13380})
        w2v.setBatchSize(100).setN(20).setWindowSize(10)  # 20000 > 10000 budget
    msgs = " ".join(str(r.message) for r in rec)
    assert "no parameter servers" in msgs
    assert "Akka" in msgs


def test_compat_dict_rows():
    sents = two_topic_corpus(100)
    rows = [{"sentence": s, "id": i} for i, s in enumerate(sents[:20])]
    w2v = (ServerSideGlintWord2Vec().setVectorSize(8).setMinCount(1)
           .setSubsampleRatio(1.0).setSeed(0))
    model = w2v.fit(rows)
    out = model.transform(rows[:3])
    # transform preserves extra columns and appends the output col (it spec:260-288)
    assert set(out[0]) == {"sentence", "id", "vector"}
    assert out[0]["vector"].shape == (8,)


def test_cbow_end_to_end():
    sents = two_topic_corpus(300)
    cfg = dict(CFG)
    cfg["cbow"] = True
    model = Word2Vec(**cfg).fit(sents)
    mat = np.asarray(model.syn0)
    assert np.all(np.isfinite(mat)) and np.abs(mat).sum() > 0


def test_config_object_plus_overrides():
    cfg = Word2VecConfig(vector_size=8)
    est = Word2Vec(cfg, window=2)
    assert est.config.vector_size == 8 and est.config.window == 2


def test_negative_pool_and_lane_padding_end_to_end():
    sents = two_topic_corpus(100)
    cfg = dict(CFG)
    cfg.update(negative_pool=16, vector_size=20)  # pads to 128 internally
    model = Word2Vec(**cfg).fit(sents)
    # exports are sliced back to the logical vector size
    assert model.transform("a").shape == (20,)
    words, mat = model.to_local()
    assert mat.shape == (6, 20)
    assert np.all(np.isfinite(mat))


def test_lane_padding_columns_stay_zero():
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer
    from glint_word2vec_tpu.config import Word2VecConfig

    sents = two_topic_corpus(50)
    vocab = build_vocab(sents, 1)
    cfg = Word2VecConfig(vector_size=20, min_count=1, pairs_per_batch=64,
                         num_iterations=1)
    tr = Trainer(cfg, vocab)
    assert tr.padded_dim == 128
    tr.fit(encode_sentences(sents, vocab))
    full = np.asarray(tr.params.syn0)
    assert full.shape[1] == 128
    np.testing.assert_array_equal(full[:, 20:], 0.0)


def test_compat_batch_size_maps_to_device_batch():
    """setBatchSize/setNumPartitions map to pairs_per_batch (their product, the
    reference's concurrent-pair count, mllib:417-429) — with a perf warning for tiny
    batches. Untouched knobs keep the TPU-efficient config default."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = (ServerSideGlintWord2Vec()
               .setBatchSize(50).setNumPartitions(4).to_config())
    assert cfg.pairs_per_batch == 200
    assert any("pairs_per_batch" in str(r.message) for r in rec)

    default_cfg = ServerSideGlintWord2Vec().to_config()
    from glint_word2vec_tpu.config import Word2VecConfig
    assert default_cfg.pairs_per_batch == Word2VecConfig().pairs_per_batch


def test_negative_and_64bit_seeds_train():
    """Any Python-int seed must work: negative and >=2**31 seeds masked to uint32
    previously crashed at trace time via int32 canonicalization (ADVICE r2)."""
    sents = two_topic_corpus(30)
    for seed in (-123, 2 ** 31 + 7, 2 ** 40 + 1):
        cfg = dict(CFG)
        cfg.update(seed=seed, num_iterations=1)
        model = Word2Vec(**cfg).fit(sents)
        assert np.all(np.isfinite(np.asarray(model.syn0)))


def test_global_step_persisted_across_resume(tmp_path):
    """The hash-PRNG counter continues after resume: the resumed trainer must not
    restart the (seed, counter) negative-sample lattice at 0 (ADVICE r2)."""
    from glint_word2vec_tpu.train.checkpoint import load_model as _load

    sents = two_topic_corpus(100)
    path = str(tmp_path / "ckpt")
    cfg = dict(CFG)
    cfg["num_iterations"] = 2
    Word2Vec(**cfg).fit(sents, checkpoint_path=path, checkpoint_every_steps=2)
    state = _load(path)["train_state"]
    assert state.global_step > 0
    from glint_word2vec_tpu.train.trainer import Trainer
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair
    import jax.numpy as jnp

    data = _load(path)
    vocab = Vocabulary.from_words_and_counts(data["words"], data["counts"])
    tr = Trainer(data["config"], vocab,
                 params=EmbeddingPair(jnp.asarray(data["syn0"]),
                                      jnp.asarray(data["syn1"])),
                 train_state=state)
    assert tr.global_step == state.global_step


def test_exact_step_resume_matches_uninterrupted(tmp_path):
    """Interrupt mid-iteration (via checkpoint), resume, and match the uninterrupted
    run's final params bit-for-bit (VERDICT r2 #8). Checkpoint cadence aligned to
    steps_per_dispatch so the PRNG dispatch boundaries replay identically."""
    sents = two_topic_corpus(200, seed=4)
    cfg = dict(CFG)
    cfg.update(num_iterations=2, steps_per_dispatch=4, pairs_per_batch=64)

    baseline = Word2Vec(**cfg).fit(sents)

    path = str(tmp_path / "ckpt")
    from glint_word2vec_tpu.train.checkpoint import load_model as _load

    class StopTraining(Exception):
        pass

    # run until the first mid-iteration checkpoint exists, then abort the process
    # the blunt way a crash would
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab = build_vocab(sents, 1)
    enc = encode_sentences(sents, vocab, 1000)
    tr = Trainer(Word2VecConfig(**cfg), vocab)
    n_dispatches = [0]
    orig_fn = tr._step_fn

    def counting(*a, **kw):
        n_dispatches[0] += 1
        if n_dispatches[0] == 3:  # partway through iteration 1, after 2 dispatches
            # save BEFORE dispatching: the step donates (and thus deletes) the input
            # params, so the consistent snapshot is the pre-dispatch state
            tr.save_checkpoint(path)
            raise StopTraining()
        return orig_fn(*a, **kw)

    # patch BOTH twins: _dispatch_step_fn may hand out the metrics-elided twin
    # for chunks no heartbeat samples
    tr._step_fn = tr._step_fn_fast = counting
    try:
        tr.fit(enc)
    except StopTraining:
        pass
    state = _load(path)["train_state"]
    assert not state.finished and state.batches_done > 0

    resumed = Word2Vec.resume(path, sents)
    np.testing.assert_array_equal(
        np.asarray(resumed.syn0), np.asarray(baseline.syn0))


def test_profile_dir_captures_trace(tmp_path):
    """config.profile_dir wraps fit() in a jax.profiler trace (SURVEY §5: the
    reference has no profiling at all; this plus the host-wait/dispatch split is
    the observability story)."""
    import os

    from glint_word2vec_tpu import Word2Vec

    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(30)]
    sents = [[words[j] for j in rng.integers(0, 30, 8)] for _ in range(40)]
    prof = str(tmp_path / "prof")
    Word2Vec(vector_size=8, min_count=1, pairs_per_batch=64, num_iterations=1,
             window=2, negatives=2, negative_pool=8, steps_per_dispatch=2,
             seed=3, profile_dir=prof).fit(sents)
    found = [os.path.join(r, f) for r, _, fs in os.walk(prof) for f in fs]
    assert found, "profiler trace directory is empty"


def test_stability_warnings_fire(caplog):
    """The trainer warns on the three measured divergence regimes (EVAL.md): pool
    overload, duplicate overload, and the compounding band that NaN'd at 60M words
    while passing both individual thresholds. Since round 5 the duplicate channel
    REFUSES at construction (tests/test_stability_gates.py); the warn-only
    behavior asserted here rides the allow_unstable override."""
    import logging

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.train.trainer import Trainer

    # Zipfy counts: top word ~0.4% of the (unsubsampled) stream
    counts = np.maximum(2_000_000 / (np.arange(5000) + 10.0) ** 1.05, 5).astype(int)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(5000)], counts)

    def warns(**kw):
        cfg = Word2VecConfig(vector_size=16, min_count=1, **kw)
        with caplog.at_level(logging.WARNING, logger="glint_word2vec_tpu"):
            caplog.clear()
            Trainer(cfg, vocab)
        return [r.message for r in caplog.records]

    # pool overload: load 5120
    assert any("pool" in m for m in warns(
        pairs_per_batch=65536, negatives=5, negative_pool=64,
        subsample_ratio=1e-4))
    # duplicate overload: no subsampling, top word >300 dups per 64k batch
    assert any("duplicates" in m for m in warns(
        pairs_per_batch=65536, negatives=5, negative_pool=1024,
        subsample_ratio=0.0, allow_unstable=True))
    # compounding band: both below individual thresholds, warned jointly
    msgs = warns(pairs_per_batch=65536, negatives=5, negative_pool=256,
                 subsample_ratio=1e-4)
    assert any("compound" in m for m in msgs), msgs
    # the duplicate channel is warned on the per-pair path too (negative_pool=0)
    assert any("duplicates" in m for m in warns(
        pairs_per_batch=65536, negatives=5, negative_pool=0,
        subsample_ratio=0.0, allow_unstable=True))
    # a safe config stays quiet
    assert not warns(pairs_per_batch=16384, negatives=5, negative_pool=64,
                     subsample_ratio=1e-4)


def test_auto_negative_pool_scales_with_batch():
    """The default (negative_pool=-1) resolves so pool load B*n/P stays <= 600 —
    the measured 60M-word stability rule (EVAL.md) — rounded to the 128 lane tile."""
    from glint_word2vec_tpu.config import Word2VecConfig

    cfg = Word2VecConfig(pairs_per_batch=65536)
    assert cfg.negative_pool >= 512
    assert cfg.negative_pool % 128 == 0
    assert 65536 * cfg.negatives / cfg.negative_pool <= 600
    small = Word2VecConfig(pairs_per_batch=8192)
    assert small.negative_pool == 128
    # below the MXU-amortization scale auto keeps the per-pair exact path:
    # shared negatives measurably cost quality on small corpora (toy bf16 gate)
    assert Word2VecConfig(pairs_per_batch=256).negative_pool == 0
    assert Word2VecConfig(pairs_per_batch=4096).negative_pool == 128
    # explicit choices pass through untouched; 0 keeps the per-pair path
    assert Word2VecConfig(negative_pool=256).negative_pool == 256
    assert Word2VecConfig(negative_pool=0).negative_pool == 0
    # the compat layer pins the reference's exact per-pair semantics
    from glint_word2vec_tpu.models.compat import ServerSideGlintWord2Vec
    assert ServerSideGlintWord2Vec().to_config().negative_pool == 0
