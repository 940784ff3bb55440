"""Trainer integration for config.device_pairgen (on-device pair generation).

Stream-level bit-equivalence is covered by tests/test_device_pairgen.py; these tests
drive the Trainer end-to-end: learning on a topical corpus, exact pair accounting,
data-parallel segments on the virtual mesh, and config validation.
"""

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.train.trainer import Trainer


def _topic_corpus(n=400, rng=None):
    rng = rng or np.random.default_rng(0)
    topics = [["a", "b", "c", "d"], ["x", "y", "z", "w"]]
    return [list(rng.choice(topics[i % 2], size=12)) for i in range(n)]


def _cos(a, b):
    return float(np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))


def _fit(cfg, sentences):
    vocab = build_vocab(sentences, min_count=1)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)
    trainer = Trainer(cfg, vocab)
    trainer.fit(encoded)
    return trainer, vocab


def test_device_feed_learns_topics():
    cfg = Word2VecConfig(
        vector_size=32, min_count=1, pairs_per_batch=256, num_iterations=5,
        learning_rate=0.025, seed=3, negative_pool=16, device_pairgen=True,
        steps_per_dispatch=4, window=3, subsample_ratio=0.0)
    trainer, vocab = _fit(cfg, _topic_corpus())
    syn0 = np.asarray(trainer.unpadded_params().syn0)
    wv = {w: syn0[vocab.index[w]] for w in "abxy"}
    assert _cos(wv["a"], wv["b"]) > 0.8
    assert _cos(wv["a"], wv["x"]) < 0.5
    # exact device-side accounting replaced the host estimate
    assert trainer.pairs_trained > 0
    assert np.isfinite(trainer.pairs_trained)


def _packer_reference_pairs(encoded, vocab, seed, iteration, shard, num_shards,
                            T, window, ratio):
    """Host replay of the device-feed packer's stream contract for one
    (iteration, shard): hashrng subsample on raw ordinals, shuffled shard order,
    kept stream cut at T boundaries, windows keyed by kept ordinals
    (host _block_pairs with keep ≡ 1 per cut block). Returns total pair count."""
    from glint_word2vec_tpu.data.hashrng import (
        STREAM_SUBSAMPLE, hash_u01_at, stream_base)
    from glint_word2vec_tpu.data.pipeline import (
        _block_pairs, keep_probabilities, stream_rng)
    keep = keep_probabilities(
        vocab.counts, vocab.train_words_count, ratio).astype(np.float32)
    rng = stream_rng(seed, iteration, shard)
    order = np.arange(shard, len(encoded), num_shards)
    rng.shuffle(order)
    sub = stream_base(seed, STREAM_SUBSAMPLE, iteration, shard)
    kept_sents, raw_ord = [], 0
    for si in order:
        arr = encoded[si]
        if ratio > 0:
            u = hash_u01_at(sub, np.arange(raw_ord, raw_ord + arr.shape[0],
                                           dtype=np.uint64))
            ks = arr[u <= keep[arr]]
        else:
            ks = arr
        raw_ord += arr.shape[0]
        if ks.shape[0]:
            kept_sents.append(ks)
    if not kept_sents:
        return 0
    tokens = np.concatenate(kept_sents)
    is_start = np.zeros(tokens.shape[0], bool)
    is_start[np.cumsum([s.shape[0] for s in kept_sents])[:-1]] = True
    is_start[0] = True
    total = 0
    for i in range(0, tokens.shape[0], T):
        tk = tokens[i:i + T]
        st = is_start[i:i + T].copy()
        st[0] = True
        idx = np.flatnonzero(st)
        lens = np.diff(np.append(idx, tk.shape[0])).astype(np.int64)
        hc, _, _, _ = _block_pairs(tk, lens, np.ones(vocab.size), window,
                                   seed, iteration, shard, i, True)
        total += hc.shape[0]
    return total


def test_device_feed_pair_totals_match_host_stream():
    """The device must train exactly the pairs the packer's stream contract emits
    (host-side subsampling + kept-ordinal-keyed windows + T-boundary cuts)."""
    sentences = _topic_corpus(200)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=512, num_iterations=1,
        seed=11, negative_pool=8, device_pairgen=True, steps_per_dispatch=2,
        window=3, subsample_ratio=1e-3, shuffle=True)
    vocab = build_vocab(sentences, min_count=1)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)
    trainer = Trainer(cfg, vocab)
    total = _packer_reference_pairs(
        encoded, vocab, 11, 1, 0, 1, trainer._tokens_per_step, 3, 1e-3)
    trainer.fit(encoded)
    assert trainer.pairs_trained == pytest.approx(total, abs=0.5)


def test_device_feed_data_parallel_segments():
    """num_data > 1 on the virtual mesh: per-segment generation matches the host
    pipeline's shard semantics (round-robin sentences, per-shard hash streams)."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    sentences = _topic_corpus(300)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=512, num_iterations=2,
        seed=5, negative_pool=8, device_pairgen=True, steps_per_dispatch=2,
        window=3, num_data_shards=2, subsample_ratio=0.0)
    vocab = build_vocab(sentences, min_count=1)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)
    trainer = Trainer(cfg, vocab)
    host_pairs = sum(
        _packer_reference_pairs(encoded, vocab, 5, it, s, 2,
                                trainer._tokens_per_step, 3, 0.0)
        for it in (1, 2) for s in (0, 1))
    trainer.fit(encoded)
    assert trainer.pairs_trained == pytest.approx(host_pairs, abs=0.5)
    syn0 = np.asarray(trainer.unpadded_params().syn0)
    wv = {w: syn0[vocab.index[w]] for w in "abxy"}
    assert _cos(wv["a"], wv["b"]) > 0.6
    assert _cos(wv["a"], wv["x"]) < 0.6


def test_device_feed_overflow_drops_counted(caplog):
    """A deliberately tiny tokens_per_step forces overflow; the trainer reports it
    and still trains the first-B prefix of each block's pairs."""
    sentences = _topic_corpus(100)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=64, num_iterations=1,
        seed=2, negative_pool=8, device_pairgen=True, steps_per_dispatch=2,
        window=5, tokens_per_step=128, max_sentence_length=64)
    import logging
    with caplog.at_level(logging.INFO, logger="glint_word2vec_tpu"):
        trainer, _ = _fit(cfg, sentences)
    assert trainer.pairs_trained > 0


def test_device_feed_config_validation():
    sentences = _topic_corpus(20)
    vocab = build_vocab(sentences, min_count=1)
    with pytest.raises(ValueError, match="skip-gram only"):
        Trainer(Word2VecConfig(min_count=1, device_pairgen=True, cbow=True,
                               negative_pool=8), vocab)


def test_device_feed_resume_is_deterministic(tmp_path):
    """Interrupt + resume lands on the same params as an uninterrupted run
    (the packer stream is a pure function of (seed, iteration, shard), and
    batches_done skips whole steps)."""
    sentences = _topic_corpus(200)
    vocab = build_vocab(sentences, min_count=1)
    encoded = encode_sentences(sentences, vocab, 1000)

    def mk():
        return Word2VecConfig(
            vector_size=16, min_count=1, pairs_per_batch=256, num_iterations=2,
            learning_rate=0.02, seed=9, negative_pool=8, device_pairgen=True,
            steps_per_dispatch=2, window=3, prefetch_chunks=0,
            subsample_ratio=0.0)

    full = Trainer(mk(), vocab)
    full.fit(encoded)
    ref = np.asarray(full.unpadded_params().syn0)

    ckpt = str(tmp_path / "ck")
    part = Trainer(mk().replace(heartbeat_every_steps=6), vocab)
    # interrupt on the SECOND heartbeat — the first _finish_round's periodic
    # checkpoint (which runs after the heartbeat) has been written by then
    calls = {"n": 0}

    def boom(_rec):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise KeyboardInterrupt

    try:
        part.fit(encoded, checkpoint_path=ckpt, checkpoint_every_steps=6,
                 on_heartbeat=boom)
    except KeyboardInterrupt:
        pass
    assert calls["n"] >= 2

    from glint_word2vec_tpu.models.estimator import Word2Vec
    resumed = Word2Vec.resume(ckpt, sentences)
    got = np.asarray(resumed.syn0)[:ref.shape[0], :ref.shape[1]]
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
