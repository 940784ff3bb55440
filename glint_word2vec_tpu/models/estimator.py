"""Estimator API — fit sentences to a Word2VecModel.

The pythonic primary surface (in the reference, Python was a Py4J shim over the Spark ML
Estimator, C11/C14; here Python is the framework's first language). One call chain:

    model = Word2Vec(vector_size=100, window=5).fit(sentences)

covers what the reference spreads over mllib fit (vocab → broadcasts → doFit,
mllib:310-326), the ML Estimator (ml:284-305) and the PySpark wrapper
(ml_glintword2vec.py:143-151).
"""

from __future__ import annotations

import logging
from typing import Iterable, Optional, Sequence

import numpy as np

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import Vocabulary, build_vocab
from glint_word2vec_tpu.models.word2vec import Word2VecModel
from glint_word2vec_tpu.parallel.mesh import MeshPlan
from glint_word2vec_tpu.train.trainer import Trainer

logger = logging.getLogger("glint_word2vec_tpu")


class Word2Vec:
    """Trains skip-gram (default) or CBOW word2vec with negative sampling."""

    def __init__(self, config: Optional[Word2VecConfig] = None, **overrides):
        if config is None:
            config = Word2VecConfig(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        self.config = config

    def fit(
        self,
        sentences: Iterable[Sequence[str]],
        plan: Optional[MeshPlan] = None,
        vocab: Optional[Vocabulary] = None,
        checkpoint_path: Optional[str] = None,
        checkpoint_every_steps: Optional[int] = None,
        encode_cache_dir: Optional[str] = None,
    ) -> Word2VecModel:
        """sentences: iterable of token sequences (the RDD[Iterable[String]] analog,
        mllib:310). Re-iterables (lists, :class:`..data.corpus.TokenFileCorpus`) are
        streamed twice (vocab pass + encode pass) without materialization; one-shot
        generators are materialized to a list first.

        ``encode_cache_dir``: write the encoded corpus there and train from
        memory-mapped shards — bounded host RAM for corpora that don't fit as
        Python lists (see data/corpus.py). Without it, encoding is in-RAM.
        """
        cfg = self.config
        if iter(sentences) is sentences:  # one-shot generator: must materialize
            sentences = list(sentences)
        if vocab is None:
            vocab = build_vocab(sentences, cfg.min_count,
                                workers=cfg.io_workers)
        logger.info("vocabSize = %d, trainWordsCount = %d",
                    vocab.size, vocab.train_words_count)
        if encode_cache_dir is not None:
            from glint_word2vec_tpu.data.corpus import encode_corpus
            encoded = encode_corpus(
                sentences, vocab, encode_cache_dir, cfg.max_sentence_length)
        else:
            encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)
        trainer = Trainer(cfg, vocab, plan=plan)
        trainer.fit(encoded, checkpoint_path=checkpoint_path,
                    checkpoint_every_steps=checkpoint_every_steps)
        params = trainer.unpadded_params()
        # runtime outcome of the fit (docs/robustness.md ladder +
        # docs/observability.md attribution): the EVAL harness emits this
        # into its rows so a stabilizer A/B reports the ENGAGED mitigation
        # state, and a telemetry-on run additionally carries the per-phase
        # time rollup. One owner: Trainer.last_run_stats.
        self.last_run_stats = trainer.last_run_stats
        return Word2VecModel(
            vocab=vocab, syn0=params.syn0, syn1=params.syn1,
            config=cfg, plan=trainer.plan, train_state=trainer.state,
            subword_buckets=trainer.subword_buckets(),
            subword_rows=trainer.subword_rows(),
            position_weights=trainer.position_weights())

    @staticmethod
    def resume(
        checkpoint_path: str,
        sentences: Iterable[Sequence[str]],
        plan: Optional[MeshPlan] = None,
        checkpoint_every_steps: Optional[int] = None,
        encode_cache_dir: Optional[str] = None,
        allow_unstable: Optional[bool] = None,
        config_overrides: Optional[dict] = None,
    ) -> Word2VecModel:
        """Resume an interrupted run from a mid-training checkpoint (capability the
        reference lacks — its runs are all-or-nothing, SURVEY §5). Resume is
        exact-step: the checkpoint records the deterministic batch-stream position
        (``TrainState.batches_done``), so already-trained batches of the interrupted
        iteration are skipped, not replayed.

        ``sentences`` may be raw token sequences or an already-encoded
        :class:`..data.corpus.EncodedCorpus`. If ``encode_cache_dir`` already holds an
        encoded corpus whose vocab fingerprint matches the checkpoint's vocabulary, it
        is reused as-is (the common resume case — no re-encoding pass, unlike
        :meth:`fit` which always re-encodes); otherwise the sentences are streamed
        into it. Either way training reads memory-mapped shards.

        ``config_overrides``/``allow_unstable``: the rebuilt Trainer otherwise
        takes the checkpoint's config verbatim, and checkpoints pin the
        RESOLVED subsample_ratio (to_dict(auto_markers=False)) — so a
        pre-round-5 checkpoint saved with the old default 1e-3 at a geometry
        now inside the measured duplicate-overload refusal region would be
        unresumable (ADVICE r5). ``allow_unstable=True`` overrides that
        refusal for the resumed run (warn-only); ``config_overrides`` replaces
        arbitrary config fields (e.g. ``{"subsample_ratio": 1e-4}``) — note
        non-feed knobs that change the batch stream will shift the recorded
        resume position's meaning."""
        import os

        from glint_word2vec_tpu.data.corpus import (
            EncodedCorpus, encode_corpus, vocab_fingerprint)
        from glint_word2vec_tpu.ops.sgns import EmbeddingPair
        from glint_word2vec_tpu.train.checkpoint import (
            load_model, load_model_header, load_params_into_plan)

        header = load_model_header(checkpoint_path)
        cfg: Word2VecConfig = header["config"]
        if config_overrides:
            cfg = cfg.replace(**config_overrides)
        if allow_unstable is not None:
            cfg = cfg.replace(allow_unstable=allow_unstable)
        state = header["train_state"]
        vocab = Vocabulary.from_words_and_counts(header["words"], header["counts"])
        streamed = None
        if plan is not None and header["layout"] == "row-shards":
            # stream the shards straight onto the target mesh — resume at the 10M-row
            # north star must not materialize [V, D] on one host (same path as
            # Word2VecModel.load(plan=...))
            from glint_word2vec_tpu.parallel.mesh import (
                pad_dim_to_lanes, pad_vocab_for_sharding)
            pv = pad_vocab_for_sharding(vocab.size, plan.num_model)
            pd = pad_dim_to_lanes(cfg.vector_size, cfg.pad_vector_to_lanes)
            syn0, syn1 = load_params_into_plan(
                checkpoint_path, plan, pv, pd, dtype=np.dtype(cfg.param_dtype),
                io_workers=cfg.io_workers)
            if syn1 is None:
                raise ValueError("checkpoint has no syn1; cannot resume training")
            streamed = EmbeddingPair(syn0, syn1)
            data = None
        else:
            # io_workers from the LIVE (override-applied) config — the saved
            # value reflects the writing host, not this one
            data = load_model(checkpoint_path, header=header,
                              io_workers=cfg.io_workers)
        if isinstance(sentences, EncodedCorpus):
            encoded = sentences
        elif encode_cache_dir is not None:
            if os.path.exists(os.path.join(encode_cache_dir, "meta.json")):
                encoded = EncodedCorpus(encode_cache_dir)
                want = vocab_fingerprint(vocab)
                got = encoded.meta.get("vocab_fingerprint")
                # the continual case (docs/continual.md): a checkpoint grown
                # by continual.extend carries a vocab_lineage chain whose
                # identity-prefix contract keeps every ANCESTOR vocabulary's
                # ids valid — a cache encoded under any of them is reused
                # as-is, not re-encoded
                from glint_word2vec_tpu.continual.extend import (
                    lineage_fingerprints)
                allowed = set(
                    lineage_fingerprints(header.get("vocab_lineage") or []))
                allowed.add(want)
                if got not in allowed:
                    raise ValueError(
                        f"encode_cache_dir {encode_cache_dir!r} was encoded under a "
                        f"different vocabulary (fingerprint {got} != checkpoint's "
                        f"{want}, and it is not an ancestor in the checkpoint's "
                        "lineage chain); ids would map to the wrong words. Point "
                        "resume at the cache dir of the interrupted run, or a "
                        "fresh directory — or, if the CORPUS drifted (new words, "
                        "shifted frequencies), migrate the checkpoint first with "
                        "glint_word2vec_tpu.continual.extend.extend_checkpoint "
                        "(vocab growth on resume, docs/continual.md) instead of "
                        "retraining from scratch.")
            else:
                encoded = encode_corpus(
                    sentences, vocab, encode_cache_dir, cfg.max_sentence_length)
        else:
            if iter(sentences) is sentences:
                sentences = list(sentences)
            encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)
        if streamed is not None:
            params = streamed
        else:
            if data["syn1"] is None:
                raise ValueError("checkpoint has no syn1; cannot resume training")
            import jax.numpy as jnp
            syn0 = jnp.asarray(data["syn0"])
            if data.get("subword_buckets") is not None:
                # a subword model's input table: its bucket rows follow the
                # vocabulary's (save_model keeps them in a file of their own)
                syn0 = jnp.concatenate(
                    [syn0, jnp.asarray(data["subword_buckets"])])
            pos = data.get("position_weights")
            params = EmbeddingPair(syn0, jnp.asarray(data["syn1"]),
                                   None if pos is None else jnp.asarray(pos))
        trainer = Trainer(cfg, vocab, plan=plan, params=params, train_state=state)
        if not state.finished:
            # pass checkpoint_every_steps explicitly to keep periodic checkpointing
            # alive across the resumed run — the cadence is a fit() argument, not
            # persisted in the checkpoint, so it cannot be inherited
            trainer.fit(encoded, checkpoint_path=checkpoint_path,
                        checkpoint_every_steps=checkpoint_every_steps)
        out = trainer.unpadded_params()
        return Word2VecModel(
            vocab=vocab, syn0=out.syn0, syn1=out.syn1, config=cfg,
            plan=trainer.plan, train_state=trainer.state,
            subword_buckets=trainer.subword_buckets(),
            subword_rows=trainer.subword_rows(),
            position_weights=trainer.position_weights())
