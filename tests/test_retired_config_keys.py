"""Stored configs of older checkpoints still carry the fields of the two step
variants that were retired (config._RETIRED_KEYS): they load, a value other
than the old default warns once by name, and the constructor itself knows none
of them."""

import json
import logging
import os

import numpy as np
import pytest

from glint_word2vec_tpu.config import _RETIRED_KEYS, Word2VecConfig
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.models.estimator import Word2Vec
from glint_word2vec_tpu.train.checkpoint import (
    TrainState, load_latest_valid, save_model)

# a value each key could hold in a stored config, other than its old default
SET = {"use_pallas": True, "hot_rows": 4096, "hot_flush_every": 8}


def _warnings(caplog):
    return [r.getMessage() for r in caplog.records
            if r.levelno == logging.WARNING and "no longer has" in r.getMessage()]


def test_table_names_exactly_the_retired_fields():
    assert set(_RETIRED_KEYS) == set(SET)
    fields = set(Word2VecConfig().to_dict())
    assert not fields & set(_RETIRED_KEYS)


@pytest.mark.parametrize("key", list(SET))
@pytest.mark.parametrize("stored", ["default", "set"])
def test_retired_config_keys(key, stored, caplog):
    d = Word2VecConfig(negative_pool=64).to_dict()
    d[key] = _RETIRED_KEYS[key] if stored == "default" else SET[key]
    with caplog.at_level(logging.WARNING, logger="glint_word2vec_tpu"):
        cfg = Word2VecConfig.from_dict(d)
    assert cfg == Word2VecConfig(negative_pool=64)
    got = _warnings(caplog)
    if stored == "default":
        assert got == []
    else:
        assert len(got) == 1 and key in got[0] and "shared-pool step" in got[0]


@pytest.mark.parametrize("key", list(SET))
def test_retired_key_is_no_constructor_argument(key):
    with pytest.raises(TypeError, match=key):
        Word2VecConfig(**{key: SET[key]})


def test_checkpoint_with_retired_keys_loads_and_resumes(tmp_path, caplog):
    rng = np.random.default_rng(0)
    sents = [[f"w{i}" for i in rng.integers(0, 40, 12)] for _ in range(200)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=8, min_count=1, pairs_per_batch=64,
                         negative_pool=16, window=3, steps_per_dispatch=2,
                         num_iterations=2, subsample_ratio=0.0, seed=1)
    ck = str(tmp_path / "ck")
    syn0 = rng.normal(scale=0.05, size=(vocab.size, 8)).astype(np.float32)
    save_model(ck, vocab.words, vocab.counts, syn0, np.zeros_like(syn0), cfg,
               TrainState(iteration=1, words_processed=10, finished=False))
    meta_path = os.path.join(ck, "metadata.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["config"].update(SET)
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    assert load_latest_valid(str(tmp_path)) == ck
    with caplog.at_level(logging.WARNING, logger="glint_word2vec_tpu"):
        model = Word2Vec.resume(ck, sents)
    assert model.train_state.finished
    assert model.train_state.global_step >= cfg.steps_per_dispatch
    assert not np.array_equal(np.asarray(model.syn0), syn0)
    got = _warnings(caplog)
    assert len(got) == len(SET)
    for key in SET:
        assert sum(key in m for m in got) == 1
