"""One fit loop over one feed interface (PR 44): the structural claim, and the
resume contract of the one-process feeds the loop runs.

The two multi-process feeds' numerics and resume are tests/test_multiprocess.py;
the loop's spans are tests/test_heartbeat_spans.py.
"""

import os
import re
import shutil

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.ops.sgns import EmbeddingPair
from glint_word2vec_tpu.train.checkpoint import load_model
from glint_word2vec_tpu.train.trainer import Trainer

_TRAIN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "glint_word2vec_tpu", "train")


@pytest.mark.parametrize("site", [
    '"dispatch.enqueue"',     # the span around the jitted chunk's call
    "._finish_round(",        # the round's bookkeeping
    "._dispatch_step_fn(",    # the choice of step twin
    "alpha_schedule(",        # the lr schedule of a round's word clocks
])
def test_the_loop_is_written_once(site):
    """A fit loop forked again (four copies until PR 44) shows up as a second
    site of what only the loop, or only the feeds' one meta helper, does."""
    found = []
    for name in sorted(os.listdir(_TRAIN)):
        if name.endswith(".py"):
            with open(os.path.join(_TRAIN, name), encoding="utf-8") as f:
                code = re.sub(r'""".*?"""', "", f.read(), flags=re.S)
            found += [f"{name}:{i}" for i, line in enumerate(code.splitlines(), 1)
                      if site in line.split("#")[0]]
    assert len(found) == 1, found


class _Stop(Exception):
    pass


# host pairs, skip-gram: tests/test_estimator.py::
# test_exact_step_resume_matches_uninterrupted holds it bit for bit already.
# tests/test_device_feed_trainer.py::test_device_feed_resume_is_deterministic
# holds the token blocks to a tolerance; here they are held to the bit.
@pytest.mark.parametrize("feed, kw", [
    ("host pairs, CBOW", dict(cbow=True)),
    ("token blocks, device pairs", dict(device_pairgen=True)),
    ("token blocks, banded CBOW", dict(cbow=True, cbow_update="banded")),
])
def test_resumed_fit_ends_where_the_uninterrupted_one_does(feed, kw, tmp_path):
    """A fit stopped at a mid-run checkpoint and resumed from it ends in the
    same params, bit for bit, as the fit nobody stopped."""
    rng = np.random.default_rng(0)
    sentences = [[f"w{j}" for j in rng.integers(0, 64, n)]
                 for n in rng.integers(3, 40, 201)]
    vocab = build_vocab(sentences, min_count=1)
    cfg = Word2VecConfig(
        vector_size=16, min_count=1, pairs_per_batch=128, num_iterations=2,
        window=3, negatives=3, negative_pool=16, steps_per_dispatch=2,
        heartbeat_every_steps=3, seed=7, subsample_ratio=0.0, **kw)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)

    whole = Trainer(cfg, vocab)
    whole.fit(encoded)

    ck, kept = str(tmp_path / "ck"), str(tmp_path / "kept")
    saves = []

    def stop_at_the_second_save(rec):
        # the save of a round comes after its heartbeat: by the heartbeat of
        # a later round the checkpoint of an earlier one is whole on disk
        if os.path.isdir(ck) and not saves:
            shutil.copytree(ck, kept)
            saves.append(load_model(kept)["train_state"])
            raise _Stop()

    with pytest.raises(_Stop):
        Trainer(cfg, vocab).fit(encoded, checkpoint_path=ck,
                                checkpoint_every_steps=4,
                                on_heartbeat=stop_at_the_second_save)
    state = saves[0]
    assert 0 < state.global_step < whole.global_step and not state.finished

    m = load_model(kept)
    resumed = Trainer(cfg, vocab, params=EmbeddingPair(m["syn0"], m["syn1"]),
                      train_state=state)
    resumed.fit(encoded)
    assert resumed.global_step == whole.global_step
    for got, want in zip(resumed.unpadded_params()[:2],
                         whole.unpadded_params()[:2]):
        assert np.array_equal(np.asarray(got), np.asarray(want)), feed
