"""Fault-tolerance suite (docs/robustness.md): checkpoint integrity digests,
torn-save recovery via ``load_latest_valid`` (SIGKILL-driven, but the kill is a
deterministic fault point — no timing races), the trainer's non-finite
guardrail policies, and the retrying ingest wrappers.

The crash legs run as subprocesses because SIGKILL is the fault model under
test: no ``finally`` blocks, no atexit — the same surface as a preemption."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train import faults
from glint_word2vec_tpu.train.checkpoint import (
    CheckpointCorruptError,
    TrainState,
    load_latest_valid,
    load_model,
    save_model,
    verify_checkpoint,
)
from glint_word2vec_tpu.train.faults import InjectedFault, NonFiniteParamsError
from glint_word2vec_tpu.train.trainer import Trainer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _flip_byte(path, offset=130):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def _save(path, step=1, scale=1.0):
    words = ["w0", "w1", "w2"]
    counts = np.array([30, 20, 10])
    syn0 = scale * np.arange(12, dtype=np.float32).reshape(3, 4)
    save_model(path, words, counts, syn0, -syn0, Word2VecConfig(vector_size=4),
               TrainState(iteration=1, words_processed=step * 10,
                          global_step=step))
    return syn0


# -- digests + verification ------------------------------------------------------------


def test_save_records_digests_and_verifies(tmp_path):
    path = str(tmp_path / "ck")
    _save(path)
    meta = verify_checkpoint(path)
    assert set(meta["digests"]) == {"words", "counts.npy", "syn0.npy",
                                    "syn1.npy"}
    load_model(path)  # verify=True default must pass on a clean checkpoint


def test_bitflip_rejected_on_load(tmp_path):
    """One flipped byte in syn0.npy must fail the digest check — silent bit rot
    or a torn write never loads as garbage rows."""
    path = str(tmp_path / "ck")
    _save(path)
    _flip_byte(os.path.join(path, "syn0.npy"))
    with pytest.raises(CheckpointCorruptError, match="syn0.npy"):
        verify_checkpoint(path)
    with pytest.raises(CheckpointCorruptError):
        load_model(path)
    # an explicit opt-out still loads (debugging/forensics)
    assert load_model(path, verify=False)["syn0"].shape == (3, 4)


def test_legacy_checkpoint_without_digests_still_loads(tmp_path):
    path = str(tmp_path / "ck")
    _save(path)
    meta_p = os.path.join(path, "metadata.json")
    with open(meta_p) as f:
        meta = json.load(f)
    del meta["digests"]  # simulate a pre-round-6 writer
    with open(meta_p, "w") as f:
        json.dump(meta, f)
    verify_checkpoint(path)  # vacuous digest pass, structural checks only
    assert load_model(path)["syn0"].shape == (3, 4)


def test_sharded_checkpoint_digests_cover_shards(tmp_path):
    sents = [[f"w{j}" for j in np.random.default_rng(0).integers(0, 40, 10)]
             for _ in range(80)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=8, pairs_per_batch=128, num_iterations=1,
                         window=2, negatives=2, negative_pool=8,
                         steps_per_dispatch=2, seed=3, sharded_checkpoint=True,
                         subsample_ratio=0.0)
    trainer = Trainer(cfg, vocab, plan=make_mesh(2, 4))
    trainer.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    path = str(tmp_path / "ck")
    trainer.save_checkpoint(path)
    meta = verify_checkpoint(path)
    shard_keys = [k for k in meta["digests"] if k.startswith("syn0.shards/")]
    assert len(shard_keys) == trainer.plan.num_model
    _flip_byte(os.path.join(path, shard_keys[0].replace("/", os.sep)))
    with pytest.raises(CheckpointCorruptError, match="syn0.shards"):
        verify_checkpoint(path)


# -- load_latest_valid -----------------------------------------------------------------


def test_load_latest_valid_skips_corrupt_and_reclaims_debris(tmp_path):
    d = str(tmp_path)
    _save(os.path.join(d, "ck-old"), step=5)
    _save(os.path.join(d, "ck-new"), step=9)
    _flip_byte(os.path.join(d, "ck-new", "syn0.npy"))
    os.makedirs(os.path.join(d, ".ck-new.tmp-12345"))  # orphaned staging dir
    got = load_latest_valid(d)
    assert os.path.basename(got) == "ck-old"  # newest VERIFIABLE, not newest
    assert not os.path.exists(os.path.join(d, ".ck-new.tmp-12345"))


def test_load_latest_valid_restores_old_swap_debris(tmp_path):
    """The torn window: the live path vanished mid-swap, leaving only the
    previous checkpoint under its .old-<pid> rename — it must come back."""
    d = str(tmp_path)
    syn0 = _save(os.path.join(d, "ck"), step=4)
    os.rename(os.path.join(d, "ck"), os.path.join(d, "ck.old-999"))
    got = load_latest_valid(d)
    assert os.path.basename(got) == "ck"
    np.testing.assert_array_equal(load_model(got)["syn0"], syn0)


def test_load_latest_valid_nothing_valid(tmp_path):
    d = str(tmp_path)
    _save(os.path.join(d, "ck"))
    _flip_byte(os.path.join(d, "ck", "counts.npy"), offset=80)
    with pytest.raises(FileNotFoundError, match="no verifiable checkpoint"):
        load_latest_valid(d)


def test_sigkill_mid_save_recovers_previous(tmp_path):
    """Acceptance path: a run SIGKILLed inside save_model's swap window (via
    the deterministic crash point, not a timed kill) leaves a torn directory;
    load_latest_valid must hand back the previous checkpoint, digest-verified."""
    d = str(tmp_path)
    script = (
        "import numpy as np\n"
        "from glint_word2vec_tpu.config import Word2VecConfig\n"
        "from glint_word2vec_tpu.train.checkpoint import save_model, TrainState\n"
        "w=['a','b']; c=np.array([2,1])\n"
        "s1=np.ones((2,4),np.float32)\n"
        f"save_model({d + '/ck'!r}, w, c, s1, None, Word2VecConfig(vector_size=4),"
        " TrainState(global_step=2))\n"
        f"save_model({d + '/ck'!r}, w, c, s1*7, None, Word2VecConfig(vector_size=4),"
        " TrainState(global_step=4))\n"
        "raise SystemExit('UNREACHABLE')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               GLINT_FAULT_CRASH_POINT="save:swap@2")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=_REPO, capture_output=True, timeout=120)
    assert proc.returncode in (-9, 137), proc.stderr.decode()[-500:]
    assert not os.path.exists(os.path.join(d, "ck"))  # genuinely torn
    got = load_latest_valid(d)
    data = load_model(got)  # digest-verified load
    assert data["train_state"].global_step == 2  # the PREVIOUS checkpoint
    np.testing.assert_array_equal(data["syn0"], np.ones((2, 4), np.float32))
    assert sorted(os.listdir(d)) == ["ck"]  # all debris reclaimed


# -- non-finite guardrails -------------------------------------------------------------


def _toy_trainer(policy, seed=0):
    rng = np.random.default_rng(seed)
    sents = [[f"w{i}" for i in rng.integers(0, 30, 20)] for _ in range(250)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=8, pairs_per_batch=128, window=3,
                         num_iterations=2, steps_per_dispatch=2,
                         heartbeat_every_steps=2, subsample_ratio=0.0,
                         prefetch_chunks=0, seed=1, nonfinite_policy=policy)
    return Trainer(cfg, vocab), encode_sentences(sents, vocab, 1000)


def test_nan_injection_rollback_recovers():
    faults.configure(nan_at_step=8)
    trainer, enc = _toy_trainer("rollback")
    trainer.fit(enc)
    assert trainer.rollbacks_performed >= 1
    assert np.isfinite(np.asarray(trainer.params.syn0)).all()
    assert np.isfinite(np.asarray(trainer.params.syn1)).all()
    # the re-seed jumped the negative-sample counter lattice
    assert trainer.global_step >= Trainer._ROLLBACK_STEP_JUMP


def test_nan_injection_halt_raises_with_diagnostic():
    faults.configure(nan_at_step=8)
    trainer, enc = _toy_trainer("halt")
    with pytest.raises(NonFiniteParamsError, match="syn0"):
        trainer.fit(enc)


def test_nan_policy_none_keeps_old_behavior():
    faults.configure(nan_at_step=8)
    trainer, enc = _toy_trainer("none")
    trainer.fit(enc)  # must not raise; NaNs train on silently (pre-round-6)
    assert not np.isfinite(np.asarray(trainer.params.syn0)).all()


def test_final_save_is_probed_too(tmp_path):
    """A blowup in the last window — after the final heartbeat/periodic round —
    must still be caught by the guard inside save_checkpoint: the end-of-fit
    finished save must never persist NaNs (code-review r6 finding)."""
    ck = str(tmp_path / "ck")
    # no heartbeat (cadence 10^6) and no periodic save (every_steps unset), so
    # nothing probes between the injection and the end-of-fit finished save
    faults.configure(nan_at_step=8)
    rng = np.random.default_rng(0)
    sents = [[f"w{i}" for i in rng.integers(0, 30, 20)] for _ in range(250)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=8, pairs_per_batch=128, window=3,
                         num_iterations=1, steps_per_dispatch=2,
                         heartbeat_every_steps=10 ** 6, subsample_ratio=0.0,
                         prefetch_chunks=0, seed=1, nonfinite_policy="halt")
    trainer = Trainer(cfg, vocab)
    enc = encode_sentences(sents, vocab, 1000)
    with pytest.raises(NonFiniteParamsError):
        trainer.fit(enc, checkpoint_path=ck)
    assert not os.path.exists(ck)  # nothing (NaN) was persisted


def test_halt_never_overwrites_good_checkpoint(tmp_path):
    """The probe runs before a periodic save: the on-disk checkpoint must be
    the last GOOD state, never the blown-up one."""
    ck = str(tmp_path / "ck")
    faults.configure(nan_at_step=8)
    trainer, enc = _toy_trainer("halt")
    with pytest.raises(NonFiniteParamsError):
        trainer.fit(enc, checkpoint_path=ck, checkpoint_every_steps=2)
    data = load_model(ck)
    assert np.isfinite(data["syn0"]).all()
    assert data["train_state"].global_step < 8


# -- retrying ingest -------------------------------------------------------------------


def test_encode_corpus_retries_injected_faults(tmp_path):
    from glint_word2vec_tpu.data.corpus import encode_corpus
    sents = [["a", "b", "c"], ["b", "c", "d"]] * 10
    vocab = build_vocab(sents, min_count=1)
    faults.configure(fail_ingest_first_n=2)
    enc = encode_corpus(sents, vocab, str(tmp_path / "enc"))
    assert len(enc) == len(sents)
    np.testing.assert_array_equal(enc[0], enc[2])


def test_encode_corpus_retry_budget_exhausts(tmp_path):
    from glint_word2vec_tpu.data.corpus import encode_corpus
    sents = [["a", "b", "c"]] * 5
    vocab = build_vocab(sents, min_count=1)
    faults.configure(fail_ingest_first_n=50)
    with pytest.raises(InjectedFault):
        encode_corpus(sents, vocab, str(tmp_path / "enc"))


def test_token_file_corpus_open_retries(tmp_path):
    from glint_word2vec_tpu.data.corpus import TokenFileCorpus
    p = tmp_path / "corpus.txt"
    p.write_text("a b c\nd e f\n")
    faults.configure(fail_ingest_first_n=2)
    assert list(TokenFileCorpus(str(p))) == [["a", "b", "c"], ["d", "e", "f"]]


# -- resume of pre-round-5 checkpoints (ADVICE r5 medium) ------------------------------


def test_resume_unstable_checkpoint_config(tmp_path):
    """A checkpoint whose stored (resolved) subsample_ratio is now inside the
    duplicate-overload refusal region must be resumable via the allow_unstable
    pass-through instead of requiring a metadata.json hand-edit."""
    from glint_word2vec_tpu.models.estimator import Word2Vec
    rng = np.random.default_rng(0)
    # tiny vocab + big batch + enough corpus to fill it: expected top-word
    # duplicates per batch land far past the ~300 refusal boundary
    sents = [[f"w{i}" for i in rng.integers(0, 5, 20)] for _ in range(3000)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=4, pairs_per_batch=8192, window=5,
                         num_iterations=2, subsample_ratio=1e-3, seed=1)
    ck = str(tmp_path / "ck")
    syn0 = rng.normal(size=(vocab.size, 4)).astype(np.float32)
    save_model(ck, vocab.words, vocab.counts, syn0, -syn0, cfg,
               TrainState(iteration=1, words_processed=10, finished=False))
    with pytest.raises(ValueError, match="duplicate"):
        Word2Vec.resume(ck, sents)
    model = Word2Vec.resume(ck, sents, allow_unstable=True,
                            config_overrides={"pairs_per_batch": 256,
                                              "num_iterations": 1})
    assert model.train_state.finished


# -- chaos runner smoke ----------------------------------------------------------------


def test_chaos_runner_smoke(tmp_path):
    """End-to-end: the scripted fault schedule in tools/chaos_run.py passes.
    Covers the full crash → recover → resume → verify loop through the real
    CLI entry point (subprocesses inside)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "chaos_run.py"),
         "--smoke", "--workdir", str(tmp_path / "chaos")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        # fourteen phases, each a fit or a fleet in subprocesses of its own:
        # ~80 s alone, and under the suite's six workers several times that
        # (500 s was not enough on the driver's run of PR 40)
        cwd=_REPO, capture_output=True, timeout=900, text=True)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "[chaos] OK" in proc.stdout
