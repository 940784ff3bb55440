"""Two-process distributed training tests — the scaled-down analog of a multi-host
TPU pod run (G1/G8 replacement; reference boots its PS cluster across executors,
mllib:354-360).

Spawns 2 coordinated JAX processes, each with 4 virtual CPU devices, builds ONE global
(2, 4) mesh spanning both, and trains end-to-end through the Trainer. Two feed modes
(parallel/distributed.py):

- sharded (default): each process generates only its sentence shard; per-round
  allgathers assemble the global batch (the repartition analog, mllib:345);
- replicated: every process regenerates the full stream.

Both must finish in lockstep and agree bit-for-bit on the final
(replicated-checksummed) parameters; the sharded mode additionally proves exact-step
resume from a mid-run sharded checkpoint.
"""

import os
import socket
import subprocess
import sys

import pytest

WORKER = r"""
import os, sys
# CPU by design, like the rest of the suite: each worker is one "host" of 4 virtual devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")

from glint_word2vec_tpu.parallel.distributed import initialize, is_multiprocess
pid = int(sys.argv[1]); port = sys.argv[2]; mode = sys.argv[3]; workdir = sys.argv[4]
initialize(coordinator_address="127.0.0.1:" + port, num_processes=2, process_id=pid)
assert is_multiprocess()
assert jax.device_count() == 8 and jax.local_device_count() == 4

import numpy as np
from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train.trainer import Trainer

rng = np.random.default_rng(0)
words = [f"w{i}" for i in range(64)]
if mode == "varlen":
    # variable sentence lengths + odd sentence count: data segments exhaust at
    # DIFFERENT rows, driving the iteration-barrier's held-offer/use-mask path
    # (advisor r4 — fixed 12-token sentences never reach it)
    lens = rng.integers(3, 40, 201)
    sentences = [[words[j] for j in rng.integers(0, 64, L)] for L in lens]
else:
    sentences = [[words[j] for j in rng.integers(0, 64, 12)] for _ in range(200)]
vocab = build_vocab(sentences, min_count=1)
cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=128,
                     num_iterations=2, window=3, negatives=3, negative_pool=16,
                     steps_per_dispatch=2, seed=7, subsample_ratio=0.0,
                     cbow=(mode in ("cbow", "banded")),
                     cbow_update=("banded" if mode == "banded" else "scatter"),
                     device_pairgen=(mode in ("device", "device42", "dresume",
                                              "eshrink", "egrow", "varlen")),
                     shard_input=(mode in ("sharded", "resume", "cbow", "device",
                                           "device42", "dresume", "eshrink",
                                           "egrow", "varlen", "banded")),
                     # every 2-process test also exercises the SPMD divergence
                     # detector on its real feeds (must stay silent)
                     feed_consistency_check=True)
# spans both processes: 8 global devices; device42 uses a 4-wide data axis so
# each process owns TWO token segments (spp=2 in feeds.GatheredTokenBlocks)
plan = make_mesh(4, 2) if mode in ("device42", "varlen") else make_mesh(2, 4)
encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)

import jax.numpy as jnp
def checksum_of(trainer):
    return float(jax.jit(lambda p: jnp.sum(p.syn0) + 1000.0 * jnp.sum(p.syn1))(
        trainer.params))

def stop_after_first_checkpoint(trainer, encoded, ck):
    # run fit with periodic checkpointing, aborting right after the first
    # mid-run save: leaves a valid mid-iteration checkpoint at ck
    seen = []
    class Stop(Exception):
        pass
    orig = Trainer.save_checkpoint
    def save_once(self, path, **kw):
        orig(self, path, **kw)
        seen.append(self.state.global_step)
        if len(seen) == 1:
            raise Stop()
    Trainer.save_checkpoint = save_once
    try:
        trainer.fit(encoded, checkpoint_path=ck, checkpoint_every_steps=4)
    except Stop:
        pass
    finally:
        Trainer.save_checkpoint = orig
    assert seen, "no mid-run checkpoint happened"

if mode == "fdiverge":
    # negative path of the SPMD divergence detector: process-DEPENDENT data
    # must be caught by the fingerprint allgather on every process
    trainer = Trainer(cfg, vocab, plan=plan)
    bad = {"x": np.full(8, pid, np.int32)}
    try:
        trainer._assert_feed_consistent(bad, np.zeros((2, 2), np.float32))
        print("DIVERGE missed", flush=True)
    except RuntimeError:
        print("DIVERGE caught", flush=True)
elif mode == "eshrink":
    # 2-process interrupted device-feed run; the parent resumes it on ONE process
    stop_after_first_checkpoint(Trainer(cfg, vocab, plan=plan),
                                encoded, os.path.join(workdir, "ck"))
    print("STOPPED ok", flush=True)
elif mode == "egrow":
    # resume (2 processes) from a single-process checkpoint the parent wrote
    # (dense layout — every process loads the same host arrays; Trainer places)
    ck = os.path.join(workdir, "ck")
    from glint_word2vec_tpu.train.checkpoint import load_model
    m = load_model(ck)
    st = m["train_state"]
    assert st.shard_feed == "tokens" and len(st.shard_progress) == 2
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair
    t2 = Trainer(cfg, vocab, plan=plan,
                 params=EmbeddingPair(m["syn0"], m["syn1"]), train_state=st)
    t2.fit(encoded)
    print(f"CHECKSUM {checksum_of(t2):.10e} steps {t2.global_step}", flush=True)
elif mode in ("resume", "dresume"):
    # uninterrupted run -> reference params
    t_ref = Trainer(cfg, vocab, plan=plan)
    assert t_ref._feed_segments == 2
    t_ref.fit(encoded)
    want = checksum_of(t_ref)
    # interrupted run: checkpoint every 4 global steps, stop after the first save
    ck = os.path.join(workdir, "ck")
    stop_after_first_checkpoint(Trainer(cfg, vocab, plan=plan), encoded, ck)
    from glint_word2vec_tpu.train.checkpoint import load_model_header, load_params_into_plan
    header = load_model_header(ck)
    st = header["train_state"]
    assert st.shard_progress is not None and len(st.shard_progress) == 2
    from glint_word2vec_tpu.parallel.mesh import pad_dim_to_lanes, pad_vocab_for_sharding
    pv = pad_vocab_for_sharding(vocab.size, plan.num_model)
    pd = pad_dim_to_lanes(cfg.vector_size, cfg.pad_vector_to_lanes)
    syn0, syn1 = load_params_into_plan(ck, plan, pv, pd)
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair
    t2 = Trainer(cfg, vocab, plan=plan, params=EmbeddingPair(syn0, syn1),
                 train_state=st)
    t2.fit(encoded)
    got = checksum_of(t2)
    assert abs(got - want) < 1e-4 * max(1.0, abs(want)), (
        f"resumed params diverge: {got!r} vs {want!r}")
    print(f"CHECKSUM {got:.10e} steps {t2.global_step}", flush=True)
else:
    trainer = Trainer(cfg, vocab, plan=plan)
    assert trainer.params.syn0.sharding.is_equivalent_to(plan.embedding, 2)
    assert trainer._feed_segments == (
        2 if mode in ("sharded", "cbow", "device", "device42", "varlen",
                      "banded") else 1)
    trainer.fit(encoded)
    checksum = checksum_of(trainer)
    assert np.isfinite(checksum)
    print(f"CHECKSUM {checksum:.10e} steps {trainer.global_step} "
          f"pairs {trainer.pairs_trained:.0f}", flush=True)
"""


def _run_two(tmp_path, mode, marker="CHECKSUM"):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), port, mode, str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\nstdout:{out}\nstderr:{err[-3000:]}"
        outs.append(out)
    lines = [next(ln for ln in o.splitlines() if ln.startswith(marker))
             for o in outs]
    assert lines[0] == lines[1], f"processes disagree: {lines}"
    return lines[0]


def _parent_device_setup(varlen=False):
    """The worker script's corpus/config/mesh, rebuilt in the parent process
    (8 local virtual devices, single process) for cross-topology comparisons."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(64)]
    if varlen:  # must mirror the worker script's "varlen" corpus exactly
        lens = rng.integers(3, 40, 201)
        sentences = [[words[j] for j in rng.integers(0, 64, L)] for L in lens]
    else:
        sentences = [[words[j] for j in rng.integers(0, 64, 12)]
                     for _ in range(200)]
    vocab = build_vocab(sentences, min_count=1)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=128,
                         num_iterations=2, window=3, negatives=3,
                         negative_pool=16, steps_per_dispatch=2, seed=7,
                         subsample_ratio=0.0, device_pairgen=True,
                         shard_input=True)
    plan = make_mesh(2, 4)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)

    def checksum(trainer):
        return float(jax.jit(
            lambda p: jnp.sum(p.syn0) + 1000.0 * jnp.sum(p.syn1))(
                trainer.params))

    return vocab, encoded, cfg, plan, checksum


def _interrupt_at_first_checkpoint(trainer, encoded, ck):
    """Run fit with periodic checkpointing, aborting right after the first
    mid-run save — leaves a valid mid-iteration checkpoint at ck. (The worker
    script carries its own copy; it is self-contained source text.)"""
    from glint_word2vec_tpu.train.trainer import Trainer

    seen = []

    class Stop(Exception):
        pass

    orig = Trainer.save_checkpoint

    def save_once(self, path, **kw):
        orig(self, path, **kw)
        seen.append(self.state.global_step)
        if len(seen) == 1:
            raise Stop()

    Trainer.save_checkpoint = save_once
    try:
        trainer.fit(encoded, checkpoint_path=ck, checkpoint_every_steps=4)
    except Stop:
        pass
    finally:
        Trainer.save_checkpoint = orig
    assert seen, "no mid-run checkpoint happened"


def test_two_process_training_replicated_feed(tmp_path):
    _run_two(tmp_path, "replicated")


def test_two_process_training_sharded_feed(tmp_path):
    """Default mode: per-process sentence shards + allgather assembly (mllib:345
    analog). Cross-process checksum agreement proves SPMD consistency of the
    assembled batches, alphas, and collective order."""
    _run_two(tmp_path, "sharded")


def test_two_process_cbow_sharded_feed(tmp_path):
    """CBOW on the sharded-input feed (round-4: the allgather protocol carries the
    grouped centers/contexts/count arrays, not just packed pairs)."""
    _run_two(tmp_path, "cbow")


def test_two_process_banded_cbow_bit_identity(tmp_path):
    """Banded CBOW (cbow_update='banded') on the sharded token-block feed: the
    halo-overlapped segment streams are deterministic and process-independent
    (pipeline.pack_halo_token_blocks over _device_seg_blocks), so the 2-process
    run must train on the byte-identical feed of the single-process banded run
    — asserted by matching its checksum and exact example count."""
    line = _run_two(tmp_path, "banded")
    got = float(line.split()[1])
    got_pairs = float(line.split()[5])

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, encoded, cfg, _, checksum = _parent_device_setup()
    cfg = Word2VecConfig.from_dict(dict(
        cfg.to_dict(), cbow=True, cbow_update="banded",
        device_pairgen=False))
    trainer = Trainer(cfg, vocab, plan=make_mesh(2, 4))
    trainer.fit(encoded)
    want = checksum(trainer)
    assert got_pairs == trainer.pairs_trained, (got_pairs, trainer.pairs_trained)
    assert abs(got - want) < 1e-6 * max(1.0, abs(want)), (got, want)


@pytest.mark.parametrize("mode,mesh", [("device", (2, 4)), ("device42", (4, 2)),
                                       ("varlen", (4, 2))])
def test_two_process_device_pairgen_bit_identity(tmp_path, mode, mesh):
    """device_pairgen across processes (round-4): each process packs token blocks
    for its own data segments only; the iteration-barrier allgather protocol
    (feeds.GatheredTokenBlocks) makes the 2-process run train on the
    byte-identical feed the single-process device-feed run sees — asserted here
    by matching the single-process run's checksum and exact pair count. The
    (4, 2) mesh gives each process TWO token segments (spp=2 — exercises the
    per-own-segment assembly, positions, and hash-base slices spp=1 cannot).
    The varlen case (advisor r4) uses variable sentence lengths (3-40 tokens,
    odd sentence count), so the four data segments exhaust at different token
    rows and the barrier's hard path — held offers, use-mask zeroing of
    lagging/leading processes, per-process differing `real` counts — actually
    executes; fixed-length corpora never reach it."""
    line = _run_two(tmp_path, mode)
    got = float(line.split()[1])
    got_pairs = float(line.split()[5])

    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, encoded, cfg, _, checksum = _parent_device_setup(
        varlen=(mode == "varlen"))
    trainer = Trainer(cfg, vocab, plan=make_mesh(*mesh))
    trainer.fit(encoded)
    want = checksum(trainer)
    assert got_pairs == trainer.pairs_trained, (got_pairs, trainer.pairs_trained)
    assert abs(got - want) < 1e-6 * max(1.0, abs(want)), (got, want)


def test_elastic_resume_shrink_two_to_one(tmp_path):
    """ELASTIC restart, N -> 1: interrupt a 2-process device-feed run at its
    first checkpoint, then resume it on a SINGLE process. Device-feed positions
    are per data segment (process-independent), so the single process picks up
    all segments and the result matches the uninterrupted single-process run
    (to the < 1-word lr-clock rebuild tolerance)."""
    _run_two(tmp_path, "eshrink", marker="STOPPED")

    from glint_word2vec_tpu.ops.sgns import EmbeddingPair
    from glint_word2vec_tpu.parallel.mesh import (
        pad_dim_to_lanes, pad_vocab_for_sharding)
    from glint_word2vec_tpu.train.checkpoint import (
        load_model_header, load_params_into_plan)
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, encoded, cfg, plan, checksum = _parent_device_setup()
    ref = Trainer(cfg, vocab, plan=plan)
    ref.fit(encoded)
    want = checksum(ref)

    ck = str(tmp_path / "ck")
    st = load_model_header(ck)["train_state"]
    assert st.shard_feed == "tokens" and len(st.shard_progress) == 2
    pv = pad_vocab_for_sharding(vocab.size, plan.num_model)
    pd = pad_dim_to_lanes(cfg.vector_size, cfg.pad_vector_to_lanes)
    syn0, syn1 = load_params_into_plan(ck, plan, pv, pd)
    t2 = Trainer(cfg, vocab, plan=plan, params=EmbeddingPair(syn0, syn1),
                 train_state=st)
    t2.fit(encoded)
    got = checksum(t2)
    assert abs(got - want) < 1e-4 * max(1.0, abs(want)), (got, want)

    # double-resume: a checkpoint written AFTER an elastic resume has row
    # counts offset from the canonical stream, so it must persist
    # batches_done=0 and keep the per-segment positions authoritative — a
    # second resume then lands correctly too
    from glint_word2vec_tpu.train.checkpoint import load_model
    syn0b, syn1b = load_params_into_plan(ck, plan, pv, pd)
    t3 = Trainer(cfg, vocab, plan=plan, params=EmbeddingPair(syn0b, syn1b),
                 train_state=st)
    ck2 = str(tmp_path / "ck2")
    _interrupt_at_first_checkpoint(t3, encoded, ck2)
    m2 = load_model(ck2)
    st2 = m2["train_state"]
    assert st2.batches_done == 0 and st2.shard_feed == "tokens"
    t4 = Trainer(cfg, vocab, plan=plan,
                 params=EmbeddingPair(m2["syn0"], m2["syn1"]), train_state=st2)
    t4.fit(encoded)
    got2 = checksum(t4)
    assert abs(got2 - want) < 1e-4 * max(1.0, abs(want)), (got2, want)


def test_elastic_resume_grow_one_to_two(tmp_path):
    """ELASTIC restart, 1 -> N: interrupt a single-process device-feed run at
    its first checkpoint (which now records per-segment positions alongside its
    own batches_done), then resume it on 2 processes; the result matches the
    uninterrupted single-process run."""
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, encoded, cfg, plan, checksum = _parent_device_setup()
    ref = Trainer(cfg, vocab, plan=plan)
    ref.fit(encoded)
    want = checksum(ref)

    # interrupted single-process run -> mid-iteration checkpoint at tmp_path/ck
    _interrupt_at_first_checkpoint(
        Trainer(cfg, vocab, plan=plan), encoded, str(tmp_path / "ck"))

    line = _run_two(tmp_path, "egrow")
    got = float(line.split()[1])
    assert abs(got - want) < 1e-4 * max(1.0, abs(want)), (got, want)


def test_two_process_device_pairgen_resume(tmp_path):
    """Interrupt a 2-process device-feed run at its first mid-run checkpoint and
    resume from the row-shards checkpoint: shard_progress indexes token-step rows
    (shard_feed="tokens") and the within-iteration lr clock is rebuilt from the
    saved word count, so the resumed run matches the uninterrupted one."""
    _run_two(tmp_path, "dresume")


def test_feed_consistency_detector_catches_divergence(tmp_path):
    """The SPMD feed-divergence detector (config.feed_consistency_check) must
    flag process-dependent feed content; its silent pass on real feeds is
    covered by every other 2-process test (the flag is on in the worker)."""
    line = _run_two(tmp_path, "fdiverge", marker="DIVERGE")
    assert line == "DIVERGE caught"


def test_two_process_sharded_resume(tmp_path):
    """Interrupt a sharded-feed run at its first mid-run checkpoint, resume from the
    row-shards checkpoint (per-process stream positions from shard_progress), and
    match the uninterrupted run's final params exactly."""
    _run_two(tmp_path, "resume")
