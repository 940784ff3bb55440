"""Parallel host data plane (PERF.md §10): bit-identity of every parallel path
against its serial twin, plus the hostbench harness smoke tier.

The contract under test: ``producer_workers`` / ``io_workers`` change WALL
CLOCK only — streams, trained parameters, checkpoint bytes, digests, and
exports are identical at any worker count, because every parallel unit is a
pure function of position-keyed inputs consumed in a fixed order.
"""

import filecmp
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from glint_word2vec_tpu.config import Word2VecConfig  # noqa: E402
from glint_word2vec_tpu.data.pipeline import (  # noqa: E402
    encode_sentences, epoch_batches, epoch_batches_cbow, ordered_pool_map)
from glint_word2vec_tpu.data.vocab import (  # noqa: E402
    build_vocab, count_words, count_words_parallel)
from glint_word2vec_tpu.train import checkpoint as ckpt  # noqa: E402
from glint_word2vec_tpu.train.feeds import _one_ahead_iter  # noqa: E402
from glint_word2vec_tpu.train.trainer import Trainer  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _corpus(n_words=60_000, vocab_size=300, sent_len=30, seed=0):
    rng = np.random.default_rng(seed)
    zipf = 1.0 / (np.arange(vocab_size) + 10.0) ** 1.05
    ids = rng.choice(vocab_size, size=n_words, p=zipf / zipf.sum())
    words = np.char.add("w", ids.astype("U8"))
    return [list(words[i:i + sent_len]) for i in range(0, n_words, sent_len)]


@pytest.fixture(scope="module")
def corpus():
    sents = _corpus()
    vocab = build_vocab(sents, min_count=1)
    return sents, vocab, encode_sentences(sents, vocab, 1000)


# -- ordered_pool_map ---------------------------------------------------------------


def test_ordered_pool_map_order_and_serial_equivalence():
    jobs = list(range(57))
    fn = lambda x: x * x  # noqa: E731
    assert list(ordered_pool_map(fn, jobs, 1)) == [x * x for x in jobs]
    assert list(ordered_pool_map(fn, jobs, 4)) == [x * x for x in jobs]


def test_ordered_pool_map_propagates_exceptions():
    def fn(x):
        if x == 3:
            raise ValueError("job 3")
        return x

    out = []
    with pytest.raises(ValueError, match="job 3"):
        for r in ordered_pool_map(fn, range(10), 4):
            out.append(r)
    assert out == [0, 1, 2]  # everything before the failing job, in order


def test_ordered_pool_map_consumer_abandon():
    # closing the generator mid-stream must not hang on in-flight futures
    gen = ordered_pool_map(lambda x: x, range(1000), 4)
    assert next(gen) == 0
    gen.close()


# -- producer bit-identity ----------------------------------------------------------


def _batches_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        for f in x.__dataclass_fields__:
            xa, ya = getattr(x, f), getattr(y, f)
            if isinstance(xa, np.ndarray):
                assert np.array_equal(xa, ya), f
            else:
                assert xa == ya, f


@pytest.mark.parametrize("fn", [epoch_batches, epoch_batches_cbow])
def test_epoch_batches_parallel_bit_identity(corpus, fn):
    _, vocab, enc = corpus
    kw = dict(pairs_per_batch=512, window=4, subsample_ratio=1e-3, seed=3,
              iteration=1, block_words=5000)  # small blocks => many slab jobs
    serial = list(fn(enc, vocab, producer_workers=1, **kw))
    parallel = list(fn(enc, vocab, producer_workers=4, **kw))
    _batches_equal(serial, parallel)


def test_epoch_batches_native_parallel_bit_identity(corpus):
    # the native backend divides its C++ thread budget across the slab pool
    # (pipeline.epoch_batches) — the stream must stay bit-identical to the
    # serial full-budget native run at any worker count
    from glint_word2vec_tpu.data.native import native_available
    if not native_available():
        pytest.skip("native generator not built")
    _, vocab, enc = corpus
    kw = dict(pairs_per_batch=512, window=4, subsample_ratio=1e-3, seed=3,
              iteration=1, block_words=5000, backend="native")
    serial = list(epoch_batches(enc, vocab, producer_workers=1, **kw))
    parallel = list(epoch_batches(enc, vocab, producer_workers=4, **kw))
    _batches_equal(serial, parallel)


def _seg_blocks(vocab, enc, workers, **cfg_kw):
    cfg = Word2VecConfig(
        vector_size=16, pairs_per_batch=512, window=3, num_iterations=1,
        seed=7, subsample_ratio=1e-3, negative_pool=128, steps_per_dispatch=2,
        producer_workers=workers, **cfg_kw)
    tr = Trainer(cfg, vocab)
    return list(tr._device_seg_blocks(enc, 1, 0))


@pytest.mark.parametrize("cfg_kw", [
    dict(device_pairgen=True),                    # plain T-boundary cut
    dict(cbow=True, cbow_update="banded"),        # ±window halo cut
], ids=["plain-cut", "halo-cut"])
def test_device_seg_blocks_parallel_bit_identity(corpus, cfg_kw):
    _, vocab, enc = corpus
    serial = _seg_blocks(vocab, enc, 1, **cfg_kw)
    parallel = _seg_blocks(vocab, enc, 4, **cfg_kw)
    assert len(serial) == len(parallel) and len(serial) > 1
    for s, p in zip(serial, parallel):
        for xa, ya in zip(s, p):
            assert np.array_equal(xa, ya)


def test_trained_params_bit_identity_across_workers(corpus):
    _, vocab, enc = corpus

    def fit(workers, device_pairgen):
        cfg = Word2VecConfig(
            vector_size=16, pairs_per_batch=512, window=3, num_iterations=1,
            seed=7, subsample_ratio=1e-3, negative_pool=128,
            steps_per_dispatch=2, prefetch_chunks=2, producer_workers=workers,
            device_pairgen=device_pairgen)
        tr = Trainer(cfg, vocab)
        tr.fit(enc)
        return np.asarray(tr.params.syn0), np.asarray(tr.params.syn1)

    for dp in (False, True):
        s0, s1 = fit(1, dp)
        p0, p1 = fit(4, dp)
        assert np.array_equal(s0, p0) and np.array_equal(s1, p1)


# -- vocab counting -----------------------------------------------------------------


def test_count_words_parallel_bit_identity(corpus):
    sents, _, _ = corpus
    serial = count_words(sents)
    parallel = count_words_parallel(sents, workers=4, slab_sentences=137)
    assert serial == parallel
    # iteration order too: the descending-count TIE-BREAK ranks equal-count
    # words by first appearance, so key order is vocabulary-identical
    assert list(serial.keys()) == list(parallel.keys())
    v1 = build_vocab(sents, min_count=2)
    v4 = build_vocab(sents, min_count=2, workers=4)
    assert v1.words == v4.words
    assert np.array_equal(v1.counts, v4.counts)


# -- alias table --------------------------------------------------------------------


def test_alias_table_exact_and_worker_independent():
    from glint_word2vec_tpu.ops.sampler import (
        build_alias_table, sampled_probabilities)
    # the last size crosses _ALIAS_PARTITION_MIN_V, so the strided-partition
    # sweep + leftover-merge path is exercised, not just the single sweep
    for V in (7, 1000, 40_000, (1 << 18) + 7):
        counts = np.maximum(1e8 / (np.arange(V) + 10.0) ** 1.07, 3.0)
        t1 = build_alias_table(counts, workers=1)
        t4 = build_alias_table(counts, workers=4)
        # deterministic per (counts, power): the worker knob must never change
        # the realized negative-sample stream
        assert np.array_equal(np.asarray(t1.prob), np.asarray(t4.prob))
        assert np.array_equal(np.asarray(t1.alias), np.asarray(t4.alias))
        # exactness: represented distribution == counts^0.75, to f32 prob res
        # (the tables store prob as float32, so the absolute error scales with
        # the largest scaled head weight)
        prob = np.asarray(t1.prob, np.float64)
        dist = prob.copy()
        np.add.at(dist, np.asarray(t1.alias), 1.0 - prob)
        target = sampled_probabilities(counts) * V
        tol = max(1e-6, 3e-7 * float(target.max()))
        assert np.abs(dist - target).max() < tol
        assert (prob >= 0).all() and (prob <= 1).all()


# -- checkpoint I/O -----------------------------------------------------------------


def _tree_files(path):
    out = {}
    for root, _, files in os.walk(path):
        for f in files:
            p = os.path.join(root, f)
            out[os.path.relpath(p, path)] = p
    return out


def _assert_same_checkpoint_bytes(a, b):
    fa, fb = _tree_files(a), _tree_files(b)
    assert set(fa) == set(fb)
    for rel in fa:
        if rel == "metadata.json":
            ma = json.load(open(fa[rel]))
            mb = json.load(open(fb[rel]))
            # the stored config legitimately records its own io_workers
            ma["config"].pop("io_workers"), mb["config"].pop("io_workers")
            assert ma == mb
        else:
            assert filecmp.cmp(fa[rel], fb[rel], shallow=False), rel


def _ckpt_fixtures(rows=500, dim=24, seed=0):
    rng = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(rows)]
    counts = rng.integers(1, 100, rows).astype(np.int64)
    syn0 = rng.standard_normal((rows, dim)).astype(np.float32)
    syn1 = rng.standard_normal((rows, dim)).astype(np.float32)
    return words, counts, syn0, syn1


def test_dense_save_parallel_bit_identity(tmp_path):
    words, counts, syn0, syn1 = _ckpt_fixtures()
    for w in (1, 4):
        ckpt.save_model(str(tmp_path / f"m{w}"), words, counts, syn0, syn1,
                        Word2VecConfig(vector_size=24, io_workers=w))
    _assert_same_checkpoint_bytes(str(tmp_path / "m1"), str(tmp_path / "m4"))
    # single-pass digests verify against a fresh re-hash
    ckpt.verify_checkpoint(str(tmp_path / "m4"), io_workers=4)
    d = ckpt.load_model(str(tmp_path / "m4"), io_workers=4)
    assert np.array_equal(d["syn0"], syn0)
    assert np.array_equal(d["syn1"], syn1)


def test_sharded_save_parallel_bit_identity(tmp_path):
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    words, counts, syn0, syn1 = _ckpt_fixtures(rows=512)
    plan = make_mesh(1, 1)
    s0 = jax.device_put(jnp.asarray(syn0), plan.embedding)
    s1 = jax.device_put(jnp.asarray(syn1), plan.embedding)
    for w in (1, 4):
        ckpt.save_model_sharded(
            str(tmp_path / f"s{w}"), words, counts, s0, s1,
            Word2VecConfig(vector_size=24, io_workers=w),
            vocab_size=512, vector_size=24)
    _assert_same_checkpoint_bytes(str(tmp_path / "s1"), str(tmp_path / "s4"))
    d1 = ckpt.load_model(str(tmp_path / "s1"), io_workers=1)
    d4 = ckpt.load_model(str(tmp_path / "s4"), io_workers=4)
    assert np.array_equal(d1["syn0"], d4["syn0"])
    assert np.array_equal(d1["syn1"], d4["syn1"])


def test_hashing_writer_digest_matches_rehash(tmp_path):
    # the single-pass digest must equal a from-scratch file hash
    arr = np.random.default_rng(0).standard_normal((100, 7))
    p = str(tmp_path / "a.npy")
    got = ckpt._save_npy_hashed(p, arr)
    assert got == ckpt._sha256_file(p)
    loaded = np.load(p)
    assert np.array_equal(loaded, arr)


def test_corrupt_checkpoint_still_detected_with_workers(tmp_path):
    words, counts, syn0, syn1 = _ckpt_fixtures()
    path = str(tmp_path / "m")
    ckpt.save_model(path, words, counts, syn0, syn1,
                    Word2VecConfig(vector_size=24, io_workers=4))
    with open(os.path.join(path, "syn0.npy"), "r+b") as f:
        f.seek(256)
        f.write(b"\xff\xff\xff\xff")
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.verify_checkpoint(path, io_workers=4)


@pytest.mark.slow
def test_large_matrix_save_load_identity(tmp_path):
    # the large-matrix variant of the round-trip (ISSUE-3 test satellite):
    # ~200 MB of matrices through the parallel writer, byte-compared
    words, counts, syn0, syn1 = _ckpt_fixtures(rows=70_000, dim=384)
    for w in (1, 4):
        ckpt.save_model(str(tmp_path / f"m{w}"), words, counts, syn0, syn1,
                        Word2VecConfig(vector_size=384, io_workers=w))
    _assert_same_checkpoint_bytes(str(tmp_path / "m1"), str(tmp_path / "m4"))
    d = ckpt.load_model(str(tmp_path / "m4"), io_workers=4)
    assert np.array_equal(d["syn0"], syn0)


# -- export -------------------------------------------------------------------------


@pytest.mark.parametrize("binary", [False, True], ids=["text", "binary"])
def test_export_parallel_byte_identity(tmp_path, binary):
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    words, counts, syn0, _ = _ckpt_fixtures()
    vocab = Vocabulary.from_words_and_counts(words, counts)
    paths = []
    for w in (1, 3):
        m = Word2VecModel(vocab, jnp.asarray(syn0),
                          config=Word2VecConfig(vector_size=24, io_workers=w))
        p = str(tmp_path / f"e{w}")
        m.export_word2vec(p, binary=binary, batch_size=64)
        paths.append(p)
        m.stop()
    assert filecmp.cmp(paths[0], paths[1], shallow=False)


# -- the scan's top-k against the tests' host ranking (tests/topk_reference.py) ------


def test_cpu_topk_matches_lax_topk():
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.ops import scan
    from topk_reference import host_topk
    words, counts, syn0, _ = _ckpt_fixtures(rows=800, dim=16)
    vocab = Vocabulary.from_words_and_counts(words, counts)
    model = Word2VecModel(vocab, jnp.asarray(syn0))
    s_ref, i_ref = host_topk(np.asarray(scan._cosine_batch(
        model._full0, model.norms, jnp.asarray(syn0[:5]), 800)), 12)
    s_dev, i_dev = scan._topk_dispatch(
        model._full0, model.norms, np.arange(5, dtype=np.int32), None, 12, 800)
    assert isinstance(s_dev, jax.Array) and isinstance(i_dev, jax.Array)
    assert np.array_equal(i_ref, np.asarray(i_dev))
    assert np.allclose(s_ref, np.asarray(s_dev), atol=1e-6)
    # and through the public API
    out = model.find_synonyms_batch(["w0", syn0[3]], 5)
    assert len(out) == 2 and len(out[0]) == 5
    model.stop()


def test_cpu_topk_tie_order_matches_lax_topk():
    # tied scores are real in this domain (duplicate rows, zero-norm rows all
    # scoring 0.0); lax.top_k breaks ties toward the LOWER index and the
    # tests' host ranking must match exactly — a plain argpartition boundary
    # does not
    from topk_reference import _cpu_topk_row
    cases = [
        (np.asarray([1.0, 1.0, 0.5, 1.0], np.float32), 2),
        (np.asarray([0.0] * 10, np.float32), 3),
        (np.asarray([0.5, -np.inf, 0.5, 0.5, -np.inf], np.float32), 4),
        (np.asarray([2.0, 1.0, 2.0, 1.0, 1.0, 1.0], np.float32), 4),
    ]
    for row, k in cases:
        s_ref, i_ref = jax.lax.top_k(jnp.asarray(row), k)
        s, i = _cpu_topk_row(row, k)
        assert np.array_equal(np.asarray(i_ref), i), (row, k, i, i_ref)
        assert np.array_equal(np.asarray(s_ref), s)
    # randomized ties: coarse-quantized scores collide constantly
    rng = np.random.default_rng(0)
    for _ in range(20):
        row = (rng.integers(0, 4, 200) / 4.0).astype(np.float32)
        k = int(rng.integers(1, 20))
        s_ref, i_ref = jax.lax.top_k(jnp.asarray(row), k)
        s, i = _cpu_topk_row(row, k)
        assert np.array_equal(np.asarray(i_ref), i)


# -- staging primitives -------------------------------------------------------------


def test_one_ahead_iter_handshake_order():
    events = []

    def gen():
        for i in range(4):
            events.append(("produce", i))
            yield i

    it = _one_ahead_iter(gen())
    for x in it:
        events.append(("consume", x))
        it.ack()
    idx = {e: i for i, e in enumerate(events)}
    for r in range(1, 4):
        # the launch-order invariant: stage r+1 strictly after round r's
        # consumption was acked
        assert idx[("produce", r)] > idx[("consume", r - 1)], events


def test_one_ahead_iter_exception_and_close():
    def boom():
        yield 1
        raise RuntimeError("boom")

    it = _one_ahead_iter(boom())
    assert next(it) == 1
    it.ack()
    with pytest.raises(RuntimeError, match="boom"):
        next(it)

    def infinite():
        i = 0
        while True:
            yield i
            i += 1

    it = _one_ahead_iter(infinite())
    assert next(it) == 0
    it.close()  # must not hang


def test_allgather_split_phase_single_process():
    from glint_word2vec_tpu.parallel.distributed import (
        allgather_fetch, allgather_start)
    tree = {"a": np.arange(6).reshape(2, 3), "b": np.float32(3.5)}
    g = allgather_fetch(allgather_start(tree))
    # process_allgather layout: leading [process_count] axis
    assert g["a"].shape == (1, 2, 3)
    assert np.array_equal(g["a"][0], tree["a"])
    assert g["b"].shape == (1,) and g["b"][0] == np.float32(3.5)


# -- hostbench smoke (the harness cannot rot) ---------------------------------------


def test_hostbench_smoke_tier():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hostbench.py"),
         "--smoke", "--workers", "2", "--repeats", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    for field in ("producer_tokens_per_sec", "ckpt_save_s", "ckpt_load_s",
                  "export_s", "vocab_build_s", "alias_build_s"):
        assert field in row and row[field] > 0, field
