"""Serving-tier tests (glint_word2vec_tpu/serve/, docs/serving.md):

- the micro-batcher: coalescing, deadline flush, bounded-queue backpressure
  (ServerOverloaded), per-request error isolation, drain-on-stop;
- the IVF ANN index: deterministic build, full-probe == exact oracle,
  recall@10 on clustered geometry, candidate-coverage expansion at tiny
  cells, zero-norm padding exclusion;
- the model's ANN entry (attach_ann + find_synonyms_batch(ann=True));
- the lease-counted serving handle: in-flight batches finish on the old
  model across a swap, buffers release exactly when leases drain;
- the assembled EmbeddingService: exact arm parity with the model, hot
  reload (explicit + watcher), schema-valid serve_* telemetry, and the
  glint_serve_* Prometheus rendering.
"""

import json
import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from glint_word2vec_tpu.data.vocab import Vocabulary, build_vocab
from glint_word2vec_tpu.models.word2vec import Word2VecModel
from glint_word2vec_tpu.obs.schema import validate_file, validate_record
from glint_word2vec_tpu.obs.statusd import serve_prometheus_text
from glint_word2vec_tpu.serve import (
    BatchingScheduler,
    EmbeddingService,
    ServerOverloaded,
    ServiceClosed,
    ServingHandle,
    build_ivf,
    decorrelated_jitter,
    load_with_retry,
)


def clustered_matrix(v=3000, d=32, clusters=40, seed=0, noise=0.35):
    """The serving bench's synthetic geometry: tight unit-centroid cells
    (trained embeddings are clustered — the eval ladder measures topic
    purity ~1.0 on healthy runs)."""
    rng = np.random.default_rng(seed)
    cents = rng.standard_normal((clusters, d)).astype(np.float32)
    cents /= np.linalg.norm(cents, axis=1, keepdims=True)
    return (cents[rng.integers(0, clusters, v)]
            + noise * rng.standard_normal((v, d)).astype(np.float32)
            / np.sqrt(d))


def make_model(v=3000, d=32, seed=0):
    m = clustered_matrix(v, d, seed=seed)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(v)], np.ones(v, np.int64))
    return Word2VecModel(vocab, jnp.asarray(m))


# -- batcher ---------------------------------------------------------------------------


def test_batcher_coalesces_concurrent_submits():
    sizes = []

    def handler(batch):
        sizes.append(len(batch))
        time.sleep(0.005)  # hold the worker so submitters pile up
        return [x * 2 for x in batch]

    b = BatchingScheduler(handler, max_batch=16, max_delay_ms=5.0,
                          max_queue=128).start()
    try:
        results = {}

        def client(i):
            results[i] = b.submit(i)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(48)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {i: i * 2 for i in range(48)}
        assert sum(sizes) == 48
        assert max(sizes) > 1, f"no coalescing happened ({sizes})"
        st = b.stats()
        assert st["submitted"] == st["completed"] == 48
        assert st["errors"] == st["refused"] == 0
        assert st["batches"] == len(sizes)
        assert st["latency_ms"]["n"] == 48
    finally:
        b.stop()


def test_batcher_deadline_flushes_lone_request():
    b = BatchingScheduler(lambda batch: [len(batch)], max_batch=1024,
                          max_delay_ms=20.0, max_queue=8).start()
    try:
        t0 = time.monotonic()
        assert b.submit("x") == 1  # a lone request must not wait forever
        assert time.monotonic() - t0 < 5.0
    finally:
        b.stop()


def test_batcher_backpressure_refuses_fast():
    gate = threading.Event()

    def handler(batch):
        gate.wait(30)
        return batch

    b = BatchingScheduler(handler, max_batch=1, max_delay_ms=0.0,
                          max_queue=4).start()
    try:
        threads = []
        # 1 in-flight inside the handler + 4 filling the queue
        for i in range(5):
            t = threading.Thread(target=lambda: b.submit(1))
            t.start()
            threads.append(t)
        deadline = time.monotonic() + 5
        while b.stats()["queue_depth"] < 4 and time.monotonic() < deadline:
            time.sleep(0.005)
        t0 = time.monotonic()
        with pytest.raises(ServerOverloaded):
            b.submit(2)
        assert time.monotonic() - t0 < 1.0, "refusal was not fast"
        assert b.stats()["refused"] == 1
        gate.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        gate.set()
        b.stop()


def test_batcher_per_request_errors_do_not_fail_the_batch():
    def handler(batch):
        return [ValueError(f"bad {x}") if x < 0 else x for x in batch]

    b = BatchingScheduler(handler, max_batch=8, max_delay_ms=2.0,
                          max_queue=32).start()
    try:
        assert b.submit(7) == 7
        with pytest.raises(ValueError, match="bad -3"):
            b.submit(-3)
        assert b.submit(9) == 9
        st = b.stats()
        assert st["errors"] == 1 and st["completed"] == 2
    finally:
        b.stop()


def test_batcher_handler_exception_reaches_every_caller():
    def handler(batch):
        raise RuntimeError("kaboom")

    b = BatchingScheduler(handler, max_batch=4, max_delay_ms=1.0,
                          max_queue=8).start()
    try:
        with pytest.raises(RuntimeError, match="kaboom"):
            b.submit(1)
    finally:
        b.stop()
    with pytest.raises(RuntimeError):
        b.submit(2)  # stopped scheduler refuses new work


def test_batcher_submit_during_and_after_shutdown_raises_typed():
    """ISSUE-12 satellite: a submit racing stop() gets the typed
    ServiceClosed (subclassing RuntimeError for old callers), during the
    drain AND after it — never whatever the dead worker queue produces."""
    gate = threading.Event()

    def handler(batch):
        gate.wait(30)
        return batch

    b = BatchingScheduler(handler, max_batch=1, max_delay_ms=0.0,
                          max_queue=8).start()
    admitted = b.submit_async(1)  # in flight when stop() lands
    stopper = threading.Thread(target=b.stop)
    stopper.start()
    try:
        deadline = time.monotonic() + 5
        while not b._stopping and time.monotonic() < deadline:
            time.sleep(0.005)
        # submit DURING shutdown (worker still draining the admitted one)
        with pytest.raises(ServiceClosed):
            b.submit(2)
        gate.set()
        stopper.join(timeout=30)
        # submit AFTER shutdown
        with pytest.raises(ServiceClosed):
            b.submit(3)
        # the admitted request was still served (drain-and-stop contract)
        assert b.wait(admitted, timeout=5) == 1
    finally:
        gate.set()
        stopper.join(timeout=5)


def test_overload_carries_retry_after_hint():
    """ISSUE-12 satellite: ServerOverloaded carries retry_after_s = queued
    batches x the observed (EWMA) batch service time — None before the
    first batch ever completed (no honest estimate exists yet)."""
    gate = threading.Event()
    first_done = threading.Event()

    def handler(batch):
        if first_done.is_set():
            gate.wait(30)
        else:
            time.sleep(0.05)  # a measured first batch: EWMA ~= 50 ms
            first_done.set()
        return batch

    b = BatchingScheduler(handler, max_batch=1, max_delay_ms=0.0,
                          max_queue=2).start()
    try:
        assert b.submit(0) == 0  # establishes the EWMA
        assert abs(b.stats()["batch_service_s"] - 0.05) < 0.04
        threads = [threading.Thread(target=lambda: b.submit(1))
                   for _ in range(3)]  # 1 in handler + 2 filling the queue
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5
        while b.stats()["queue_depth"] < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(ServerOverloaded) as ei:
            b.submit(9)
        hint = ei.value.retry_after_s
        assert hint is not None and hint > 0, \
            "refusal after a measured batch must carry the drain hint"
        # 2 queued batches x ~50 ms EWMA, loose upper bound for CI noise
        assert hint < 2.0, f"hint implausibly large: {hint}"
        gate.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        gate.set()
        b.stop()


def test_overload_hint_is_none_before_first_batch():
    gate = threading.Event()
    b = BatchingScheduler(lambda batch: gate.wait(30) or batch,
                          max_batch=1, max_delay_ms=0.0, max_queue=1).start()
    try:
        t = threading.Thread(target=lambda: b.submit(1))
        t.start()
        deadline = time.monotonic() + 5

        def taken():        # the worker holds the first ticket at the gate
            s = b.stats()
            return s["submitted"] == 1 and s["queue_depth"] == 0

        # only then the second: sent at once it can find the first still
        # queued and be refused itself, and nothing would fill the queue
        while not taken() and time.monotonic() < deadline:
            time.sleep(0.005)
        t2 = threading.Thread(target=lambda: b.submit(2))
        t2.start()
        while b.stats()["queue_depth"] < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(ServerOverloaded) as ei:
            b.submit(3)
        assert ei.value.retry_after_s is None  # no measured batch yet
        gate.set()
        t.join(timeout=30)
        t2.join(timeout=30)
    finally:
        gate.set()
        b.stop()


# -- decorrelated-jitter backoff (ISSUE-12 satellite) ----------------------------------


def test_decorrelated_jitter_seeded_sequence():
    """Same seed -> the exact same delay sequence; different seeds ->
    decorrelated sequences (the anti-thundering-herd property N fleet
    replicas retrying one publish path rely on); every delay in
    [base, cap]."""
    a_gen = decorrelated_jitter(0.25, 2.0, np.random.default_rng(3))
    a = [next(a_gen) for _ in range(6)]
    b_gen = decorrelated_jitter(0.25, 2.0, np.random.default_rng(3))
    b = [next(b_gen) for _ in range(6)]
    assert a == b, "seeded jitter must be reproducible"
    c_gen = decorrelated_jitter(0.25, 2.0, np.random.default_rng(4))
    c = [next(c_gen) for _ in range(6)]
    assert a != c, "different seeds must decorrelate"
    for d in a + c:
        assert 0.25 <= d <= 2.0
    assert len(set(a)) > 1, "fixed-interval retry is the bug this removes"


def test_load_with_retry_backoff_uses_seeded_jitter(tmp_path, monkeypatch):
    """The retry loop's sleeps are exactly the decorrelated-jitter
    sequence of the rng passed in (unit-tested with a seeded RNG, per the
    ISSUE) — not the old synchronized fixed interval."""
    slept = []
    monkeypatch.setattr(
        "glint_word2vec_tpu.serve.reload.time.sleep", slept.append)
    with pytest.raises(FileNotFoundError):
        load_with_retry(str(tmp_path / "never-published"), attempts=5,
                        delay=0.25, max_delay=2.0,
                        rng=np.random.default_rng(11))
    want_gen = decorrelated_jitter(0.25, 2.0, np.random.default_rng(11))
    want = [next(want_gen) for _ in range(4)]  # attempts-1 sleeps
    assert slept == want
    assert len(set(slept)) > 1


# -- ANN index -------------------------------------------------------------------------


def test_ivf_build_is_deterministic():
    m = clustered_matrix()
    a = build_ivf(m, seed=3, measure_recall=False)
    b = build_ivf(m, seed=3, measure_recall=False)
    np.testing.assert_array_equal(a._centroids, b._centroids)
    np.testing.assert_array_equal(a._ids, b._ids)
    c = build_ivf(m, seed=4, measure_recall=False)
    assert not np.array_equal(a._centroids, c._centroids)


def test_ivf_full_probe_matches_exact_oracle():
    m = clustered_matrix(v=800, d=16)
    idx = build_ivf(m, seed=0, measure_recall=False)
    normed = m / np.maximum(
        np.linalg.norm(m, axis=1, keepdims=True), 1e-12)
    q = normed[:8]
    s, ids = idx.search(q, 5, nprobe=idx.num_centroids)
    exact = q @ normed.T
    for r in range(8):
        want = np.argsort(-exact[r], kind="stable")[:5]
        assert set(ids[r]) == set(want), "full probe must equal exact scan"


def test_ivf_recall_on_clustered_geometry():
    idx = build_ivf(clustered_matrix(v=5000, d=32), seed=0)
    assert idx.stats["recall_at_10"] >= 0.95  # the serving acceptance bar
    # recall is monotone toward 1.0 as nprobe grows to C
    probes = np.arange(64)
    full = idx.measure_recall(probes, k=10, nprobe=idx.num_centroids)
    assert full == 1.0


def test_ivf_small_cells_still_fill_topk():
    """The serve-reload chaos finding: at toy vocab the nprobe budget can
    land on cells with fewer than k rows — probing must expand until the
    candidate pool covers k, never return a short result."""
    m = clustered_matrix(v=30, d=8, clusters=5)
    idx = build_ivf(m, seed=0, measure_recall=False)
    s, ids = idx.search(m[:4], 6, nprobe=1)
    assert (ids >= 0).all(), f"short result at tiny cells: {ids}"


def test_ivf_zero_norm_rows_never_surface():
    m = clustered_matrix(v=200, d=16)
    m[50] = 0.0  # a sharding-padding-style zero row
    idx = build_ivf(m, seed=0, measure_recall=False)
    _, ids = idx.search(m[:16], 10, nprobe=idx.num_centroids)
    assert 50 not in set(ids.ravel().tolist())


# -- model ANN entry -------------------------------------------------------------------


def test_model_ann_routing_and_parity():
    model = make_model()
    with pytest.raises(RuntimeError, match="no index attached"):
        model.find_synonyms_batch(["w0"], 5, ann=True)
    index = build_ivf(np.asarray(model.syn0), seed=0)
    model.attach_ann(index)
    assert model.ann is index
    exact = model.find_synonyms_batch(["w0", "w7"], 8)
    ann_full = model.find_synonyms_batch(
        ["w0", "w7"], 8, ann=True, nprobe=index.num_centroids)
    # full probe: identical neighbors, identical self-exclusion semantics
    assert [[w for w, _ in row] for row in ann_full] == \
           [[w for w, _ in row] for row in exact]
    for row_a, row_e in zip(ann_full, exact):
        np.testing.assert_allclose([s for _, s in row_a],
                                   [s for _, s in row_e], rtol=1e-5)
    ann = model.find_synonyms_batch(["w0"], 10, ann=True)
    assert len(ann[0]) == 10 and "w0" not in [w for w, _ in ann[0]]
    model.stop()
    assert model.ann is None


# -- serving handle --------------------------------------------------------------------


def test_handle_swap_drains_leases_before_release():
    old, new = make_model(v=100, d=8, seed=1), make_model(v=100, d=8, seed=2)
    h = ServingHandle(old)
    with h.lease() as (m, _):
        assert m is old
        h.swap(new)
        # the in-flight lease still serves the OLD model, un-released
        assert m.num_words == 100 and not m._stopped
        assert h.models_released == 0
        with h.lease() as (m2, _):
            assert m2 is new  # future leases see the new generation
    # lease drained -> old released exactly once
    assert h.models_released == 1 and old._stopped and not new._stopped
    h.stop()
    assert new._stopped and h.models_released == 2
    with pytest.raises(RuntimeError):
        with h.lease():
            pass


# -- the assembled service -------------------------------------------------------------


def _train_tiny(tmp_path, seed=9, n=120):
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.train.trainer import Trainer
    rng = np.random.default_rng(seed)
    sents = [[f"w{j}" for j in rng.integers(0, 40, 12)] for _ in range(n)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=128,
                         num_iterations=1, window=2, negatives=3,
                         negative_pool=8, steps_per_dispatch=2, seed=seed)
    trainer = Trainer(cfg, vocab)
    trainer.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    ck = str(tmp_path / "model")
    trainer.save_checkpoint(ck)
    return trainer, vocab, ck, sents


def test_service_exact_arm_matches_model(tmp_path):
    trainer, vocab, ck, _ = _train_tiny(tmp_path)
    local = Word2VecModel.load(ck)
    want = local.find_synonyms("w0", 5)
    svc = EmbeddingService(checkpoint=ck, ann=False)
    try:
        got = svc.synonyms("w0", 5)
        assert [w for w, _ in got] == [w for w, _ in want]
        np.testing.assert_allclose([s for _, s in got],
                                   [s for _, s in want], rtol=1e-5)
        np.testing.assert_allclose(svc.vector("w1"), local.transform("w1"),
                                   rtol=1e-6)
        batch = svc.synonyms_batch(["w0", "w1", "w2"], 5)
        assert len(batch) == 3 and all(len(r) == 5 for r in batch)
        with pytest.raises(KeyError, match="not in vocabulary"):
            svc.synonyms("nope", 5)
        info = svc.info()
        assert info["num_words"] == vocab.size and info["finished"]
    finally:
        svc.close()
    local.stop()


def test_service_reload_and_telemetry(tmp_path):
    trainer, vocab, ck, sents = _train_tiny(tmp_path)
    log = str(tmp_path / "serve.jsonl")
    svc = EmbeddingService(checkpoint=ck, ann=True, telemetry_path=log)
    try:
        r1 = svc.synonyms("w0", 5)
        assert len(r1) == 5
        # the trainer publishes a newer checkpoint; explicit reload swaps
        from glint_word2vec_tpu.data.pipeline import encode_sentences
        trainer.fit(encode_sentences(sents, vocab, 1000))
        trainer.save_checkpoint(ck)
        model = svc.reload_now()
        assert model.num_words == vocab.size
        assert svc.stats()["reloads"] == 1
        assert svc.stats()["models_released"] == 1  # old buffers gone
        assert len(svc.synonyms("w0", 5)) == 5
        svc.emit_stats()
    finally:
        svc.close()
    summary = validate_file(log)
    assert summary["ok"], summary["errors"][:3]
    kinds = summary["kinds"]
    assert kinds.get("serve_start") == 1
    assert kinds.get("serve_reload") == 1
    assert kinds.get("serve_stats") == 1
    assert kinds.get("serve_end") == 1
    with open(log) as f:
        recs = [json.loads(line) for line in f]
    start = next(r for r in recs if r["kind"] == "serve_start")
    assert start["ann"]["centroids"] >= 1  # index stats ride the record


def test_service_watcher_hot_reloads(tmp_path):
    trainer, vocab, ck, sents = _train_tiny(tmp_path, seed=11)
    svc = EmbeddingService(checkpoint=ck, ann=True, watch=True,
                           reload_poll_s=0.05)
    try:
        from glint_word2vec_tpu.data.pipeline import encode_sentences
        trainer.fit(encode_sentences(sents, vocab, 1000))
        trainer.save_checkpoint(ck)  # the publish signal
        deadline = time.monotonic() + 10
        while svc.stats()["reloads"] < 1 and time.monotonic() < deadline:
            assert len(svc.synonyms("w0", 5)) == 5  # serving never stops
            time.sleep(0.02)
        assert svc.stats()["reloads"] >= 1, "watcher never saw the publish"
        assert svc.stats()["models_released"] >= 1
    finally:
        svc.close()


def test_watcher_sees_publish_landing_during_boot_load(tmp_path, monkeypatch):
    """Review finding: the publish signature must be captured BEFORE the
    (slow) initial load + index build — a trainer publish landing inside
    that window must still fire the watcher, not be recorded as served."""
    trainer, vocab, ck, sents = _train_tiny(tmp_path, seed=13)
    import glint_word2vec_tpu.serve.service as service_mod
    real_load = service_mod.load_with_retry

    def slow_load_with_publish(path, plan=None, **kw):
        model = real_load(path, plan=plan, **kw)
        # the trainer publishes AGAIN while the boot load is in flight
        trainer.save_checkpoint(ck)
        return model

    monkeypatch.setattr(service_mod, "load_with_retry",
                        slow_load_with_publish)
    svc = EmbeddingService(checkpoint=ck, ann=False, watch=True,
                           reload_poll_s=0.05)
    monkeypatch.setattr(service_mod, "load_with_retry", real_load)
    try:
        deadline = time.monotonic() + 10
        while svc.stats()["reloads"] < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.stats()["reloads"] >= 1, \
            "publish during the boot load was swallowed"
    finally:
        svc.close()


def test_watcher_survives_delete_then_recreate(tmp_path):
    """ISSUE-12 satellite: the publish path deleted mid-watch (operator
    mistake, retention sweep) must not crash or wedge the watcher — the
    ABSENT state is not a signal, the current model keeps serving, and a
    later re-publish at the same path fires a normal reload."""
    trainer, vocab, ck, sents = _train_tiny(tmp_path, seed=17)
    svc = EmbeddingService(checkpoint=ck, ann=False, watch=True,
                           reload_poll_s=0.05)
    try:
        assert len(svc.synonyms("w0", 5)) == 5
        shutil.rmtree(ck)  # the publish path vanishes mid-watch
        time.sleep(0.3)  # several polls over the absent path
        assert len(svc.synonyms("w0", 5)) == 5  # still serving, no crash
        assert svc.stats()["reloads"] == 0
        trainer.save_checkpoint(ck)  # recreated: a fresh publish identity
        deadline = time.monotonic() + 15
        while svc.stats()["reloads"] < 1 and time.monotonic() < deadline:
            assert len(svc.synonyms("w0", 5)) == 5
            time.sleep(0.02)
        assert svc.stats()["reloads"] >= 1, \
            "recreated publish path never fired the watcher"
        assert len(svc.synonyms("w0", 5)) == 5
    finally:
        svc.close()


def test_watcher_survives_torn_publish_metadata_before_arrays(tmp_path):
    """ISSUE-12 satellite: metadata.json appearing BEFORE its arrays (the
    torn-publish window a non-atomic copy/rsync produces) must end in a
    served model, never a crash — the watcher fires on the metadata
    identity, load_with_retry absorbs the missing-arrays window, and a
    failed round leaves the old model serving with the next poll
    retrying."""
    trainer, vocab, ck, sents = _train_tiny(tmp_path, seed=19)
    staging = str(tmp_path / "staged")
    shutil.copytree(ck, staging)  # a complete publish to tear apart
    svc = EmbeddingService(checkpoint=ck, ann=False, watch=True,
                           reload_poll_s=0.05)
    try:
        assert len(svc.synonyms("w0", 5)) == 5
        # the torn window: re-publish metadata/words/counts, arrays ABSENT
        shutil.rmtree(ck)
        os.makedirs(ck)
        for f in ("metadata.json", "words", "counts.npy"):
            shutil.copy2(os.path.join(staging, f), os.path.join(ck, f))
        time.sleep(0.4)  # the watcher fires into the torn window
        assert len(svc.synonyms("w0", 5)) == 5  # old model still serving
        # the arrays land; the in-flight retry (or the next poll) heals
        for f in ("syn0.npy", "syn1.npy"):
            shutil.copy2(os.path.join(staging, f), os.path.join(ck, f))
        deadline = time.monotonic() + 30
        while svc.stats()["reloads"] < 1 and time.monotonic() < deadline:
            assert len(svc.synonyms("w0", 5)) == 5
            time.sleep(0.02)
        assert svc.stats()["reloads"] >= 1, \
            "torn publish never healed into a served model"
        assert len(svc.synonyms("w0", 5)) == 5
    finally:
        svc.close()


def test_stats_carry_served_publish_generation(tmp_path):
    """The fleet staleness channel: stats()['publish_sig'] is the served
    publish identity — None for in-memory models, refreshed by reload."""
    trainer, vocab, ck, sents = _train_tiny(tmp_path, seed=23)
    svc = EmbeddingService(checkpoint=ck, ann=False)
    try:
        sig0 = svc.stats()["publish_sig"]
        assert sig0, "checkpoint-backed service must report its generation"
        trainer.save_checkpoint(ck)
        svc.reload_now()
        sig1 = svc.stats()["publish_sig"]
        assert sig1 and sig1 != sig0, "reload must advance the generation"
    finally:
        svc.close()
    mem = EmbeddingService(model=make_model(v=50, d=8), ann=False)
    try:
        assert mem.stats()["publish_sig"] is None
    finally:
        mem.close()


def test_failed_init_does_not_leak_threads_or_model():
    """Review finding: a failed __init__ (here: status port already bound)
    must stop the already-started batcher thread and leave a caller-owned
    model untouched."""
    import socket
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    model = make_model(v=100, d=8)
    try:
        with pytest.raises(OSError):
            EmbeddingService(model=model, ann=False, status_port=port)
        deadline = time.monotonic() + 5
        while (any(t.name == "glint-serve-batcher"
                   for t in threading.enumerate())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert not any(t.name == "glint-serve-batcher"
                       for t in threading.enumerate()), \
            "batcher thread leaked past the failed init"
        assert not model._stopped  # caller-owned model stays alive
        # the pure-validation errors raise before ANY resource exists
        with pytest.raises(ValueError, match="watch=True needs"):
            EmbeddingService(model=model, watch=True)
    finally:
        blocker.close()
        model.stop()


def test_serve_record_kinds_validate():
    base = {"schema": 1, "t": 0.0}
    ok = [
        {**base, "kind": "serve_start", "checkpoint": "/ck",
         "vocab_size": 10, "vector_size": 4, "ann": {"centroids": 2}},
        {**base, "kind": "serve_reload", "vocab_size": 10, "reloads": 1,
         "load_seconds": 0.5},
        {**base, "kind": "serve_stats", "submitted": 5, "refused": 0,
         "batches": 2, "queue_depth": 0, "reloads": 1,
         "latency_ms": {"p50": 1.0}, "occupancy_mean": 2.5},
        {**base, "kind": "serve_end", "submitted": 5, "refused": 0,
         "reloads": 1},
    ]
    for rec in ok:
        assert validate_record(rec) == [], rec["kind"]
    bad = {**base, "kind": "serve_stats", "submitted": 5}
    assert validate_record(bad), "missing required fields must fail"
    wrong = {**base, "kind": "serve_start", "checkpoint": "/ck",
             "vocab_size": 10, "vector_size": 4, "ann": "not-a-dict"}
    assert validate_record(wrong), "optional field with wrong type must fail"


def test_serve_prometheus_rendering():
    snap = {"status": "serving", "submitted": 12, "refused": 1,
            "completed": 11, "errors": 0, "batches": 4, "queue_depth": 2,
            "occupancy_mean": 3.0, "reloads": 2, "models_released": 2,
            "vocab_size": 1000, "load_seconds": 0.4,
            "latency_ms": {"p50": 1.5, "p95": 3.0, "p99": 4.5, "n": 11},
            "ann": {"recall_at_10": 0.99, "nprobe": 8, "centroids": 64,
                    "build_seconds": 0.2}}
    text = serve_prometheus_text(snap)
    for needle in ("glint_serve_up 1", "glint_serve_submitted_total 12",
                   "glint_serve_refused_total 1",
                   "glint_serve_queue_depth 2",
                   'glint_serve_latency_ms{quantile="p99"} 4.5',
                   "glint_serve_ann_recall_at_10 0.99",
                   "glint_serve_reloads_total 2"):
        assert needle in text, f"{needle!r} missing from:\n{text}"
    assert "glint_serve_up 0" in serve_prometheus_text({"status": "closed"})


# -- the serve spans (obs/spans.py; docs/observability.md §4) --------------------------

_SERVE_TABLE = {"serve.coalesce", "serve.batch", "serve.dispatch",
                "serve.row_fetch", "serve.scan_enqueue", "serve.result_fetch",
                "serve.reply_build", "serve.queue_wait"}


@pytest.fixture
def serve_tracer():
    """The process-wide tracer, telemetry off and empty, and left so."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
    yield tracer
    tracer.configure(enabled=False)
    tracer.clear()


def _ask(svc, n=6):
    """``n`` concurrent callers of one query each, all answered."""
    out = {}

    def call(i):
        out[i] = svc.synonyms(f"w{i}", 5)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads) and len(out) == n
    return out


def test_service_records_every_serve_span_under_a_live_trace(
        tmp_path, serve_tracer):
    import jax.profiler as jp
    model = make_model(v=400, d=16)
    svc = EmbeddingService(model=model, ann=False, max_delay_ms=20.0)
    try:
        _ask(svc)                       # warm: compiles outside the trace
        assert serve_tracer.events() == []
        jp.start_trace(str(tmp_path))
        try:
            _ask(svc)
            time.sleep(0.05)            # the worker's post-batch records
        finally:
            jp.stop_trace()
    finally:
        svc.close()
        model.stop()
    evs = serve_tracer.events()
    by_id = {e["id"]: e for e in evs}
    assert {e["name"] for e in evs} >= _SERVE_TABLE
    # parents: by the worker's stack in a batch's first half, by id in the
    # half the completer runs, and across threads for the tickets
    parent_name = {e["name"]: by_id[e["parent"]]["name"]
                   for e in evs if e["parent"] in by_id}
    assert parent_name["serve.dispatch"] == "serve.batch"
    for leaf in ("serve.row_fetch", "serve.scan_enqueue",
                 "serve.result_fetch", "serve.reply_build"):
        assert parent_name[leaf] == "serve.dispatch"
    # every span of the table once per batch, each child under ITS batch's
    # dispatch; the worker records what ends on it, the completer the rest
    dispatch_of = {e["id"]: e["parent"] for e in evs if e["name"] == "serve.dispatch"}
    for name, thread in (("serve.dispatch", "glint-serve-batcher-completer"),
                         ("serve.row_fetch", "glint-serve-batcher"),
                         ("serve.scan_enqueue", "glint-serve-batcher"),
                         ("serve.result_fetch", "glint-serve-batcher-completer"),
                         ("serve.reply_build", "glint-serve-batcher-completer")):
        mine = [e for e in evs if e["name"] == name]
        assert {e["thread"] for e in mine} == {thread}
        of_batch = sorted(dispatch_of.get(e["parent"], e["parent"]) for e in mine)
        assert of_batch == sorted(e["id"] for e in evs if e["name"] == "serve.batch")
    for e in evs:
        if e["name"] == "serve.batch":
            assert e["thread"] == "glint-serve-batcher-completer"
            assert e["args"]["inflight"] in (0, 1)
    waits = [e for e in evs if e["name"] == "serve.queue_wait"]
    batches = {e["id"]: e for e in evs if e["name"] == "serve.batch"}
    assert len(waits) == 6 == sum(b["args"]["size"] for b in batches.values())
    assert len({w["args"]["request"] for w in waits}) == 6
    for w in waits:
        # a ticket waits until ITS batch closes: the wait ends where the
        # batch that names it as parent starts, on the one clock
        batch = batches[w["parent"]]
        assert w["ts_s"] + w["dur_s"] == pytest.approx(batch["ts_s"], abs=1e-6)
    # counts ride as args, where the work happens
    for e in evs:
        if e["name"] == "serve.row_fetch":
            # word queries: the id array alone, whatever the batch's size
            assert e["args"]["ops"] == 1
        if e["name"] == "serve.scan_enqueue":
            assert e["args"]["queries"] >= 1


def _row_fetch_ops(tracer, model, queries):
    tracer.clear()
    model.find_synonyms_batch(queries, 5)
    return [e["args"]["ops"] for e in tracer.events()
            if e["name"] == "serve.row_fetch"]


@pytest.mark.parametrize("kind,ops", [("words", 1), ("mixed", 2)])
def test_row_fetch_ops_do_not_grow_with_the_batch(serve_tracer, kind, ops):
    """No device operation per query: the id array is one put, a vector
    block a second, for a batch of 2 as for a batch of 40."""
    model = make_model(v=400, d=16)
    vec = np.asarray(model.syn0[7])
    serve_tracer.configure(enabled=True)

    def batch(n):
        return ([f"w{i}" for i in range(n - 1)]
                + [vec if kind == "mixed" else "w0"])

    assert (_row_fetch_ops(serve_tracer, model, batch(2))
            == _row_fetch_ops(serve_tracer, model, batch(40)) == [ops])
    model.stop()


@pytest.mark.parametrize("v,topk_rows", [
    (400, 400),                          # one lax.top_k over every row
    (3000, -(-3000 // 128) + 6 * 128)])          # 24 run maxima, 6 runs' members
def test_scan_enqueue_carries_topk_rows(serve_tracer, v, topk_rows):
    """The scores one query's selection ranks, as the scan's program was
    traced: all of a tiny vocabulary, G + k·b over the threshold."""
    model = make_model(v=v, d=16)
    serve_tracer.configure(enabled=True)
    model.find_synonyms_batch(["w1", "w2", "w1"], 5)
    assert [e["args"] for e in serve_tracer.events()
            if e["name"] == "serve.scan_enqueue"] == [
                dict(queries=3, topk_rows=topk_rows, shards=1, merge_rows=0)]
    model.stop()


@pytest.mark.parametrize("mesh", [(1, 4), (2, 2)], ids=lambda m: f"{m[0]}x{m[1]}")
def test_service_over_a_mesh_answers_as_one_device_does(serve_tracer, mesh):
    """``EmbeddingService(model=...)`` over a table partitioned by rows: the
    batcher, the lease and the two halves as they are; concurrent callers'
    replies are the one-device model's; ``serve.scan_enqueue`` says what the
    program ran over (the per-shard ``topk_rows``, ``shards``,
    ``merge_rows``) and ``serve.row_fetch`` issued one put a batch."""
    from concurrent.futures import ThreadPoolExecutor

    from glint_word2vec_tpu.ops.scan import _topk_rows
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one = make_model(v=3001, d=16)
    sharded = Word2VecModel(one.vocab, np.asarray(one.syn0), plan=make_mesh(*mesh))
    words = [f"w{i}" for i in (0, 1, 750, 751, 1502, 2253, 3000, 3000, 17)]
    want = [one.find_synonyms(w, 5) for w in words]
    svc = EmbeddingService(model=sharded, ann=False)
    try:
        serve_tracer.configure(enabled=True)
        with ThreadPoolExecutor(len(words)) as pool:
            got = list(pool.map(lambda w: svc.synonyms(w, 5), words))
        serve_tracer.configure(enabled=False)
        stats = svc.stats()
    finally:
        svc.close()
    for g, w in zip(got, want):
        assert [x for x, _ in g] == [x for x, _ in w]
        np.testing.assert_allclose([s for _, s in g], [s for _, s in w],
                                   rtol=0, atol=1e-6)
    assert stats["completed"] == len(words) and stats["batches"] < len(words)
    events = serve_tracer.events()
    scans = [e["args"] for e in events if e["name"] == "serve.scan_enqueue"]
    shards, rows = mesh[1], 3008 // mesh[1]
    assert scans and all(
        (a["shards"], a["topk_rows"], a["merge_rows"])
        == (shards, _topk_rows(rows, 6), shards * 6) for a in scans)
    assert {e["args"]["ops"] for e in events if e["name"] == "serve.row_fetch"} == {1}
    one.stop()
    sharded.stop()


_COMPILED = []      # every backend compile of this process, by function


def _on_compile(name, seconds, **kw):
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILED.append(kw.get("fun_name"))


@pytest.mark.parametrize("size", [1, 2, 3, 4])
def test_one_program_per_all_word_batch_size(size):
    """A size's first all-word batch compiles the scan-with-gather and
    nothing else (no stack, no row read); its second compiles nothing."""
    import jax.monitoring
    from glint_word2vec_tpu.ops.scan import _gather_topk_batch
    if not _COMPILED:       # jax.monitoring has no public unregister
        _COMPILED.append("listening")
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
    # a vocabulary of its own, so no other test has compiled these shapes
    model = make_model(v=417 + size, d=16)
    model.norms
    cached, mark = _gather_topk_batch._cache_size(), len(_COMPILED)
    words = [f"w{i}" for i in range(size)]
    first = model.find_synonyms_batch(words, 5)
    assert _COMPILED[mark:] == ["jit(_gather_topk_batch)"]
    assert model.find_synonyms_batch(words, 5) == first
    assert len(_COMPILED) == mark + 1
    assert _gather_topk_batch._cache_size() == cached + 1
    model.stop()


def test_service_records_no_span_with_tracing_off(serve_tracer, monkeypatch):
    from glint_word2vec_tpu.obs import spans
    built = []
    real = spans.TraceAnnotation

    class Counting(real):
        def __init__(self, name):
            built.append(name)
            super().__init__(name)

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    model = make_model(v=400, d=16)
    svc = EmbeddingService(model=model, ann=False)
    try:
        _ask(svc)
        assert svc.stats()["completed"] == 6
    finally:
        svc.close()
        model.stop()
    assert serve_tracer.events() == [] and built == []


def test_ann_arm_spans(serve_tracer):
    model = make_model(v=600, d=16)
    model.attach_ann(build_ivf(np.asarray(model.syn0), 16))
    serve_tracer.configure(enabled=True)
    got = model.find_synonyms_batch(["w1", "w2"], 5, ann=True)
    assert len(got) == 2 and all(len(r) == 5 for r in got)
    evs = serve_tracer.events()
    assert [e["name"] for e in evs] == [
        "serve.row_fetch", "serve.ann_search", "serve.reply_build"]
    assert evs[0]["args"] == {"ops": 0} and evs[1]["args"] == {"queries": 2}
    model.stop()


def test_batcher_hooks_are_fed_the_batch_spans_times(serve_tracer):
    """One pair of clock reads per batch: the observer's service time and
    the trace hook's records come from the ``serve.batch`` span, recorded or
    not, and agree with each other to the nanosecond."""
    seen, spans_out = [], []

    def handler(batch):
        time.sleep(0.01)
        return batch

    b = BatchingScheduler(
        handler, max_batch=4, max_delay_ms=1.0,
        span_emit=lambda tr, name, t0, dur: spans_out.append((name, t0, dur)),
        batch_observer=lambda n, service, wait: seen.append(
            (n, service, wait))).start()
    try:
        t = b.submit_async(1, trace={"tid": "t1", "ps": "s1"})
        assert b.wait(t, 30) == 1
    finally:
        b.stop()
    (n, service_s, wait_s), = seen
    assert n == 1 and service_s >= 0.01 and wait_s >= 0.0
    by = {name: (t0, dur) for name, t0, dur in spans_out}
    assert set(by) == {"queue_wait", "batch_service"}
    assert by["batch_service"][1] == int(service_s * 1e9)
    assert by["queue_wait"][0] + by["queue_wait"][1] == by["batch_service"][0]
    assert serve_tracer.events() == []      # telemetry off, no live trace


# -- the handler's two halves, overlapped (serve/batcher.py; docs/serving.md §1) -------


class _Halves:
    """A two-half handler that records its calls; ``hold`` maps a payload to
    an event its batch's finish waits for."""

    def __init__(self, hold=None, fail_begin=(), fail_finish=()):
        self.hold = hold or {}
        self.fail_begin, self.fail_finish = set(fail_begin), set(fail_finish)
        self.log = []                   # ("begin" | "finish" | "finished", batch)
        self.begun = {}                 # payload -> event set once its begin ran
        self._lock = threading.Lock()

    def seen(self, payload):
        with self._lock:
            return self.begun.setdefault(payload, threading.Event())

    def begin(self, batch):
        with self._lock:
            self.log.append(("begin", tuple(batch)))
        for x in batch:
            self.seen(x).set()
        if self.fail_begin & set(batch):
            raise RuntimeError(f"begin failed on {batch}")
        return list(batch)

    def finish(self, pending):
        with self._lock:
            self.log.append(("finish", tuple(pending)))
        for x in pending:
            if x in self.hold:
                assert self.hold[x].wait(30)
        if self.fail_finish & set(pending):
            raise RuntimeError(f"finish failed on {pending}")
        with self._lock:
            self.log.append(("finished", tuple(pending)))
        return [x * 10 for x in pending]

    def scheduler(self, **kw):
        kw.setdefault("max_batch", 1)
        kw.setdefault("max_delay_ms", 0.0)
        return BatchingScheduler(self.begin, finish=self.finish, **kw).start()


def _threads_alive(b):
    return [t.is_alive() for t in (b._thread, b._completer)]


def test_batcher_begins_the_next_batch_while_a_finish_is_held_and_never_a_third():
    gate = threading.Event()
    h = _Halves(hold={1: gate})
    b = h.scheduler()
    try:
        t1 = b.submit_async(1)
        assert h.seen(1).wait(30)
        t2 = b.submit_async(2)
        # batch 2 begins while batch 1's finish has not returned ...
        assert h.seen(2).wait(30) and ("finished", (1,)) not in h.log
        t3 = b.submit_async(3)
        # ... and a third does not while two are in flight
        assert not h.seen(3).wait(0.2)
        assert b.stats()["queue_depth"] == 1 and not t1.done.is_set()
        gate.set()
        assert [b.wait(t, 30) for t in (t1, t2, t3)] == [10, 20, 30]
        assert h.log.index(("begin", (2,))) < h.log.index(("finished", (1,)))
        assert h.log.index(("begin", (3,))) > h.log.index(("finished", (1,)))
        st = b.stats()
        assert st["batches"] == 3 and st["overlapped_batches"] >= 1
    finally:
        gate.set()
        b.stop()


def test_batcher_pairs_results_and_finishes_in_close_order_under_overlap():
    rng = np.random.default_rng(3)
    naps = {i: float(rng.uniform(0, 0.004)) for i in range(120)}
    begun, finished, live = [], [], []

    def begin(batch):
        begun.append(tuple(batch))
        live.append(len(begun) - len(finished))
        return list(batch)

    def finish(pending):
        time.sleep(naps[pending[0]])
        finished.append(tuple(pending))
        return [("r", x) for x in pending]

    b = BatchingScheduler(begin, finish=finish, max_batch=4, max_delay_ms=0.5,
                          max_queue=256).start()
    results = {}

    def client(i):
        results[i] = b.submit(i, timeout=60)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(120)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)         # more interleavings than cores give
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        b.stop()
    assert results == {i: ("r", i) for i in range(120)}     # item i, result i
    assert max(live) == 2               # two in flight, never a third
    assert finished == begun and sum(map(len, begun)) == 120
    st = b.stats()
    assert st["completed"] == 120 and st["batches"] == len(begun)
    assert 0 < st["overlapped_batches"] < st["batches"]


@pytest.mark.parametrize("half", ["begin", "finish"])
def test_batcher_an_exception_in_either_half_reaches_only_its_batch(half):
    h = _Halves(**{f"fail_{half}": {2}})
    b = h.scheduler()
    try:
        tickets = [b.submit_async(i) for i in (1, 2, 3)]
        assert b.wait(tickets[0], 30) == 10
        with pytest.raises(RuntimeError, match=f"{half} failed"):
            b.wait(tickets[1], 30)
        assert b.wait(tickets[2], 30) == 30
        assert _threads_alive(b) == [True, True]            # both live on
        assert b.submit(4) == 40
        st = b.stats()
        assert st["errors"] == 1 and st["completed"] == 3 and st["batches"] == 4
    finally:
        b.stop()


def test_batcher_stop_with_two_batches_in_flight_serves_both_then_refuses():
    gate = threading.Event()
    h = _Halves(hold={1: gate})
    b = h.scheduler()
    t1, t2 = b.submit_async(1), None
    try:
        assert h.seen(1).wait(30)
        t2 = b.submit_async(2)
        assert h.seen(2).wait(30)
        t3 = b.submit_async(3)                  # admitted, still in the queue
        worker, completer = b._thread, b._completer
        stopper = threading.Thread(target=b.stop)
        stopper.start()
        deadline = time.monotonic() + 5
        while not b._stopping and time.monotonic() < deadline:
            time.sleep(0.002)
        with pytest.raises(ServiceClosed):
            b.submit_async(4)                   # refused during the drain
        assert not t1.done.is_set()
        gate.set()
        stopper.join(timeout=30)
        assert not stopper.is_alive()
        assert [b.wait(t, 5) for t in (t1, t2, t3)] == [10, 20, 30]
        assert not worker.is_alive() and not completer.is_alive()
        with pytest.raises(ServiceClosed):
            b.submit(5)
        assert b.stop() == 0                    # idempotent, nothing leaked
    finally:
        gate.set()
        b.stop()


def test_batcher_tickets_that_arrive_while_both_slots_are_full_leave_as_one_batch():
    gate = threading.Event()
    h = _Halves(hold={1: gate})
    b = h.scheduler(max_batch=16, max_delay_ms=1.0)
    try:
        t1 = b.submit_async(1)
        assert h.seen(1).wait(30)
        t2 = b.submit_async(2)
        assert h.seen(2).wait(30)
        # both slots taken: these outlive the 1 ms deadline many times over
        later = []
        for x in range(3, 9):
            later.append(b.submit_async(x))
            time.sleep(0.004)
        assert b.stats()["queue_depth"] == 6
        gate.set()
        assert [b.wait(t, 30) for t in [t1, t2] + later] == [
            10 * x for x in range(1, 9)]
        begins = [batch for what, batch in h.log if what == "begin"]
        assert begins == [(1,), (2,), (3, 4, 5, 6, 7, 8)]
    finally:
        gate.set()
        b.stop()


def test_batcher_one_half_handler_goes_through_the_completer_too():
    b = BatchingScheduler(lambda batch: [x + 1 for x in batch], max_batch=4,
                          max_delay_ms=1.0).start()
    try:
        assert [b.submit(i) for i in range(5)] == [1, 2, 3, 4, 5]
        assert _threads_alive(b) == [True, True]
        assert b.stats()["overlapped_batches"] == 0          # one caller in turn
    finally:
        assert b.stop() == 0


def test_batch_spans_overlap_with_their_parents_intact(serve_tracer):
    """Two batches in flight: one serve.batch per batch, begun on the worker
    and recorded by the completer, ``inflight`` 0 then 1; each ticket's wait
    names its own batch."""
    gate = threading.Event()
    h = _Halves(hold={1: gate})
    serve_tracer.configure(enabled=True)
    b = h.scheduler()
    try:
        t1 = b.submit_async(1)
        assert h.seen(1).wait(30)
        t2 = b.submit_async(2)
        assert h.seen(2).wait(30)
        gate.set()
        assert [b.wait(t1, 30), b.wait(t2, 30)] == [10, 20]
    finally:
        gate.set()
        b.stop()
    evs = serve_tracer.events()
    batches = [e for e in evs if e["name"] == "serve.batch"]
    assert [e["args"] for e in batches] == [
        {"size": 1, "inflight": 0, "inflight_share": 0.5},
        {"size": 1, "inflight": 1, "inflight_share": 1.0}]
    first, second = batches
    assert second["ts_s"] < first["ts_s"] + first["dur_s"]   # they overlap
    assert {e["thread"] for e in batches} == {"glint-serve-batcher-completer"}
    waits = [e for e in evs if e["name"] == "serve.queue_wait"]
    assert [w["parent"] for w in waits] == [first["id"], second["id"]]
    # the worker's stack is empty again: its next span has no parent
    assert [e["parent"] for e in evs if e["name"] == "serve.coalesce"] == [None] * 2


def _hold_finish(monkeypatch, model):
    """Hold ``model.find_synonyms_finish`` behind the event returned."""
    gate = threading.Event()
    real = model.find_synonyms_finish

    def held(pending):
        assert gate.wait(30)
        return real(pending)

    monkeypatch.setattr(model, "find_synonyms_finish", held)
    return gate


def _in_flight(svc, want):
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if svc._batcher._closed_batches - svc._batcher._batches >= want:
            return True
        time.sleep(0.002)
    return False


def test_service_swap_waits_for_the_leases_of_both_batches_in_flight(monkeypatch):
    old, new = make_model(v=300, d=8, seed=1), make_model(v=300, d=8, seed=2)
    want = [old.find_synonyms("w1", 3), old.find_synonyms("w2", 3)]
    gate = _hold_finish(monkeypatch, old)
    svc = EmbeddingService(model=old, ann=False, max_batch=1, max_delay_ms=0.0)
    try:
        t1 = svc.synonyms_async("w1", 3)
        t2 = svc.synonyms_async("w2", 3)
        assert _in_flight(svc, 2)
        svc._handle.swap(new)
        # two leases out: the old generation stays whole under the swap
        assert not old._stopped and svc._handle.models_released == 0
        assert svc.synonyms_async("w3", 3) is not None       # queued, for `new`
        gate.set()
        assert [svc.wait_result(t1), svc.wait_result(t2)] == want
        deadline = time.monotonic() + 10
        while not old._stopped and time.monotonic() < deadline:
            time.sleep(0.002)
        assert old._stopped and svc._handle.models_released == 1
        assert svc.synonyms("w3", 3) == new.find_synonyms("w3", 3)
    finally:
        gate.set()
        svc.close()
        new.stop()


def test_service_two_halves_answer_what_the_model_answers(monkeypatch):
    """Words, a vector, an OOV word, a ``vec`` and an unknown op in one
    batch: each caller its own result, the scan's through both halves."""
    model = make_model(v=300, d=8)
    vec = np.asarray(model.syn0[5])
    svc = EmbeddingService(model=model, ann=False, max_batch=8, max_delay_ms=50.0)
    try:
        tickets = [svc.synonyms_async("w1", 4), svc.synonyms_async(vec, 2),
                   svc.synonyms_async("nope", 4),
                   svc._batcher.submit_async(("vec", "w7")),
                   svc._batcher.submit_async(("bogus",))]
        # the batch's own scan: the model's, through both halves
        rows = model.find_synonyms_batch(["w1", vec], 4)
        assert svc.wait_result(tickets[0]) == rows[0]
        assert svc.wait_result(tickets[1]) == rows[1][:2]
        with pytest.raises(KeyError, match="nope"):
            svc.wait_result(tickets[2])
        np.testing.assert_array_equal(svc.wait_result(tickets[3]),
                                      model.transform("w7"))
        with pytest.raises(ValueError, match="unknown op"):
            svc.wait_result(tickets[4])
        assert svc.stats()["overlapped_batches"] == 0
    finally:
        svc.close()
        model.stop()


def test_every_reply_of_the_service_is_handed_out_by_find_synonyms_batch(monkeypatch):
    """The second half goes through ``find_synonyms_batch(begun=...)``: what
    wraps that one method (the benchmark's altered-answer test does) sees
    every reply, the service's too, and the scan is not begun twice."""
    model = make_model(v=300, d=8)
    want = model.find_synonyms("w4", 3)
    real, begins = Word2VecModel.find_synonyms_batch, []
    real_begin = Word2VecModel.find_synonyms_begin

    def tagged(self, queries, num, **kw):
        assert kw["begun"] is not None and queries == ["w4"]
        return [[("tag", 0.0)] + row for row in real(self, queries, num, **kw)]

    def counted(self, *a, **kw):
        begins.append(threading.current_thread().name)
        return real_begin(self, *a, **kw)

    monkeypatch.setattr(Word2VecModel, "find_synonyms_batch", tagged)
    monkeypatch.setattr(Word2VecModel, "find_synonyms_begin", counted)
    svc = EmbeddingService(model=model, ann=False)
    try:
        assert svc.synonyms("w4", 3) == [("tag", 0.0)] + want[:2]
    finally:
        svc.close()
        model.stop()
    assert begins == ["glint-serve-batcher"]


@pytest.mark.parametrize("arm", ["ann"])
def test_service_arms_with_no_device_result_do_their_work_in_begin(
        arm, serve_tracer):
    """The ANN arm leaves nothing to fetch: chosen from what the batch
    holds, and the completer's finish hands it back."""
    model = make_model(v=600, d=16)
    want = model.find_synonyms("w3", 5)
    serve_tracer.configure(enabled=True)
    svc = EmbeddingService(model=model, ann=arm == "ann", ann_centroids=16,
                           nprobe=16)
    try:
        got = svc.synonyms("w3", 5)
    finally:
        svc.close()
        model.stop()
    assert [w for w, _ in got] == [w for w, _ in want]
    by = {e["name"]: e["thread"] for e in serve_tracer.events()}
    assert "serve.result_fetch" not in by
    assert by["serve.ann_search"] == by["serve.reply_build"] == "glint-serve-batcher"


# -- the benchmark's span reader over two batches in flight ----------------------------
# (benchmark/readers/program_spans.py is read-only to a PR that claims a gain, and so
# is its test file: these cases live here)


_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "benchmark")


def _program_spans():
    """The benchmark's reader module (benchmark/readers/program_spans.py)."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from readers import program_spans
    return program_spans


def _ev(name, ts, dur, id_, parent=None, tid=1, **args):
    e = {"name": name, "tid": tid, "thread": f"t{tid}", "ts_s": ts, "dur_s": dur,
         "id": id_, "parent": parent}
    if args:
        e["args"] = args
    return e


# the worker (tid 1) begins batch 7 while the completer (tid 3) still fetches
# batch 1; each span is recorded by the thread that ended it, its children
# name it by id
_OVERLAPPING = [
    _ev("serve.row_fetch", 0.5, 1.0, 3, parent=2, tid=1, ops=1),
    _ev("serve.scan_enqueue", 1.5, 0.5, 4, parent=2, tid=1, queries=4, topk_rows=100),
    _ev("serve.row_fetch", 4.5, 1.0, 9, parent=8, tid=1, ops=1),
    _ev("serve.scan_enqueue", 5.5, 0.5, 10, parent=8, tid=1, queries=2, topk_rows=100),
    _ev("serve.result_fetch", 2.0, 5.0, 5, parent=2, tid=3),
    _ev("serve.reply_build", 7.0, 1.0, 6, parent=2, tid=3),
    _ev("serve.dispatch", 0.5, 7.5, 2, parent=1, tid=3, size=4),
    _ev("serve.batch", 0.0, 9.0, 1, tid=3, size=4, inflight=0, inflight_share=0.5),
    _ev("serve.queue_wait", -2.0, 2.0, 13, parent=1, tid=3, request=1),
    _ev("serve.result_fetch", 8.0, 4.0, 11, parent=8, tid=3),
    _ev("serve.reply_build", 12.0, 1.0, 12, parent=8, tid=3),
    _ev("serve.dispatch", 4.5, 8.5, 8, parent=7, tid=3, size=2),
    _ev("serve.batch", 4.0, 10.0, 7, tid=3, size=2, inflight=1, inflight_share=1.0),
]


@pytest.mark.parametrize("args, want", [
    ({"span": "serve.result_fetch", "stat": "ms_per", "per": "serve.batch"}, 4500.0),
    ({"span": "serve.row_fetch", "stat": "ms_per", "per": "serve.batch"}, 1000.0),
    # the batches begun while an earlier one's result was not in yet: 1 of 2
    ({"span": "serve.batch", "stat": "arg_mean", "arg": "inflight"}, 0.5),
    ({"span": "serve.batch", "stat": "arg_mean", "arg": "inflight_share"}, 0.75),
    ({"span": "serve.scan_enqueue", "stat": "arg_mean", "arg": "topk_rows"}, 100.0),
    ({"span": "serve.queue_wait", "stat": "mean_ms"}, 2000.0),
    # batch 1: 9 - 7.5; dispatch 2: 7.5 - (1 + .5 + 5 + 1); batch 7: 10 - 8.5;
    # dispatch 8: 8.5 - (1 + .5 + 4 + 1): 5 of the 19 s of serve.batch
    ({"span": ["serve.batch", "serve.dispatch"], "stat": "self_share",
      "over": "serve.batch"}, 5.0 / 19.0),
])
def test_the_benchmarks_reader_over_two_batches_in_flight(args, want):
    """Overlapping serve.batch spans on two threads: every stat the serve
    layers read goes by name and by parent id, so it is finite and the same."""
    program_spans = _program_spans()
    assert program_spans.reduce_events(args, _OVERLAPPING) == pytest.approx(want)


@pytest.mark.parametrize("name", ["dispatch_overlap_share",
                                  "subword_query_dispatch_overlap_share"])
def test_the_overlap_layers_read_what_the_batcher_records(name, serve_tracer):
    """The two layer files name the reader, span and arg the batcher's own
    spans carry: fed the ring of a run with two batches in flight they read
    its share, and nothing from a program whose spans have no such arg."""
    program_spans = _program_spans()
    with open(os.path.join(_BENCH, "layers", name + ".json")) as f:
        layer = json.load(f)
    assert layer["reader"] == "program_spans"
    gate = threading.Event()
    h = _Halves(hold={1: gate})
    serve_tracer.configure(enabled=True)
    b = h.scheduler()
    try:
        t1 = b.submit_async(1)
        assert h.seen(1).wait(30)
        t2 = b.submit_async(2)
        assert h.seen(2).wait(30)
        gate.set()
        assert [b.wait(t1, 30), b.wait(t2, 30)] == [10, 20]
    finally:
        gate.set()
        b.stop()
    assert program_spans.read(layer["args"], {"slice": {"window_s": 1.0}}) == 0.75
    parents = [_ev("serve.batch", 0.0, 1.0, 1, size=4)]
    assert program_spans.reduce_events(layer["args"], parents) is None
