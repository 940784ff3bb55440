#!/usr/bin/env python
"""Chaos runner: drive a toy-corpus run through a scripted fault schedule
end-to-end and verify the fault-tolerance layer holds (docs/robustness.md).

Every fault is deterministic (train/faults.py) — no sleep/kill-timing races:

1. **crash-resume** — a subprocess trains with periodic checkpointing and is
   SIGKILLed *inside* the second checkpoint's swap window (the torn state: old
   checkpoint renamed aside, replacement not yet in place). The parent recovers
   via ``load_latest_valid`` (which reclaims the staging debris and restores
   the renamed-aside previous checkpoint), resumes training from it, and
   verifies the finished checkpoint's digests.
2. **corrupt-fallback** — a newer checkpoint is saved with scripted bit-flips;
   ``load_latest_valid`` must reject it on digest mismatch and fall back to the
   older clean one.
3. **nan-rollback / nan-halt** — NaN is injected into the params carry at a
   scripted step; under ``nonfinite_policy="rollback"`` the run finishes with
   finite embeddings, under ``"halt"`` it fails fast with a diagnostic.
4. **norm-blowup** — the params carry is scaled by 1e6 at a scripted step: a
   FINITE blowup (the measured 1.6M-vocab collapse signature, ROADMAP item 2).
   ``nonfinite_policy`` alone must stay silent, ``norm_watch="warn"`` must
   record firings and finish, ``norm_watch="halt"`` must fail fast.
5. **norm-recover** — the full detect→mitigate→recover ladder
   (docs/robustness.md): the same finite blowup under
   ``norm_watch="recover"`` (beside ``nonfinite_policy="halt"`` — the
   snapshot ring must arm for the watchdog even though nonfinite rollback
   never does) must roll back, back the lr off, engage the row-norm clamp,
   and FINISH with finite params and ``recoveries_performed >= 1``; a
   repeatedly-reblowing run past ``max_recoveries`` must degrade to the
   halt contract (NormBlowupError).
6. **blackbox** — chaos-proven forensics (docs/observability.md): a
   SIGTERM'd telemetry-on subprocess (``crash_at_step`` +
   ``crash_signal=TERM`` — the preemption first-warning surface) and an
   injected finite blowup under ``norm_watch="halt"`` must each leave a
   schema-valid ``<telemetry_path>.blackbox.json`` flight-recorder dump
   carrying ≥ 1 heartbeat and the terminal cause (signal / exception).
7. **serve-reload** — the serving tier under publish chaos (ISSUE 10,
   docs/serving.md): a trainer thread publishes checkpoints every few steps
   while a query storm runs against an EmbeddingService watching the same
   path — zero failed/refused queries, ≥ 3 observed hot-reloads, and every
   superseded model's buffers released once its in-flight leases drained.
   The epilogue drives the cross-publish V-GREW case (ISSUE 11): the
   checkpoint is vocabulary-extended mid-storm, the service must hot-reload
   at the new V (index rebuilt, ``vocab_change_reloads`` counted) and answer
   a query for a word that did not exist one publish earlier.
8. **continual-drift** — the closed continual loop (ISSUE 11,
   docs/continual.md): base fit → corpus append with unseen words → a
   SIGTERM'd mid-increment driver subprocess must leave a resumable
   published checkpoint and an unconsumed cursor → the retried increment
   grows V with the fingerprint lineage recorded → a live serve replica
   hot-reloads the grown model, answers a query for a NEW word, and an old
   word's neighbors stay inside its co-occurrence cluster.
9. **fleet-kill** — the serving FLEET under replica death (ISSUE 12,
   docs/serving.md §5): N replica subprocesses behind a FleetRouter, one
   SIGKILL'd mid-query-storm → its circuit breaker opens, ZERO client
   queries fail (retries land on the survivors), the ReplicaSet restarts
   it, and the breaker recovers through the half-open trial to closed;
   then a 3-publish rolling-reload storm keeps >= N-1 replicas serving
   with every reload issued only to a drained replica.
10. **flaky-ingest** — the first N ingest I/O attempts raise; the bounded
    exponential-backoff wrapper in ``data/`` must absorb them.
11. **train-preempt / train-stall / train-crashloop** — the training
    SUPERVISOR under scripted faults (ISSUE 16, docs/robustness.md
    §supervisor, delegating to tools/train_run.py): a SIGTERM'd fit
    emergency-checkpoints within its preemption deadline and resumes to
    match an uninterrupted twin's purity gate; an injected in-step hang
    is detected within 2x the stall horizon, diagnosed (flight-recorder
    dump), killed, and resumed; a deterministic every-attempt crash walks
    the escalation ladder and is quarantined with a machine-readable
    verdict in bounded attempts.

Usage::

    python tools/chaos_run.py           # moderate sizes
    python tools/chaos_run.py --smoke   # small + fast (wired into tier-1 tests)
    python tools/chaos_run.py --only serve-reload   # one phase (CI serving job)
    python tools/chaos_run.py --list    # print available phase names

Exit code 0 iff every phase passed.
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# CPU by design: the schedule kills and restarts toy fits in subprocesses; a
# chip would be held by the first of them
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def toy_sentences(n_sentences: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [[f"w{i}" for i in rng.integers(0, 30, 20)]
            for _ in range(n_sentences)]


def toy_config(policy: str = "halt", **kw):
    from glint_word2vec_tpu.config import Word2VecConfig
    return Word2VecConfig(
        vector_size=8, pairs_per_batch=128, window=3, num_iterations=2,
        steps_per_dispatch=2, heartbeat_every_steps=2, subsample_ratio=0.0,
        prefetch_chunks=0, seed=1, nonfinite_policy=policy, **kw)


def _fit(sentences, cfg, **kw):
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer
    vocab = build_vocab(sentences, min_count=1)
    enc = encode_sentences(sentences, vocab, 1000)
    trainer = Trainer(cfg, vocab)
    trainer.fit(enc, **kw)
    return trainer


def worker_crash(workdir: str, n_sentences: int) -> None:
    """The crashing training leg — launched as a subprocess with
    GLINT_FAULT_CRASH_POINT=save:swap@2 in its env, so the first periodic save
    completes and the second dies mid-swap. Never returns normally."""
    _fit(toy_sentences(n_sentences), toy_config(),
         checkpoint_path=os.path.join(workdir, "ck"),
         checkpoint_every_steps=2)
    print("WORKER SURVIVED (fault did not fire)", flush=True)
    sys.exit(3)


def worker_blackbox(workdir: str, n_sentences: int) -> None:
    """The SIGTERM'd telemetry-on leg of the blackbox phase — launched with
    GLINT_FAULT_CRASH_AT_STEP + GLINT_FAULT_CRASH_SIGNAL=TERM in its env,
    so the trainer's SIGTERM hook (obs/blackbox.py) must dump the flight
    recorder before the process dies. Never returns normally."""
    _fit(toy_sentences(n_sentences), toy_config(
        telemetry_path=os.path.join(workdir, "run.jsonl")))
    print("WORKER SURVIVED (fault did not fire)", flush=True)
    sys.exit(3)


def phase_crash_resume(workdir: str, n_sentences: int) -> str:
    from glint_word2vec_tpu.models.estimator import Word2Vec
    from glint_word2vec_tpu.train.checkpoint import (
        load_latest_valid, verify_checkpoint)

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               GLINT_FAULT_CRASH_POINT="save:swap@2")
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--worker", "crash",
         "--workdir", workdir, "--sentences", str(n_sentences)],
        env=env)
    if rc not in (-9, 137):
        return f"worker exited {rc}, expected SIGKILL (-9/137)"
    entries = sorted(os.listdir(workdir))
    if not any(".old-" in e or ".tmp-" in e for e in entries):
        return f"no interrupted-save debris found ({entries}) — fault missed"
    ck = load_latest_valid(workdir)
    meta = verify_checkpoint(ck)
    step = meta["train_state"]["global_step"]
    if meta["train_state"]["finished"] or step <= 0:
        return f"recovered checkpoint is not a mid-run state (step {step})"
    model = Word2Vec.resume(ck, toy_sentences(n_sentences),
                            checkpoint_every_steps=2)
    if not model.train_state.finished:
        return "resumed run did not finish"
    verify_checkpoint(ck)  # the finished save must verify too
    if not np.isfinite(np.asarray(model.syn0)).all():
        return "resumed run produced non-finite embeddings"
    return ""


def phase_corrupt_fallback(workdir: str) -> str:
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.checkpoint import (
        TrainState, load_latest_valid, save_model)

    words = ["a", "b", "c"]
    counts = np.array([3, 2, 1])
    syn0 = np.random.default_rng(0).normal(size=(3, 8)).astype(np.float32)
    cfg = Word2VecConfig(vector_size=8)
    save_model(os.path.join(workdir, "ck-a"), words, counts, syn0, -syn0,
               cfg, TrainState(global_step=10))
    faults.configure(corrupt_checkpoint_bytes=3)
    try:
        save_model(os.path.join(workdir, "ck-b"), words, counts, syn0, -syn0,
                   cfg, TrainState(global_step=20))
    finally:
        faults.reset()
    got = load_latest_valid(workdir)
    if os.path.basename(got) != "ck-a":
        return f"picked {got!r}; expected the older clean ck-a (ck-b is corrupt)"
    return ""


def phase_nan(policy: str) -> str:
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.faults import NonFiniteParamsError

    faults.configure(nan_at_step=8)
    try:
        trainer = _fit(toy_sentences(200, seed=2), toy_config(policy))
    except NonFiniteParamsError as e:
        faults.reset()
        if policy == "halt":
            return "" if "non-finite parameters" in str(e) else \
                f"halt diagnostic unclear: {e}"
        return f"rollback run raised instead of recovering: {e}"
    finally:
        faults.reset()
    if policy == "halt":
        return "halt run finished instead of raising"
    if not np.isfinite(np.asarray(trainer.params.syn0)).all():
        return "rollback run ended with non-finite params"
    if trainer.rollbacks_performed < 1:
        return "rollback run never rolled back (fault missed)"
    return ""


def phase_norm_blowup() -> str:
    """The finite-blowup watchdog (ISSUE 6 / ROADMAP item 2): scale the params
    carry by 1e6 mid-run — a FINITE norm blowup, the measured 1.6M-vocab
    collapse signature. The non-finite guardrail alone must stay silent (no
    NaN ever appears — exactly the round-5 blindness), norm_watch='warn' must
    record firings and finish, norm_watch='halt' must fail fast."""
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.faults import NormBlowupError

    # 1. nonfinite halt alone: silent (the blowup is finite)
    faults.configure(scale_params_at_step=8)
    try:
        trainer = _fit(toy_sentences(200, seed=2), toy_config("halt"))
    except Exception as e:  # noqa: BLE001 — any raise here is the failure
        return f"nonfinite_policy='halt' fired on a FINITE blowup: {e}"
    finally:
        faults.reset()
    if not np.isfinite(np.asarray(trainer.params.syn0)).all():
        return "scaled params went non-finite — injection no longer finite"
    if trainer.norm_watchdog.fires:
        return "watchdog fired with norm_watch='off'"

    # 2. warn: fires, training continues to completion
    faults.configure(scale_params_at_step=8)
    try:
        trainer = _fit(toy_sentences(200, seed=2),
                       toy_config("halt", norm_watch="warn"))
    finally:
        faults.reset()
    if trainer.norm_watchdog.fires < 1:
        return "norm_watch='warn' never fired on the injected blowup"

    # 3. halt: fail fast with the diagnostic
    faults.configure(scale_params_at_step=8)
    try:
        _fit(toy_sentences(200, seed=2),
             toy_config("halt", norm_watch="halt"))
    except NormBlowupError as e:
        return "" if "finite norm blowup" in str(e) else \
            f"halt diagnostic unclear: {e}"
    finally:
        faults.reset()
    return "norm_watch='halt' finished instead of raising"


def phase_norm_recover() -> str:
    """Close the loop (ISSUE 7): the injected finite blowup must drive the
    full warn→recover→resume→finish ladder — watchdog fires, the run rolls
    back to a ring snapshot, lr backs off, the row-norm clamp engages, and
    fit() COMPLETES with finite params; and a run that re-blows past its
    recovery budget must degrade to the fail-fast halt contract."""
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.faults import NormBlowupError

    # 1. recover: blowup mid-run -> rollback + mitigation -> finish.
    #    nonfinite_policy stays 'halt' on purpose: the ring must arm for the
    #    WATCHDOG consumer (the pre-round-12 arming bug left it empty here).
    faults.configure(scale_params_at_step=8)
    try:
        trainer = _fit(toy_sentences(200, seed=2),
                       toy_config("halt", norm_watch="recover"))
    except Exception as e:  # noqa: BLE001 — a recover run must not raise
        return f"norm_watch='recover' raised instead of recovering: {e}"
    finally:
        faults.reset()
    if trainer.recoveries_performed < 1:
        return "recover run finished but never recovered (fault missed?)"
    if trainer.norm_watchdog.fires < 1:
        return "recover run finished without a watchdog firing"
    if not np.isfinite(np.asarray(trainer.params.syn0)).all():
        return "recovered run ended with non-finite params"
    norms = np.linalg.norm(
        np.asarray(trainer.params.syn0, np.float64), axis=1)
    if norms.max() > trainer.config.norm_watch_threshold * 1.001:
        return (f"recovered run still carries blown rows "
                f"(max norm {norms.max():.3g}) — mitigation not engaged?")
    if trainer._lr_scale >= 1.0:
        return "recovery did not back the learning rate off"
    if not trainer._stabilizers.max_row_norm:
        return "recovery did not engage max_row_norm"

    # 2. budget exhaustion: the blowup re-fires every round (times=99), so
    #    after max_recoveries the ladder must degrade to halt, fail-fast
    faults.configure(scale_params_at_step=8, scale_params_times=99)
    try:
        _fit(toy_sentences(200, seed=2),
             toy_config("halt", norm_watch="recover", max_recoveries=2))
    except NormBlowupError as e:
        return "" if "budget exhausted" in str(e) else \
            f"exhaustion diagnostic unclear: {e}"
    except Exception as e:  # noqa: BLE001
        return f"budget exhaustion raised the wrong error: {e}"
    finally:
        faults.reset()
    return "budget-exhaustion run finished instead of halting"


def phase_blackbox(workdir: str, n_sentences: int) -> str:
    """Chaos-proven forensics (ISSUE 9): an injected crash (SIGTERM'd
    subprocess — the preemption first-warning surface) and an injected
    finite blowup (NormBlowupError through the abort path) must each leave
    a SCHEMA-VALID ``<telemetry_path>.blackbox.json`` carrying the ring
    contents (>= 1 heartbeat) and the terminal cause record."""
    import json
    from glint_word2vec_tpu.obs.schema import validate_blackbox_file
    from glint_word2vec_tpu.train import faults
    from glint_word2vec_tpu.train.faults import NormBlowupError

    # 1. injected crash: SIGTERM at a scripted step, in a real subprocess —
    #    the dump must be written by the signal hook before the process dies
    crash_dir = os.path.join(workdir, "crash")
    os.makedirs(crash_dir, exist_ok=True)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               GLINT_FAULT_CRASH_AT_STEP="8",
               GLINT_FAULT_CRASH_SIGNAL="TERM")
    rc = subprocess.call(
        [sys.executable, os.path.abspath(__file__), "--worker", "blackbox",
         "--workdir", crash_dir, "--sentences", str(n_sentences)],
        env=env)
    if rc not in (-15, 143):
        return f"worker exited {rc}, expected SIGTERM (-15/143)"
    dump = os.path.join(crash_dir, "run.jsonl.blackbox.json")
    if not os.path.exists(dump):
        return "SIGTERM'd run left no blackbox dump"
    v = validate_blackbox_file(dump)
    if not v["ok"]:
        return f"crash dump not schema-valid: {v['errors'][:3]}"
    with open(dump) as f:
        doc = json.load(f)
    if doc["cause"] != {"kind": "signal", "signal": "SIGTERM", "signum": 15}:
        return f"crash dump cause wrong: {doc['cause']}"
    if len(doc["heartbeats"]) < 1:
        return "crash dump carries no heartbeats"
    if not doc["dispatches"]:
        return "crash dump carries no dispatch records"

    # 2. injected finite blowup: NormBlowupError rides the abort path and
    #    must dump with the exception as the terminal cause (and the
    #    watchdog record in the event ring — the record-before-raise
    #    contract made durable)
    blow_dir = os.path.join(workdir, "blowup")
    os.makedirs(blow_dir, exist_ok=True)
    run_log = os.path.join(blow_dir, "run.jsonl")
    faults.configure(scale_params_at_step=8)
    try:
        _fit(toy_sentences(n_sentences, seed=2),
             toy_config("halt", norm_watch="halt", telemetry_path=run_log))
        return "norm_watch='halt' finished instead of raising"
    except NormBlowupError:
        pass
    except Exception as e:  # noqa: BLE001
        return f"blowup raised the wrong error: {e}"
    finally:
        faults.reset()
    dump = run_log + ".blackbox.json"
    if not os.path.exists(dump):
        return "blowup run left no blackbox dump"
    v = validate_blackbox_file(dump)
    if not v["ok"]:
        return f"blowup dump not schema-valid: {v['errors'][:3]}"
    with open(dump) as f:
        doc = json.load(f)
    cause = doc["cause"]
    if cause.get("kind") != "exception" or cause.get("type") != "NormBlowupError":
        return f"blowup dump cause wrong: {cause}"
    if len(doc["heartbeats"]) < 1:
        return "blowup dump carries no heartbeats"
    kinds = [e["kind"] for e in doc["events"]]
    if "watchdog" not in kinds:
        return f"blowup dump events missing the watchdog record ({kinds})"
    if "run_end" not in kinds:
        return f"blowup dump events missing the terminal run_end ({kinds})"
    return ""


def phase_serve_reload(workdir: str, n_sentences: int) -> str:
    """Serving-tier chaos (ISSUE 10): the trainer publishes checkpoints
    mid-query-storm. The service must (a) answer every query — no errors,
    no refusals, no torn reads across the atomic swap; (b) observe >= 3
    hot-reloads through the publish-signal watcher; (c) release every
    superseded model's buffers once its in-flight leases drain."""
    import threading
    import time

    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.serve import EmbeddingService
    from glint_word2vec_tpu.train.trainer import Trainer

    sents = toy_sentences(n_sentences, seed=4)
    vocab = build_vocab(sents, min_count=1)
    cfg = toy_config()
    enc = encode_sentences(sents, vocab, cfg.max_sentence_length)
    trainer = Trainer(cfg, vocab)
    ck = os.path.join(workdir, "ck")
    trainer.save_checkpoint(ck)  # the service needs a first publish to boot

    service = EmbeddingService(
        checkpoint=ck, ann=True, watch=True, reload_poll_s=0.02,
        max_batch=16, max_delay_ms=1.0)
    fit_err, query_errs = [], []
    queries = [0]

    def fit():
        try:
            # checkpoint every 4 global steps: many publishes race the
            # watcher's reloads and the storm below
            trainer.fit(enc, checkpoint_path=ck, checkpoint_every_steps=4)
            trainer.save_checkpoint(ck)
        except Exception as e:  # noqa: BLE001 — re-raised via fit_err
            fit_err.append(e)

    t = threading.Thread(target=fit)
    words = {f"w{i}" for i in range(30)}
    storm_on = threading.Event()
    storm_on.set()

    def storm(ci: int):
        i = 0
        while storm_on.is_set() or i == 0:
            i += 1
            try:
                res = service.synonyms(f"w{(ci * 7 + i) % 30}", 5)
                if len(res) != 5 or not all(
                        w in words and np.isfinite(s) for w, s in res):
                    query_errs.append(f"bad result: {res}")
            except Exception as e:  # noqa: BLE001 — ANY raise is the failure
                query_errs.append(f"{type(e).__name__}: {e}")
            queries[0] += 1

    clients = [threading.Thread(target=storm, args=(c,)) for c in range(3)]
    t.start()
    for c in clients:
        c.start()
    t.join()
    # the acceptance needs >= 3 OBSERVED publishes. Training publishes
    # plenty, but on a loaded host a reload cycle (load + index build) can
    # outlast the whole toy fit — so keep the storm up and keep PUBLISHING
    # until the watcher has demonstrably observed three, bounded by a
    # deadline (a watcher that never observes them is the failure)
    deadline = time.monotonic() + 60
    while service.stats()["reloads"] < 3 and time.monotonic() < deadline:
        trainer.save_checkpoint(ck)
        settle = time.monotonic() + 2
        while (service.stats()["reloads"] < 3
               and time.monotonic() < min(settle, deadline)):
            time.sleep(0.05)
    storm_on.clear()
    for c in clients:
        c.join()
    try:
        if fit_err:
            return f"trainer died under the storm: {fit_err[0]}"
        if query_errs:
            return (f"{len(query_errs)} failed queries during publishes "
                    f"(first: {query_errs[0]})")
        stats = service.stats()
        if stats["refused"]:
            return f"{stats['refused']} queries refused (queue never fills here)"
        if stats["reloads"] < 3:
            return (f"only {stats['reloads']} hot-reloads observed across "
                    f"the publish storm (need >= 3)")
        if stats["models_released"] != stats["reloads"]:
            return (f"buffer leak: {stats['reloads']} reloads but only "
                    f"{stats['models_released']} old models released")
        if queries[0] < 50:
            return f"storm too thin ({queries[0]} queries) to prove overlap"

        # cross-publish V-GREW epilogue (ISSUE 11): extend the vocabulary
        # between publishes; the watcher must hot-reload at the new V with
        # a freshly built index and serve the brand-new word
        from glint_word2vec_tpu.continual import extend_checkpoint
        rep = extend_checkpoint(
            ck, {"brandnew0": 50, "brandnew1": 40}, min_count=1)
        deadline = time.monotonic() + 30
        while (service.info()["num_words"] != rep["new_vocab_size"]
               and time.monotonic() < deadline):
            time.sleep(0.05)
        info = service.info()
        if info["num_words"] != rep["new_vocab_size"]:
            return (f"service never reloaded the V-grew publish "
                    f"(serving {info['num_words']} words, want "
                    f"{rep['new_vocab_size']})")
        if service.stats()["vocab_change_reloads"] < 1:
            return "V-grew reload not counted as a vocab change"
        res = service.synonyms("brandnew0", 3)
        if not res or not all(np.isfinite(s) for _, s in res):
            return f"new-vocab word query failed after the V-grew reload: {res}"

        # QUANTIZED V-grew epilogue (ISSUE 18): a second service pinned to
        # the int8 arm rides the same checkpoint; another vocabulary
        # extension must hot-reload it at the SAME quant mode with recall
        # re-measured at the new V (floor 0: toy-vocab probe loss is about
        # the scale, not the quantizer — docs/serving.md §6), and the
        # brand-new word must serve through the quantized index
        qsvc = EmbeddingService(
            checkpoint=ck, ann=True, watch=True, reload_poll_s=0.02,
            max_batch=16, max_delay_ms=1.0,
            ann_quant="int8", ann_recall_floor=0.0)
        try:
            before = qsvc.info()["ann"]
            if before.get("quant") != "int8":
                return f"quantized service built arm {before.get('quant')!r}"
            rep2 = extend_checkpoint(ck, {"brandnew2": 30}, min_count=1)
            deadline = time.monotonic() + 30
            while (qsvc.info()["num_words"] != rep2["new_vocab_size"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            qinfo = qsvc.info()
            if qinfo["num_words"] != rep2["new_vocab_size"]:
                return (f"quantized service never reloaded the V-grew "
                        f"publish (serving {qinfo['num_words']} words, "
                        f"want {rep2['new_vocab_size']})")
            after = qinfo["ann"]
            if after.get("quant") != "int8":
                return (f"V-grew reload changed the quant arm: "
                        f"{before.get('quant')!r} -> {after.get('quant')!r}")
            if after.get("rows") != rep2["new_vocab_size"]:
                return (f"quantized index not rebuilt at the new V "
                        f"(index rows {after.get('rows')})")
            if not isinstance(after.get("recall_at_10"), float):
                return ("quantized V-grew rebuild did not re-measure "
                        f"recall: {after.get('recall_at_10')!r}")
            qres = qsvc.synonyms("brandnew2", 3)
            if not qres or not all(np.isfinite(s) for _, s in qres):
                return (f"new-vocab word query failed through the "
                        f"quantized index: {qres}")
        finally:
            qsvc.close()
    finally:
        service.close()
    return ""


def phase_continual_drift(workdir: str, n_sentences: int) -> str:
    """The closed continual loop under fault injection (ISSUE 11,
    docs/continual.md): base fit -> corpus append with unseen words -> a
    SIGTERM'd mid-increment driver must leave a RESUMABLE published
    checkpoint and an unconsumed cursor -> the retried increment grows V
    (lineage recorded, carried rows verified by the extension itself) -> a
    live serve replica hot-reloads the grown model and answers a query for
    a NEW word, with an old word's neighbors still in its cluster."""
    import json as _json
    import time

    from glint_word2vec_tpu.continual import ContinualRunner, StreamCursor
    from glint_word2vec_tpu.serve import EmbeddingService
    from glint_word2vec_tpu.train.checkpoint import (
        load_latest_valid, load_model_header, verify_checkpoint)
    from tools.continual_run import (
        _CLUSTER_A, _NEW_WORDS, _write_cluster_segment)

    corpus_dir = os.path.join(workdir, "corpus")
    work_dir = os.path.join(workdir, "work")
    ck = os.path.join(workdir, "publish", "ck")
    os.makedirs(corpus_dir, exist_ok=True)
    _write_cluster_segment(
        os.path.join(corpus_dir, "seg-000.txt"), n_sentences, seed=1)
    overrides = dict(
        vector_size=16, min_count=2, window=3, num_iterations=2,
        pairs_per_batch=128, subsample_ratio=0.0, seed=1,
        prefetch_chunks=0, steps_per_dispatch=2, heartbeat_every_steps=2)
    runner = ContinualRunner(ck, corpus_dir, work_dir,
                             config_overrides=overrides,
                             checkpoint_every_steps=4)
    base = runner.ensure_base()
    v_base = base["vocab_size"]
    _write_cluster_segment(
        os.path.join(corpus_dir, "seg-001.txt"), n_sentences, seed=2,
        extra_a_words=_NEW_WORDS)

    # 1. SIGTERM mid-increment: the subprocess driver extends + starts the
    #    incremental fit, then dies at a scripted step. crash_at_step fires
    #    on global_step, which CONTINUES from the base checkpoint — 1 is
    #    already exceeded, so the first fit round of the increment dies.
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               GLINT_FAULT_CRASH_AT_STEP="1",
               GLINT_FAULT_CRASH_SIGNAL="TERM")
    rc = subprocess.call(
        [sys.executable,
         os.path.join(_REPO, "tools", "continual_run.py"),
         "--checkpoint", ck, "--corpus-dir", corpus_dir,
         "--work-dir", work_dir, "--max-increments", "1",
         "--idle-polls", "1"],
        env=env, stdout=subprocess.DEVNULL)
    if rc not in (-15, 143):
        return f"driver exited {rc}, expected SIGTERM (-15/143)"
    # resumable: the publish path (or its swap debris) verifies, and the
    # cursor did NOT consume the tail — the increment will retry
    try:
        recovered = load_latest_valid(os.path.dirname(ck))
        verify_checkpoint(recovered)
    except Exception as e:  # noqa: BLE001 — unrecoverable = the failure
        return f"no resumable checkpoint after mid-increment SIGTERM: {e}"
    cursor = StreamCursor(work_dir)
    if "seg-001.txt" in cursor.consumed:
        return "SIGTERM'd increment was marked consumed (not resumable)"

    # 2. retry the increment in-process, with a live serve replica watching
    service = EmbeddingService(
        checkpoint=ck, ann=True, watch=True, reload_poll_s=0.05,
        max_batch=16, max_delay_ms=1.0)
    try:
        runner2 = ContinualRunner(ck, corpus_dir, work_dir,
                                  config_overrides=overrides,
                                  checkpoint_every_steps=4)
        rep = runner2.run_once()
        if rep["action"] != "increment":
            return f"retried increment did not run: {rep}"
        header = load_model_header(ck)
        if header["vocab_size"] <= v_base:
            return (f"vocab did not grow across the increment "
                    f"({v_base} -> {header['vocab_size']})")
        lineage = header["vocab_lineage"]
        if not lineage or lineage[0].get("remap") != "identity-prefix":
            return f"fingerprint lineage missing/wrong: {lineage}"
        deadline = time.monotonic() + 30
        while (service.info()["num_words"] != header["vocab_size"]
               and time.monotonic() < deadline):
            time.sleep(0.05)
        if service.info()["num_words"] != header["vocab_size"]:
            return "serve replica never hot-reloaded the grown model"
        res = service.synonyms(_NEW_WORDS[0], 4)
        if not res or not all(np.isfinite(s) for _, s in res):
            return f"new-word query failed on the grown model: {res}"
        old = service.synonyms(_CLUSTER_A[0], 4)
        a_like = set(_CLUSTER_A) | set(_NEW_WORDS)
        if sum(1 for w, _ in old if w in a_like) < 2:
            return (f"old word {_CLUSTER_A[0]!r} lost its cluster after "
                    f"the increment: {old}")
        if service.stats()["refused"]:
            return "queries refused during the continual publishes"
        # the cursor JSON round-trips (the next driver run starts clean)
        with open(os.path.join(work_dir, "cursor.json")) as f:
            doc = _json.load(f)
        if "seg-001.txt" not in doc.get("consumed", {}):
            return "completed increment did not consume its segment"
    finally:
        service.close()
        runner.close()
    return ""


def phase_fleet_kill(workdir: str, n_sentences: int) -> str:
    """Serving-fleet chaos (ISSUE 12, docs/serving.md §5): SIGKILL a
    replica subprocess mid-query-storm — its circuit breaker must open,
    ZERO client queries may fail (retries land elsewhere), the ReplicaSet
    must restart it, and the breaker must recover through the half-open
    trial probe to closed; then a 3-publish rolling-reload storm must keep
    >= N-1 replicas serving at all times with every reload issued only
    after that replica's in-flight count drained (lease-drain per
    replica). Delegates to the fleet driver's drill (tools/fleet_run.py
    run_smoke — the same assertions CI's fleet job runs standalone)."""
    from tools.fleet_run import run_smoke
    try:
        rep = run_smoke(workdir, n_sentences, replicas=3)
    except AssertionError as e:
        return str(e)
    except Exception as e:  # noqa: BLE001 — any raise is the failure
        return f"{type(e).__name__}: {e}"
    if rep.get("failed_queries") != 0:
        return f"failed queries: {rep}"
    return ""


def _phase_supervisor(drill, workdir: str, n_sentences: int) -> str:
    """Shared wrapper for the three supervisor drills (ISSUE 16,
    docs/robustness.md §supervisor) — each delegates to the training
    driver's drill (tools/train_run.py, the same assertions CI's
    supervisor job runs standalone) and reports its first broken
    invariant."""
    os.makedirs(workdir, exist_ok=True)
    try:
        drill(workdir, n_sentences)
    except AssertionError as e:
        return str(e)
    except Exception as e:  # noqa: BLE001 — any raise is the failure
        return f"{type(e).__name__}: {e}"
    return ""


def phase_train_preempt(workdir: str, n_sentences: int) -> str:
    """A SIGTERM'd supervised fit must emergency-checkpoint within its
    preemption deadline (losing at most one dispatch chunk), get restarted
    from the verified save, and finish matching an uninterrupted twin."""
    from tools.train_run import run_preempt_drill
    return _phase_supervisor(run_preempt_drill, workdir, n_sentences)


def phase_train_stall(workdir: str, n_sentences: int) -> str:
    """An injected in-step hang must be detected within 2x the stall
    horizon, diagnosed (SIGTERM flight-recorder dump, then SIGKILL), and
    the run resumed to completion."""
    from tools.train_run import run_stall_drill
    return _phase_supervisor(run_stall_drill, workdir, n_sentences)


def phase_train_crashloop(workdir: str, n_sentences: int) -> str:
    """A deterministic every-attempt crash must walk the escalation ladder
    and quarantine with a machine-readable verdict in bounded attempts."""
    from tools.train_run import run_crashloop_drill
    return _phase_supervisor(run_crashloop_drill, workdir, n_sentences)


def phase_flaky_ingest(workdir: str) -> str:
    from glint_word2vec_tpu.data.corpus import encode_corpus
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train import faults

    sents = toy_sentences(50, seed=3)
    vocab = build_vocab(sents, min_count=1)
    faults.configure(fail_ingest_first_n=2)
    try:
        enc = encode_corpus(sents, vocab, os.path.join(workdir, "enc"))
    except OSError as e:
        return f"retry wrapper did not absorb 2 injected faults: {e}"
    finally:
        faults.reset()
    if len(enc) != len(sents):
        return f"encoded {len(enc)} sentences, expected {len(sents)}"
    return ""


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus / fast phases (tier-1 smoke)")
    ap.add_argument("--workdir", default="",
                    help="working directory (default: a fresh temp dir)")
    ap.add_argument("--worker", choices=["crash", "blackbox"],
                    help="internal: run a fault-target worker leg")
    ap.add_argument("--sentences", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="comma-separated phase names to run (default: all) "
                         "— the CI serving job runs --only serve-reload")
    ap.add_argument("--list", action="store_true",
                    help="print available phase names and exit")
    args = ap.parse_args()

    n_sentences = args.sentences or (300 if args.smoke else 1500)
    if args.worker == "crash":
        worker_crash(args.workdir, n_sentences)
        return 3  # unreachable
    if args.worker == "blackbox":
        worker_blackbox(args.workdir, n_sentences)
        return 3  # unreachable

    workdir = args.workdir or tempfile.mkdtemp(prefix="glint_chaos_")
    os.makedirs(workdir, exist_ok=True)
    phases = [
        ("crash-resume",
         lambda: phase_crash_resume(os.path.join(workdir, "p1"), n_sentences)),
        ("corrupt-fallback",
         lambda: phase_corrupt_fallback(os.path.join(workdir, "p2"))),
        ("nan-rollback", lambda: phase_nan("rollback")),
        ("nan-halt", lambda: phase_nan("halt")),
        ("norm-blowup", phase_norm_blowup),
        ("norm-recover", phase_norm_recover),
        ("blackbox",
         lambda: phase_blackbox(os.path.join(workdir, "p5"), n_sentences)),
        ("serve-reload",
         lambda: phase_serve_reload(os.path.join(workdir, "p6"), n_sentences)),
        ("continual-drift",
         lambda: phase_continual_drift(os.path.join(workdir, "p7"),
                                       min(n_sentences, 400))),
        ("fleet-kill",
         lambda: phase_fleet_kill(os.path.join(workdir, "p8"),
                                  min(n_sentences, 300))),
        ("flaky-ingest",
         lambda: phase_flaky_ingest(os.path.join(workdir, "p4"))),
        ("train-preempt",
         lambda: phase_train_preempt(os.path.join(workdir, "p9"),
                                     min(n_sentences, 200))),
        ("train-stall",
         lambda: phase_train_stall(os.path.join(workdir, "p10"),
                                   min(n_sentences, 200))),
        ("train-crashloop",
         lambda: phase_train_crashloop(os.path.join(workdir, "p11"),
                                       min(n_sentences, 200))),
    ]
    if args.list:
        for name, _ in phases:
            print(name)
        return 0
    if args.only:
        want = {p.strip() for p in args.only.split(",") if p.strip()}
        names = [name for name, _ in phases]
        unknown = want - set(names)
        if unknown:
            print(f"[chaos] unknown phase(s): {sorted(unknown)} — "
                  f"available: {', '.join(names)}", flush=True)
            return 2
        phases = [(name, fn) for name, fn in phases if name in want]
    failures = 0
    for name, fn in phases:
        for sub in ("p1", "p2", "p4", "p6", "p8"):
            os.makedirs(os.path.join(workdir, sub), exist_ok=True)
        err = fn()
        status = "PASS" if not err else f"FAIL: {err}"
        print(f"[chaos] {name:18s} {status}", flush=True)
        failures += bool(err)
    if not args.workdir:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"[chaos] {'OK' if not failures else 'FAILED'} "
          f"({len(phases) - failures}/{len(phases)} phases passed)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
