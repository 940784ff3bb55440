#!/usr/bin/env python
"""Continual-training driver CLI (docs/continual.md): watch an append-only
corpus directory, extend the vocabulary when it drifts, train incremental
fits, and publish each one through the atomic checkpoint-swap signal the
serving tier hot-reloads from — the closed train→serve loop, as a process.

Stdout carries exactly ONE JSON line (graftlint R7 — the driver contract);
human progress goes to stderr.

Usage::

    # drive a real deployment: poll corpus-dir until bounds trip
    python tools/continual_run.py --checkpoint CK --corpus-dir DIR \
        --work-dir WORK [--max-increments N] [--idle-polls N] [--poll-s S]

    # the self-contained end-to-end drill (tier-1 + CI): base fit → corpus
    # append with unseen words → incremental fit grows V (lineage recorded)
    # → publish → a LIVE EmbeddingService hot-reloads and answers a query
    # for a new-vocab word with zero failed queries
    python tools/continual_run.py --smoke

Exit code 0 iff the run (or the drill's every assertion) passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --- the smoke drill corpus: two co-occurrence clusters, so "neighbors
# intact" is a checkable structure, not a vibe -------------------------------

_CLUSTER_A = [f"a{i}" for i in range(6)]
_CLUSTER_B = [f"b{i}" for i in range(6)]
_NEW_WORDS = ["n0", "n1", "n2"]


def _write_cluster_segment(path: str, n_sentences: int, seed: int,
                           extra_a_words=()) -> None:
    """Sentences drawn from ONE cluster each; ``extra_a_words`` join cluster
    A's draws (the appended segment's unseen words co-occur with A, so the
    drill can check a new word's neighbors land in A)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    a = list(_CLUSTER_A) + list(extra_a_words)
    with open(path, "w", encoding="utf-8") as f:
        for _ in range(n_sentences):
            ws = a if rng.integers(0, 2) == 0 else _CLUSTER_B
            f.write(" ".join(ws[i] for i in rng.integers(0, len(ws), 12))
                    + "\n")


def run_smoke(workdir: str, n_sentences: int = 400) -> dict:
    """The end-to-end drill. Returns the report dict; raises AssertionError
    with a named failure on any broken invariant."""
    import threading

    import numpy as np

    from glint_word2vec_tpu.continual import ContinualRunner
    from glint_word2vec_tpu.serve import EmbeddingService
    from glint_word2vec_tpu.train.checkpoint import load_model_header

    corpus_dir = os.path.join(workdir, "corpus")
    work_dir = os.path.join(workdir, "work")
    ck = os.path.join(workdir, "publish", "ck")
    os.makedirs(corpus_dir, exist_ok=True)
    _write_cluster_segment(
        os.path.join(corpus_dir, "seg-000.txt"), n_sentences, seed=1)

    overrides = dict(
        vector_size=16, min_count=2, window=3, num_iterations=2,
        pairs_per_batch=128, subsample_ratio=0.0, seed=1, prefetch_chunks=0,
        steps_per_dispatch=2, heartbeat_every_steps=4,
        continual_lr_rewarm=0.8, continual_iterations=2)
    runner = ContinualRunner(
        ck, corpus_dir, work_dir, config_overrides=overrides,
        checkpoint_every_steps=8,
        telemetry_path=os.path.join(workdir, "continual.jsonl"))
    base = runner.ensure_base()
    log(f"[smoke] base fit: {base}")
    assert base["action"] == "base", "bootstrap did not run a base fit"
    v_base = base["vocab_size"]

    # the serve replica: watches the SAME publish path the runner writes
    service = EmbeddingService(
        checkpoint=ck, ann=True, watch=True, reload_poll_s=0.05,
        max_batch=16, max_delay_ms=1.0)
    query_errs: list = []
    queries = [0]
    storm_on = threading.Event()
    storm_on.set()

    def storm():
        known = list(_CLUSTER_A) + list(_CLUSTER_B)
        i = 0
        while storm_on.is_set() or i == 0:
            w = known[i % len(known)]
            i += 1
            try:
                res = service.synonyms(w, 4)
                if not res or not all(np.isfinite(s) for _, s in res):
                    query_errs.append(f"bad result for {w!r}: {res}")
            except Exception as e:  # noqa: BLE001 — any raise is a failure
                query_errs.append(f"{w!r}: {type(e).__name__}: {e}")
            queries[0] += 1

    client = threading.Thread(target=storm)
    client.start()
    try:
        # the drift: an appended segment whose unseen words co-occur with
        # cluster A
        _write_cluster_segment(
            os.path.join(corpus_dir, "seg-001.txt"), n_sentences, seed=2,
            extra_a_words=_NEW_WORDS)
        inc = runner.run_once()
        log(f"[smoke] increment: {inc}")
        assert inc["action"] == "increment", "increment did not run"
        assert inc["grew"] and inc["new_words"] >= len(_NEW_WORDS), \
            f"vocab did not grow ({inc})"
        v_new = inc["vocab_size"]
        assert v_new > v_base, "vocab_size did not increase"

        # the live replica must observe the grown publish and answer a
        # query for a NEW word — bounded wait on the reload watcher
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            info = service.info()
            if info["num_words"] == v_new:
                break
            time.sleep(0.05)
        info = service.info()
        assert info["num_words"] == v_new, (
            f"service never reloaded the grown model "
            f"(serving {info['num_words']} words, want {v_new})")
        new_syn = service.synonyms(_NEW_WORDS[0], 4)
        assert new_syn and all(np.isfinite(s) for _, s in new_syn), \
            f"new-word query failed: {new_syn}"
        # old-word neighbors intact: cluster A words still neighbor cluster
        # A (the forgetting smoke check; the measured gate is
        # eval_quality.py --continual-ab)
        old_syn = service.synonyms(_CLUSTER_A[0], 4)
        a_like = set(_CLUSTER_A) | set(_NEW_WORDS)
        hits = sum(1 for w, _ in old_syn if w in a_like)
        assert hits >= 2, (
            f"old word {_CLUSTER_A[0]!r} lost its cluster after the "
            f"increment: {old_syn}")
    finally:
        storm_on.clear()
        client.join()
        stats = service.stats()
        service.close()
        runner.close()
    assert not query_errs, (
        f"{len(query_errs)} failed queries during the continual publishes "
        f"(first: {query_errs[0]})")
    assert stats["refused"] == 0, f"{stats['refused']} refused queries"
    assert stats["reloads"] >= 1, "no hot-reload observed"
    assert stats["vocab_change_reloads"] >= 1, \
        "the V-grew reload was not detected"
    header = load_model_header(ck)
    lineage = header["vocab_lineage"]
    assert len(lineage) == 1 and lineage[0]["new_words"] == inc["new_words"], \
        f"lineage chain wrong: {lineage}"
    return {
        "ok": True,
        "vocab_base": v_base,
        "vocab_grown": v_new,
        "new_words": inc["new_words"],
        "lineage_depth": len(lineage),
        "reloads": stats["reloads"],
        "vocab_change_reloads": stats["vocab_change_reloads"],
        "queries": queries[0],
        "failed_queries": 0,
        "refused": stats["refused"],
        "new_word_top1": (new_syn[0][0] if new_syn else None),
        "increment_train_seconds": inc["train_seconds"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--checkpoint", default="",
                    help="publish path (the directory serving replicas "
                         "watch); bootstrapped with a base fit if absent")
    ap.add_argument("--corpus-dir", default="",
                    help="append-only segment directory (*.txt)")
    ap.add_argument("--work-dir", default="",
                    help="cursor + encode-cache directory")
    ap.add_argument("--max-increments", type=int, default=None,
                    help="stop after this many completed increments")
    ap.add_argument("--idle-polls", type=int, default=None,
                    help="stop after this many consecutive empty polls")
    ap.add_argument("--poll-s", type=float, default=None,
                    help="poll cadence (default: the continual_poll_s knob)")
    ap.add_argument("--checkpoint-every-steps", type=int, default=None)
    ap.add_argument("--telemetry", default="",
                    help="write continual_* telemetry records here (JSONL)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the self-contained end-to-end drill "
                         "(tier-1/CI) in a temp dir")
    ap.add_argument("--workdir", default="",
                    help="--smoke working directory (default: fresh temp)")
    args = ap.parse_args()

    # single-print shape: exactly one JSON line leaves this function on
    # every path (graftlint R7 — the rule that forced perfgate into the
    # same shape)
    if args.smoke:
        workdir = args.workdir or tempfile.mkdtemp(prefix="glint_continual_")
        try:
            out, rc = run_smoke(workdir), 0
        except AssertionError as e:
            out, rc = {"ok": False, "error": str(e)}, 1
        finally:
            if not args.workdir:
                shutil.rmtree(workdir, ignore_errors=True)
    else:
        if not (args.checkpoint and args.corpus_dir and args.work_dir):
            ap.error("--checkpoint, --corpus-dir and --work-dir are "
                     "required (or use --smoke)")
        from glint_word2vec_tpu.continual import ContinualRunner
        runner = ContinualRunner(
            args.checkpoint, args.corpus_dir, args.work_dir,
            checkpoint_every_steps=args.checkpoint_every_steps,
            telemetry_path=args.telemetry)
        try:
            base = runner.ensure_base()
            if base["action"] == "base":
                log(f"[continual] bootstrapped base model: {base}")
            result = runner.run_forever(
                max_increments=args.max_increments,
                max_idle_polls=args.idle_polls,
                poll_s=args.poll_s)
        finally:
            runner.close()
        out, rc = {"ok": True, **result,
                   "bootstrapped": base["action"] == "base"}, 0
    print(json.dumps(out))
    return rc


if __name__ == "__main__":
    sys.exit(main())
