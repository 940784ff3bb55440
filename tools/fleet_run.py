#!/usr/bin/env python
"""Serving-fleet driver CLI (docs/serving.md §5): boot N replica processes
behind a FleetRouter — health probes, circuit breakers, hedged retries,
rolling reload — off one checkpoint publish path.

Stdout carries exactly ONE JSON line (graftlint R7 — the driver contract);
human progress goes to stderr.

Usage::

    # drive a real fleet: N serve_checkpoint.py replicas + the router,
    # until --duration expires (0 = until SIGINT)
    python tools/fleet_run.py --checkpoint CK [--replicas N] [--ann]
        [--status-port P] [--telemetry PATH] [--duration S]

    # the self-contained fleet-kill drill (tier-1 + CI): tiny fit → N
    # subprocess replicas → query storm → SIGKILL one replica (breaker
    # opens, zero failed queries, replica restarts, breaker half-open →
    # closed) → 3-publish rolling-reload storm (capacity never below N-1,
    # every reload issued to a drained replica)
    python tools/fleet_run.py --smoke

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


def _train_checkpoint(workdir: str, n_sentences: int, seed: int = 4):
    """A tiny trained checkpoint for the drill (the serve-reload chaos
    phase's corpus shape: 30 words, structure enough to answer top-5).
    Trainer telemetry is ON: its sink carries the run_start clock anchor
    and — crucially for the drill's collector leg — one ``publish`` record
    per checkpoint save, the trainer half of every publish chain."""
    import numpy as np

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer

    rng = np.random.default_rng(seed)
    sents = [[f"w{i}" for i in rng.integers(0, 30, 20)]
             for _ in range(n_sentences)]
    cfg = Word2VecConfig(
        vector_size=8, pairs_per_batch=128, window=3, num_iterations=1,
        steps_per_dispatch=2, heartbeat_every_steps=4, subsample_ratio=0.0,
        prefetch_chunks=0, seed=1, min_count=1,
        telemetry_path=os.path.join(workdir, "trainer.jsonl"))
    vocab = build_vocab(sents, min_count=1)
    trainer = Trainer(cfg, vocab)
    trainer.fit(encode_sentences(sents, vocab, cfg.max_sentence_length))
    ck = os.path.join(workdir, "publish", "ck")
    trainer.save_checkpoint(ck)
    return ck, trainer, vocab, sents


def run_smoke(workdir: str, n_sentences: int = 300,
              replicas: int = 3) -> dict:
    """The fleet-kill drill (the chaos phase calls this too). Returns the
    report dict; raises AssertionError with a named failure on any broken
    invariant."""
    import threading

    import numpy as np

    from glint_word2vec_tpu.obs.schema import validate_file
    from glint_word2vec_tpu.obs.slo import SloObjectives
    from glint_word2vec_tpu.serve.fleet import (
        CircuitBreaker, FleetRouter, ReplicaSet)

    ck, trainer, vocab, sents = _train_checkpoint(workdir, n_sentences)
    log(f"[fleet] checkpoint ready: V={vocab.size}")
    telemetry = os.path.join(workdir, "fleet.jsonl")
    # telemetry_dir arms the full observability plane per replica: sink +
    # trace spans + flight recorder — the artifact set the collector leg
    # below merges into the one incident timeline (ISSUE 13)
    rs = ReplicaSet.spawn(ck, replicas, stderr_dir=workdir,
                          telemetry_dir=workdir)
    log(f"[fleet] {replicas} replicas ready "
        f"(pids {[r.pid for r in rs.replicas]})")
    # drill-scoped SLO (obs/slo.py: same math as production, seconds-scale
    # windows + a container-tolerant latency bound — a 2-core CI host under
    # a 3-thread storm is not the 250 ms production tier)
    slo_objectives = SloObjectives(
        availability=0.999, latency_ms=2000.0, latency_target=0.99,
        short_window_s=30.0, long_window_s=300.0)
    router = FleetRouter(
        rs, checkpoint=ck, probe_s=0.1, breaker_failures=2,
        breaker_reset_s=0.5, retry_deadline_s=60.0, attempt_timeout_s=5.0,
        telemetry_path=telemetry, slo=slo_objectives)

    query_errs: list = []
    queries = [0]
    storm_on = threading.Event()
    storm_on.set()
    words = {f"w{i}" for i in range(30)}

    def storm(ci: int) -> None:
        i = 0
        while storm_on.is_set() or i == 0:
            i += 1
            try:
                res = router.synonyms(f"w{(ci * 7 + i) % 30}", 5)
                if len(res) != 5 or not all(
                        w in words and np.isfinite(s) for w, s in res):
                    query_errs.append(f"bad result: {res}")
            except Exception as e:  # noqa: BLE001 — ANY raise is the failure
                query_errs.append(f"{type(e).__name__}: {e}")
            queries[0] += 1

    clients = [threading.Thread(target=storm, args=(c,)) for c in range(3)]
    for c in clients:
        c.start()
    report: dict = {}
    try:
        # let the storm + probes settle so breakers are warm
        time.sleep(1.0)
        assert not query_errs, f"pre-kill failures: {query_errs[0]}"

        # --- 1. the kill: SIGKILL one replica mid-traffic ------------------
        victim = rs.replicas[0]
        old_pid = victim.pid
        log(f"[fleet] SIGKILL replica {victim.name} (pid {old_pid})")
        victim.kill()
        # assert on the TRANSITION HISTORY, not the instantaneous state —
        # the prober can restart + trial-close faster than a state poll
        deadline = time.monotonic() + 30
        while (not any((f, t) == ("closed", "open") for f, t, _
                       in router.breaker_transitions(victim.name))
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert any((f, t) == ("closed", "open") for f, t, _
                   in router.breaker_transitions(victim.name)), \
            (f"breaker never opened on the killed replica (transitions "
             f"{router.breaker_transitions(victim.name)})")
        log("[fleet] breaker OPEN on the victim; storm continues on "
            f"{replicas - 1} replicas")

        # --- 2. recovery: restart → half-open trial → closed ---------------
        deadline = time.monotonic() + 120
        while (router.breaker_states()[victim.name] != CircuitBreaker.CLOSED
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert router.breaker_states()[victim.name] == \
            CircuitBreaker.CLOSED, \
            (f"killed replica never recovered to CLOSED "
             f"(state {router.breaker_states()[victim.name]}, "
             f"alive {victim.alive()})")
        assert victim.alive() and victim.pid != old_pid, \
            "victim was not respawned as a new process"
        trans = router.breaker_transitions(victim.name)
        states = [t[1] for t in trans]
        assert "open" in states and "half-open" in states, \
            f"breaker skipped states: {trans}"
        last_closed = max(i for i, s in enumerate(states) if s == "closed")
        assert trans[last_closed][0] == "half-open", \
            f"final close did not come from the half-open trial: {trans}"
        log(f"[fleet] victim recovered (pid {victim.pid}); breaker "
            f"transitions: {[f'{a}->{b}' for a, b, _ in trans]}")
        assert not query_errs, \
            f"{len(query_errs)} failed queries across the kill " \
            f"(first: {query_errs[0]})"

        # --- 3. rolling-reload storm: 3 publishes, capacity >= N-1 ---------
        publishes = 3
        for p in range(publishes):
            rounds_before = router.stats()["reload_rounds"]
            trainer.save_checkpoint(ck)  # the publish signal (fresh
            # inode + mtime per atomic save — no refit needed)
            deadline = time.monotonic() + 90
            while (router.stats()["reload_rounds"] <= rounds_before
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert router.stats()["reload_rounds"] > rounds_before, \
                f"rolling reload round {p + 1} never ran"
            log(f"[fleet] rolling reload round {p + 1} done")
        st = router.stats()
        assert st["reload_rounds"] >= publishes, \
            f"only {st['reload_rounds']} rolling rounds for {publishes} " \
            f"publishes"
        assert st["min_serving_during_reloads"] >= replicas - 1, \
            (f"fleet capacity dropped below N-1 during rolling reload "
             f"(min serving {st['min_serving_during_reloads']})")
        for name, rep in st["replicas"].items():
            assert rep["reloads"] >= publishes, \
                f"replica {name} reloaded only {rep['reloads']}x " \
                f"for {publishes} publishes"
            # lease-drain per replica: every reload was issued only after
            # the router drained that replica's in-flight count to zero
            assert rep["drained_reloads"] == rep["reloads"], \
                (f"replica {name}: {rep['reloads']} reloads but only "
                 f"{rep['drained_reloads']} were drain-first")
        assert not query_errs, \
            f"{len(query_errs)} failed queries across the reload storm " \
            f"(first: {query_errs[0]})"

        # --- 4. the graceful kill: SIGTERM leaves a flight-recorder dump ---
        # SIGKILL (leg 1) can never exercise the dump path — this is the
        # half the serving flight recorder exists for (obs/blackbox.py via
        # EmbeddingService.dump_blackbox + serve_checkpoint.py's handler)
        victim2 = rs.replicas[1]
        dump_path = f"{victim2.telemetry_path}.blackbox.json"
        log(f"[fleet] SIGTERM replica {victim2.name} (pid {victim2.pid})")
        victim2.terminate()
        deadline = time.monotonic() + 30
        while not os.path.exists(dump_path) and time.monotonic() < deadline:
            time.sleep(0.05)
        assert os.path.exists(dump_path), \
            f"SIGTERM'd replica left no flight-recorder dump at {dump_path}"
        # let the prober respawn it so close() tears down a whole fleet
        deadline = time.monotonic() + 60
        while not victim2.alive() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim2.alive(), "SIGTERM'd replica was never respawned"
        assert not query_errs, \
            f"{len(query_errs)} failed queries across the graceful kill " \
            f"(first: {query_errs[0]})"
    finally:
        storm_on.clear()
        for c in clients:
            c.join()
        stats = router.stats()
        slo = router.slo_snapshot()
        slo_ok = router.slo_within_budget()
        router.close()
    assert not query_errs, f"failed queries: {query_errs[0]}"
    assert stats["failures"] == 0, \
        f"{stats['failures']} requests exhausted the retry deadline"
    assert stats["shed_single"] == 0, \
        f"{stats['shed_single']} single queries shed (fleet never saturates " \
        f"at toy scale)"
    assert queries[0] >= 100, \
        f"storm too thin ({queries[0]} queries) to prove overlap"
    summary = validate_file(telemetry)
    assert summary["ok"], f"fleet telemetry not schema-valid: " \
        f"{summary['errors'][:3]}"
    kinds = summary["kinds"]
    assert kinds.get("fleet_start") == 1 and kinds.get("fleet_end") == 1
    assert kinds.get("fleet_breaker", 0) >= 2, \
        f"breaker transitions missing from telemetry ({kinds})"
    assert kinds.get("fleet_reload", 0) >= publishes
    assert kinds.get("trace_span", 0) >= queries[0], \
        (f"router emitted {kinds.get('trace_span', 0)} spans for "
         f"{queries[0]} queries — trace propagation is off")
    assert kinds.get("fleet_slo", 0) >= 1, "no fleet_slo record"

    # --- 5. the SLO verdict: "zero failed queries" as a MEASURED objective
    assert slo["samples"] >= queries[0] - 3 * replicas, \
        f"SLO tracker missed queries ({slo['samples']}/{queries[0]})"
    assert slo_ok, f"SLO burn over budget across the storm: {slo}"

    # --- 6. the collector leg (ISSUE 13 acceptance): merge EVERY artifact
    # the drill left — router sink, N replica sinks, the trainer's sink,
    # the SIGTERM dump — and reconstruct the incident end-to-end
    from glint_word2vec_tpu.obs.collect import collect
    timeline, merged = collect([workdir], objectives=slo_objectives)
    assert len(merged["processes"]) >= replicas + 2, \
        (f"collector saw only {merged['processes']} — expected router + "
         f"{replicas} replicas + trainer")
    # a retried query's trace: the failed attempt on the SIGKILLed replica
    # AND the success elsewhere, under ONE trace id
    retried = [
        t for t in timeline["traces"].values()
        if any(s.get("name") == "attempt" and s.get("outcome") == "failed"
               and s.get("replica") == victim.name for s in t["spans"])
        and any(s.get("name") == "attempt"
                and s.get("outcome") in ("ok", "win")
                and s.get("replica") != victim.name for s in t["spans"])]
    assert retried, \
        "no merged trace shows failed-attempt-on-victim + success-elsewhere"
    # replica-side children crossed the wire: some trace carries spans from
    # BOTH the router process and a replica process
    cross = [t for t in timeline["traces"].values()
             if len({s["_process"] for s in t["spans"]}) >= 2]
    assert cross, "no trace carries spans from more than one process"
    # breaker transitions appear on the merged timeline
    merged_breakers = [e for e in timeline["events"]
                       if e["kind"] == "fleet_breaker"]
    bstates = [(e.get("from_state"), e.get("to_state"))
               for e in merged_breakers]
    assert ("closed", "open") in bstates and \
        ("half-open", "closed") in bstates, \
        f"breaker story incomplete on the merged timeline: {bstates}"
    # the publish chain: the trainer's publish record joined to fleet
    # rolling-reload rounds by publish_sig
    chained = [sig for sig, evs in timeline["publish_chains"].items()
               if {"publish"} & {e["kind"] for e in evs}
               and {"fleet_reload", "serve_reload"} & {e["kind"]
                                                      for e in evs}]
    assert chained, \
        f"no publish_sig joins trainer save to a reload " \
        f"({list(timeline['publish_chains'])})"
    # the SIGTERM dump was ingested with its signal cause
    assert any(b["cause"].get("kind") == "signal"
               for b in timeline["blackboxes"]), \
        f"no signal-cause blackbox in {merged['blackboxes']}"
    # offline SLO recompute (same burn math as the live gauge) in budget
    assert merged["slo"]["within_budget"], \
        f"offline SLO burn over budget: {merged['slo']}"

    victim_stats = stats["replicas"]["r0"]
    return {
        "ok": True,
        "replicas": replicas,
        "queries": queries[0],
        "failed_queries": 0,
        "retries": stats["retries"],
        "hedges": stats["hedges"],
        "hedge_wins": stats["hedge_wins"],
        "victim_restarts": victim_stats["restarts"],
        "breaker_transitions": [f"{a}->{b}" for a, b, _ in trans],
        "reload_rounds": stats["reload_rounds"],
        "min_serving_during_reloads": stats["min_serving_during_reloads"],
        "telemetry_kinds": kinds,
        "slo": {k: slo[k] for k in ("samples", "availability",
                                    "budget_remaining")},
        "collector": {
            "processes": merged["processes"],
            "traces": merged["traces"],
            "spans": merged["spans"],
            "attempt_outcomes": merged["attempt_outcomes"],
            "retried_traces": len(retried),
            "publish_chains": len(chained),
            "slo_within_budget": merged["slo"]["within_budget"],
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--checkpoint", default="",
                    help="publish path the replicas serve + the router "
                         "watches for rolling reloads")
    ap.add_argument("--replicas", type=int, default=None,
                    help="fleet size (default: the checkpoint's "
                         "serve_fleet_replicas knob)")
    ap.add_argument("--ann", action="store_true",
                    help="replicas serve the IVF ANN arm")
    ap.add_argument("--status-port", type=int, default=0,
                    help="> 0: serve the fleet-aggregated glint_serve_* "
                         "gauges on 127.0.0.1:<port>")
    ap.add_argument("--telemetry", default="",
                    help="write fleet_* telemetry records here (JSONL)")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="serve this many seconds then exit (0 = until "
                         "SIGINT)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the self-contained fleet-kill drill "
                         "(tier-1/CI) in a temp dir")
    ap.add_argument("--smoke-replicas", type=int, default=3)
    ap.add_argument("--sentences", type=int, default=300)
    ap.add_argument("--workdir", default="",
                    help="--smoke working directory (default: fresh temp)")
    args = ap.parse_args()

    # single-print shape: exactly one JSON line leaves this function on
    # every path (graftlint R7)
    if args.smoke:
        workdir = args.workdir or tempfile.mkdtemp(prefix="glint_fleet_")
        os.makedirs(workdir, exist_ok=True)
        try:
            out, rc = run_smoke(workdir, args.sentences,
                                args.smoke_replicas), 0
        except AssertionError as e:
            out, rc = {"ok": False, "error": str(e)}, 1
        except Exception as e:  # noqa: BLE001 — the one-JSON-line contract
            # (R7) holds on EVERY path: a boot timeout / OSError must
            # still leave a parseable line, not an empty stdout that makes
            # CI's json.tool step mask the real failure
            out, rc = {"ok": False,
                       "error": f"{type(e).__name__}: {e}"}, 1
        finally:
            if not args.workdir:
                shutil.rmtree(workdir, ignore_errors=True)
    else:
        if not args.checkpoint:
            ap.error("--checkpoint is required (or use --smoke)")
        from glint_word2vec_tpu.serve.fleet import (
            FleetRouter, ReplicaSet, fleet_knobs_from_checkpoint)
        knobs = fleet_knobs_from_checkpoint(
            args.checkpoint, replicas=args.replicas)
        n = knobs.pop("replicas")
        log(f"[fleet] spawning {n} replicas on {args.checkpoint}")
        rs = ReplicaSet.spawn(args.checkpoint, n, ann=args.ann)
        router = FleetRouter(
            rs, checkpoint=args.checkpoint, telemetry_path=args.telemetry,
            status_port=args.status_port, **knobs)
        log("[fleet] serving; Ctrl-C to stop"
            + (f" (auto-stop in {args.duration:g}s)" if args.duration
               else ""))
        try:
            if args.duration:
                time.sleep(args.duration)
            else:
                while True:
                    time.sleep(3600)
        except KeyboardInterrupt:
            log("[fleet] stopping")
        finally:
            stats = router.stats()
            router.close()
        out, rc = {"ok": True, "replicas": n, **{
            k: stats[k] for k in ("queries", "failures", "retries",
                                  "hedges", "reload_rounds", "healthy")}}, 0
    print(json.dumps(out))
    return rc


if __name__ == "__main__":
    sys.exit(main())
