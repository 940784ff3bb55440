#!/usr/bin/env python
"""Scripted telemetry fit: the acceptance driver for the observability layer
(docs/observability.md) and the CI artifact producer.

Runs a toy-corpus fit with telemetry ON (JSONL sink + host trace spans +
norm watchdog armed) through the production Trainer, then:

1. validates every emitted JSONL record against the schema catalogue
   (obs/schema.py — the drift gate CI fails on);
2. checks the exported Chrome-trace file parses and carries the
   producer/stage/dispatch/probe/checkpoint spans;
3. (``--overhead``) measures telemetry cost: interleaved fits with telemetry
   off/on (3 trials each, median pairs/s) — the acceptance bar is < 2%
   regression at heartbeat cadence.

Artifacts land under ``--out`` (``run.jsonl`` + ``run.jsonl.trace.json``) so
the CI job can upload them. Prints exactly ONE JSON line on stdout (the R7
driver-tool contract); progress goes to stderr.

Usage::

    python tools/telemetry_run.py --out /tmp/telemetry [--smoke] [--overhead]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402

# span names the scripted fit must produce (the acceptance list from ISSUE 6;
# "producer" covers the feed producer, "stage_put" the staging path,
# "health_probe" the fused probe, "checkpoint_save" the save path)
REQUIRED_SPANS = ("producer", "stage_put", "dispatch", "health_probe",
                  "checkpoint_save")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def toy_sentences(n_sentences: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [[f"w{i}" for i in rng.integers(0, 50, 20)]
            for _ in range(n_sentences)]


def _build(sentences, **cfg_kw):
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer
    vocab = build_vocab(sentences, min_count=1)
    enc = encode_sentences(sentences, vocab, 1000)
    cfg = Word2VecConfig(
        vector_size=16, pairs_per_batch=512, window=3, num_iterations=2,
        steps_per_dispatch=4, heartbeat_every_steps=8, subsample_ratio=0.0,
        seed=1, **cfg_kw)
    return Trainer(cfg, vocab), enc


def scripted_fit(out_dir: str, n_sentences: int) -> dict:
    """One telemetry-on fit; returns the artifact summary (validated)."""
    from glint_word2vec_tpu.obs.schema import validate_file
    run_log = os.path.join(out_dir, "run.jsonl")
    trainer, enc = _build(
        toy_sentences(n_sentences), telemetry_path=run_log,
        norm_watch="warn")
    trainer.fit(enc, checkpoint_path=os.path.join(out_dir, "ck"),
                checkpoint_every_steps=16)
    trace_path = run_log + ".trace.json"

    summary = validate_file(run_log)
    spans: list = []
    trace_ok = False
    try:
        with open(trace_path) as f:
            doc = json.load(f)
        spans = sorted({e["name"] for e in doc.get("traceEvents", [])
                        if e.get("ph") == "X"})
        trace_ok = True
    except (OSError, json.JSONDecodeError, KeyError) as e:
        summary["errors"] = summary.get("errors", []) + [f"trace: {e}"]
    missing = [s for s in REQUIRED_SPANS if s not in spans]
    # a CLEAN run must leave no flight-recorder dump — the blackbox is a
    # death artifact (obs/blackbox.py); chaos_run's `blackbox` phase proves
    # the dying-run half
    blackbox_absent = not os.path.exists(run_log + ".blackbox.json")
    ok = bool(summary["ok"] and trace_ok and not missing and blackbox_absent
              and summary["kinds"].get("run_start") == 1
              and summary["kinds"].get("run_end") == 1
              and summary["kinds"].get("heartbeat", 0) >= 1)
    return {
        "ok": ok,
        "blackbox_absent": blackbox_absent,
        "run_log": run_log,
        "trace": trace_path,
        "records": summary["records"],
        "kinds": summary["kinds"],
        "schema_valid": summary["ok"],
        "schema_errors": summary.get("errors", [])[:5],
        "spans": spans,
        "missing_spans": missing,
        "steps": int(trainer.global_step),
        "heartbeats_in_ring": len(trainer.heartbeats),
    }


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def measure_overhead(n_sentences: int, trials: int = 4,
                     workdir: str = "", status: bool = False) -> dict:
    """Interleaved telemetry-off/on A/B at heartbeat cadence (the PERF.md §3
    interleaving methodology), with two noise defenses this container made
    necessary: (1) ALTERNATING arm order per trial — a fixed off-then-on
    order measured a phantom 5% "overhead" that was pure host drift (the
    first fit of each pair ran hotter); (2) steady-state scoring. Geometry is
    production-PROPORTIONED, not toy: multi-ms dispatch chunks at a 16-step
    cadence (6x more frequent than the production default of 100) — probing
    a microsecond-step toy fit every 2 steps measures the probe's fixed
    cost, not the heartbeat-cadence overhead the acceptance bar is about.
    Importable — bench.py --smoke prints this measurement as its JSON line.

    ``status=True``: the on arm ADDITIONALLY serves the live status endpoint
    (config.status_port, obs/statusd.py) and each on-trial scrapes
    /status.json + /metrics once mid-fit from the heartbeat callback — so
    the measured arm is a REALLY-serving endpoint, not an idle socket. Same
    < 2% acceptance bar (docs/observability.md)."""
    workdir = workdir or tempfile.mkdtemp(prefix="glint_obs_bench_")
    # floor the corpus so every fit spans >= ~10 heartbeat windows — the
    # steady-state scoring below needs windows to drop and windows to keep
    n_sentences = max(n_sentences, 3000)
    rng = np.random.default_rng(4)
    sents = [[f"w{i}" for i in rng.integers(0, 2000, 30)]
             for _ in range(n_sentences)]
    geom = dict(vector_size=64, pairs_per_batch=4096, window=3,
                num_iterations=6, steps_per_dispatch=8,
                heartbeat_every_steps=16, subsample_ratio=0.0, seed=1)

    def build(**kw):
        from glint_word2vec_tpu.config import Word2VecConfig
        from glint_word2vec_tpu.data.pipeline import encode_sentences
        from glint_word2vec_tpu.data.vocab import build_vocab
        from glint_word2vec_tpu.train.trainer import Trainer
        vocab = build_vocab(sents, min_count=1)
        return (Trainer(Word2VecConfig(**geom, **kw), vocab),
                encode_sentences(sents, vocab, 1000))

    # steady-state scoring: each heartbeat already reports pairs/s over its
    # own window (probe + sink cost INCLUDED in the on-arm windows, since the
    # probe runs before the heartbeat clock is read); the first windows carry
    # the jit compile and are dropped. Whole-fit wall clock would fold 1-2 s
    # of compile into a ~5 s fit and swamp a 2% bar with compile-time noise.
    warmup = 2
    samples = {"off": [], "on": []}
    scrapes = 0
    for trial in range(trials):
        arms = ("off", "on") if trial % 2 == 0 else ("on", "off")
        for arm in arms:
            kw = {}
            on_heartbeat = None
            if arm == "on":
                kw = dict(telemetry_path=os.path.join(
                    workdir, f"run_{trial}.jsonl"), norm_watch="warn")
                if status:
                    port = _free_port()
                    kw["status_port"] = port
                    scraped = []

                    def on_heartbeat(rec, _port=port, _s=scraped):
                        if _s:
                            return
                        import urllib.request
                        snap = json.load(urllib.request.urlopen(
                            f"http://127.0.0.1:{_port}/status.json",
                            timeout=5))
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{_port}/metrics",
                            timeout=5).read()
                        assert snap["status"] == "running", snap
                        _s.append(True)
            trainer, enc = build(**kw)
            trainer.fit(enc, on_heartbeat=on_heartbeat)
            if arm == "on" and status:
                scrapes += len(scraped)
            window_pps = [hb.pairs_per_sec
                          for hb in trainer.heartbeats][warmup:]
            samples[arm].extend(window_pps)
            log(f"overhead trial {trial} {arm}: "
                f"{np.median(window_pps):,.0f} pairs/s "
                f"({len(window_pps)} windows)")
    off = float(np.median(samples["off"]))
    on = float(np.median(samples["on"]))
    spread = float(np.percentile(samples["off"], 75)
                   / max(np.percentile(samples["off"], 25), 1e-9) - 1.0)
    if status:
        assert scrapes == trials, (
            f"status arm scraped {scrapes}/{trials} fits — the endpoint "
            f"was not live during every on-trial")
    return {
        **({"status_arm": True, "status_scrapes": scrapes}
           if status else {}),
        "telemetry_off_pairs_per_sec": round(off, 1),
        "telemetry_on_pairs_per_sec": round(on, 1),
        # signed: a negative value means the on-arm measured FASTER, i.e. the
        # true overhead is below this host's noise floor (see window_iqr_frac)
        "telemetry_overhead_frac": round(1.0 - on / off, 4),
        "window_iqr_frac": round(spread, 4),
        "trials": trials,
        "basis": ("median steady-state heartbeat-window pairs/s, "
                  f"{warmup} warmup windows dropped, arm order alternated "
                  "per trial"),
        "windows_per_arm": len(samples["off"]),
    }


def measure_trace_overhead(trials: int = 4, queries: int = 400,
                           workdir: str = "") -> dict:
    """The ISSUE-13 zero-cost acceptance A/B: an in-process 2-replica
    fleet (ReplicaSet.adopt — no subprocess noise) serving one in-memory
    model, queried back-to-back with tracing OFF (no sinks anywhere: the
    router allocates no trace context, requests cross the submit path
    byte-identical to the pre-trace protocol) vs ON (router + replica
    sinks, every query emitting its full 5-span breakdown). Interleaved
    trials with alternating arm order and median-of-QPS scoring — the
    same drift defenses as :func:`measure_overhead`. The batcher runs at
    ``max_delay_ms=0`` so the measured path is the submit/dispatch hot
    path, not the coalescing timer."""
    import time as _time

    import jax.numpy as jnp

    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve.fleet import FleetRouter, ReplicaSet
    from glint_word2vec_tpu.serve.service import EmbeddingService

    workdir = workdir or tempfile.mkdtemp(prefix="glint_trace_bench_")
    os.makedirs(workdir, exist_ok=True)
    v, d = 512, 32
    rng = np.random.default_rng(7)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(v)], np.ones(v, np.int64))
    model = Word2VecModel(vocab, jnp.asarray(
        rng.standard_normal((v, d)).astype(np.float32)))
    samples = {"off": [], "on": [], "sampled": []}
    for trial in range(trials):
        order = ("off", "on", "sampled")
        arms = order if trial % 2 == 0 else order[::-1]
        for arm in arms:
            def p(name):
                return (os.path.join(workdir, f"t{trial}_{name}.jsonl")
                        if arm != "off" else "")
            svcs = [EmbeddingService(model=model, ann=False,
                                     max_delay_ms=0.0,
                                     telemetry_path=p(f"{arm}_r{i}"),
                                     process_name=f"r{i}")
                    for i in range(2)]
            router = FleetRouter(ReplicaSet.adopt(svcs), probe_s=30.0,
                                 hedge_ms=0.0, retry_deadline_s=10.0,
                                 telemetry_path=p(f"{arm}_router"),
                                 trace_sample=16 if arm == "sampled" else 1)
            try:
                for i in range(32):  # warm the dispatch path
                    router.synonyms(f"w{i}", 5)
                t0 = _time.perf_counter()
                for i in range(queries):
                    router.synonyms(f"w{i % v}", 5)
                dt = _time.perf_counter() - t0
            finally:
                router.close()
            samples[arm].append(queries / dt)
            log(f"trace-overhead trial {trial} {arm}: "
                f"{queries / dt:,.0f} q/s")
    off = float(np.median(samples["off"]))
    on = float(np.median(samples["on"]))
    sampled = float(np.median(samples["sampled"]))
    return {
        "tracing_off_qps": round(off, 1),
        "tracing_on_qps": round(on, 1),
        "tracing_sampled_16_qps": round(sampled, 1),
        # the off arm IS the zero-cost claim: no sink → no trace context
        # born at submit (fleet._request), no span ids, no clock reads —
        # these measure what tracing costs when you TURN IT ON (signed;
        # negative = below this host's noise floor). The on-arm cost is
        # ~5 flushed sink writes per query, which toy-latency queries
        # make look enormous — trace_sample=16 is the production lever
        # (docs/observability.md §9).
        "tracing_on_overhead_frac": round(1.0 - on / off, 4),
        "tracing_sampled_16_overhead_frac": round(1.0 - sampled / off, 4),
        "trials": trials,
        "queries_per_arm_per_trial": queries,
        "basis": ("median q/s over interleaved off/on/sampled trials, arm "
                  "order alternated, in-process 2-replica fleet, "
                  "max_delay_ms=0"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--out", default="",
                    help="artifact directory (default: a fresh temp dir)")
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus / fast (tier-1 + CI)")
    ap.add_argument("--overhead", action="store_true",
                    help="also run the interleaved telemetry-off/on "
                         "throughput A/B")
    ap.add_argument("--status-overhead", action="store_true",
                    help="overhead A/B with the live status endpoint "
                         "SERVING (and scraped mid-fit) on the on arm — "
                         "the obs/statusd.py acceptance measurement")
    ap.add_argument("--trace-overhead", action="store_true",
                    help="fleet trace-propagation off/on A/B (ISSUE 13 "
                         "zero-cost-when-off acceptance; obs/trace.py)")
    args = ap.parse_args()

    out_dir = args.out or tempfile.mkdtemp(prefix="glint_telemetry_")
    os.makedirs(out_dir, exist_ok=True)
    n = 300 if args.smoke else 1500

    log(f"telemetry_run: scripted fit -> {out_dir}")
    result = scripted_fit(out_dir, n)
    if args.overhead:
        result["overhead"] = measure_overhead(
            n, workdir=os.path.join(out_dir, "bench"))
    if args.status_overhead:
        result["status_overhead"] = measure_overhead(
            n, workdir=os.path.join(out_dir, "bench_status"), status=True)
    if args.trace_overhead:
        result["trace_overhead"] = measure_trace_overhead(
            trials=3 if args.smoke else 4,
            queries=200 if args.smoke else 400,
            workdir=os.path.join(out_dir, "bench_trace"))
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
