#!/usr/bin/env python
"""Serving-tier QPS/latency bench: exact vs ANN arms through the real service.

The serving twin of bench.py (ROADMAP item 1 / ISSUE 10): measures the
production query path — the request batcher coalescing concurrent clients,
the IVF ANN index vs the exact full-vocab oracle, backpressure under
offered load — and prints exactly ONE JSON line on stdout (graftlint R7)
for tools/perfgate.py's serving bands (``--kind serve``).

Arms:

1. **exact per-query** — sequential ``find_synonyms`` calls, one device
   dispatch each: the pre-subsystem baseline (the 230-375 ms/query regime
   at V=1M through a thin link; smaller here, same shape).
2. **exact batched (service)** — closed loop: N client threads hammer the
   service, the micro-batcher coalesces into batched exact dispatches.
3. **ANN batched (service)** — the same closed loop over the IVF arm; the
   index's oracle-checked ``recall@10`` (measured at build against the
   exact full scan, serve/ann.py) rides the JSON line.
4. **offered load** — open loop at target arrival rates derived from the
   ANN closed-loop capacity (0.5x/1.0x/1.5x): workers fire at scheduled
   arrival times, refusals (ServerOverloaded, the 429 analog) and p99 are
   counted per target; ``offered_qps_sustained`` is the highest target
   with < 1% refusals.
5. **quantized arms** (ISSUE 18) — the int8 and PQ index builds through
   the same closed loop, with footprint columns: ``*_index_bytes``,
   ``*_bytes_cut`` (f32-index bytes over quant bytes — higher is better,
   so perfgate can band it), ``int8_qps_ratio`` vs the f32 ANN arm, and
   each arm's own oracle-measured recall@10. ``--shard-native`` adds a
   smoke build straight from a row-shards checkpoint
   (serve/quant.build_ivf_from_shards) with a code-parity check against
   the in-memory build.

Latency vs throughput reporting: closed-loop percentiles at saturation are
a QUEUEING artifact (Little's law: N clients / capacity), so the headline
``ann_p50_ms``/``ann_p99_ms`` quote the HALF-CAPACITY offered-load row —
the latency a deployment sees at a sane utilization — and the closed-loop
row keeps its own ``ann_closed_*`` keys as the capacity measurement. The
acceptance headline ``ann_speedup_p50`` is exact PER-QUERY p50 (the path
this subsystem replaces) over that operating-point ANN p50.

Model: ``--checkpoint`` serves a real trained model; the default is a
synthetic CLUSTERED matrix (mixture of unit gaussian cells — trained
embedding geometry is clustered; a uniform-random matrix has no structure
for ANY index and would bench an assumption no deployment makes). Queries
are vocabulary words (self-exclusion semantics included), drawn uniformly.

Usage::

    python tools/servebench.py                 # full tier on this host
    python tools/servebench.py --smoke         # small + fast (CI)
    python tools/servebench.py --checkpoint /path/to/ckpt
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Dict, List

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402
from glint_word2vec_tpu.lockcheck import make_lock


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def pct(lats_ms: List[float], p: float) -> float:
    if not lats_ms:
        return float("nan")
    s = sorted(lats_ms)
    return round(s[min(len(s) - 1, int(p * len(s)))], 3)


def make_model(vocab_size: int, dim: int, clusters: int, seed: int):
    """Synthetic clustered embedding matrix (module doc) wrapped as a model."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    rng = np.random.default_rng(seed)
    cents = rng.standard_normal((clusters, dim)).astype(np.float32)
    cents /= np.maximum(np.linalg.norm(cents, axis=1, keepdims=True), 1e-12)
    # noise norm ~0.35 RELATIVE to the unit centroid at any dim (a fixed
    # per-dim sigma would swamp the structure as dim grows — and trained
    # embeddings are tightly clustered: the eval ladder measures topic
    # purity@10 ~1.0 on healthy runs, tools/eval_quality.py)
    noise = rng.standard_normal((vocab_size, dim)).astype(np.float32)
    m = cents[rng.integers(0, clusters, vocab_size)] + 0.35 * noise / np.sqrt(dim)
    words = [f"w{i}" for i in range(vocab_size)]
    vocab = Vocabulary.from_words_and_counts(
        words, np.ones(vocab_size, np.int64))
    return Word2VecModel(vocab, jnp.asarray(m))


def closed_loop(service, words: List[str], num: int, clients: int,
                duration_s: float) -> Dict:
    """N client threads issue queries back-to-back for ``duration_s``;
    returns qps + latency percentiles (the service's max sustainable
    throughput proxy at this client count)."""
    from glint_word2vec_tpu.serve import ServerOverloaded
    lats: List[List[float]] = [[] for _ in range(clients)]
    errs = [0] * clients
    stop_at = time.monotonic() + duration_s

    def client(ci: int) -> None:
        rng = np.random.default_rng(1000 + ci)
        while time.monotonic() < stop_at:
            w = words[int(rng.integers(0, len(words)))]
            t0 = time.monotonic()
            try:
                service.synonyms(w, num)
            except ServerOverloaded:
                errs[ci] += 1
                continue
            lats[ci].append((time.monotonic() - t0) * 1000)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    flat = [x for l in lats for x in l]
    return {"qps": round(len(flat) / wall, 1), "completed": len(flat),
            "refused": sum(errs), "p50_ms": pct(flat, 0.50),
            "p95_ms": pct(flat, 0.95), "p99_ms": pct(flat, 0.99)}


def offered_load(service, words: List[str], num: int, target_qps: float,
                 duration_s: float, workers: int = 16) -> Dict:
    """Open loop: arrivals scheduled at 1/target_qps intervals; a late
    worker pool means queueing shows up as latency/refusals, not as a
    silently slower arrival process."""
    from glint_word2vec_tpu.serve import ServerOverloaded
    n = max(1, int(target_qps * duration_s))
    start = time.monotonic() + 0.05
    arrivals = [start + i / target_qps for i in range(n)]
    lock = make_lock("tools.servebench.tickets")
    nxt = [0]
    lats: List[float] = []
    refused = [0]
    failed = [0]

    def worker() -> None:
        rng = np.random.default_rng(17)
        while True:
            with lock:
                i = nxt[0]
                if i >= n:
                    return
                nxt[0] += 1
            wait = arrivals[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            w = words[int(rng.integers(0, len(words)))]
            t0 = time.monotonic()
            try:
                service.synonyms(w, num)
            except ServerOverloaded:
                with lock:
                    refused[0] += 1
                continue
            except Exception:  # noqa: BLE001 — counted, not raised
                with lock:
                    failed[0] += 1
                continue
            dt = (time.monotonic() - t0) * 1000
            with lock:
                lats.append(dt)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - start
    done = len(lats)
    return {"target_qps": round(target_qps, 1),
            "achieved_qps": round(done / max(wall, 1e-9), 1),
            "offered": n, "completed": done, "refused": refused[0],
            "failed": failed[0],
            "refused_frac": round(refused[0] / max(n, 1), 4),
            "p50_ms": pct(lats, 0.50), "p99_ms": pct(lats, 0.99)}


def fleet_tier(args) -> Dict:
    """The fleet arms (ISSUE 12): N in-process replicas (each its own
    model instance + batcher; ONE shared IVF index — search is read-only)
    behind a FleetRouter. Reported at the half-capacity offered operating
    point like the single-service headline, N=1 vs N=3 on exact and ANN;
    then the hedge A/B: the same N=3 ANN fleet under a deterministic
    1-in-``--straggle-every`` batch stall of ``--straggle-ms``, hedge off
    vs hedge at the measured HEALTHY p99 (the provenance rule: hedge past
    the healthy tail, so duplicates stay rare — deriving from the
    straggled p99 would fire after the stall already resolved)."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve import (
        EmbeddingService, FleetRouter, ReplicaSet, build_ivf)

    v, d, n_rep = args.fleet_vocab, args.dim, args.fleet_replicas
    base = make_model(v, d, min(args.clusters, max(8, v // 64)), args.seed)
    matrix = np.array(base.syn0)  # forced copy: base's buffer is released
    vocab = base.vocab
    base.stop()
    index = build_ivf(matrix, nprobe=args.nprobe or 0, seed=args.seed)
    log(f"[fleet] shared IVF built: C={index.stats['centroids']} "
        f"recall@10={index.stats.get('recall_at_10')}")
    rng = np.random.default_rng(args.seed + 2)
    qwords = [vocab.words[i] for i in rng.integers(0, v, 2048)]
    num, dur = args.num, args.duration

    def build_fleet(n: int, ann: bool, hedge_ms: float,
                    straggle: bool):
        models = [Word2VecModel(vocab, jnp.asarray(matrix))
                  for _ in range(n)]
        # max_delay_ms=0: the router already spreads concurrency across N
        # batchers, so per-replica occupancy is low and the coalescing
        # deadline is pure added latency — the latency-critical setting
        # docs/serving.md §1 documents (queued requests still coalesce).
        # The straggler injection hits REPLICA 0 ONLY: one degraded node
        # in an otherwise healthy fleet is the scenario hedging exists
        # for (a fleet where EVERY replica stalls is a capacity problem,
        # not a tail problem — hedging provably cannot fix that)
        svcs = [EmbeddingService(
            model=m, ann=ann, ann_index=(index if ann else None),
            nprobe=args.nprobe or None, max_delay_ms=0.0,
            straggle_every=(args.straggle_every
                            if straggle and i == 0 else 0),
            straggle_ms=(args.straggle_ms
                         if straggle and i == 0 else 0.0))
            for i, m in enumerate(models)]
        router = FleetRouter(
            ReplicaSet.adopt(svcs), hedge_ms=hedge_ms, probe_s=0.25,
            retry_deadline_s=60.0)
        return router, models

    def run_arm(n: int, ann: bool, hedge_ms: float = 0.0,
                straggle: bool = False, target_qps: float = 0.0) -> Dict:
        router, models = build_fleet(n, ann, hedge_ms, straggle)
        try:
            router.synonyms(qwords[0], num)  # warm
            row: Dict = {}
            if not target_qps:
                cl = closed_loop(router, qwords, num, args.clients, dur)
                row["qps"] = cl["qps"]
                target_qps = max(cl["qps"], 1.0) / 2
            off = offered_load(router, qwords, num, target_qps,
                               min(dur, 2.0))
            row.update(target_qps=off["target_qps"], p50_ms=off["p50_ms"],
                       p99_ms=off["p99_ms"], refused=off["refused"],
                       failed=off["failed"])
            st = router.stats()
            row["hedges"] = st["hedges"]
            row["hedge_wins"] = st["hedge_wins"]
            return row
        finally:
            router.close()
            for m in models:
                m.stop()

    out: Dict = {"fleet_vocab": v, "fleet_replicas": n_rep,
                 "fleet_recall_at_10": index.stats.get("recall_at_10"),
                 # in-process replicas SHARE one read-only index; a real
                 # deployment pays one copy per replica host — both numbers
                 # derive from this (statusd's fleet scrape sums what each
                 # replica actually reports)
                 "fleet_index_bytes": index.stats.get("index_bytes")}
    half_targets: Dict = {}
    for ann in (False, True):
        arm = "ann" if ann else "exact"
        for n in (1, n_rep):
            row = run_arm(n, ann)
            half_targets[(n, ann)] = row["target_qps"]
            out[f"fleet{n}_{arm}_qps"] = row["qps"]
            out[f"fleet{n}_{arm}_p50_ms"] = row["p50_ms"]
            out[f"fleet{n}_{arm}_p99_ms"] = row["p99_ms"]
            log(f"[fleet] N={n} {arm}: {row['qps']} qps closed, half-cap "
                f"p50 {row['p50_ms']} ms p99 {row['p99_ms']} ms")
    # hedge A/B: same N=3 ANN fleet + injected straggler, same offered
    # target, hedge off vs hedge at the measured HEALTHY p99 (floored at
    # 5 ms): past the 99th percentile of the healthy distribution so
    # duplicates stay rare (~1% + the straggled fraction), but BEFORE the
    # straggler tail — deriving from the STRAGGLED p99 would fire after
    # the stall already resolved. This is the provenance rule documented
    # in docs/serving.md §5.
    healthy_p99 = out[f"fleet{n_rep}_ann_p99_ms"]
    hedge_delay = (max(5.0, healthy_p99)
                   if healthy_p99 == healthy_p99 else 5.0)  # NaN-safe
    target = half_targets[(n_rep, True)]
    offrow = run_arm(n_rep, True, hedge_ms=0.0, straggle=True,
                     target_qps=target)
    onrow = run_arm(n_rep, True, hedge_ms=hedge_delay, straggle=True,
                    target_qps=target)
    out["fleet_straggle"] = (
        f"r0:1/{args.straggle_every}x{args.straggle_ms}ms")
    out["fleet_hedge_delay_ms"] = round(hedge_delay, 3)
    out["fleet_hedge_off_p99_ms"] = offrow["p99_ms"]
    out["fleet_hedge_on_p99_ms"] = onrow["p99_ms"]
    out["fleet_hedges"] = onrow["hedges"]
    out["fleet_hedge_wins"] = onrow["hedge_wins"]
    out["fleet_hedge_p99_cut"] = (
        round(offrow["p99_ms"] / onrow["p99_ms"], 2)
        if onrow["p99_ms"] and onrow["p99_ms"] == onrow["p99_ms"] else None)
    log(f"[fleet] hedge A/B under straggler {out['fleet_straggle']}: "
        f"p99 {offrow['p99_ms']} ms (off) -> {onrow['p99_ms']} ms (on, "
        f"delay {hedge_delay:.1f} ms), {onrow['hedges']} hedges "
        f"({onrow['hedge_wins']} wins)")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--checkpoint", default="",
                    help="serve a real checkpoint instead of the synthetic "
                         "clustered matrix")
    ap.add_argument("--vocab", type=int, default=400_000,
                    help="synthetic vocabulary rows — sized so the exact "
                         "per-query arm sits in the regime the subsystem "
                         "exists to replace (tens of ms per query)")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--clusters", type=int, default=512)
    ap.add_argument("--num", type=int, default=10, help="top-k per query")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--duration", type=float, default=4.0,
                    help="seconds per closed-loop arm")
    ap.add_argument("--per-query", type=int, default=30,
                    help="sequential queries for the exact per-query arm")
    ap.add_argument("--nprobe", type=int, default=0, help="0 = auto")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", action="store_true",
                    help="add the fleet tier (ISSUE 12): N=1 vs N=3 "
                         "in-process replicas behind a FleetRouter on the "
                         "exact and ANN arms (half-capacity operating "
                         "point), plus the hedge A/B under an injected "
                         "1-in-N straggler")
    ap.add_argument("--fleet-replicas", type=int, default=3)
    ap.add_argument("--fleet-vocab", type=int, default=100_000,
                    help="fleet-tier vocabulary rows (N replica copies of "
                         "the matrix must coexist — smaller than the "
                         "single-service arms by design, recorded in the "
                         "JSON)")
    ap.add_argument("--straggle-every", type=int, default=3,
                    help="hedge A/B fault injection: every Nth batch of "
                         "REPLICA 0 (one degraded node) stalls "
                         "--straggle-ms (serve/batcher.py)")
    ap.add_argument("--straggle-ms", type=float, default=60.0)
    ap.add_argument("--shard-native", action="store_true",
                    help="add the shard-native build leg: save the bench "
                         "matrix as a row-shards checkpoint, build the "
                         "int8 index via build_ivf_from_shards (bounded "
                         "blocks, no dense [V,D] f32), and parity-check "
                         "its codes against the in-memory build")
    ap.add_argument("--smoke", action="store_true",
                    help="small + fast (CI): proves the harness, not the host")
    args = ap.parse_args()

    if args.smoke:
        args.vocab = min(args.vocab, 20_000)
        args.dim = min(args.dim, 64)
        args.clusters = min(args.clusters, 128)
        args.duration = min(args.duration, 1.0)
        args.clients = min(args.clients, 4)
        args.per_query = min(args.per_query, 8)
        args.fleet_vocab = min(args.fleet_vocab, 8_000)
        args.straggle_ms = min(args.straggle_ms, 40.0)

    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve import EmbeddingService

    if args.checkpoint:
        model = Word2VecModel.load(args.checkpoint)
        log(f"serving checkpoint {args.checkpoint}: V={model.num_words:,} "
            f"D={model.vector_size}")
    else:
        model = make_model(args.vocab, args.dim, args.clusters, args.seed)
        log(f"synthetic clustered matrix: V={args.vocab:,} D={args.dim} "
            f"({args.clusters} cells)")
    rng = np.random.default_rng(args.seed + 1)
    qwords = [model.vocab.words[i] for i in
              rng.integers(0, model.num_words, 4096)]

    # -- arm 1: exact per-query (the pre-subsystem baseline) ----------------
    model.norms  # materialize the cached norms outside the timed region
    for w in qwords[:3]:
        model.find_synonyms(w, args.num)  # warm the jit cache
    per_lats = []
    for w in qwords[:args.per_query]:
        t0 = time.monotonic()
        model.find_synonyms(w, args.num)
        per_lats.append((time.monotonic() - t0) * 1000)
    exact_pq = {"p50_ms": pct(per_lats, 0.50), "p95_ms": pct(per_lats, 0.95),
                "p99_ms": pct(per_lats, 0.99), "n": len(per_lats)}
    log(f"exact per-query: p50 {exact_pq['p50_ms']} ms over {len(per_lats)}")

    # -- arm 2: exact batched through the service ---------------------------
    svc = EmbeddingService(model=model, ann=False)
    svc.synonyms(qwords[0], args.num)  # warm
    exact_cl = closed_loop(svc, qwords, args.num, args.clients, args.duration)
    occupancy = svc.stats().get("occupancy_mean")
    svc.close()  # in-memory model= stays alive for the next arm
    log(f"exact batched: {exact_cl['qps']} qps, p50 {exact_cl['p50_ms']} ms, "
        f"p99 {exact_cl['p99_ms']} ms, occupancy {occupancy}")

    # -- arm 3: ANN batched through the service -----------------------------
    svc = EmbeddingService(model=model, ann=True,
                           nprobe=args.nprobe or None)
    ann_stats = dict(model.ann.stats)
    log(f"IVF built in {ann_stats['build_seconds']}s: "
        f"C={ann_stats['centroids']} nprobe={ann_stats['nprobe']} "
        f"recall@10={ann_stats.get('recall_at_10')}")
    svc.synonyms(qwords[0], args.num)  # warm
    ann_cl = closed_loop(svc, qwords, args.num, args.clients, args.duration)
    ann_occ = svc.stats().get("occupancy_mean")
    log(f"ann batched: {ann_cl['qps']} qps, p50 {ann_cl['p50_ms']} ms, "
        f"p99 {ann_cl['p99_ms']} ms, occupancy {ann_occ}")

    # -- arm 4: offered load (targets derived from the ANN capacity) --------
    offered_rows = []
    sustained = 0.0
    base = max(ann_cl["qps"], 1.0)
    for frac in (0.5, 1.0, 1.5):
        row = offered_load(svc, qwords, args.num, base * frac,
                           min(args.duration, 2.0))
        offered_rows.append(row)
        log(f"offered {row['target_qps']} qps: achieved "
            f"{row['achieved_qps']}, refused {row['refused_frac']:.1%}, "
            f"p50 {row['p50_ms']} ms, p99 {row['p99_ms']} ms")
        if row["refused_frac"] < 0.01 and row["failed"] == 0:
            sustained = max(sustained, row["achieved_qps"])
    svc.close()

    # -- arm 5: quantized indexes (ISSUE 18) --------------------------------
    # same closed loop over the int8 and PQ arms; recall floors stay AUTO
    # (the documented per-arm gates — a full-bench refusal here IS the
    # signal) except under --smoke, where toy-scale probe loss would fire
    # the floor about the host, not the code
    from glint_word2vec_tpu.serve import build_ivf
    matrix = np.asarray(model.syn0)
    quant_floor = 0.0 if args.smoke else -1.0
    quant_fields: Dict = {}
    f32_bytes = ann_stats.get("index_bytes") or 1
    for quant in ("int8", "pq"):
        qix = build_ivf(matrix, nprobe=args.nprobe or 0, seed=args.seed,
                        quant=quant, recall_floor=quant_floor)
        qstats = dict(qix.stats)
        qsvc = EmbeddingService(model=model, ann=True, ann_index=qix,
                                nprobe=args.nprobe or None)
        qsvc.synonyms(qwords[0], args.num)  # warm
        qcl = closed_loop(qsvc, qwords, args.num, args.clients,
                          args.duration)
        qsvc.close()
        quant_fields.update({
            f"{quant}_qps": qcl["qps"],
            f"{quant}_closed_p50_ms": qcl["p50_ms"],
            f"{quant}_closed_p99_ms": qcl["p99_ms"],
            f"{quant}_recall_at_10": qstats.get("recall_at_10"),
            f"{quant}_index_bytes": qstats["index_bytes"],
            f"{quant}_bytes_per_vector": qstats["bytes_per_vector"],
            f"{quant}_bytes_ratio": round(
                qstats["index_bytes"] / f32_bytes, 4),
            # the gateable direction: f32 bytes over quant bytes
            f"{quant}_bytes_cut": round(
                f32_bytes / max(qstats["index_bytes"], 1), 2),
            f"{quant}_qps_ratio": round(
                qcl["qps"] / max(ann_cl["qps"], 1e-9), 3),
            f"{quant}_build_s": qstats["build_seconds"],
        })
        if quant == "pq":
            quant_fields["pq_m"] = qstats.get("pq_m")
            quant_fields["pq_rerank"] = qstats.get("rerank")
        log(f"{quant} batched: {qcl['qps']} qps ("
            f"{quant_fields[f'{quant}_qps_ratio']}x f32-ann), recall@10 "
            f"{qstats.get('recall_at_10')}, "
            f"{qstats['bytes_per_vector']} B/vec "
            f"({quant_fields[f'{quant}_bytes_ratio']}x f32 bytes)")

    # -- shard-native build leg (--shard-native) ----------------------------
    if args.shard_native:
        import shutil
        import tempfile

        import jax.numpy as jnp

        from glint_word2vec_tpu.config import Word2VecConfig
        from glint_word2vec_tpu.serve import build_ivf_from_shards
        from glint_word2vec_tpu.train.checkpoint import save_model_sharded
        tmp = tempfile.mkdtemp(prefix="servebench-shards-")
        try:
            ck = os.path.join(tmp, "ck")
            cfg = Word2VecConfig(vector_size=model.vector_size, min_count=1)
            save_model_sharded(ck, model.vocab.words,
                               np.asarray(model.vocab.counts),
                               jnp.asarray(matrix), None, cfg,
                               vocab_size=model.num_words,
                               vector_size=model.vector_size)
            six = build_ivf_from_shards(
                ck, quant="int8", nprobe=args.nprobe or 0, seed=args.seed,
                recall_floor=quant_floor)
            # proof the stream is the same index: the in-memory int8 build
            # at the same seed produced bit-identical codes
            mem = build_ivf(matrix, nprobe=args.nprobe or 0,
                            seed=args.seed, quant="int8",
                            recall_floor=quant_floor)
            parity = bool(
                np.array_equal(mem._ids, six._ids)
                and np.array_equal(mem._storage._codes,
                                   six._storage._codes))
            quant_fields.update({
                "shard_native_build_s": six.stats["build_seconds"],
                "shard_native_recall_at_10": six.stats.get("recall_at_10"),
                "shard_native_index_bytes": six.stats["index_bytes"],
                "shard_native_parity": parity,
            })
            log(f"shard-native int8 build: "
                f"{six.stats['build_seconds']}s, recall@10 "
                f"{six.stats.get('recall_at_10')}, parity={parity}")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # operating-point latency: the half-capacity offered row (module doc)
    op = offered_rows[0]
    speedup = (round(exact_pq["p50_ms"] / op["p50_ms"], 2)
               if op["p50_ms"] == op["p50_ms"] and op["p50_ms"] else None)
    result = {
        "metric": "serving_qps_p99",
        "vocab_size": model.num_words,
        "dim": model.vector_size,
        "num": args.num,
        "clients": args.clients,
        "smoke": bool(args.smoke),
        "exact_per_query_p50_ms": exact_pq["p50_ms"],
        "exact_per_query_p99_ms": exact_pq["p99_ms"],
        "exact_qps": exact_cl["qps"],
        "exact_closed_p50_ms": exact_cl["p50_ms"],
        "exact_closed_p99_ms": exact_cl["p99_ms"],
        "exact_occupancy_mean": occupancy,
        "ann_qps": ann_cl["qps"],
        "ann_p50_ms": op["p50_ms"],
        "ann_p99_ms": op["p99_ms"],
        "ann_closed_p50_ms": ann_cl["p50_ms"],
        "ann_closed_p99_ms": ann_cl["p99_ms"],
        "ann_occupancy_mean": ann_occ,
        "ann_recall_at_10": ann_stats.get("recall_at_10"),
        "ann_centroids": ann_stats["centroids"],
        "ann_nprobe": ann_stats["nprobe"],
        "ann_build_s": ann_stats["build_seconds"],
        "ann_index_bytes": ann_stats.get("index_bytes"),
        "ann_bytes_per_vector": ann_stats.get("bytes_per_vector"),
        **quant_fields,
        # the ISSUE-10 acceptance headline: the batched ANN arm's
        # operating-point p50 vs the exact PER-QUERY p50 it replaces
        # (>= 10x at recall@10 >= 0.95)
        "ann_speedup_p50": speedup,
        "offered_qps_sustained": round(sustained, 1),
        "offered": offered_rows,
    }
    if args.fleet:
        model.stop()  # release the single-service matrix before N copies
        result.update(fleet_tier(args))
    print(json.dumps(result))  # the ONE stdout line (graftlint R7)
    return 0


if __name__ == "__main__":
    sys.exit(main())
