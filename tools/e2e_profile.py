"""Profile the e2e host pipeline: where do the pairs/s go between step and trainer?

Stages measured on the bench corpus (4M words, 50k vocab, Zipf):
    producer-only  — drain the Trainer's chunk_stream with no device work at all:
                     the host-side ceiling for any amount of pipelining
    pairgen-only   — raw epoch_batches drain (no K-stacking/packing/alpha)
    e2e fit        — the real thing (3 trials, median), with host-wait/dispatch split

Since round 13 the e2e leg RIDES THE TELEMETRY LAYER (docs/observability.md)
instead of private timers: each trial runs with a sink + spans + the
per-phase log2 histograms armed, and the report is the same per-phase
attribution (producer-wait / stage / dispatch / device-block, p50/p99/total)
every telemetry-on production run gets — one owner of e2e profiling, so this
tool can never drift from what the run log says. The run artifacts
(`run.jsonl`, `.trace.json`) are left under --out (default: a temp dir) for
Perfetto/run_report.py; the CLI contract (flags, human output on stderr) is
unchanged.

Run on TPU: python tools/e2e_profile.py [--batch 65536] [--pool 512] [--k 32]
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--pool", type=int, default=512)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--prefetch", type=int, default=8)
    ap.add_argument("--logits", default="float32")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--device-pairgen", action="store_true")
    ap.add_argument("--skip-host-stages", action="store_true")
    ap.add_argument("--skip-fit", action="store_true")
    ap.add_argument("--out", default="",
                    help="where the telemetry artifacts (run.jsonl + "
                         ".trace.json) land; default: a fresh temp dir")
    args = ap.parse_args()

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.pipeline import encode_sentences, epoch_batches
    from glint_word2vec_tpu.data.vocab import build_vocab
    from glint_word2vec_tpu.train.trainer import Trainer

    out_dir = args.out or tempfile.mkdtemp(prefix="glint_e2e_profile_")
    os.makedirs(out_dir, exist_ok=True)
    run_log = os.path.join(out_dir, "run.jsonl")

    rng = np.random.default_rng(0)
    n_words, sent_len, vocab_sz = 4_000_000, 40, 50_000
    zipf = 1.0 / (np.arange(vocab_sz) + 10.0) ** 1.05
    ids = rng.choice(vocab_sz, size=n_words, p=zipf / zipf.sum())
    words = np.char.add("w", ids.astype("U8"))
    sentences = [list(words[i:i + sent_len]) for i in range(0, n_words, sent_len)]
    vocab = build_vocab(sentences, min_count=5)
    cfg = Word2VecConfig(
        vector_size=300, min_count=5, pairs_per_batch=args.batch,
        num_iterations=1, window=5, negatives=5, negative_pool=args.pool,
        steps_per_dispatch=args.k, seed=1, subsample_ratio=1e-4,
        prefetch_chunks=args.prefetch, logits_dtype=args.logits,
        param_dtype=args.param_dtype, device_pairgen=args.device_pairgen,
        telemetry_path=run_log)
    encoded = encode_sentences(sentences, vocab, cfg.max_sentence_length)

    trainer = Trainer(cfg, vocab)
    from glint_word2vec_tpu.data.native import native_available
    print(f"native pairgen: {native_available()}  device_pairgen: "
          f"{cfg.device_pairgen}  telemetry -> {run_log}", file=sys.stderr)
    if cfg.device_pairgen:
        print(f"tokens_per_step: {trainer._tokens_per_step}", file=sys.stderr)

    if not args.skip_host_stages:
        # --- pairgen-only ----------------------------------------------------
        t0 = time.perf_counter()
        pairs = 0
        for b in epoch_batches(encoded, vocab, pairs_per_batch=args.batch,
                               window=5, subsample_ratio=1e-4, seed=1,
                               iteration=1):
            pairs += b.num_real_pairs
        dt = time.perf_counter() - t0
        print(f"pairgen-only : {pairs:,} pairs in {dt:.2f}s -> "
              f"{pairs / dt:,.0f} pairs/s", file=sys.stderr)

        # --- producer-only (batch stream + packing, no device) ---------------
        t0 = time.perf_counter()
        pairs = 0
        K = cfg.steps_per_dispatch
        pending = 0
        pack = np.empty((K, 2, args.batch), trainer._pair_dtype)
        for b in trainer._batch_stream(encoded, 1):
            pack[pending % K, 0] = b["centers"]
            pack[pending % K, 1] = b["contexts"]
            pairs += b["real"]
            pending += 1
        dt = time.perf_counter() - t0
        print(f"producer-only: {pairs:,} pairs in {dt:.2f}s -> "
              f"{pairs / dt:,.0f} pairs/s (batch stream + packing)",
              file=sys.stderr)

    if args.skip_fit:
        return

    # --- full e2e, attributed through the telemetry layer --------------------
    import jax.numpy as jnp
    trainer.fit(encoded[:400])  # warm jit
    rates = []
    for trial in range(3):
        trainer.state = type(trainer.state)()
        trainer.pairs_trained = 0.0
        t0 = time.perf_counter()
        trainer.fit(encoded)
        float(jnp.sum(trainer.params.syn0[:128]))
        dt = time.perf_counter() - t0
        rates.append(trainer.pairs_trained / dt)
        print(f"  e2e trial {trial}: {trainer.pairs_trained:,.0f} pairs in {dt:.1f}s "
              f"-> {rates[-1]:,.0f} pairs/s [host-wait {trainer.host_wait_time:.2f}s "
              f"dispatch {trainer.dispatch_time:.2f}s]", file=sys.stderr)
        if not np.isfinite(float(jnp.sum(trainer.params.syn0[:1024]))):
            raise RuntimeError("diverged")
    print(f"e2e median: {float(np.median(rates)):,.0f} pairs/s", file=sys.stderr)
    # per-phase attribution of the LAST trial (obs/phases.py — the same
    # rollup the run log's run_end record carries)
    phases = trainer.last_run_stats.get("phases", {})
    for name in ("producer_wait", "stage", "dispatch", "device_block"):
        ph = phases.get(name)
        if not ph:
            continue
        print(f"  phase {name:14s} count {ph['count']:>6}  "
              f"total {ph['total_s']:8.2f}s  p50 {ph['p50_s']:.2e}s  "
              f"p99 {ph['p99_s']:.2e}s  max {ph['max_s']:.3f}s",
              file=sys.stderr)
    print(f"artifacts: {run_log} (+ .trace.json) — summarize with "
          f"tools/run_report.py, tail with tools/telemetry_tail.py",
          file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
