"""Benchmark: fused SGNS training throughput (word-pairs/sec + MFU) on one chip.

Round-4 contract (VERDICT r3 items 2/10): every published number comes from a config
with *stability evidence* — the headline step config must appear in EVAL_RUNS.jsonl
(written by tools/eval_quality.py) as a ≥60M-word run that did NOT diverge, or the
bench refuses to headline it and falls back. The r3 headline (B=64k/pool=64) trained
to NaN in EVAL; its row is kept below as frontier context only, clearly marked.

Measured rows (stderr; e2e first — step benches leave allocator state behind that
throttles the host producer):

    e2e trainer (device feed) — Word2Vec-style end-to-end incl. vocab/windowing;
        on-device pair generation (ops/pairgen.py): the host ships kept-token blocks
        (~1 byte/pair), the jitted chunk derives subsample/window draws itself.
        Medians of 3 trials.
    e2e trainer (host feed)  — the packed-uint16-pairs feed, for comparison.
    step rows — the trainer-shaped jitted step (scan-chunked, hash-PRNG negatives)
        at EVAL-stable geometries: pool scaled to batch per the load<=600 rule the
        60M-word runs validated. f32 and bf16 storage; bf16 negative-logit chain
        (config.logits_dtype) on the bf16 row — PERF.md §4's one real lever.
    cbow rows — scatter (shipped default) and banded (cbow_update="banded",
        ops/cbow_banded.py) CBOW steps at the same pool list as the SGNS rows;
        the JSON line records cbow_step_ms / cbow_banded_examples_per_sec /
        cbow_banded_step_ms so the trajectory captures the banded win.
    step pool=64 (UNSTABLE) — the r3 headline geometry, context only: fastest
        per-step but EVAL-measured divergent at scale. Never the headline.
    V=1M scaling — the same step at a 1M-row vocabulary (~3 GB pair at f32; run at
        bf16), plus alias-table build and find_synonyms top-k timings: BASELINE
        config 3's single-chip shadow (no data above 200k vocab existed before).
    cpu-torch — identical step math on the host CPU at the SAME batch as e2e, so
        vs_baseline is one honest basis: TPU end-to-end vs CPU compute-only loop
        (the CPU number has no host pipeline, which *flatters* the baseline).
    host rows — tools/hostbench.py small tier (interleaved serial-vs-parallel
        medians): producer_tokens_per_sec, ckpt_save_s/ckpt_load_s/export_s,
        vocab_build_s/alias_build_s — the ISSUE-3 host data-plane trajectory.

Timing: two-point slopes over donated, data-dependent chunk chains closed by a
dependent device→host fetch (tools/microbench.py). MFU is reported because
BASELINE names it; the step is scatter-emitter-bound (~27 ns/update-row), not
FLOP-bound — see PERF.md for the cost model and why the ≥50% MFU north star
cannot apply to SGNS.

Device rows run on a TPU only: on any other platform, or on a device_kind
missing from DEVICE_PEAKS, the bench refuses instead of writing a CPU timing
under a device metric's name. A phase that raises fails the run. The JSON line
is stamped with platform, device_kind and device count.

Prints exactly ONE JSON line on stdout; all tables go to stderr.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))

V, D, NEG = 200_000, 300, 5
PAD_D = 384        # lane-padded physical dim (config.pad_vector_to_lanes)
K = 16             # steps per dispatch chunk (step rows)
B_MAIN = 65536
E2E_K = 32
E2E_POOL = 512     # EVAL_RUNS-validated at 60M words (load 640, bf16+f32)
E2E_SUBSAMPLE = 1e-4  # the stability-evidence subsample ratio: the SAME key at
                      # 1e-3 is measured-divergent (EVAL round-4 addendum), so
                      # the headline gate matches on it too
CPU_STEPS = 3
V_SCALE = 1_000_000

# Published per-chip peaks keyed by jax's device_kind. Source: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s. A device
# that is not in this table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_sec": 819e9},
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# the ONE dtype short-label map: "bfloat16"[:4] truncation drifted into the
# r01-r05 "bflo" label typo ("e2e_feed": "device_bflo"); every label and JSON
# key goes through here so it cannot drift again. Perfgate gates only the
# numeric fields, so the archived rungs stay comparable.
_SHORT_DTYPE = {"float32": "f32", "bfloat16": "bf16"}


def device_stamp() -> dict:
    """What every JSON line says about where it ran."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def device_peaks() -> dict:
    """Peaks of the chip under test; refuses anything that is not a known TPU."""
    stamp = device_stamp()
    if stamp["platform"] != "tpu":
        raise SystemExit(
            f"bench: device rows need a TPU, found platform "
            f"{stamp['platform']!r} — refusing to time another backend under "
            "a device metric's name")
    if stamp["device_kind"] not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: no published peaks for device_kind "
            f"{stamp['device_kind']!r}; add it to DEVICE_PEAKS with its source")
    return DEVICE_PEAKS[stamp["device_kind"]]


def zipf_counts(v: int) -> np.ndarray:
    return np.maximum(1e9 / (np.arange(v) + 10.0) ** 1.07, 5.0)


def step_flops(pool: int, b: int) -> float:
    """Matmul FLOPs per step of the shared-pool path: f_neg (B,D)x(D,P),
    d_in += g_neg@Z (B,P)x(P,D), d_Z = g_negT@e_in (P,B)x(B,D), plus elementwise."""
    return 3 * 2.0 * b * pool * PAD_D + 10.0 * b * PAD_D


_ZIPF_P = {}


def _zipf_indices(rng, shape, v=V) -> np.ndarray:
    """Batch indices with the corpus's own frequency profile — uniform indices
    understate the real step cost (duplicate handling inside XLA's scatter)."""
    if v not in _ZIPF_P:
        c = zipf_counts(v)
        _ZIPF_P[v] = c / c.sum()
    return rng.choice(v, size=shape, p=_ZIPF_P[v])


def load_eval_stability(repo_root: str) -> list:
    path = os.path.join(repo_root, "EVAL_RUNS.jsonl")
    rows = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return rows


def eval_stable(rows: list, batch: int, pool: int, param_dtype: str,
                logits_dtype: str, subsample_ratio: float) -> bool:
    """True iff tools/eval_quality.py trained this geometry on >=60M words without
    divergence. The bench REFUSES to headline configs without this evidence.
    The match key is the FULL stability-relevant config — (batch, pool,
    param_dtype, logits_dtype, subsample_ratio) — because EVAL_RUNS holds both a
    stable (64k, 512, bf16, subsample 1e-4) and a divergent (same, 1e-3) row:
    matching on the first three alone would bless the measured-NaN config
    (VERDICT r4 weak #3). Rescored rows don't count: their config metadata comes
    from CLI flags, unverified against the saved model they re-scored."""
    for r in rows:
        if (not r.get("rescored")
                and r.get("pairs_per_batch") == batch
                and r.get("negative_pool") == pool
                and r.get("param_dtype") == param_dtype
                and r.get("logits_dtype") == logits_dtype
                and r.get("subsample_ratio") == subsample_ratio
                and r.get("corpus_words", 0) >= 60_000_000
                and not r.get("diverged")):
            return True
    return False


def bench_step(counts, b: int, pool: int, dtype: str = "float32",
               param_dtype: str = "float32", logits_dtype: str = "float32",
               v: int = V, label_extra: str = "", fused: bool = False,
               chain: bool = False) -> tuple:
    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives_hash
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, init_embeddings, sgns_step_shared_core)

    table = build_alias_table(counts)
    prob, alias = table.prob, table.alias
    pdt = jnp.dtype(param_dtype)
    cdt = jnp.dtype(dtype)
    ldt = jnp.dtype(logits_dtype)
    syn0_0 = init_embeddings(v, PAD_D, jax.random.key(0)).syn0.astype(pdt)
    rng = np.random.default_rng(0)
    syn1_0 = jnp.asarray(rng.standard_normal((v, PAD_D), np.float32) * 0.05, pdt)

    def chunk(params, batches, base_step, prob, alias):
        negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, pool))

        def body(p, inp):
            batch, ng = inp
            # with_metrics=False: the production steady state — the trainer
            # dispatches the metrics-elided twin for every chunk without a
            # heartbeat (~6 of 7 dispatches at the default cadence); the fetch
            # below pulls from the PARAMS carry, which depends on every update
            new_p, m = sgns_step_shared_core(
                p, batch["centers"], batch["contexts"], batch["mask"],
                ng, jnp.float32(0.025), NEG, "exact", cdt, False, ldt,
                with_metrics=False, fused=fused, bf16_chain=chain)
            return new_p, m.loss

        return jax.lax.scan(body, params, (batches, negs))

    f = jax.jit(chunk, donate_argnums=(0,))

    all_batches = []
    for i in range(8):
        r = np.random.default_rng(1000 + i)
        all_batches.append({
            "centers": jnp.asarray(_zipf_indices(r, (K, b), v), jnp.int32),
            "contexts": jnp.asarray(_zipf_indices(r, (K, b), v), jnp.int32),
            "mask": jnp.ones((K, b), jnp.float32),
        })

    def run(p, batches, base):
        return f(p, batches, base, prob, alias)

    ts = []
    for _ in range(3):
        spc = time_chunked(
            run,
            make_carry=lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
            args_for_iter=lambda i: (all_batches[i % 8], np.int32(100 + i)),
            n_lo=2, n_hi=8,
            # the loss channel is elided (constant 0) — the barrier fetch MUST
            # depend on the updated params or the chain can be elided
            fetch=lambda c, out: c.syn0[0, 0].astype(jnp.float32))
        ts.append(spc / K)
    spp = float(np.median(ts))
    ms = spp * 1e3
    pps = b / spp
    mfu = step_flops(pool, b) / spp / device_peaks()["bf16_flops"]
    # min/median/max across the interleaved trials (VERDICT r8 item 4): the
    # published number is the median; the spread is the honesty bar for it
    stats = {"ms_min": round(min(ts) * 1e3, 4),
             "ms_median": round(ms, 4),
             "ms_max": round(max(ts) * 1e3, 4)}
    label = (f"xla {_SHORT_DTYPE.get(param_dtype)}"
             f"/logits-{_SHORT_DTYPE.get(logits_dtype)}{label_extra}")
    log(f"step {label:26s} V={v:8,d} B={b:6d} pool={pool:5d}: {ms:7.3f} ms/step"
        f" [{stats['ms_min']:.3f}-{stats['ms_max']:.3f}]"
        f" -> {pps:13,.0f} pairs/s  mfu={mfu * 100:5.2f}%")
    return pps, mfu, stats


def bench_cbow_step(counts, b: int, pools, param_dtype: str = "bfloat16",
                    window: int = 5) -> dict:
    """CBOW shared-pool SCATTER step (BASELINE config 5): grouped [B, 2w] context
    windows, hidden = masked context mean, negatives from the shared pool.
    Benches every pool in ``pools`` (the same list the SGNS step rows use, so
    CBOW and SGNS geometry stay comparable round to round) over one shared
    batch/embedding setup. Returns {pool: (examples_per_sec, ms_per_step)}."""
    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives_hash
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, cbow_step_shared_core, init_embeddings)

    C = 2 * window
    table = build_alias_table(counts)
    prob, alias = table.prob, table.alias
    pdt = jnp.dtype(param_dtype)
    syn0_0 = init_embeddings(V, PAD_D, jax.random.key(0)).syn0.astype(pdt)
    rng = np.random.default_rng(0)
    syn1_0 = jnp.asarray(rng.standard_normal((V, PAD_D), np.float32) * 0.05, pdt)

    all_batches = []
    for i in range(6):
        r = np.random.default_rng(3000 + i)
        nctx = r.integers(1, C + 1, (K, b))
        all_batches.append({
            "centers": jnp.asarray(_zipf_indices(r, (K, b)), jnp.int32),
            "contexts": jnp.asarray(_zipf_indices(r, (K, b, C)), jnp.int32),
            "ctx_mask": jnp.asarray(
                np.arange(C)[None, None, :] < nctx[..., None], jnp.float32),
            "mask": jnp.ones((K, b), jnp.float32),
        })

    out = {}
    for pool in pools:
        def chunk(params, batches, base_step, prob, alias, pool=pool):
            negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, pool))

            def body(p, inp):
                batch, ng = inp
                # with_metrics=False + params-carry fetch below: the same
                # metrics-elided production regime bench_step measures — the
                # trainer dispatches the elided twin on the CBOW shared-pool
                # path too, so the CBOW and SGNS step rows stay comparable
                new_p, m = cbow_step_shared_core(
                    p, batch["centers"], batch["contexts"], batch["ctx_mask"],
                    batch["mask"], ng, jnp.float32(0.025), NEG, "exact", pdt,
                    jnp.bfloat16 if param_dtype == "bfloat16" else jnp.float32,
                    with_metrics=False)
                return new_p, m.loss

            return jax.lax.scan(body, params, (batches, negs))

        f = jax.jit(chunk, donate_argnums=(0,))
        ts = []
        for _ in range(3):
            spc = time_chunked(
                lambda p, bt, base: f(p, bt, base, prob, alias),
                make_carry=lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                args_for_iter=lambda i: (all_batches[i % 6], np.int32(100 + i)),
                n_lo=2, n_hi=8,
                # loss is elided — the barrier fetch must depend on the updated
                # params or the whole chain can be elided (same as bench_step)
                fetch=lambda c, out: c.syn0[0, 0].astype(jnp.float32))
            ts.append(spc / K)
        spp = float(np.median(ts))
        # a CBOW "example" trains ~mean(nctx) positive word-context links;
        # report examples/s (the step unit) and links/s for pair comparison
        eps = b / spp
        short = _SHORT_DTYPE[param_dtype]
        log(f"step cbow scatter {short:9s} V={V:8,d} B={b:6d} "
            f"pool={pool:5d}: {spp * 1e3:7.3f} ms/step -> {eps:13,.0f} "
            f"examples/s (~{eps * (C + 1) / 2:,.0f} word-link/s)")
        out[pool] = (eps, spp * 1e3)
    return out


def bench_cbow_banded_step(counts, b: int, pools, param_dtype: str = "bfloat16",
                           window: int = 5) -> dict:
    """Banded CBOW step (config.cbow_update="banded", ops/cbow_banded.py):
    sentence-contiguous halo token blocks, window intervals derived on device
    from the hash lattice, context traffic via prefix sums — ~B update rows
    instead of B·C. Trainer-shaped chunk (scan + hash-PRNG negatives +
    metrics-elided), same pool list as the scatter row. Examples/s counts the
    REAL examples trained (~(w−1)/w of the B core slots; the scatter row's
    batches are dense, so the two rows are comparable on examples/s, not
    ms/step). Returns {pool: (examples_per_sec, ms_per_step)}."""
    import jax
    import jax.numpy as jnp
    from cbow_feed import make_banded_chunk, pack_banded_feeds
    from microbench import time_chunked

    from glint_word2vec_tpu.data.hashrng import (
        STREAM_WINDOW, hash_mod_at, stream_base)
    from glint_word2vec_tpu.ops.sampler import build_alias_table
    from glint_word2vec_tpu.ops.sgns import EmbeddingPair, init_embeddings

    H = window
    T = b + 2 * H
    n_sets = 6
    table = build_alias_table(counts)
    prob, alias = table.prob, table.alias
    pdt = jnp.dtype(param_dtype)
    ldt = jnp.bfloat16 if param_dtype == "bfloat16" else jnp.float32
    syn0_0 = init_embeddings(V, PAD_D, jax.random.key(0)).syn0.astype(pdt)
    rng = np.random.default_rng(0)
    syn1_0 = jnp.asarray(rng.standard_normal((V, PAD_D), np.float32) * 0.05, pdt)

    # one synthetic kept-token stream with the corpus's frequency profile,
    # 40-token sentences, cut into halo blocks exactly like the trainer feed
    stream_len = n_sets * K * b + 2 * H
    toks = _zipf_indices(rng, stream_len).astype(np.int32)
    starts = np.zeros(stream_len, bool)
    starts[::40] = True
    win_base = stream_base(1234, STREAM_WINDOW, 1, 0)
    feeds = pack_banded_feeds(toks, starts, T, H, n_sets, K)
    # real examples per step: live window draws among the core tokens
    bdraw = hash_mod_at(
        win_base, np.arange(n_sets * K * b, dtype=np.uint64), window)
    live_rate = float((bdraw >= 1).mean())  # boundary clipping ~negligible @40
    real_per_step = b * live_rate

    out = {}
    for pool in pools:
        f = jax.jit(make_banded_chunk(window, pool, NEG, pdt, ldt,
                                      win_base, K),
                    donate_argnums=(0,))
        ts = []
        for _ in range(3):
            spc = time_chunked(
                lambda p, bt, base: f(p, bt, base, prob, alias),
                make_carry=lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                args_for_iter=lambda i: (feeds[i % n_sets], np.int32(100 + i)),
                n_lo=2, n_hi=8,
                fetch=lambda c, out: c.syn0[0, 0].astype(jnp.float32))
            ts.append(spc / K)
        spp = float(np.median(ts))
        eps = real_per_step / spp
        short = _SHORT_DTYPE[param_dtype]
        log(f"step cbow banded  {short:9s} V={V:8,d} B={b:6d} "
            f"pool={pool:5d}: {spp * 1e3:7.3f} ms/step -> {eps:13,.0f} "
            f"examples/s ({real_per_step:,.0f} real ex/step)")
        out[pool] = (eps, spp * 1e3)
    return out


_E2E_CORPUS = None


def e2e_corpus():
    """The shared e2e corpus (vocab + encoded sentences) — built once; both feed
    modes and every trial reuse it (building it twice cost ~1 min of bench wall)."""
    global _E2E_CORPUS
    if _E2E_CORPUS is None:
        from glint_word2vec_tpu.data.pipeline import encode_sentences
        from glint_word2vec_tpu.data.vocab import build_vocab
        rng = np.random.default_rng(0)
        n_words, sent_len, vocab_sz = 4_000_000, 40, 50_000
        zipf = 1.0 / (np.arange(vocab_sz) + 10.0) ** 1.05
        ids = rng.choice(vocab_sz, size=n_words, p=zipf / zipf.sum())
        words = np.char.add("w", ids.astype("U8"))
        sentences = [list(words[i:i + sent_len])
                     for i in range(0, n_words, sent_len)]
        vocab = build_vocab(sentences, min_count=5)
        encoded = encode_sentences(sentences, vocab, 1000)
        _E2E_CORPUS = (vocab, encoded)
    return _E2E_CORPUS


def bench_e2e(device_pairgen: bool, param_dtype: str, logits_dtype: str,
              pool: int) -> tuple:
    """End-to-end Word2Vec-style fit on a synthetic Zipf corpus — includes vocab
    build, subsampling, window generation, feed transfer. Returns
    (median pairs/s, host_wait_fraction)."""
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.train.trainer import Trainer

    vocab, encoded = e2e_corpus()
    cfg = Word2VecConfig(
        vector_size=D, min_count=5, pairs_per_batch=B_MAIN, num_iterations=1,
        window=5, negatives=NEG, negative_pool=pool, steps_per_dispatch=E2E_K,
        seed=1, subsample_ratio=E2E_SUBSAMPLE, device_pairgen=device_pairgen,
        param_dtype=param_dtype, compute_dtype=param_dtype,
        logits_dtype=logits_dtype)
    trainer = Trainer(cfg, vocab)
    trainer.fit(encoded[:400])  # warm the jit cache
    rates, hw = [], []
    for trial in range(3):
        trainer.state = type(trainer.state)()
        trainer.pairs_trained = 0.0
        t0 = time.perf_counter()
        trainer.fit(encoded)
        # a dependent fetch closes the timed region
        float(jnp.sum(trainer.params.syn0[:128].astype(jnp.float32)))
        dt = time.perf_counter() - t0
        rates.append(trainer.pairs_trained / dt)
        hw.append(trainer.host_wait_time / dt)
        if not np.isfinite(float(jnp.sum(
                trainer.params.syn0[:1024].astype(jnp.float32)))):
            raise RuntimeError("e2e training diverged (NaN params) — the bench "
                               "must measure a run that actually learns")
        log(f"  e2e trial {trial}: {trainer.pairs_trained:,.0f} pairs in {dt:.1f}s"
            f" -> {rates[-1]:,.0f} pairs/s  [host-wait {trainer.host_wait_time:.2f}s"
            f" dispatch {trainer.dispatch_time:.2f}s]")
    med = int(np.argsort(rates)[1])  # index of the median-rate trial
    feed = "device feed" if device_pairgen else "host feed"
    log(f"e2e trainer ({feed}, {param_dtype}, pool={pool}): median "
        f"{float(np.median(rates)):,.0f} pairs/s over 3 trials")
    return float(np.median(rates)), float(hw[med])


def bench_scale_1m() -> dict:
    """V=1M rows (BASELINE config 3's single-chip shadow): alias build,
    step throughput, find_synonyms top-k — none of which had data above 200k."""
    import jax
    import jax.numpy as jnp

    out = {}
    counts = zipf_counts(V_SCALE)
    t0 = time.perf_counter()
    from glint_word2vec_tpu.ops.sampler import build_alias_table
    build_alias_table(counts)
    out["alias_build_s"] = time.perf_counter() - t0
    log(f"V=1M alias table build: {out['alias_build_s']:.2f}s (host, O(2V))")

    pps, _, stats = bench_step(counts, b=B_MAIN, pool=E2E_POOL, dtype="bfloat16",
                               param_dtype="bfloat16", logits_dtype="bfloat16",
                               v=V_SCALE)
    out["step_bf16_pairs_per_sec"] = pps
    out["step_trials_ms"] = stats

    # find_synonyms: sharded matvec + top-k over 1M rows (model ops G5/C8)
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    words = np.char.add("w", np.arange(V_SCALE).astype("U8"))
    vocab = Vocabulary.from_words_and_counts(list(words), counts.astype(np.int64))
    # create the 1.2 GB test embedding ON device — a host array here would
    # time the host->device copy, not the model op
    syn0 = jax.random.normal(jax.random.key(1), (V_SCALE, D), jnp.float32) * 0.1
    syn0.block_until_ready()
    model = Word2VecModel(vocab, syn0, syn1=None,
                          config=Word2VecConfig(vector_size=D))
    model.find_synonyms("w0", 10)  # compile + warm
    t0 = time.perf_counter()
    for i in range(5):
        model.find_synonyms(f"w{i + 1}", 10)
    out["find_synonyms_ms"] = (time.perf_counter() - t0) / 5 * 1e3
    log(f"V=1M find_synonyms(top-10): {out['find_synonyms_ms']:.1f} ms/query "
        "(matvec + top-k over 1M rows)")
    # batched variant: one [64, V] dispatch amortizes the per-query launch and
    # fetch (models/word2vec.py find_synonyms_batch)
    qs = [f"w{i + 10}" for i in range(64)]
    model.find_synonyms_batch(qs, 10, chunk=64)  # compile + warm
    t0 = time.perf_counter()
    model.find_synonyms_batch(qs, 10, chunk=64)
    out["find_synonyms_batch_ms"] = (time.perf_counter() - t0) / 64 * 1e3
    log(f"V=1M find_synonyms_batch(64 queries): "
        f"{out['find_synonyms_batch_ms']:.1f} ms/query")
    model.stop()
    return out


def bench_cpu_torch(b: int) -> float:
    """Same step math on host CPU with torch at the SAME batch as e2e — the
    vs_baseline denominator (compute-only: no host pipeline, flatters the CPU)."""
    import torch

    vocab_sz = 50_000
    counts = zipf_counts(vocab_sz)
    torch.manual_seed(0)
    g = torch.Generator().manual_seed(0)
    syn0 = (torch.rand(vocab_sz, D, generator=g) - 0.5) / D
    syn1 = torch.zeros(vocab_sz, D)
    probs = torch.tensor(counts ** 0.75, dtype=torch.float64)
    probs /= probs.sum()
    alpha = 0.025
    rng = np.random.default_rng(0)
    centers = torch.tensor(_zipf_indices(rng, b, vocab_sz), dtype=torch.long)
    contexts = torch.tensor(_zipf_indices(rng, b, vocab_sz), dtype=torch.long)

    def step():
        negatives = torch.multinomial(probs.float(), E2E_POOL, replacement=True)
        e_in = syn0[centers]
        e_pos = syn1[contexts]
        Z = syn1[negatives]
        f_pos = (e_in * e_pos).sum(-1)
        f_neg = e_in @ Z.T
        neg_valid = (negatives[None, :] != contexts[:, None]).float()
        g_pos = (1 - torch.sigmoid(f_pos)) * alpha
        g_neg = (0 - torch.sigmoid(f_neg)) * alpha * neg_valid * (NEG / E2E_POOL)
        d_in = g_pos[:, None] * e_pos + g_neg @ Z
        syn0.index_add_(0, centers, d_in)
        syn1.index_add_(0, contexts, g_pos[:, None] * e_in)
        syn1.index_add_(0, negatives, g_neg.T @ e_in)

    step()  # warmup
    t0 = time.perf_counter()
    for _ in range(CPU_STEPS):
        step()
    dt = time.perf_counter() - t0
    pps = CPU_STEPS * b / dt
    log(f"cpu-torch baseline (B={b}, pool={E2E_POOL}): {CPU_STEPS} steps in "
        f"{dt:.2f}s -> {pps:,.0f} pairs/s (compute only, no host pipeline)")
    return pps


def main() -> None:
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    stamp = device_stamp()
    device_peaks()  # refuses a non-TPU platform or an unknown device_kind
    log(f"device: {stamp}")
    repo_root = os.path.dirname(os.path.abspath(__file__))
    eval_rows = load_eval_stability(repo_root)
    counts = zipf_counts(V)

    # e2e rows FIRST (allocator state from step benches throttles the producer)
    e2e = {}
    for dp, pdt, ldt in ((True, "bfloat16", "bfloat16"),
                         (False, "float32", "float32")):
        key = f"{'device' if dp else 'host'}_{_SHORT_DTYPE[pdt]}"
        e2e[key] = bench_e2e(dp, pdt, ldt, E2E_POOL)

    rows = {}
    rows["f32_p512"] = bench_step(counts, B_MAIN, E2E_POOL)
    rows["bf16_p512"] = bench_step(counts, B_MAIN, E2E_POOL, dtype="bfloat16",
                                   param_dtype="bfloat16",
                                   logits_dtype="bfloat16")
    # logits bf16 on the p1024 row too: that is the config EVAL_RUNS holds
    # stability evidence for (the gate matches on logits_dtype now)
    rows["bf16_p1024"] = bench_step(counts, B_MAIN, 1024, dtype="bfloat16",
                                    param_dtype="bfloat16",
                                    logits_dtype="bfloat16")
    # ISSUE-14 step-restructuring rows at the headline geometry, LAYERED so
    # the trajectory shows which layer pays (PERF.md §11): the fused
    # coefficient chain alone, + the end-to-end bf16 chain. Never the
    # headline until their geometry carries its own EVAL evidence.
    bf16kw = dict(dtype="bfloat16", param_dtype="bfloat16",
                  logits_dtype="bfloat16")
    rows["bf16_fused"] = bench_step(
        counts, B_MAIN, E2E_POOL, fused=True,
        label_extra=" +fused", **bf16kw)
    rows["bf16_chain"] = bench_step(
        counts, B_MAIN, E2E_POOL, fused=True, chain=True,
        label_extra=" +fused+chain", **bf16kw)
    # CBOW rows at the same pool list as the SGNS step rows (comparable
    # geometry round to round): scatter (shipped default) and banded
    # (cbow_update="banded" — the ISSUE-2 prefix-sum path; step_ab.py --cbow
    # is the same-session interleaved A/B of the two)
    cbow_pools = (E2E_POOL, 1024)
    cbow_rows = bench_cbow_step(counts, B_MAIN, cbow_pools)
    cbow_banded_rows = bench_cbow_banded_step(counts, B_MAIN, cbow_pools)
    # frontier context ONLY: EVAL-measured divergent at training scale
    bench_step(counts, B_MAIN, 64, label_extra=" [UNSTABLE @64]")
    log("  ^ pool=64 row is frontier context only: EVAL measured this "
        "geometry training to NaN — never the headline")

    scale = bench_scale_1m()

    # host data-plane rows (ISSUE-3): producer tokens/s + checkpoint/export/
    # cold-start wall clock via the interleaved hostbench harness, so
    # BENCH_r06+ tracks the host trajectory alongside the step/e2e rows
    import hostbench
    # hostbench.run (not .main): the bench's contract is ONE JSON line on
    # stdout, so the host row merges into the result instead of printing
    host = hostbench.run(["--scale", "small",
                          "--workers", str(min(os.cpu_count() or 1, 8))])

    cpu_pps = bench_cpu_torch(B_MAIN)

    # headline: fastest STEP row whose geometry has >=60M-word non-divergent
    # EVAL evidence (the r3 failure mode: headlining a config that NaNs)
    dtype_of = {"f32_p512": ("float32", E2E_POOL, "float32"),
                "bf16_p512": ("bfloat16", E2E_POOL, "bfloat16"),
                "bf16_p1024": ("bfloat16", 1024, "bfloat16")}
    stable_keys = [k for k in rows
                   if k in dtype_of  # restructured rows never headline (they
                                     # need their own EVAL evidence per arm)
                   and eval_stable(eval_rows, B_MAIN, dtype_of[k][1],
                                   dtype_of[k][0], dtype_of[k][2],
                                   E2E_SUBSAMPLE)]
    if not stable_keys:
        log("WARNING: no step row has 60M-word EVAL evidence; refusing a step "
            "headline, publishing the e2e number instead")
    head_key = (max(stable_keys, key=lambda k: rows[k][0])
                if stable_keys else None)

    e2e_best_key = max(e2e, key=lambda k: e2e[k][0])
    e2e_pps = e2e[e2e_best_key][0]
    result = {
        **stamp,
        "metric": "sgns_word_pairs_per_sec_per_chip",
        "value": round(rows[head_key][0]) if head_key else round(e2e_pps),
        "unit": "pairs/s",
        # ONE consistent basis: TPU end-to-end vs CPU-torch compute loop at the
        # SAME batch and pool (VERDICT r3 item 10)
        "vs_baseline": round(e2e_pps / cpu_pps, 2),
        "vs_baseline_basis": "e2e_tpu_over_cpu_torch_step_loop_same_batch",
        "config": head_key,
        "headline_eval_evidence": "EVAL_RUNS.jsonl >=60M words, no divergence",
        "mfu": round(rows[head_key][1], 4) if head_key else None,
        "step_f32_pairs_per_sec": round(rows["f32_p512"][0]),
        # per-row min/median/max ms across the 3 interleaved trials (VERDICT
        # r8 item 4): the spread that qualifies every step number above
        "step_trials_ms": {k: rows[k][2] for k in rows},
        # flat per-row scalars (ADDITIVE beside the nested spread dict): one
        # `step_<row>_pairs_per_sec` + `step_<row>_step_ms` pair per step row
        # above, so tools/perfgate.py gates every row by a stable top-level
        # name instead of digging step_trials_ms
        **{f"step_{k}_pairs_per_sec": round(rows[k][0]) for k in rows},
        **{f"step_{k}_step_ms": rows[k][2]["ms_median"] for k in rows},
        "v1m_step_trials_ms": scale["step_trials_ms"],
        "e2e_pairs_per_sec": round(e2e_pps),
        "e2e_feed": e2e_best_key,
        # ISSUE-14 restructured step rows (same harness/geometry as the
        # bf16_p512 row, so ratios are in-run honest; perfgate gates them
        # from the first rung that carries them)
        "step_fused_pairs_per_sec": round(rows["bf16_fused"][0]),
        "step_bf16_chain_pairs_per_sec": round(rows["bf16_chain"][0]),
        "v1m_step_pairs_per_sec": round(scale["step_bf16_pairs_per_sec"]),
        "cbow_examples_per_sec": round(cbow_rows[E2E_POOL][0]),
        "cbow_step_ms": round(cbow_rows[E2E_POOL][1], 3),
        "cbow_banded_examples_per_sec": round(cbow_banded_rows[E2E_POOL][0]),
        "cbow_banded_step_ms": round(cbow_banded_rows[E2E_POOL][1], 3),
        # host data plane (tools/hostbench.py small tier, interleaved medians)
        "producer_tokens_per_sec": host.get("producer_tokens_per_sec"),
        "producer_speedup": host.get("producer_speedup"),
        "ckpt_save_s": host.get("ckpt_save_s"),
        "ckpt_save_speedup": host.get("ckpt_save_speedup"),
        "ckpt_load_s": host.get("ckpt_load_s"),
        "export_s": host.get("export_s"),
        "vocab_build_s": host.get("vocab_build_s"),
        "alias_build_s": host.get("alias_build_s"),
    }
    print(json.dumps(result))


def run_smoke() -> None:
    """``--smoke``: the fast gate only — the interleaved telemetry-off/on
    trainer A/B (tools/telemetry_run.measure_overhead, 3 trials each arm at
    heartbeat cadence). The acceptance bar for the observability layer is
    telemetry_overhead_frac < 0.02; the full bench rows are untouched (run
    without flags for BENCH_r* artifacts). One JSON line on stdout (R7)."""
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    stamp = device_stamp()
    log(f"device: {stamp} — smoke mode (telemetry overhead A/B)")
    import telemetry_run
    res = telemetry_run.measure_overhead(600)
    print(json.dumps({
        **stamp,
        "metric": "telemetry_overhead_frac",
        "value": res["telemetry_overhead_frac"],
        "acceptance": "< 0.02 at heartbeat cadence (docs/observability.md)",
        **res,
    }))


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        run_smoke()
    else:
        main()
