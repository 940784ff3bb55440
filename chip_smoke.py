#!/usr/bin/env python3
"""chip_smoke.py — the one command that proves the system still starts on the chip.

Drives the main path once through the public estimator, in ONE process:

    Word2Vec(...).fit(sentences, vocab=...)  ->  model.find_synonyms*(...)

at the full width of BASELINE config 3's table (V = 1,000,000 x d = 300,
lane-padded to 384; window 5, 5 negatives, 65,536 pairs/step, 16 steps per
dispatch, bf16 params/compute/logits, pool and subsample left on AUTO), with
random weights from a seed and a corpus drawn from a seed. Depth is cut: a few
dispatches, not an epoch of enwiki.

Legs: (a) host-feed fit; (b) device_pairgen fit; (c) find_synonyms and
find_synonyms_batch against a NumPy cosine top-k of get_vectors(); (d) leg
(a) again on 1x4 and 2x2 meshes and under step_lowering="shard_map" when the
machine has four devices. Any leg that raises fails the run.

No arguments = the real thing: it requires a TPU and exits non-zero without
one. ``--tiny`` runs the same legs at toy width on whatever platform there is,
to debug the command before chip time is spent.

Every time printed here is a SMOKE time — it says the run got through, it is
not a metric. The last stdout line is one JSON object.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import sys
import time

SEED = 21
SENTENCE_LEN = 40
NUM_SYNONYMS = 10

# window/negatives/dtypes are BASELINE config 3's and the same at both widths;
# only the table, the batch and the corpus shrink under --tiny
FULL = dict(v=1_000_000, d=300, b=65_536, k=16, tokens=2_400_000)
TINY = dict(v=20_000, d=40, b=4096, k=4, tokens=200_000)

# cosine tolerance of leg (c), absolute: the tables are bf16 and the package
# sets no matmul precision, so the device cosine carries bf16 rounding of the
# normalized query, of the [Q, V] product and of the row norms (~3 x 2^-8
# relative on values <= 1); the NumPy reference is exact float32.
COSINE_TOL = 2e-2
# first-heartbeat loss, sharded vs one chip (leg d): same batches and the same
# math, but bf16 scatter-adds associate differently per layout, and 32 steps of
# that drift a 65k-pair mean loss in the 3rd digit. 2% is ~10x the drift seen.
SHARDED_LOSS_RTOL = 2e-2


def log(msg: str = "") -> None:
    print(msg, flush=True)


class CompileLog:
    """Every backend compile of the process, timestamped, via jax.monitoring —
    so a leg can tell warm-up compiles from a recompile in steady state."""

    _TIMED = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as monitoring
        self.compiles = []      # (perf_counter at end, fun_name)
        self.seconds = 0.0      # trace + lower + backend compile, all programs
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, seconds, **kw):
        if name in self._TIMED:
            self.seconds += seconds
        if name == self._TIMED[-1]:
            self.compiles.append((time.perf_counter(), kw.get("fun_name", "?")))

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return len(self.compiles), self.seconds


@contextlib.contextmanager
def observed_trainer():
    """The estimator builds its Trainer and drops it; the checks below need that
    trainer's heartbeat ring, jit caches and placed params. For the length of one
    fit the estimator's Trainer is a subclass that records itself and marks each
    heartbeat with a clock reading taken after the params carry is ready. It
    observes; it changes nothing the fit computes."""
    import jax

    from glint_word2vec_tpu.models import estimator

    seen = {"marks": []}

    class ObservedTrainer(estimator.Trainer):
        def fit(self, *args, **kw):
            seen["trainer"] = self

            def mark(rec):
                jax.block_until_ready(self.params)
                seen["marks"].append((time.perf_counter(), rec.global_step))

            return super().fit(*args, on_heartbeat=mark, **kw)

    original = estimator.Trainer
    estimator.Trainer = ObservedTrainer
    try:
        yield seen
    finally:
        estimator.Trainer = original


def make_problem(spec: dict) -> dict:
    """Vocabulary with bench.py's Zipf counts and a corpus drawn from it by seed."""
    import numpy as np

    from bench import zipf_counts
    from glint_word2vec_tpu.data.pipeline import encode_sentences
    from glint_word2vec_tpu.data.vocab import Vocabulary

    v, n = spec["v"], spec["tokens"]
    counts = zipf_counts(v).astype(np.int64)
    words = np.char.add("w", np.arange(v).astype("U8")).tolist()
    vocab = Vocabulary.from_words_and_counts(words, counts)
    rng = np.random.default_rng(SEED)
    ids = rng.choice(v, size=n, p=counts / counts.sum())
    tokens = np.asarray(words, dtype=object)[ids]
    sentences = [tokens[i:i + SENTENCE_LEN].tolist()
                 for i in range(0, n, SENTENCE_LEN)]
    # the estimator encodes for itself; this copy only counts the batches the
    # corpus implies (expected_steps)
    encoded = encode_sentences(sentences, vocab, 1000)
    return dict(vocab=vocab, sentences=sentences, encoded=encoded)


def base_config(spec: dict) -> dict:
    return dict(
        vector_size=spec["d"], window=5, negatives=5, min_count=1,
        pairs_per_batch=spec["b"], steps_per_dispatch=spec["k"],
        param_dtype="bfloat16", compute_dtype="bfloat16",
        logits_dtype="bfloat16", seed=SEED,
        # one heartbeat every second dispatch: both step twins run (metrics-
        # elided and full), and a few dispatches already give two loss readings
        heartbeat_every_steps=2 * spec["k"])


def expected_steps(problem: dict, trainer) -> int:
    """Steps the corpus implies under the trainer's RESOLVED config, counted
    from the host pipeline alone (deterministic per seed): the host feed takes
    one step per pair batch; the device feed one per T kept tokens."""
    from glint_word2vec_tpu.data.pipeline import epoch_batches

    cfg = trainer.config
    batches, kept = 0, 0
    for batch in epoch_batches(
            problem["encoded"], problem["vocab"],
            pairs_per_batch=cfg.pairs_per_batch, window=cfg.window,
            subsample_ratio=cfg.subsample_ratio, seed=cfg.seed, iteration=1,
            shuffle=cfg.shuffle):
        batches += 1
        kept = batch.words_seen
    if cfg.device_pairgen:
        return math.ceil(kept / trainer._tokens_per_step)
    return batches


def fit_leg(name: str, spec: dict, problem: dict, clog: CompileLog,
            plan=None, **overrides) -> dict:
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.models.estimator import Word2Vec

    log(f"--- leg {name}")
    gc.collect()  # an earlier leg's trainer is a reference cycle holding tables
    n0, s0 = clog.snapshot()
    t0 = time.perf_counter()
    with observed_trainer() as seen:
        model = Word2Vec(**base_config(spec), **overrides).fit(
            problem["sentences"], vocab=problem["vocab"], plan=plan)
    jax.block_until_ready((model.syn0, model.syn1))
    wall = time.perf_counter() - t0
    n1, s1 = clog.snapshot()
    trainer, marks = seen["trainer"], seen["marks"]
    cfg = trainer.config
    log(f"resolved: negative_pool={cfg.negative_pool} "
        f"subsample_ratio={cfg.subsample_ratio:.3e} params "
        f"{list(trainer.params.syn0.shape)} {trainer.params.syn0.dtype} "
        f"mesh {trainer.plan.num_data}x{trainer.plan.num_model}")

    for mat in (model.syn0, model.syn1):
        assert bool(jnp.isfinite(mat.astype(jnp.float32)).all()), \
            f"{name}: non-finite params"
    want = expected_steps(problem, trainer)
    got = int(model.train_state.global_step)
    assert got == want == int(trainer.global_step), \
        f"{name}: global_step {got} but the corpus implies {want}"
    assert want >= 4 * spec["k"], \
        f"{name}: corpus too short ({want} steps < four full dispatches)"

    beats = list(trainer.heartbeats)
    assert len(beats) >= 2 and len(marks) == len(beats), \
        f"{name}: {len(beats)} heartbeats"
    assert all(math.isfinite(h.loss) for h in beats), f"{name}: loss not finite"
    assert beats[-1].loss < beats[0].loss, \
        f"{name}: loss did not fall ({beats[0].loss} -> {beats[-1].loss})"

    # one compile per step twin (the tools/stepaudit.py count) ...
    twins = {"full": trainer._step_fn._cache_size()}
    if trainer._step_fn_fast is not trainer._step_fn:
        twins["fast"] = trainer._step_fn_fast._cache_size()
    assert all(c == 1 for c in twins.values()), \
        f"{name}: step twins compiled {twins}, want 1 each"
    # ... and nothing at all compiles once the first heartbeat has passed
    # (both twins and the health probe have run by then) until the last one
    first_t, first_step = marks[0]
    last_t, last_step = marks[-1]
    late = [fn for t, fn in clog.compiles[n0:n1] if first_t < t <= last_t]
    assert not late, f"{name}: compiled in steady state: {late}"
    steady_steps = last_step - first_step
    log(f"smoke: {got} steps, {n1 - n0} programs compiled in "
        f"{s1 - s0:.1f}s (compile), {steady_steps} steady steps in "
        f"{last_t - first_t:.2f}s (steady, closed by block_until_ready), "
        f"{wall:.1f}s wall; loss {beats[0].loss:.4f} -> {beats[-1].loss:.4f}")
    return dict(model=model, trainer=trainer, first_loss=beats[0].loss)


def synonyms_leg(model, rng) -> None:
    """find_synonyms / find_synonyms_batch vs an exact float32 NumPy cosine
    top-k over get_vectors(), for a handful of seeded query words."""
    import numpy as np

    log("--- leg c: find_synonyms / find_synonyms_batch vs NumPy")
    words = model.vocab.words
    vectors = model.get_vectors()
    mat = np.stack([vectors[w] for w in words]).astype(np.float32)
    norms = np.linalg.norm(mat, axis=1)
    v = len(words)
    # frequent head, mid, and rare tail ids
    qids = sorted({3, 17, v // 100, v // 10, v // 2,
                   *rng.integers(0, v, 3).tolist()})
    queries = [words[i] for i in qids]
    t0 = time.perf_counter()
    single = [model.find_synonyms(q, NUM_SYNONYMS) for q in queries]
    batched = model.find_synonyms_batch(queries, NUM_SYNONYMS)
    wall = time.perf_counter() - t0
    for n, (qi, q) in enumerate(zip(qids, queries)):
        ref = np.where(norms > 0, mat @ (mat[qi] / max(norms[qi], 1e-12))
                       / np.maximum(norms, 1e-12), 0.0)
        ref[qi] = -np.inf
        kth = np.partition(ref, v - NUM_SYNONYMS)[v - NUM_SYNONYMS]
        for label, got in (("find_synonyms", single[n]),
                           ("find_synonyms_batch", batched[n])):
            assert len(got) == NUM_SYNONYMS, f"{label}({q}): {len(got)} results"
            for w, score in got:
                assert w != q, f"{label}({q}) returned the query itself"
                true = ref[model.vocab.get(w)]
                assert abs(score - true) <= COSINE_TOL, \
                    f"{label}({q}): {w} scored {score}, reference {true}"
                assert true >= kth - COSINE_TOL, \
                    f"{label}({q}): {w} (cos {true}) is not a top-" \
                    f"{NUM_SYNONYMS} neighbour (k-th is {kth})"
    log(f"smoke: {len(queries)} queries x2 agree with the reference within "
        f"{COSINE_TOL} (compile included: {wall:.1f}s)")


def four_chip_leg(spec: dict, problem: dict, clog: CompileLog,
                  one_chip_loss: float) -> list:
    """Leg (a) again on sharded tables: 1x4 and 2x2 under the default lowering,
    2x2 under shard_map. Returns the per-device shard shapes seen."""
    import jax

    from glint_word2vec_tpu.parallel.mesh import make_mesh

    devices = jax.devices()[:4]
    shapes = []
    for (nd, nm), lowering in (((1, 4), "gspmd"), ((2, 2), "gspmd"),
                               ((2, 2), "shard_map")):
        out = fit_leg(f"d: host feed on {nd}x{nm}, {lowering}", spec, problem,
                      clog, plan=make_mesh(nd, nm, devices=devices),
                      step_lowering=lowering)
        trainer = out["trainer"]
        rows = trainer.padded_vocab // nm
        shards = trainer.params.syn0.addressable_shards
        assert len(shards) == 4, f"{len(shards)} addressable shards"
        per_device = {}
        for shard in shards:
            assert shard.data.shape == (rows, trainer.padded_dim), \
                f"{shard.device}: shard {shard.data.shape}, want " \
                f"({rows}, {trainer.padded_dim})"
            per_device[str(shard.device)] = list(shard.data.shape)
        stats = [d.memory_stats() for d in devices]
        if all(stats):
            used = [s["bytes_in_use"] for s in stats]
            assert all(u > 0 for u in used), f"an idle device: {used}"
            assert used[0] < 0.5 * sum(used), f"device 0 holds the lot: {used}"
            # peaks are process-wide, for the record only: device 0 also ran
            # legs a-d, and every trainer inits both full tables there first
            mem = (" MiB in use " + "/".join(f"{u >> 20}" for u in used)
                   + ", process peak " + "/".join(
                       f"{s['peak_bytes_in_use'] >> 20}" for s in stats))
        else:
            mem = f" (memory_stats not reported on {devices[0].platform})"
        rel = abs(out["first_loss"] - one_chip_loss) / abs(one_chip_loss)
        assert rel <= SHARDED_LOSS_RTOL, \
            f"first-heartbeat loss {out['first_loss']} vs one chip " \
            f"{one_chip_loss} (rel {rel:.3e} > {SHARDED_LOSS_RTOL})"
        log(f"smoke: syn0 shards {per_device};{mem}; first-heartbeat loss "
            f"within {rel:.1e} of one chip")
        shapes.append({"mesh": [nd, nm], "lowering": lowering,
                       "syn0_shard": [rows, trainer.padded_dim]})
        out["model"].stop()
    return shapes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy width on whatever platform there is (debugging)")
    args = ap.parse_args(argv)
    spec = TINY if args.tiny else FULL

    import glint_word2vec_tpu  # noqa: F401 — must initialise no backend
    import jax
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized(), \
        "importing glint_word2vec_tpu initialised a JAX backend"
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"platform {dev.platform}  device_kind {dev.device_kind}  "
        f"devices {len(jax.devices())}")
    log(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu_version}")
    log(f"compile cache: {cache_dir}")
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); this check "
              "does not fall back — use --tiny to debug on other platforms",
              file=sys.stderr)
        return 1
    log(f"width: V={spec['v']:,} d={spec['d']} B={spec['b']:,} "
        f"K={spec['k']} corpus {spec['tokens']:,} tokens"
        + ("  [--tiny: NOT the chip check]" if args.tiny else ""))

    import numpy as np

    from glint_word2vec_tpu.data.native import native_available

    clog = CompileLog()
    t_start = time.perf_counter()
    problem = make_problem(spec)

    # the numpy pair generator is a bit-identical but 4-5x slower stand-in;
    # a chip fed by it is not the system under test
    assert native_available(), "native pair generator did not build (g++?)"
    leg = fit_leg("a: host feed", spec, problem, clog)
    one_chip_loss = leg["first_loss"]
    leg["model"].stop()
    leg = fit_leg("b: device_pairgen feed", spec, problem, clog,
                  device_pairgen=True)
    synonyms_leg(leg["model"], np.random.default_rng(SEED))
    leg["model"].stop()
    del leg  # the trainer's placed tables go with it
    if len(jax.devices()) >= 4:
        shards = four_chip_leg(spec, problem, clog, one_chip_loss)
    else:
        shards = None
        log(f"four_chip: skipped ({len(jax.devices())} device)")

    log(f"smoke total: {time.perf_counter() - t_start:.1f}s wall, "
        f"{len(clog.compiles)} programs compiled in {clog.seconds:.1f}s, "
        f"persistent cache {clog.cache_hits} hits / {clog.cache_misses} misses")
    if shards:
        log(f"four_chip: {json.dumps(shards)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
