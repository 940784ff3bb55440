"""Interleaved GSPMD-vs-shard_map A/B across mesh shapes (the scale-out step).

For each (data, model) mesh shape over the devices it is given — every
factorization of the device count: 1x4, 2x2, 4x1 on a four-chip host; 1x8,
2x4, 4x2, 8x1 on the 8-device smoke mesh — this builds TWO production Trainers
that differ ONLY in
``config.step_lowering`` ("gspmd" = compiler-scheduled collectives,
"shard_map" = the explicit owner-local schedule of ops/sgns_shard.py), feeds
both the identical packed-pair chunk, and reports:

- step time per lowering (interleaved A/B medians, the PERF.md §3
  methodology: variants alternate within one process so allocator drift and
  co-tenant noise hit both alike; two-point-slope timing via
  tools/microbench.py);
- numeric agreement: max |Δ| between the two lowerings' params after one
  identical chunk from identical initial params (they are NOT bit-identical —
  different FP reduction orders — but must agree to f32 reassociation noise;
  the f64 ~1e-12 equivalence lives in tests/test_shard_map_step.py).

The tool takes the platform it is given and fails when there are fewer than
two devices; only ``--smoke`` run as a script provisions the 8-device virtual
CPU mesh (the tier-1 wiring). On a CPU mesh the TIME column is not a device
metric (CPU collective/scatter economics are nothing like ICI + the TPU
scatter emitter) — the result's ``backend`` field says which it was; the
collective-bytes evidence is tools/collectives.py. The agreement column is
meaningful everywhere.

Run:  python tools/shard_ab.py [--smoke] [--b 16384] [--v 100000] [--d 384]
      [--pool 512] [--k 4] [--repeats 3]
Prints a table on stderr and exactly ONE JSON line on stdout.
``--smoke`` (tiny geometry, 1 repeat) is wired into tier-1
(tests/test_shard_map_step.py) so the harness cannot rot.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# --smoke is the tier-1 wiring: it alone provisions the virtual 8-device CPU
# mesh (BEFORE jax initializes); a real run uses the devices the machine has
if __name__ == "__main__" and "--smoke" in sys.argv[1:]:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def mesh_shapes(n_devices: int):
    """Every (data, model) factorization of the device count, data ascending."""
    return [(d, n_devices // d) for d in range(1, n_devices + 1)
            if n_devices % d == 0]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_trainer(lowering: str, shape, vocab, args, sync_every: int = 1,
                 steps_per_dispatch: int = 0):
    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.trainer import Trainer

    cfg = Word2VecConfig(
        vector_size=args.d, min_count=1, pairs_per_batch=args.b,
        negatives=5, negative_pool=args.pool,
        steps_per_dispatch=steps_per_dispatch or args.k,
        window=5, seed=7, step_lowering=lowering, sync_every=sync_every)
    return Trainer(cfg, vocab, plan=make_mesh(*shape))


def ab_one_mesh(shape, vocab, args) -> dict:
    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sgns import EmbeddingPair

    K, B = args.k, args.b
    res = {"mesh": list(shape)}
    trainers = {low: make_trainer(low, shape, vocab, args)
                for low in ("gspmd", "shard_map")}
    t0 = trainers["gspmd"]
    # identical initial params on both (same seed/geometry -> same init);
    # host copies survive donation so every timing run re-places fresh params
    syn0_h = np.asarray(t0.params.syn0)
    syn1_h = np.asarray(t0.params.syn1)
    assert np.array_equal(syn0_h, np.asarray(trainers["shard_map"].params.syn0))

    n_sets = 4
    feeds = []
    for i in range(n_sets):
        r = np.random.default_rng(500 + i)
        feeds.append(jax.device_put(
            r.integers(0, vocab.size, (K, 2, B)).astype(t0._pair_dtype),
            t0.plan.pairs_stacked))
    meta = np.stack([np.full((K,), 0.025, np.float32),
                     np.full((K,), B, np.float32)])

    # numeric agreement: one identical chunk from identical params
    outs = {}
    for low, tr in trainers.items():
        p = EmbeddingPair(jax.device_put(syn0_h, tr.plan.embedding),
                          jax.device_put(syn1_h, tr.plan.embedding))
        new_p, _ = tr._step_fn(p, {"pairs": feeds[0]}, meta, np.int32(1),
                               tr._table_prob, tr._table_alias)
        outs[low] = jax.tree.map(np.asarray, new_p)
    diff = max(
        float(np.max(np.abs(outs["gspmd"].syn0.astype(np.float64)
                            - outs["shard_map"].syn0.astype(np.float64)))),
        float(np.max(np.abs(outs["gspmd"].syn1.astype(np.float64)
                            - outs["shard_map"].syn1.astype(np.float64)))))
    res["max_abs_diff"] = diff
    # scale reference so the smoke assertion is relative, not absolute
    res["param_abs_max"] = float(np.max(np.abs(outs["gspmd"].syn0)))

    times = {"gspmd": [], "shard_map": []}
    for rep in range(args.repeats):
        for low in ("gspmd", "shard_map"):      # interleaved
            tr = trainers[low]

            def run(p, feed, base, tr=tr):
                return tr._step_fn(p, {"pairs": feed}, meta, base,
                                   tr._table_prob, tr._table_alias)

            make_carry = lambda tr=tr: EmbeddingPair(       # noqa: E731
                jax.device_put(syn0_h, tr.plan.embedding),
                jax.device_put(syn1_h, tr.plan.embedding))
            args_for_iter = lambda i: (feeds[i % n_sets],   # noqa: E731
                                       np.int32(100 + i))
            fetch = lambda c, out: c.syn0[0, 0].astype(jnp.float32)  # noqa: E731
            try:
                spc = time_chunked(run, make_carry=make_carry,
                                   args_for_iter=args_for_iter,
                                   n_lo=2, n_hi=6, fetch=fetch)
            except RuntimeError:
                # loaded/noisy host: the two-point slope can go non-positive
                # on sub-100ms chunks. Fall back to direct chained timing,
                # closed by the same dependent fetch
                import time as _time
                c = make_carry()
                c, out = run(c, *args_for_iter(0))          # warm
                float(fetch(c, out))
                t0 = _time.perf_counter()
                n = 4
                for i in range(n):
                    c, out = run(c, *args_for_iter(i))
                float(fetch(c, out))
                spc = (_time.perf_counter() - t0) / n
            times[low].append(spc / K * 1e3)
    for low in ("gspmd", "shard_map"):
        res[f"{low}_ms"] = float(np.median(times[low]))
    res["speedup_shard_map"] = res["gspmd_ms"] / res["shard_map_ms"]
    log(f"mesh {shape[0]}x{shape[1]}: gspmd {res['gspmd_ms']:8.2f} ms/step  "
        f"shard_map {res['shard_map_ms']:8.2f} ms/step  "
        f"(x{res['speedup_shard_map']:.2f})  max|dparam| {diff:.2e}")
    return res


def localsgd_ab_one_mesh(shape, vocab, args) -> dict:
    """sync_every interleaved arm (docs/sharding.md §Local-SGD): same mesh,
    same packed-pair chunk, shard_map lowering throughout; arms differ ONLY in
    ``config.sync_every`` ∈ args.sync_set. Every arm runs with
    steps_per_dispatch = max(sync_set) so chunk geometry (and therefore the
    feed, the metrics shape, and the per-step normalization) is identical —
    only the merge cadence moves. Reports per-arm ms/step plus the one-chunk
    params divergence of each local arm vs the sync_every=1 arm (the staleness
    column; quality impact is gated by tools/eval_quality.py --localsgd-ab)."""
    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sgns import EmbeddingPair

    ks = sorted(set(args.sync_set))
    K, B = max(ks), args.b
    res = {"mesh": list(shape), "steps_per_dispatch": K, "arms": {}}
    trainers = {k: make_trainer("shard_map", shape, vocab, args,
                                sync_every=k, steps_per_dispatch=K)
                for k in ks}
    t0 = trainers[ks[0]]
    syn0_h = np.asarray(t0.params.syn0)
    syn1_h = np.asarray(t0.params.syn1)

    n_sets = 4
    feeds = []
    for i in range(n_sets):
        r = np.random.default_rng(700 + i)
        feeds.append(jax.device_put(
            r.integers(0, vocab.size, (K, 2, B)).astype(t0._pair_dtype),
            t0.plan.pairs_stacked))
    meta = np.stack([np.full((K,), 0.025, np.float32),
                     np.full((K,), B, np.float32)])

    # one-chunk divergence of each local arm vs the synchronous arm — the
    # cheap staleness indicator (at nd=1 this is exactly 0 by construction)
    outs = {}
    for k, tr in trainers.items():
        p = EmbeddingPair(jax.device_put(syn0_h, tr.plan.embedding),
                          jax.device_put(syn1_h, tr.plan.embedding))
        new_p, _ = tr._step_fn(p, {"pairs": feeds[0]}, meta, np.int32(1),
                               tr._table_prob, tr._table_alias)
        outs[k] = jax.tree.map(np.asarray, new_p)

    times = {k: [] for k in ks}
    for rep in range(args.repeats):
        for k in ks:                                # interleaved
            tr = trainers[k]

            def run_step(p, feed, base, tr=tr):
                return tr._step_fn(p, {"pairs": feed}, meta, base,
                                   tr._table_prob, tr._table_alias)

            make_carry = lambda tr=tr: EmbeddingPair(       # noqa: E731
                jax.device_put(syn0_h, tr.plan.embedding),
                jax.device_put(syn1_h, tr.plan.embedding))
            args_for_iter = lambda i: (feeds[i % n_sets],   # noqa: E731
                                       np.int32(100 + i))
            fetch = lambda c, out: c.syn0[0, 0].astype(jnp.float32)  # noqa: E731
            try:
                spc = time_chunked(run_step, make_carry=make_carry,
                                   args_for_iter=args_for_iter,
                                   n_lo=2, n_hi=6, fetch=fetch)
            except RuntimeError:
                import time as _time
                c = make_carry()
                c, out = run_step(c, *args_for_iter(0))     # warm
                float(fetch(c, out))
                t1 = _time.perf_counter()
                n = 4
                for i in range(n):
                    c, out = run_step(c, *args_for_iter(i))
                float(fetch(c, out))
                spc = (_time.perf_counter() - t1) / n
            times[k].append(spc / K * 1e3)
    base_ms = float(np.median(times[ks[0]]))
    for k in ks:
        ms = float(np.median(times[k]))
        diff = max(
            float(np.max(np.abs(outs[ks[0]].syn0.astype(np.float64)
                                - outs[k].syn0.astype(np.float64)))),
            float(np.max(np.abs(outs[ks[0]].syn1.astype(np.float64)
                                - outs[k].syn1.astype(np.float64)))))
        res["arms"][str(k)] = {"sync_every": k, "ms_per_step": ms,
                               "speedup_vs_sync": base_ms / ms,
                               "max_abs_diff_vs_sync": diff}
        log(f"mesh {shape[0]}x{shape[1]} localsgd k={k:<3d} {ms:8.2f} ms/step"
            f"  (x{base_ms / ms:.2f} vs sync)  max|dparam vs sync| {diff:.2e}")
    return res


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry, 1 repeat (the tier-1 wiring)")
    ap.add_argument("--b", type=int, default=16384)
    ap.add_argument("--v", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=384)
    ap.add_argument("--pool", type=int, default=512)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--sync-set", type=str, default="1,4,16",
                    help="comma list of sync_every arms for the local-SGD A/B")
    args = ap.parse_args(argv)
    if args.smoke:
        args.b, args.v, args.d, args.pool = 1024, 8192, 64, 128
        args.k, args.repeats = 2, 1
        args.sync_set = "1,2"
    args.sync_set = [int(s) for s in args.sync_set.split(",") if s.strip()]

    import jax
    n_dev = len(jax.devices())
    have = f"have {n_dev} x {jax.devices()[0].platform}"
    if args.smoke and n_dev != 8:
        raise SystemExit(f"--smoke needs the 8-device virtual CPU mesh "
                         f"({have}); run it as a script so it self-provisions")
    if n_dev < 2:
        raise SystemExit(f"a mesh A/B needs a multi-chip host ({have})")
    meshes = mesh_shapes(n_dev)
    if (jax.devices()[0].platform == "cpu"
            and (os.cpu_count() or 1) < n_dev):
        log(f"WARNING: host has {os.cpu_count()} cores for a "
            f"{n_dev}-device virtual mesh — device steps are "
            "contended; treat ms/step as relative, not absolute")
    log(f"device: {jax.devices()[0]} x{n_dev}  B={args.b} V={args.v} "
        f"D={args.d} pool={args.pool} K={args.k} repeats={args.repeats}")

    from glint_word2vec_tpu.data.vocab import Vocabulary
    counts = np.maximum(1e9 / (np.arange(args.v) + 10.0) ** 1.07, 5.0)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(args.v)], counts.astype(np.int64))

    result = {
        "geometry": {"b": args.b, "v": args.v, "d": args.d,
                     "pool": args.pool, "k": args.k},
        "backend": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": n_dev,
        "meshes": [ab_one_mesh(shape, vocab, args) for shape in meshes],
    }
    # local-SGD arm: only meshes with >1 data shard carry a real merge (at
    # nd=1 every sync_every is bit-identical to synchronous); smoke keeps one
    # mesh so the tier-1 wiring stays cheap
    ls_meshes = [(2, 4)] if args.smoke else [m for m in meshes if m[0] > 1]
    result["localsgd_sync_set"] = args.sync_set
    result["localsgd_meshes"] = [
        localsgd_ab_one_mesh(shape, vocab, args) for shape in ls_meshes]
    return result


def main(argv=None) -> None:
    print(json.dumps(run(argv)))


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
