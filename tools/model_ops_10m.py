"""Model ops at 10M vocabulary (VERDICT r4 item 5) — the reference's core scale
claim (README.md:4-9: vocabularies beyond single-machine worker memory)
demonstrated END TO END, not just as a step benchmark.

Two phases, one per bound:

  --phase host   (CPU by design: 8 virtual devices via XLA_FLAGS)
      The IO ops on a host-resident 10M x 384 bf16 matrix placed on an 8-way
      row-sharded mesh: row-shards save -> streamed mmap load onto the mesh ->
      export_word2vec (binary). Disk + host-RAM bound — the same code path a
      pod host runs after its device->host fetch.
  --phase device (run on the TPU)
      The device-resident ops at 10M rows on one v5e chip: syn0 bf16 lives in
      HBM (7.7 GB of 16), find_synonyms / find_synonyms_batch at full vocab.
      Save/export are not repeated here: the host phase times their disk
      half; the 7.7 GB device->host fetch in front of them is not measured.

Prints one JSON line per phase; tables to stderr. Peak RSS is reported via
resource.getrusage (linux: KB).
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V = 10_000_000
D = 384  # lane-padded production width (vector_size 384 keeps export honest)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def peak_gb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def build_vocab():
    from glint_word2vec_tpu.data.vocab import Vocabulary
    t0 = time.perf_counter()
    counts = np.maximum(1e10 / (np.arange(V) + 10.0) ** 1.07, 5.0).astype(np.int64)
    words = np.char.add("w", np.arange(V).astype("U8")).tolist()
    vocab = Vocabulary.from_words_and_counts(words, counts)
    log(f"vocab build ({V:,} types): {time.perf_counter() - t0:.1f}s "
        f"(host, rss {peak_gb():.1f} GB)")
    return vocab


def host_syn0():
    import ml_dtypes
    t0 = time.perf_counter()
    out = np.empty((V, D), ml_dtypes.bfloat16)
    rng = np.random.default_rng(0)
    for s in range(0, V, 1_000_000):  # blockwise: avoid a 15 GB f32 transient
        out[s:s + 1_000_000] = rng.standard_normal(
            (min(1_000_000, V - s), D), np.float32).astype(ml_dtypes.bfloat16)
    log(f"syn0 host build [{V:,} x {D}] bf16 ({out.nbytes / 1e9:.1f} GB): "
        f"{time.perf_counter() - t0:.1f}s")
    return out


def phase_host(outdir):
    import jax
    # the host phase is the disk + host-RAM half by design (see module docstring)
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.train.checkpoint import (
        load_params_into_plan, save_model_sharded)

    assert len(jax.devices()) >= 8, "run with xla_force_host_platform_device_count=8"
    res = {"phase": "host", "vocab": V, "dim": D}
    vocab = build_vocab()
    mat = host_syn0()
    plan = make_mesh(1, 8)  # 8-way row sharding, the production embedding layout

    t0 = time.perf_counter()
    syn0 = jax.make_array_from_callback(
        (V, D), plan.embedding, lambda idx: mat[idx])
    jax.block_until_ready(syn0)
    res["place_on_mesh_s"] = round(time.perf_counter() - t0, 1)
    log(f"placed on (1, 8) mesh: {res['place_on_mesh_s']}s")

    ck = os.path.join(outdir, "ck10m")
    cfg = Word2VecConfig(vector_size=D, min_count=1, sharded_checkpoint=True)
    t0 = time.perf_counter()
    save_model_sharded(ck, vocab.words, vocab.counts, syn0, None, cfg)
    res["sharded_save_s"] = round(time.perf_counter() - t0, 1)
    sz = sum(os.path.getsize(os.path.join(r, f))
             for r, _, fs in os.walk(ck) for f in fs)
    res["checkpoint_gb"] = round(sz / 1e9, 2)
    log(f"row-shards save: {res['sharded_save_s']}s ({res['checkpoint_gb']} GB, "
        f"{sz / 1e9 / res['sharded_save_s']:.2f} GB/s)")

    del syn0  # free the placed device copy (7.7 GB) before the next step

    t0 = time.perf_counter()
    syn0_l, syn1_l = load_params_into_plan(ck, plan, V, D, dtype=jnp.bfloat16)
    jax.block_until_ready(syn0_l)
    res["streamed_load_s"] = round(time.perf_counter() - t0, 1)
    assert syn1_l is None
    log(f"streamed mmap load onto mesh: {res['streamed_load_s']}s")
    # spot-check a row survived the round trip
    np.testing.assert_array_equal(np.asarray(syn0_l[12345]), mat[12345])
    del syn0_l  # free before export (the first attempt OOM'd holding 3 copies)

    # NO host-phase find_synonyms: lax.top_k over 10M rows lowers to a per-row
    # sort on the CPU backend (measured: >30 min for 64 queries — killed); the
    # real number is the --phase device one, where top_k runs on the TPU.
    # Export streams from the HOST matrix (blockwise f32 convert) — the same
    # writer a real pod host runs after its PCIe fetch.
    model = Word2VecModel(vocab, mat, syn1=None, config=cfg)
    exp = os.path.join(outdir, "vectors_10m.bin")
    t0 = time.perf_counter()
    model.export_word2vec(exp, binary=True)
    res["export_binary_s"] = round(time.perf_counter() - t0, 1)
    res["export_gb"] = round(os.path.getsize(exp) / 1e9, 2)
    log(f"export_word2vec binary: {res['export_binary_s']}s "
        f"({res['export_gb']} GB, "
        f"{res['export_gb'] / res['export_binary_s']:.2f} GB/s)")
    with open(exp, "rb") as f:
        head = f.readline().split()
        assert int(head[0]) == V and int(head[1]) == D

    res["peak_rss_gb"] = round(peak_gb(), 1)
    print(json.dumps(res))


def phase_device(outdir):
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.models.word2vec import Word2VecModel

    dev = jax.devices()[0]
    log(f"device: {dev}")
    res = {"phase": "device", "vocab": V, "dim": D, "device": str(dev)}
    vocab = build_vocab()

    t0 = time.perf_counter()
    syn0 = jax.random.normal(jax.random.key(1), (V, D), jnp.bfloat16) * 0.1
    syn0.block_until_ready()
    log(f"syn0 on device [{V:,} x {D}] bf16 "
        f"({V * D * 2 / 1e9:.1f} GB HBM): {time.perf_counter() - t0:.1f}s")

    cfg = Word2VecConfig(vector_size=D, min_count=1)
    model = Word2VecModel(vocab, syn0, syn1=None, config=cfg)

    model.find_synonyms("w0", 10)  # compile + warm
    t0 = time.perf_counter()
    for i in range(5):
        model.find_synonyms(f"w{i + 1}", 10)
    res["find_synonyms_ms"] = round((time.perf_counter() - t0) / 5 * 1e3, 1)
    log(f"find_synonyms(top-10) over {V:,} rows: "
        f"{res['find_synonyms_ms']} ms/query")

    qs = [f"w{i * 991 + 3}" for i in range(128)]
    # warm at the SAME query-stack shape as the timed call — a different shape
    # would retrace and the timed dispatch would include the compile
    model.find_synonyms_batch(qs, 10, chunk=128)
    t0 = time.perf_counter()
    got = model.find_synonyms_batch(qs, 10, chunk=128)
    res["synonyms_batch128_ms_per_query"] = round(
        (time.perf_counter() - t0) / 128 * 1e3, 1)
    assert len(got) == 128
    log(f"find_synonyms_batch(128): "
        f"{res['synonyms_batch128_ms_per_query']} ms/query")

    res["save_export_note"] = (
        "run in --phase host: disk timings are measured there on the same code "
        "path; the 7.7 GB device->host fetch in front of them is not measured")
    log(res["save_export_note"])
    print(json.dumps(res))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["host", "device"], required=True)
    ap.add_argument("--out", default="/tmp/model_ops_10m")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    if args.phase == "host":
        phase_host(args.out)
    else:
        phase_device(args.out)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
