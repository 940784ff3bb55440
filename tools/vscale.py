"""Vocabulary-scaling probe beyond the bench's V=1M row — BASELINE config 4's
10M-vocab regime on ONE chip.

BASELINE config 4 (Common Crawl, 10M vocab, d=300, v5e-64) sizes the embedding
pair at 10M x 384 x 2 x 2B(bf16) = 15.4 GB — more than one v5e's 16 GB HBM once
step workspace is counted, which is exactly WHY that config names a 64-chip pod
(row-sharding divides rows per chip; parallel/mesh.py). What one chip CAN answer
is how the per-row costs scale to 10M rows, measured here at a width that fits
(d=128 -> pair = 5.1 GB bf16, honestly labeled):

    step                gather/scatter address spread over 10M rows
    alias table build   O(2V) host cost at 10M entries
    find_synonyms       matvec + top-k over 10M rows

Run: python tools/vscale.py [--vocab 10000000] [--dim 128] [--batch 65536]
     [--pool 512]. Results recorded in PERF.md §6.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=10_000_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--pool", type=int, default=512)
    ap.add_argument("--skip-step", action="store_true")
    args = ap.parse_args()
    V, D = args.vocab, args.dim

    import bench

    counts = bench.zipf_counts(V)

    t0 = time.perf_counter()
    from glint_word2vec_tpu.ops.sampler import build_alias_table
    build_alias_table(counts)
    print(f"V={V:,} alias table build: {time.perf_counter() - t0:.2f}s "
          "(host, O(2V))", file=sys.stderr)

    if not args.skip_step:
        # bench.bench_step pads dim via PAD_D; override for the reduced width
        old_pad = bench.PAD_D
        bench.PAD_D = D
        try:
            bench.bench_step(counts, b=args.batch, pool=args.pool,
                             dtype="bfloat16", param_dtype="bfloat16",
                             logits_dtype="bfloat16", v=V,
                             label_extra=f" d={D}")
        finally:
            bench.PAD_D = old_pad

    # find_synonyms over 10M rows (embedding created ON device — a host array
    # would time the transfer wire, not the op)
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.config import Word2VecConfig
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    words = np.char.add("w", np.arange(V).astype("U8"))
    vocab = Vocabulary.from_words_and_counts(list(words), counts.astype(np.int64))
    syn0 = (jax.random.normal(jax.random.key(1), (V, D), jnp.bfloat16) * 0.1
            ).astype(jnp.float32)
    syn0.block_until_ready()
    model = Word2VecModel(vocab, syn0, syn1=None,
                          config=Word2VecConfig(vector_size=D))
    model.find_synonyms("w0", 10)  # compile + warm
    t0 = time.perf_counter()
    for i in range(5):
        model.find_synonyms(f"w{i + 1}", 10)
    ms = (time.perf_counter() - t0) / 5 * 1e3
    print(f"V={V:,} find_synonyms(top-10): {ms:.1f} ms/query", file=sys.stderr)
    model.stop()


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
