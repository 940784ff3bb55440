"""Performance frontier sweeps on the live chip (round-3 workstream).

Usage: python tools/sweep.py pallas|xla|all

Sweeps, with the same slope harness AND the same Zipf-distributed batch indices as
bench.py (the harness is imported from it, so the two cannot drift):
- pallas: kernel tile x nbuf grid at B=8192 (tile was fixed at 512 / nbuf at 8 so far)
- xla: batch curve x compute/param dtype x negative-pool size for the shared-pool step

Round-3 measured conclusions (recorded in bench.py's docstring and
ops/pallas/sgns_kernel.py): pallas flat across the whole grid (issue-overhead bound,
demoted); bf16-stored params +30-40%; batch curve peaks at B=65536; pool=1024 trades
~15% pairs/s for 10x MFU.
"""

import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
_root = os.path.dirname(_here)
sys.path.insert(0, _here)                      # tools/ (microbench)
sys.path.insert(0, _root)                      # repo root (glint_word2vec_tpu, bench)

from bench import bench_step, log, zipf_counts  # noqa: E402


def main():
    import jax
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    log(f"device: {jax.devices()[0]}")
    counts = zipf_counts(200_000)
    if which in ("pallas", "all"):
        from functools import partial

        from glint_word2vec_tpu.ops.pallas import sgns_kernel
        for tile in (256, 512):
            for nbuf in (8, 32):
                if nbuf > tile:
                    continue
                orig = sgns_kernel.make_pallas_sgns_step
                sgns_kernel.make_pallas_sgns_step = partial(
                    orig, tile=tile, nbuf=nbuf)
                try:
                    log(f"[tile={tile} nbuf={nbuf}]")
                    bench_step(counts, 8192, use_pallas=True)
                except Exception as e:
                    log(f"pallas tile={tile} nbuf={nbuf} FAILED: "
                        f"{type(e).__name__}: {e}")
                finally:
                    sgns_kernel.make_pallas_sgns_step = orig
    if which in ("xla", "all"):
        for b in (32768, 65536, 131072):
            for pdt in ("float32", "bfloat16"):
                for cdt in ("float32", "bfloat16"):
                    bench_step(counts, b, dtype=cdt, param_dtype=pdt)
        for pool in (256, 1024):
            bench_step(counts, 32768, pool=pool)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
