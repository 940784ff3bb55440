"""Scatter-add cost model: is XLA TPU scatter row-issue-bound or byte-bound?

If cost scales with the number of update rows but not with row bytes (D), the
HBM-roofline framing ("93 GB/s of 819 GB/s") is invalid — the step's floor is
rows x ns/row, and only reducing scattered rows (or finding a denser op) helps.

Measures mat.at[idx].add(upd) for a D sweep at fixed B and a B sweep at fixed D,
Zipf indices, f32 + bf16, with interleaved slope repeats (median reported).
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V, K = 200_000, 16


def main() -> None:
    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    print(f"device: {jax.devices()[0]}", file=sys.stderr)
    rng = np.random.default_rng(0)
    c = np.maximum(1e9 / (np.arange(V) + 10.0) ** 1.07, 5.0)
    p = c / c.sum()

    def measure(b, d, dt, repeats=3):
        mat0 = jnp.asarray(rng.normal(0, 0.05, (V, d)), dt)
        upd0 = jnp.asarray(rng.normal(0, 1e-4, (b, d)), dt)
        idx = jnp.asarray(np.stack(
            [np.random.default_rng(100 + j).choice(V, size=b, p=p)
             for j in range(K)]), jnp.int32)

        def chunk(m, u, idxs):
            def body(cc, ix):
                return cc.at[ix].add(u), ()
            out, _ = jax.lax.scan(body, m, idxs)
            return out, out[0, 0]

        f = jax.jit(chunk, donate_argnums=(0,))
        ts = []
        for _ in range(repeats):
            spc = time_chunked(f, lambda: mat0 + 0, lambda i: (upd0, idx),
                               n_lo=2, n_hi=8, fetch=lambda cc, o: o)
            ts.append(spc / K * 1e3)
        return float(np.median(ts))

    for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        print(f"\n-- D sweep at B=65536 [{dt_name}] --", file=sys.stderr)
        for d in (64, 128, 384, 768):
            ms = measure(65536, d, dt)
            print(f"  D={d:4d}: {ms:7.3f} ms  ({ms * 1e6 / 65536:6.1f} ns/row, "
                  f"{2 * 65536 * d * (4 if dt_name == 'f32' else 2) / (ms / 1e3) / 1e9:6.1f} GB/s)",
                  file=sys.stderr)
        print(f"-- B sweep at D=384 [{dt_name}] --", file=sys.stderr)
        for b in (8192, 32768, 65536, 131072):
            ms = measure(b, 384, dt)
            print(f"  B={b:6d}: {ms:7.3f} ms  ({ms * 1e6 / b:6.1f} ns/row)",
                  file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
