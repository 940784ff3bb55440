"""Focused A/B: plain scatter-add vs sorted+flagged scatter-add, with repeats.

tools/rowbench.py showed up to 7x run-to-run variance on single slope measurements
(round 3). This tool interleaves R slope repeats of each variant
and prints per-variant median [min..max], which is the only defensible basis for a
design decision. Variants:

    plain          — mat.at[zipf_idx].add(upd)
    sorted         — same indices pre-sorted, no XLA flag
    sorted+flag    — pre-sorted + indices_are_sorted=True
    sorted+permute — pre-sorted + flag, plus the [B,D] update-row permute the real
                     step needs for its second scatter (upd[order])

Run: python tools/scatter_ab.py [--dtype f32|bf16] [--repeats 5]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V, D, B, K = 200_000, 384, 65_536, 16


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    dt = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
    itemsize = 4 if args.dtype == "f32" else 2
    print(f"device: {jax.devices()[0]}  dtype={args.dtype}", file=sys.stderr)

    rng = np.random.default_rng(0)
    c = np.maximum(1e9 / (np.arange(V) + 10.0) ** 1.07, 5.0)
    p = c / c.sum()
    mat0 = jnp.asarray(rng.normal(0, 0.05, (V, D)), dt)
    upd0 = jnp.asarray(rng.normal(0, 1e-4, (B, D)), dt)

    zipf = np.stack([np.random.default_rng(100 + j).choice(V, size=B, p=p)
                     for j in range(K)])
    order = np.argsort(zipf, axis=-1)
    zipf_sorted = np.take_along_axis(zipf, order, axis=-1)
    idx_plain = jnp.asarray(zipf, jnp.int32)
    idx_sorted = jnp.asarray(zipf_sorted, jnp.int32)
    idx_order = jnp.asarray(order, jnp.int32)

    def make(fn):
        f = jax.jit(fn, donate_argnums=(0,))

        def run():
            return time_chunked(
                f, lambda: mat0 + 0, lambda i: (upd0, idx_plain, idx_sorted,
                                                idx_order),
                n_lo=2, n_hi=8, fetch=lambda cc, o: o)
        return run

    def plain(m, u, ip, isrt, iord):
        def body(cc, ix):
            return cc.at[ix].add(u), ()
        out, _ = jax.lax.scan(body, m, ip)
        return out, out[0, 0]

    def sorted_noflag(m, u, ip, isrt, iord):
        def body(cc, ix):
            return cc.at[ix].add(u), ()
        out, _ = jax.lax.scan(body, m, isrt)
        return out, out[0, 0]

    def sorted_flag(m, u, ip, isrt, iord):
        def body(cc, ix):
            return cc.at[ix].add(u, indices_are_sorted=True), ()
        out, _ = jax.lax.scan(body, m, isrt)
        return out, out[0, 0]

    def sorted_flag_permute(m, u, ip, isrt, iord):
        def body(cc, inp):
            ix, od = inp
            return cc.at[ix].add(u[od], indices_are_sorted=True), ()
        out, _ = jax.lax.scan(body, m, (isrt, iord))
        return out, out[0, 0]

    variants = {
        "plain": make(plain),
        "sorted": make(sorted_noflag),
        "sorted+flag": make(sorted_flag),
        "sorted+flag+permute": make(sorted_flag_permute),
    }
    times = {k: [] for k in variants}
    for r in range(args.repeats):
        for name, run in variants.items():
            spc = run()
            times[name].append(spc / K * 1e3)
    print(f"\nB={B} rows x D={D} {args.dtype} into V={V} "
          f"({args.repeats} interleaved slope repeats):", file=sys.stderr)
    for name, ts in times.items():
        med = float(np.median(ts))
        gbs = 2 * B * D * itemsize / (med / 1e3) / 1e9
        print(f"  {name:22s} median {med:7.3f} ms  [{min(ts):7.3f} .. "
              f"{max(ts):7.3f}]  ~{gbs:6.1f} GB/s", file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
