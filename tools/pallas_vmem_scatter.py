"""Measure the in-VMEM scatter-apply rate — the last unmeasured number in the
Pallas-SGNS analysis (VERDICT r4 item 6).

The coalesced-DMA kernel shape the round-4 verdict asked about ("pool-resident
VMEM, batch-tiled, sorted segment updates, double-buffered DMA") decomposes
into three costs:

  1. getting update rows into VMEM        — free: they arrive as grid blocks
  2. getting TARGET rows in/out of VMEM   — the r3 measurement: ~0.25 us per
     row DMA issue, 10x the XLA emitter's 27 ns/row; only a CONTIGUOUS head
     block escapes this (one bulk DMA), which Zipf makes attractive (63% of
     update rows hit the top-2048 ids — PERF.md §3 probe)
  3. APPLYING updates row-by-row inside VMEM — measured HERE

If (3) alone is at or above the emitter's ~27 ns/row, a Pallas kernel cannot
beat the XLA scatter even with all data movement free, and the head-hybrid is
doubly dead (the §3 drop probe already showed the tail scatter still costs
full price). The kernel: update rows stream through VMEM as grid blocks, a
[H, D] head accumulator stays VMEM-resident across the grid, and a scalar
fori_loop applies each row to its target via dynamic VMEM addressing — exactly
the apply loop any coalesced-segment design bottoms out in.

Run: python tools/pallas_vmem_scatter.py [--h 2048] [--d 384] [--tile 1024]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--h", type=int, default=2048)
    ap.add_argument("--d", type=int, default=384)
    ap.add_argument("--b", type=int, default=65536)
    ap.add_argument("--tile", type=int, default=1024)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    H, D, B, T = args.h, args.d, args.b, args.tile
    assert B % T == 0

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from microbench import time_chunked

    print(f"device: {jax.devices()[0]}  H={H} D={D} B={B} tile={T}",
          file=sys.stderr)

    def kernel(idx_ref, x_ref, o_ref):
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        def body(i, _):
            h = idx_ref[i]
            o_ref[pl.ds(h, 1), :] += x_ref[pl.ds(i, 1), :]
            return 0

        jax.lax.fori_loop(0, T, body, 0)

    @jax.jit
    def apply_updates(idx, x):
        return pl.pallas_call(
            kernel,
            grid=(B // T,),
            in_specs=[
                pl.BlockSpec((T,), lambda t: (t,), memory_space=pltpu.SMEM),
                pl.BlockSpec((T, D), lambda t: (t, 0)),
            ],
            out_specs=pl.BlockSpec((H, D), lambda t: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((H, D), jnp.float32),
        )(idx, x)

    rng = np.random.default_rng(0)
    # Zipf-hot indices into the head, like the production hot rows
    p = 1.0 / (np.arange(H) + 10.0) ** 1.07
    p /= p.sum()
    idxs = [jnp.asarray(rng.choice(H, size=B, p=p), jnp.int32)
            for _ in range(8)]
    x = jnp.asarray(rng.standard_normal((B, D), np.float32) * 1e-3)

    def step(carry, idx):
        out = apply_updates(idx, x)
        return carry + out[0, 0], out

    ts = []
    for _ in range(args.repeats):
        spc = time_chunked(
            step, lambda: jnp.float32(0.0),
            lambda i: (idxs[i % 8],),
            n_lo=2, n_hi=8,
            fetch=lambda c, out: c)
        ts.append(spc)
    med = float(np.median(ts))
    print(f"in-VMEM scatter-apply: {med * 1e3:7.3f} ms per {B} rows "
          f"-> {med / B * 1e9:6.1f} ns/row  "
          f"[{min(ts) / B * 1e9:.1f} .. {max(ts) / B * 1e9:.1f}]",
          file=sys.stderr)
    print(f"(XLA sorted-scatter emitter reference: ~27 ns/row, PERF.md §2)",
          file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
