"""Row-traffic primitive benchmark: where does the SGNS step's bandwidth go?

The round-3 verdict computed that the B=64k f32 step moves ~604 MB of row traffic in
6.46 ms ≈ 93 GB/s against ~819 GB/s of v5e HBM — ~11% of roofline — and asked for a
component-level accounting. This tool times the step's constituent memory primitives
in isolation with the slope method (tools/microbench.py):

    gather        — out = mat[idx]                      (read B rows)
    scatter-add   — mat.at[idx].add(upd)                (RMW B rows)
    dedup-scatter — sort idx, segment_sum rows, scatter unique rows only
    full permute  — upd[order]                          (read+write B rows)

each × {unique-shuffled, zipf} indices × {f32, bf16}, plus a copy bandwidth anchor
(mat + 1) to calibrate what "roofline" means for this chip through this runtime.

Run: python tools/rowbench.py [--quick]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V, D, B = 200_000, 384, 65_536


def zipf_counts(v: int) -> np.ndarray:
    return np.maximum(1e9 / (np.arange(v) + 10.0) ** 1.07, 5.0)


def make_indices(kind: str, rng: np.random.Generator, n: int) -> np.ndarray:
    if kind == "unique":
        # B distinct rows, shuffled — no duplicate serialization possible
        return rng.choice(V, size=n, replace=False)
    if kind == "zipf":
        c = zipf_counts(V)
        return rng.choice(V, size=n, p=c / c.sum())
    if kind == "zipf_sorted":
        c = zipf_counts(V)
        return np.sort(rng.choice(V, size=n, p=c / c.sum()))
    raise ValueError(kind)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr)
    K = 8 if args.quick else 16

    rng = np.random.default_rng(0)

    def report(name, spc, bytes_moved):
        ms = spc / K * 1e3
        gbs = bytes_moved / (spc / K) / 1e9
        print(f"{name:42s} {ms:8.3f} ms  {gbs:8.1f} GB/s", file=sys.stderr)
        return ms, gbs

    results = {}
    for dt_name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        itemsize = 4 if dt_name == "f32" else 2
        row_bytes = D * itemsize
        mat0 = jnp.asarray(rng.normal(0, 0.05, (V, D)), dt)
        upd0 = jnp.asarray(rng.normal(0, 1e-4, (B, D)), dt)

        # ---- copy anchor: read V rows + write V rows -------------------------
        def copy_chunk(m, _):
            def body(c, _x):
                return c * jnp.asarray(1.0001, dt), ()
            out, _ = jax.lax.scan(body, m, None, length=K)
            return out, out[0, 0]

        f = jax.jit(copy_chunk, donate_argnums=(0,))
        spc = time_chunked(f, lambda: mat0 + 0, lambda i: ((),),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"copy_{dt_name}"] = report(
            f"copy mat*c [{dt_name}] (2x{V}x{D})", spc, 2 * V * D * itemsize)

        idx_sets = {k: jnp.asarray(
            np.stack([make_indices(k, np.random.default_rng(100 + j), B)
                      for j in range(K)]), jnp.int32)
            for k in ("unique", "zipf", "zipf_sorted")}

        # ---- gather ----------------------------------------------------------
        for kind in ("unique", "zipf"):
            def gather_chunk2(c, m, idxs):
                def body(cc, ix):
                    g = m[ix]
                    return cc + g.astype(jnp.float32).sum(), ()
                out, _ = jax.lax.scan(body, c, idxs)
                return out, out

            f = jax.jit(gather_chunk2)
            spc = time_chunked(f, lambda: jnp.float32(0.0),
                               lambda i: (mat0, idx_sets[kind]),
                               n_lo=2, n_hi=8, fetch=lambda c, o: o)
            results[f"gather_{kind}_{dt_name}"] = report(
                f"gather B rows [{kind} {dt_name}]", spc, B * row_bytes)

        # ---- scatter-add -----------------------------------------------------
        for kind in ("unique", "zipf", "zipf_sorted"):
            def scat_chunk(m, u, idxs):
                def body(c, ix):
                    return c.at[ix].add(u), ()
                out, _ = jax.lax.scan(body, m, idxs)
                return out, out[0, 0]

            f = jax.jit(scat_chunk, donate_argnums=(0,))
            spc = time_chunked(f, lambda: mat0 + 0,
                               lambda i: (upd0, idx_sets[kind]),
                               n_lo=2, n_hi=8, fetch=lambda c, o: o)
            # RMW of ~B rows: B read + B write (upper bound; duplicates make it less)
            results[f"scatter_{kind}_{dt_name}"] = report(
                f"scatter-add B rows [{kind} {dt_name}]", spc, 2 * B * row_bytes)

        # ---- scatter-add with XLA's sorted/unique fast-path flags ------------
        for kind, flags in (("zipf_sorted", dict(indices_are_sorted=True)),
                            ("unique", dict(unique_indices=True)),):
            def scat_flag_chunk(m, u, idxs):
                def body(c, ix):
                    return c.at[ix].add(u, **flags), ()
                out, _ = jax.lax.scan(body, m, idxs)
                return out, out[0, 0]

            f = jax.jit(scat_flag_chunk, donate_argnums=(0,))
            spc = time_chunked(f, lambda: mat0 + 0,
                               lambda i: (upd0, idx_sets[kind]),
                               n_lo=2, n_hi=8, fetch=lambda c, o: o)
            fl = "+".join(k for k in flags)
            results[f"scatter_{kind}_{fl}_{dt_name}"] = report(
                f"scatter-add [{kind} {fl} {dt_name}]", spc, 2 * B * row_bytes)

        # unique AND sorted with both flags — the theoretical XLA fast path
        uniq_sorted = jnp.sort(idx_sets["unique"], axis=-1)

        def scat_us_chunk(m, u, idxs):
            def body(c, ix):
                return c.at[ix].add(u, indices_are_sorted=True,
                                    unique_indices=True), ()
            out, _ = jax.lax.scan(body, m, idxs)
            return out, out[0, 0]

        f = jax.jit(scat_us_chunk, donate_argnums=(0,))
        spc = time_chunked(f, lambda: mat0 + 0, lambda i: (upd0, uniq_sorted),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"scatter_uniqsorted_bothflags_{dt_name}"] = report(
            f"scatter-add [unique sorted both-flags {dt_name}]", spc,
            2 * B * row_bytes)

        # ---- scatter-add with half the rows dropped (OOB index) --------------
        drop_idx = np.stack([make_indices("zipf", np.random.default_rng(300 + j), B)
                             for j in range(K)])
        dmask = np.random.default_rng(9).random((K, B)) < 0.5
        drop_idx = np.where(dmask, V, drop_idx)  # OOB -> dropped by XLA scatter

        def scat_drop_chunk(m, u, idxs):
            def body(c, ix):
                return c.at[ix].add(u, mode="drop"), ()
            out, _ = jax.lax.scan(body, m, idxs)
            return out, out[0, 0]

        f = jax.jit(scat_drop_chunk, donate_argnums=(0,))
        spc = time_chunked(f, lambda: mat0 + 0,
                           lambda i: (upd0, jnp.asarray(drop_idx, jnp.int32)),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"scatter_half_dropped_{dt_name}"] = report(
            f"scatter-add [zipf 50% OOB-dropped {dt_name}]", spc, B * row_bytes)

        # ---- hot-row accumulate via one-hot matmul (MXU path) ----------------
        for H in (1024, 2048):
            def onehot_chunk(m, u, idxs):
                def body(c, ix):
                    oh = (ix[:, None] == jnp.arange(H)[None, :]).astype(dt)
                    hot = (oh.T @ u.astype(dt)).astype(dt)       # [H, D] on MXU
                    return c.at[jnp.arange(H)].add(hot), ()
                out, _ = jax.lax.scan(body, m, idxs)
                return out, out[0, 0]

            f = jax.jit(onehot_chunk, donate_argnums=(0,))
            spc = time_chunked(f, lambda: mat0 + 0,
                               lambda i: (upd0, idx_sets["zipf"]),
                               n_lo=2, n_hi=8, fetch=lambda c, o: o)
            results[f"onehot_H{H}_{dt_name}"] = report(
                f"one-hot matmul accum H={H} [{dt_name}]", spc,
                B * row_bytes + 2 * H * row_bytes)

        # ---- cumsum over [B, D] (sorted-segment-sum building block) ----------
        def cumsum_chunk(c, u, idxs):
            def body(cc, ix):
                s = jnp.cumsum(u.astype(jnp.float32), axis=0)
                return cc + s[-1, 0], ()
            out, _ = jax.lax.scan(body, c, idxs)
            return out, out

        f = jax.jit(cumsum_chunk)
        spc = time_chunked(f, lambda: jnp.float32(0.0),
                           lambda i: (upd0, idx_sets["zipf"]),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"cumsum_{dt_name}"] = report(
            f"cumsum [B,D] [{dt_name}]", spc, 2 * B * row_bytes)

        # ---- dedup scatter-add (sort + segment_sum + unique-row scatter) -----
        for kind in ("unique", "zipf"):
            def dedup_chunk(m, u, idxs):
                def body(c, ix):
                    order = jnp.argsort(ix)
                    sidx = ix[order]
                    supd = u[order]
                    seg_start = jnp.concatenate(
                        [jnp.ones((1,), jnp.int32),
                         (sidx[1:] != sidx[:-1]).astype(jnp.int32)])
                    seg_id = jnp.cumsum(seg_start) - 1
                    sums = jax.ops.segment_sum(supd, seg_id, num_segments=B)
                    seg_row = jnp.full((B,), V, jnp.int32).at[seg_id].min(sidx)
                    return c.at[seg_row].add(sums.astype(dt)), ()
                out, _ = jax.lax.scan(body, m, idxs)
                return out, out[0, 0]

            f = jax.jit(dedup_chunk, donate_argnums=(0,))
            spc = time_chunked(f, lambda: mat0 + 0,
                               lambda i: (upd0, idx_sets[kind]),
                               n_lo=2, n_hi=8, fetch=lambda c, o: o)
            results[f"dedup_{kind}_{dt_name}"] = report(
                f"dedup scatter-add [{kind} {dt_name}]", spc, 2 * B * row_bytes)

        # ---- dedup, pre-sorted indices (host sorts; no permute gather) -------
        def dedup_sorted_chunk(m, u, idxs):
            def body(c, ix):
                seg_start = jnp.concatenate(
                    [jnp.ones((1,), jnp.int32),
                     (ix[1:] != ix[:-1]).astype(jnp.int32)])
                seg_id = jnp.cumsum(seg_start) - 1
                sums = jax.ops.segment_sum(u, seg_id, num_segments=B)
                seg_row = jnp.full((B,), V, jnp.int32).at[seg_id].min(ix)
                return c.at[seg_row].add(sums.astype(dt)), ()
            out, _ = jax.lax.scan(body, m, idxs)
            return out, out[0, 0]

        f = jax.jit(dedup_sorted_chunk, donate_argnums=(0,))
        spc = time_chunked(f, lambda: mat0 + 0,
                           lambda i: (upd0, idx_sets["zipf_sorted"]),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"dedup_presorted_{dt_name}"] = report(
            f"dedup scatter-add [presorted zipf {dt_name}]", spc, 2 * B * row_bytes)

        # ---- row permute (cost of reordering a [B,D] update) -----------------
        perm = jnp.asarray(np.stack([np.random.default_rng(7 + j).permutation(B)
                                     for j in range(K)]), jnp.int32)

        def perm_chunk(c, u, perms):
            def body(cc, pr):
                return cc + u[pr].astype(jnp.float32).sum(), ()
            out, _ = jax.lax.scan(body, c, perms)
            return out, out

        f = jax.jit(perm_chunk)
        spc = time_chunked(f, lambda: jnp.float32(0.0), lambda i: (upd0, perm),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"permute_{dt_name}"] = report(
            f"permute B update rows [{dt_name}]", spc, B * row_bytes)

        # ---- argsort cost ----------------------------------------------------
        def sort_chunk(c, idxs):
            def body(cc, ix):
                return cc + jnp.argsort(ix)[0], ()
            out, _ = jax.lax.scan(body, c, idxs)
            return out, out

        f = jax.jit(sort_chunk)
        spc = time_chunked(f, lambda: jnp.int32(0), lambda i: (idx_sets["zipf"],),
                           n_lo=2, n_hi=8, fetch=lambda c, o: o)
        results[f"argsort_{dt_name}"] = report(
            f"argsort B int32 [{dt_name} run]", spc, 2 * B * 4)

    print("\nsummary ms/op:", file=sys.stderr)
    for k, (ms, gbs) in results.items():
        print(f"  {k:28s} {ms:8.3f} ms {gbs:8.1f} GB/s", file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
