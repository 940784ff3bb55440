"""Round-5 step A/B: trimming the NON-scatter ~40% of the stable bf16 step.

PERF.md §4's cost model says the two B-row scatters are the floor (~4.2 ms at
B=64k bf16) and everything else — gathers, pool matmuls, the [B,P] logit chain,
the loss reduction — is the remaining ~2.1 ms. The VERDICT r4 target is a
bf16 B=64k/pool=512 step at ~5 ms. Variants (all identical update math; only
metric/loss side-channels differ where named):

    shipped        — sgns_step_shared_core, bf16 params/compute/logits
    nometrics      — update math only, loss/metrics skipped entirely: the
                     UPPER BOUND of what metric elision can buy
    lastloss       — full metrics on the LAST step of the K-step scan only
                     (the production candidate: heartbeat telemetry needs one
                     loss sample per dispatch, not K)
    pos-loss       — per-step loss from the positive term only (a [B] chain);
                     the [B,P] negative loss pass skipped
    fused          — nometrics + the g_neg chain restructured into one where()
                     expression (alpha·n/P folded to one scalar, no separate
                     neg_valid array) — tests whether XLA's fusion already got
                     this (expect ~no delta)

Scatter-drop probe (gates the hot-row-carry design, VERDICT r4 item 2): pure
scatter-adds at the production shape where the rows hitting the top-H vocab ids
are redirected OOB (mode=drop). If dropped rows cost full emitter time (the §3
claim, measured at 50% uniform drops), a dense hot-row accumulator can never
pay for itself — the cold scatter still processes B rows.

Run: python tools/step_lean.py [--b 65536] [--pool 512] [--repeats 3]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V, D, NEG, K = 200_000, 384, 5, 16


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=65536)
    ap.add_argument("--pool", type=int, default=512)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--skip-probe", action="store_true")
    ap.add_argument("--probe-only", action="store_true")
    args = ap.parse_args()
    B, P = args.b, args.pool

    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives_hash
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, _log_sigmoid, _sigmoid, init_embeddings,
        sgns_step_shared_core)

    dt = jnp.bfloat16
    print(f"device: {jax.devices()[0]}  bf16 B={B} pool={P}", file=sys.stderr)

    rng = np.random.default_rng(0)
    counts = np.maximum(1e9 / (np.arange(V) + 10.0) ** 1.07, 5.0)
    p = counts / counts.sum()
    table = build_alias_table(counts)
    prob, alias = table.prob, table.alias
    syn0_0 = init_embeddings(V, D, jax.random.key(0)).syn0.astype(dt)
    syn1_0 = jnp.asarray(rng.normal(0, 0.05, (V, D)), dt)

    batches = []
    for i in range(12):
        r = np.random.default_rng(1000 + i)
        batches.append({
            "centers": jnp.asarray(r.choice(V, size=(K, B), p=p), jnp.int32),
            "contexts": jnp.asarray(r.choice(V, size=(K, B), p=p), jnp.int32),
            "mask": jnp.ones((K, B), jnp.float32),
        })

    ALPHA = 0.025

    def updates(syn0, syn1, centers, contexts, mask, negatives, fused=False):
        """The shared update math (bf16 end to end), returning the three deltas
        plus the logit arrays the loss variants may consume."""
        e_in = syn0[centers]                      # [B, D] bf16
        e_pos = syn1[contexts]
        Z = syn1[negatives]                       # [P, D]
        f_pos = jnp.sum(e_in * e_pos, axis=-1).astype(jnp.float32)
        f_neg = e_in @ Z.T                        # [B, P] bf16 — MXU
        g_pos = ((1.0 - _sigmoid(f_pos, "exact")) * ALPHA
                 * mask).astype(dt)               # [B] f32 chain, cast once
        if fused:
            scale = jnp.asarray(ALPHA * NEG / P, dt)
            g_neg = jnp.where(
                (negatives[None, :] != contexts[:, None])
                & (mask[:, None] > 0),
                (0.0 - _sigmoid(f_neg, "exact")) * scale,
                jnp.asarray(0.0, dt))
        else:
            neg_valid = (negatives[None, :] != contexts[:, None]).astype(dt) \
                * mask[:, None].astype(dt)
            g_neg = ((0.0 - _sigmoid(f_neg, "exact"))
                     * jnp.asarray(ALPHA, dt) * neg_valid
                     * jnp.asarray(NEG / P, dt))
        d_in = g_pos[:, None] * e_pos + g_neg @ Z
        d_pos = g_pos[:, None] * e_in
        d_Z = g_neg.T @ e_in
        return d_in, d_pos, d_Z, f_pos, f_neg

    def full_loss(f_pos, f_neg, mask, negatives, contexts):
        neg_valid = (negatives[None, :] != contexts[:, None]).astype(jnp.float32) \
            * mask[:, None]
        return (-_log_sigmoid(f_pos) * mask
                - jnp.sum(_log_sigmoid(-f_neg.astype(jnp.float32)) * neg_valid,
                          axis=-1) * (NEG / P)).sum() / jnp.maximum(mask.sum(), 1.0)

    def make_runner(kind):
        def chunk(params, batch, base_step, prob, alias):
            negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, P))

            def body(s, inp):
                b, ng, i = inp
                if kind == "shipped":
                    new_p, m = sgns_step_shared_core(
                        s, b["centers"], b["contexts"], b["mask"], ng,
                        jnp.float32(ALPHA), NEG, "exact", dt, False, dt)
                    return new_p, m.loss
                syn0, syn1 = s.syn0, s.syn1
                d_in, d_pos, d_Z, f_pos, f_neg = updates(
                    syn0, syn1, b["centers"], b["contexts"], b["mask"], ng,
                    fused=(kind == "fused"))
                new_syn0 = syn0.at[b["centers"]].add(d_in)
                new_syn1 = syn1.at[b["contexts"]].add(d_pos)
                new_syn1 = new_syn1.at[ng].add(d_Z)
                if kind in ("nometrics", "fused"):
                    loss = jnp.float32(0.0)
                elif kind == "pos-loss":
                    loss = (-_log_sigmoid(f_pos) * b["mask"]).sum() \
                        / jnp.maximum(b["mask"].sum(), 1.0)
                elif kind == "lastloss":
                    loss = jax.lax.cond(
                        i == K - 1,
                        lambda: full_loss(f_pos, f_neg, b["mask"], ng,
                                          b["contexts"]),
                        lambda: jnp.float32(0.0))
                else:
                    raise ValueError(kind)
                return EmbeddingPair(new_syn0, new_syn1), loss

            return jax.lax.scan(body, params,
                                (batch, negs, jnp.arange(K)))

        f = jax.jit(chunk, donate_argnums=(0,))

        def run():
            return time_chunked(
                f, lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                lambda i: (batches[i % 12], np.int32(100 + i), prob, alias),
                n_lo=2, n_hi=8, fetch=lambda c, out: out[-1])
        return run

    if not args.probe_only:
        runners = {
            "shipped (bf16/logits-bf16)": make_runner("shipped"),
            "nometrics": make_runner("nometrics"),
            "lastloss (metrics 1/K)": make_runner("lastloss"),
            "pos-loss": make_runner("pos-loss"),
            "fused-gneg": make_runner("fused"),
        }
        times = {k: [] for k in runners}
        for _ in range(args.repeats):
            for name, run in runners.items():
                spc = run()
                times[name].append(spc / K * 1e3)
        print(f"\nlean-step A/B (B={B}, pool={P}, bf16, median of "
              f"{args.repeats} interleaved repeats):", file=sys.stderr)
        for name, ts in times.items():
            med = float(np.median(ts))
            print(f"  {name:28s} median {med:7.3f} ms/step  "
                  f"[{min(ts):7.3f} .. {max(ts):7.3f}]  "
                  f"{B / (med / 1e3):13,.0f} pairs/s", file=sys.stderr)

    if args.skip_probe:
        return

    # ---- scatter-drop probe: do OOB-dropped rows cost emitter time? ----------
    # Redirect the rows whose target id < H (the Zipf-hot head) to V (dropped).
    # If the emitter charged per APPLIED row, the dropped variants would speed
    # up by the hot-row share; §3's claim is they do not.
    print("\nscatter-drop probe (pure scatter-add, [B,D] bf16 updates, "
          "Zipf indices):", file=sys.stderr)
    # ONE [B, D] update array, passed as a jit ARGUMENT and reused every scan
    # step — a [K, B, D] closure constant is baked into the compiled program
    # (the ops/prng.py rule: big arrays enter as arguments)
    upd = jnp.asarray(rng.normal(0, 1e-4, (B, D)), dt)

    def make_scatter(drop_h, sort=False):
        def chunk(mat, idx, up):
            def body(m, ix):
                return m.at[ix].add(up, mode="drop"), jnp.float32(0)
            return jax.lax.scan(body, mat, idx)

        f = jax.jit(chunk, donate_argnums=(0,))
        idxs = []
        for i in range(12):
            ix = np.asarray(batches[i]["centers"])
            if drop_h:
                ix = np.where(ix < drop_h, V, ix)
            if sort:
                ix = np.sort(ix, axis=-1)
            idxs.append(jnp.asarray(ix, jnp.int32))

        def run():
            return time_chunked(
                f, lambda: syn0_0 + 0,
                lambda i: (idxs[i % 12], upd),
                n_lo=2, n_hi=8,
                # the scan output is constant zeros — the barrier must fetch
                # from the updated carry
                fetch=lambda c, out: c[0, 0].astype(jnp.float32))
        return run

    hot_share = {h: float(np.mean(np.asarray(batches[0]["centers"]) < h))
                 for h in (256, 2048, 16384)}
    probe = {"plain (0% dropped)": make_scatter(0)}
    for h in (256, 2048, 16384):
        probe[f"drop id<{h} ({hot_share[h]:.0%} rows)"] = make_scatter(h)
    probe["drop id<2048, host-sorted"] = make_scatter(2048, sort=True)
    ptimes = {k: [] for k in probe}
    for _ in range(args.repeats):
        for name, run in probe.items():
            spc = run()
            ptimes[name].append(spc / K * 1e3)
    for name, ts in ptimes.items():
        med = float(np.median(ts))
        print(f"  {name:32s} median {med:7.3f} ms  "
              f"[{min(ts):7.3f} .. {max(ts):7.3f}]", file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
