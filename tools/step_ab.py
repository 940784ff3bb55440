"""Full-step A/B: current 3-scatter shared-pool SGNS step vs merged-scatter variants.

HLO analysis (tools/scatter_model.py + compiled-HLO dump) shows each scatter-add pays
a fixed cost — index sort + a [B,D] update permute + a serial sorted-scatter emitter
(~27 ns/row) — and the production step pays it three times (syn0[centers],
syn1[contexts], syn1[pool]). Variants measured here, all mathematically identical to
sgns_step_shared_core (scatter-add is order-independent up to FP associativity):

    current     — sgns_step_shared_core as shipped (3 scatters)
    merged-syn1 — contexts+pool in one scatter (2 scatters)
    merged-all  — one [2V,D] array, centers/contexts/pool in ONE scatter
    merged-all + dense head H — rows < H updated via one-hot matmul (MXU) and a
                  dense slab add; only tail rows scattered. Exact (one-hot of a
                  head row is zero for tail ids), no compaction needed for A/B —
                  scatter still processes B rows but the cost model says rows are
                  what matters, so this row only shows matmul overhead vs scatter
                  savings potential with compaction.

Run: python tools/step_ab.py [--dtype f32|bf16] [--b 65536] [--pool 256]

--cbow mode: interleaved A/B of the two CBOW step formulations on the SAME
synthetic Zipf sentence stream (PERF.md §9's measurement harness):

    scatter — cbow_step_shared_core as shipped: grouped [B, 2w] context
              batches, B·C-row syn0 gather+scatter (the BENCH cbow row)
    banded  — cbow_step_banded_core: sentence-contiguous halo token blocks,
              windows derived on device from the same hash lattice, context
              traffic via prefix sums (ops/cbow_banded.py)

Both run metrics-elided with a params-carry fetch (the production regime) and
report examples/s over the REAL examples each step trains (the scatter batch
packs B live examples; a banded block trains its ~(w−1)/w·B live core slots).

Run: python tools/step_ab.py --cbow [--dtype bf16] [--b 65536] [--pool 512]
     [--window 5] [--v 200000] [--d 384]
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V, D, NEG, K = 200_000, 384, 5, 16


def run_cbow_ab(args) -> None:
    """Interleaved banded-vs-scatter CBOW A/B on one shared sentence stream."""
    import jax
    import jax.numpy as jnp
    from cbow_feed import make_banded_chunk, pack_banded_feeds
    from microbench import time_chunked

    from glint_word2vec_tpu.data.hashrng import (
        STREAM_WINDOW, hash_mod_at, stream_base)
    from glint_word2vec_tpu.ops.sampler import (
        build_alias_table, sample_negatives_hash)
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, cbow_step_shared_core, init_embeddings)

    Vv, Dd = args.v, args.d
    B, P, W = args.b, args.pool, args.window
    C = 2 * W
    H = W
    T = B + 2 * H                      # banded: B core slots per step
    n_sets = 4                         # rotating chunk sets (cache variety)
    seed = 1234
    dt = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
    print(f"device: {jax.devices()[0]}  CBOW A/B  dtype={args.dtype} "
          f"B={B} pool={P} window={W} V={Vv} D={Dd}", file=sys.stderr)

    rng = np.random.default_rng(0)
    counts = np.maximum(1e9 / (np.arange(Vv) + 10.0) ** 1.07, 5.0)
    p = counts / counts.sum()
    table = build_alias_table(counts)
    prob, alias = table.prob, table.alias
    syn0_0 = init_embeddings(Vv, Dd, jax.random.key(0)).syn0.astype(dt)
    syn1_0 = jnp.asarray(rng.normal(0, 0.05, (Vv, Dd)), dt)

    # ---- one shared kept-token stream: Zipf tokens, 40-token sentences ------
    # sized so BOTH feeds draw fresh examples: banded consumes B stream tokens
    # per step, scatter B LIVE examples (~(w-1)/w of tokens are live)
    stream_len = int(n_sets * K * B * W / (W - 1) * 1.05) + 2 * H
    toks = rng.choice(Vv, size=stream_len, p=p).astype(np.int32)
    starts = np.zeros(stream_len, bool)
    starts[::40] = True
    win_base = stream_base(seed, STREAM_WINDOW, 1, 0)

    # host mirror of the device window derivation (sentence-clamped extents),
    # for the scatter batches and the real-example accounting
    ordinals = np.arange(stream_len, dtype=np.uint64)
    bdraw = hash_mod_at(win_base, ordinals, W).astype(np.int64)
    sent_id = np.cumsum(starts) - 1
    sstarts = np.flatnonzero(starts)                       # [n_sentences]
    pos = np.arange(stream_len) - sstarts[sent_id]
    nxt = np.concatenate([sstarts[1:], [stream_len]])
    avail = nxt[sent_id] - 1 - np.arange(stream_len)
    left = np.minimum(bdraw, pos)
    right = np.clip(np.minimum(bdraw - 1, avail), 0, None)
    total = left + right
    live = np.flatnonzero(total > 0)

    # ---- banded feed: K halo blocks per set (shared harness: cbow_feed.py) --
    banded_sets = pack_banded_feeds(toks, starts, T, H, n_sets, K)
    banded_live = float(len(live[live < n_sets * K * B])) / (n_sets * K)

    # ---- scatter feed: K dense [B, C] grouped batches per set ---------------
    scatter_sets = []
    li = 0
    for _ in range(n_sets):
        cb, xb, nb = [], [], []
        for _ in range(K):
            sel = live[li:li + B]
            li += B
            lv, rv = left[sel], right[sel]
            j = np.arange(C, dtype=np.int64)[None, :]
            cpos = np.where(j < lv[:, None], sel[:, None] - lv[:, None] + j,
                            sel[:, None] + j - lv[:, None] + 1)
            valid = j < (lv + rv)[:, None]
            cb.append(toks[sel])
            xb.append(np.where(valid, toks[np.clip(cpos, 0, stream_len - 1)],
                               0).astype(np.int32))
            nb.append((lv + rv).astype(np.int32))
        scatter_sets.append({
            "centers": jnp.asarray(np.stack(cb), jnp.int32),
            "contexts": jnp.asarray(np.stack(xb), jnp.int32),
            "nctx": jnp.asarray(np.stack(nb), jnp.int32),
        })

    ldt = dt
    banded_chunk = make_banded_chunk(W, P, NEG, dt, ldt, win_base, K,
                                     seed=seed)

    def scatter_chunk(params, feed, base_step, prob, alias):
        negs = sample_negatives_hash(prob, alias, seed, base_step, (K, P))

        def body(pr, inp):
            c, x, nc, ng = inp
            cmask = (jnp.arange(C)[None, :] < nc[:, None]).astype(jnp.float32)
            new_p, m = cbow_step_shared_core(
                pr, c, x, cmask, jnp.ones(B, jnp.float32), ng,
                jnp.float32(0.025), NEG, "exact", dt, ldt,
                with_metrics=False)
            return new_p, m.loss

        return jax.lax.scan(body, params, (
            feed["centers"], feed["contexts"], feed["nctx"], negs))

    runners = {}
    for name, fn, sets in (("scatter (B*C rows)", scatter_chunk, scatter_sets),
                           ("banded (prefix sums)", banded_chunk, banded_sets)):
        f = jax.jit(fn, donate_argnums=(0,))

        def run(f=f, sets=sets):
            return time_chunked(
                f,
                lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                lambda i: (sets[i % n_sets], np.int32(100 + i), prob, alias),
                n_lo=2, n_hi=8,
                # losses are elided — the fetch must depend on the params carry
                fetch=lambda c, out: c.syn0[0, 0].astype(jnp.float32))
        runners[name] = run

    times = {k: [] for k in runners}
    for _ in range(args.repeats):
        for name, run in runners.items():
            spc = run()
            times[name].append(spc / K * 1e3)
    ex_per_step = {"scatter (B*C rows)": float(B),
                   "banded (prefix sums)": banded_live}
    print(f"\nCBOW step A/B (B={B}, pool={P}, window={W}, {args.dtype}, "
          f"median of {args.repeats} interleaved repeats):", file=sys.stderr)
    meds = {}
    for name, ts in times.items():
        med = float(np.median(ts))
        meds[name] = med
        ex = ex_per_step[name]
        print(f"  {name:24s} median {med:8.3f} ms/step  "
              f"[{min(ts):8.3f} .. {max(ts):8.3f}]  "
              f"{ex / (med / 1e3):13,.0f} examples/s "
              f"({ex:,.0f} real ex/step)", file=sys.stderr)
    sc = ex_per_step["scatter (B*C rows)"] / meds["scatter (B*C rows)"]
    bd = ex_per_step["banded (prefix sums)"] / meds["banded (prefix sums)"]
    print(f"  banded/scatter examples/s ratio: {bd / sc:.2f}x", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--b", type=int, default=65536)
    ap.add_argument("--pool", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--cbow", action="store_true",
                    help="A/B the banded vs scatter CBOW step instead")
    ap.add_argument("--window", type=int, default=5)
    ap.add_argument("--v", type=int, default=V)
    ap.add_argument("--d", type=int, default=D)
    args = ap.parse_args()
    if args.cbow:
        if args.window < 2:
            ap.error("--cbow needs --window >= 2: the reference's legacy "
                     "asymmetric window draws b = nextInt(1) = 0 at window=1, "
                     "which emits no contexts at all (the config path refuses "
                     "cbow_update='banded' there for the same reason)")
        run_cbow_ab(args)
        return
    B, P = args.b, args.pool

    import jax
    import jax.numpy as jnp
    from microbench import time_chunked

    from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives_hash
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, _log_sigmoid, _sigmoid, init_embeddings,
        sgns_step_shared_core)

    dt = jnp.float32 if args.dtype == "f32" else jnp.bfloat16
    print(f"device: {jax.devices()[0]}  dtype={args.dtype} B={B} pool={P}",
          file=sys.stderr)

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

    def core_merged(syn, centers, contexts, mask, negatives, alpha, dense_head=0):
        """One-scatter variant on merged [2V, D] (rows V..2V-1 are syn1)."""
        cdt = jnp.float32
        e_in = syn[centers].astype(cdt)
        e_pos = syn[V + contexts].astype(cdt)
        Z = syn[V + negatives].astype(cdt)
        f_pos = jnp.sum(e_in * e_pos, axis=-1)
        f_neg = e_in @ Z.T
        neg_valid = (negatives[None, :] != contexts[:, None]).astype(cdt) \
            * mask[:, None]
        g_pos = (1.0 - _sigmoid(f_pos, "exact")) * alpha * mask
        g_neg = (0.0 - _sigmoid(f_neg, "exact")) * alpha * neg_valid * (NEG / P)
        d_in = g_pos[:, None] * e_pos + g_neg @ Z
        d_pos = g_pos[:, None] * e_in
        d_Z = g_neg.T @ e_in
        idx = jnp.concatenate([centers, V + contexts, V + negatives])
        upd = jnp.concatenate([d_in, d_pos, d_Z]).astype(syn.dtype)
        if dense_head:
            H = dense_head
            # head rows (idx % V < H) ride the MXU: one-hot matmul -> dense add
            local = jnp.where(idx >= V, idx - V, idx)
            half = (idx >= V).astype(jnp.int32)
            is_head = local < H
            oh = ((local[:, None] == jnp.arange(H)[None, :]) &
                  (half[:, None] == 0)).astype(upd.dtype)
            oh1 = ((local[:, None] == jnp.arange(H)[None, :]) &
                   (half[:, None] == 1)).astype(upd.dtype)
            head0 = oh.T @ upd
            head1 = oh1.T @ upd
            syn = syn.at[:H].add(head0)
            syn = syn.at[V:V + H].add(head1)
            idx = jnp.where(is_head, 2 * V, idx)  # dropped
            syn = syn.at[idx].add(upd, mode="drop")
        else:
            syn = syn.at[idx].add(upd)
        loss = (-_log_sigmoid(f_pos) * mask
                - jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1)
                * (NEG / P)).sum() / jnp.maximum(mask.sum(), 1.0)
        return syn, loss

    def core_merged_syn1(params, centers, contexts, mask, negatives, alpha):
        """contexts+pool in one scatter; syn0/syn1 stay separate (2 scatters)."""
        syn0, syn1 = params.syn0, params.syn1
        cdt = jnp.float32
        e_in = syn0[centers].astype(cdt)
        e_pos = syn1[contexts].astype(cdt)
        Z = syn1[negatives].astype(cdt)
        f_pos = jnp.sum(e_in * e_pos, axis=-1)
        f_neg = e_in @ Z.T
        neg_valid = (negatives[None, :] != contexts[:, None]).astype(cdt) \
            * mask[:, None]
        g_pos = (1.0 - _sigmoid(f_pos, "exact")) * alpha * mask
        g_neg = (0.0 - _sigmoid(f_neg, "exact")) * alpha * neg_valid * (NEG / P)
        d_in = g_pos[:, None] * e_pos + g_neg @ Z
        d_pos = g_pos[:, None] * e_in
        d_Z = g_neg.T @ e_in
        new_syn0 = syn0.at[centers].add(d_in.astype(syn0.dtype))
        idx1 = jnp.concatenate([contexts, negatives])
        upd1 = jnp.concatenate([d_pos, d_Z]).astype(syn1.dtype)
        new_syn1 = syn1.at[idx1].add(upd1)
        loss = (-_log_sigmoid(f_pos) * mask
                - jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1)
                * (NEG / P)).sum() / jnp.maximum(mask.sum(), 1.0)
        return EmbeddingPair(new_syn0, new_syn1), loss

    def make_runner(kind, dense_head=0):
        def chunk(state, batch, base_step, prob, alias):
            negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, P))

            def body(s, inp):
                b, ng = inp
                if kind == "current":
                    new_p, m = sgns_step_shared_core(
                        s, b["centers"], b["contexts"], b["mask"], ng,
                        jnp.float32(0.025), NEG, "exact", jnp.float32)
                    return new_p, m.loss
                if kind == "merged_syn1":
                    return core_merged_syn1(
                        s, b["centers"], b["contexts"], b["mask"], ng,
                        jnp.float32(0.025))
                return core_merged(
                    s, b["centers"], b["contexts"], b["mask"], ng,
                    jnp.float32(0.025), dense_head)
            return jax.lax.scan(body, state, (batch, negs))

        f = jax.jit(chunk, donate_argnums=(0,))

        if kind == "merged":
            def mk():
                return jnp.concatenate([syn0_0, syn1_0])
        else:
            def mk():
                return EmbeddingPair(syn0_0 + 0, syn1_0 + 0)

        def run():
            return time_chunked(
                f, mk, lambda i: (batches[i % 12], np.int32(100 + i), prob, alias),
                n_lo=2, n_hi=8, fetch=lambda c, out: out[-1])
        return run

    # ---- center-grouped variant: the reference's wOutput shape (mllib:419) ----
    # skip-gram emits ~2*window pairs per center; grouping contexts per center
    # cuts syn0 gather+scatter rows and the pool matmul by the group width.
    W = 10                      # 2*window slots
    FILL = 0.655                # mean window fill under the reference's shrink rule
    Bc = max(1, int(B * 1.0 / (W * FILL)))  # groups per batch ~ same real pairs

    gbatches = []
    for i in range(12):
        r = np.random.default_rng(2000 + i)
        centers = np.sort(r.choice(V, size=(K, Bc), p=p), axis=-1)  # host-sorted
        ctx = r.choice(V, size=(K, Bc, W), p=p)
        n_ctx = r.integers(1, W + 1, size=(K, Bc))
        cmask = (np.arange(W)[None, None, :] < n_ctx[..., None])
        gbatches.append({
            "centers": jnp.asarray(centers, jnp.int32),
            "ctx": jnp.asarray(ctx, jnp.int32),
            "cmask": jnp.asarray(cmask, jnp.float32),
        })
    real_pairs = float(np.mean([np.asarray(g["cmask"]).sum(axis=(1, 2)).mean()
                                for g in gbatches]))

    def core_grouped(params, centers, ctx, cmask, negatives, alpha):
        syn0, syn1 = params.syn0, params.syn1
        cdt = jnp.float32
        e_in = syn0[centers].astype(cdt)                 # [Bc, D]
        e_pos = syn1[ctx].astype(cdt)                    # [Bc, W, D]
        Z = syn1[negatives].astype(cdt)                  # [P, D]
        f_pos = jnp.einsum("bd,bwd->bw", e_in, e_pos)
        f_neg = e_in @ Z.T                               # [Bc, P] — per center!
        neg_valid = (negatives[None, :] != centers[:, None]).astype(cdt)
        n_ctx = cmask.sum(axis=-1)                       # [Bc]
        g_pos = (1.0 - _sigmoid(f_pos, "exact")) * alpha * cmask
        # per-pair negative term depends only on the center -> weight by n_ctx
        g_neg = ((0.0 - _sigmoid(f_neg, "exact")) * alpha * neg_valid
                 * (NEG / P)) * n_ctx[:, None]
        d_in = jnp.einsum("bw,bwd->bd", g_pos, e_pos) + g_neg @ Z
        d_pos = g_pos[..., None] * e_in[:, None, :]      # [Bc, W, D]
        d_Z = g_neg.T @ e_in                             # [P, D]
        new_syn0 = syn0.at[centers].add(d_in.astype(syn0.dtype),
                                        indices_are_sorted=True)
        new_syn1 = syn1.at[ctx.reshape(-1)].add(
            d_pos.reshape(-1, D).astype(syn1.dtype))
        new_syn1 = new_syn1.at[negatives].add(d_Z.astype(syn1.dtype))
        loss = (f_pos * cmask).sum() / jnp.maximum(cmask.sum(), 1.0)
        return EmbeddingPair(new_syn0, new_syn1), loss

    def make_grouped_runner():
        def chunk(state, batch, base_step, prob, alias):
            negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, P))

            def body(s, inp):
                b, ng = inp
                return core_grouped(s, b["centers"], b["ctx"], b["cmask"], ng,
                                    jnp.float32(0.025))
            return jax.lax.scan(body, state, (batch, negs))

        f = jax.jit(chunk, donate_argnums=(0,))

        def run():
            return time_chunked(
                f, lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                lambda i: (gbatches[i % 12], np.int32(100 + i), prob, alias),
                n_lo=2, n_hi=8, fetch=lambda c, out: out[-1])
        return run

    # ---- host-sorted batch + indices_are_sorted on the syn0 scatter ----------
    sbatches = []
    for i in range(12):
        b = batches[i]
        c = np.asarray(b["centers"])
        x = np.asarray(b["contexts"])
        order = np.argsort(c, axis=-1)
        sbatches.append({
            "centers": jnp.asarray(np.take_along_axis(c, order, -1), jnp.int32),
            "contexts": jnp.asarray(np.take_along_axis(x, order, -1), jnp.int32),
            "mask": b["mask"],
        })

    def core_sorted(params, centers, contexts, mask, negatives, alpha):
        syn0, syn1 = params.syn0, params.syn1
        cdt = jnp.float32
        e_in = syn0[centers].astype(cdt)
        e_pos = syn1[contexts].astype(cdt)
        Z = syn1[negatives].astype(cdt)
        f_pos = jnp.sum(e_in * e_pos, axis=-1)
        f_neg = e_in @ Z.T
        neg_valid = (negatives[None, :] != contexts[:, None]).astype(cdt) \
            * mask[:, None]
        g_pos = (1.0 - _sigmoid(f_pos, "exact")) * alpha * mask
        g_neg = (0.0 - _sigmoid(f_neg, "exact")) * alpha * neg_valid * (NEG / P)
        d_in = g_pos[:, None] * e_pos + g_neg @ Z
        d_pos = g_pos[:, None] * e_in
        d_Z = g_neg.T @ e_in
        new_syn0 = syn0.at[centers].add(d_in.astype(syn0.dtype),
                                        indices_are_sorted=True)
        new_syn1 = syn1.at[contexts].add(d_pos.astype(syn1.dtype))
        new_syn1 = new_syn1.at[negatives].add(d_Z.astype(syn1.dtype))
        loss = (-_log_sigmoid(f_pos) * mask
                - jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1)
                * (NEG / P)).sum() / jnp.maximum(mask.sum(), 1.0)
        return EmbeddingPair(new_syn0, new_syn1), loss

    def make_sorted_runner():
        def chunk(state, batch, base_step, prob, alias):
            negs = sample_negatives_hash(prob, alias, 1234, base_step, (K, P))

            def body(s, inp):
                b, ng = inp
                return core_sorted(s, b["centers"], b["contexts"], b["mask"], ng,
                                   jnp.float32(0.025))
            return jax.lax.scan(body, state, (batch, negs))

        f = jax.jit(chunk, donate_argnums=(0,))

        def run():
            return time_chunked(
                f, lambda: EmbeddingPair(syn0_0 + 0, syn1_0 + 0),
                lambda i: (sbatches[i % 12], np.int32(100 + i), prob, alias),
                n_lo=2, n_hi=8, fetch=lambda c, out: out[-1])
        return run

    runners = {
        "current (3 scatters)": make_runner("current"),
        "sorted-centers + flag": make_sorted_runner(),
        "merged-syn1 (2 scatters)": make_runner("merged_syn1"),
        "grouped-centers": make_grouped_runner(),
    }
    times = {k: [] for k in runners}
    for r in range(args.repeats):
        for name, run in runners.items():
            spc = run()
            times[name].append(spc / K * 1e3)
    print(f"\nSGNS step A/B (B={B}, pool={P}, {args.dtype}, median of "
          f"{args.repeats} interleaved repeats):", file=sys.stderr)
    for name, ts in times.items():
        med = float(np.median(ts))
        pairs = real_pairs if name == "grouped-centers" else B
        print(f"  {name:28s} median {med:7.3f} ms/step  "
              f"[{min(ts):7.3f} .. {max(ts):7.3f}]  "
              f"{pairs / (med / 1e3):13,.0f} pairs/s", file=sys.stderr)
    print(f"  (grouped: Bc={Bc} groups x W={W} slots, "
          f"{real_pairs:,.0f} real pairs/step)", file=sys.stderr)


if __name__ == "__main__":
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
