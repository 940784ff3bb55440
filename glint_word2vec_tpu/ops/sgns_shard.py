"""Explicit shard_map lowering of the shared-pool SGNS step (docs/sharding.md).

The GSPMD path (:func:`.sgns.sgns_step_shared_core` under jit +
``with_sharding_constraint``) leaves the sharded step's collective schedule to
the compiler pass (Xu et al., "GSPMD", 2021); its collective profile at the
production geometry was never inspected — every multi-chip number in PERF.md §7
was a formula estimate. This module is the hand-lowered replacement, the TPU
analog of the reference's CIKM'16 discipline (Ordentlich et al.: ship indices
and scalar coefficients, keep embedding-row traffic off the wire):

Per step, on the (data, model) mesh with rows sharded over ``model``
(each shard owns ``Vs = V/num_model`` contiguous rows) and the batch split
over ``data`` (``Bl = B/num_data`` pairs per shard):

1. **Forward assembly — ONE psum over the model axis.** Each model shard
   gathers the rows it owns (``index − row_offset``, OOB rows masked to zero)
   for this data shard's centers, contexts, and the shared pool, concatenated
   into one ``[2·Bl + P, D]`` block; a single ``psum`` over ``model``
   assembles the full rows (every row has exactly one owner, so the psum adds
   exact zeros). This is the only model-axis collective in the step.
2. **Local logit/coefficient chain.** f_pos/f_neg/g_pos/g_neg and the update
   deltas d_in/d_pos/d_Z run per data shard on the assembled rows — op-for-op
   the shared helpers of :mod:`.sgns`, so the two lowerings cannot drift.
3. **Data-axis payload exchange — ONE all_gather over the data axis.** The
   per-shard update payload (``[2·Bl + P, D]`` deltas, already cast to the
   param dtype, plus the int32 index list) is all-gathered over ``data``:
   bytes scale with the BATCH (2·Bl·D·b per shard), not with V/num_model —
   the dense alternative (scatter into a [Vs, D] zero delta, psum_scatter by
   row ownership, all_gather the applied sub-blocks back) moves
   ~2·Vs·D·b and loses whenever V/num_model > ~2·B/num_data, which includes
   every north-star geometry (V=1M B=64k: 98 MB vs 50 MB per shard at 2×4);
   it is recorded here as considered-and-priced-out, not built.
4. **Owner-local scatters only.** Every shard localizes the gathered index
   list (``index − row_offset``; rows it does not own become an out-of-range
   sentinel and are DROPPED by the scatter), then applies ONE scatter-add per
   matrix. ZERO update bytes cross the model axis — vs the ~4·B·D·b
   round-trip PERF.md §7 priced for the default lowering — and each shard's
   applied update rows are only those targeting its ``Vs`` rows, so the
   per-update-row scatter bound (PERF.md §2, ~27 ns/row) divides by
   ``num_model`` (dropped candidates ride the §3-measured cheap regime:
   at num_model ≥ 8 the drop fraction ≥ 87.5% is past the 81% knee).

Metrics (when not elided) are per-shard scalars psum'd over ``data`` — three
floats, not a collective that shows up in a bytes audit.

The schedule is audited, not asserted: ``tools/collectives.py`` compiles both
lowerings and tabulates every collective in the HLO with its mesh axis and
bytes; ``tools/shard_ab.py`` A/Bs step time and numeric agreement across mesh
shapes. Equivalence: f64 ~1e-12 against both the GSPMD lowering and the
single-device step at every 8-device mesh shape (tests/test_shard_map_step.py).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair, StepMetrics, Stabilizers, clip_update_rows,
    shared_pool_coeffs, shared_pool_loss_terms, stabilize_rows)
from glint_word2vec_tpu.parallel.distributed import local_sgd_delta_merge
from glint_word2vec_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _owned_rows(mat: jax.Array, idx: jax.Array, row_offset: jax.Array) -> jax.Array:
    """Gather ``mat[idx]`` restricted to this shard's rows: local index =
    ``idx − row_offset``, out-of-range rows exactly zero (so the model-axis
    psum of all shards' partials reconstructs each row bit-exactly — one
    owner contributes the row, the rest contribute 0.0, and x + 0.0 == x)."""
    vs = mat.shape[0]
    loc = idx - row_offset
    inb = (loc >= 0) & (loc < vs)
    rows = mat[jnp.where(inb, loc, 0)]
    return jnp.where(inb[..., None], rows, jnp.zeros((), mat.dtype))


def _owner_local_scatter_add(
    mat: jax.Array, idx: jax.Array, upd: jax.Array, row_offset: jax.Array,
) -> jax.Array:
    """``mat.at[idx].add(upd)`` applying ONLY rows this shard owns: non-owned
    indices map to the out-of-range sentinel ``Vs`` and are dropped by the
    scatter (mode="drop") — zero collective traffic, ~1/num_model of the
    update rows actually applied per shard."""
    vs = mat.shape[0]
    loc = idx - row_offset
    loc = jnp.where((loc >= 0) & (loc < vs), loc, vs)
    return mat.at[loc].add(upd, mode="drop")


def make_shard_map_sgns_step(
    mesh: Mesh,
    num_negatives: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    logits_dtype: jnp.dtype = jnp.float32,
    with_metrics: bool = True,
    stabilizers: Optional[Stabilizers] = None,
    fused: bool = False,
    bf16_chain: bool = False,
    sync_every: int = 1,
) -> Callable[..., Tuple[EmbeddingPair, StepMetrics]]:
    """Build the explicitly-scheduled sharded step. The returned function has
    the trainer's ``inner`` signature — ``(params, batch, negatives, alpha) ->
    (EmbeddingPair, StepMetrics)`` on GLOBAL arrays — so
    ``trainer._build_step`` swaps it in for :func:`.sgns.sgns_step_shared_core`
    behind ``config.step_lowering`` with no other plumbing.

    Requirements (validated at trace time with real messages): the padded
    vocab divides ``num_model`` (pad_vocab_for_sharding guarantees it) and the
    batch divides ``num_data``. ``duplicate_scaling`` has no shard_map form
    (global in-batch occurrence counts would need a [V]-sized psum) — the
    config selection matrix refuses the combination up front.

    ``fused``/``bf16_chain`` (config.fused_logits / config.bf16_chain —
    ISSUE 14): the coefficient chain lives in the shared
    :func:`..sgns.shared_pool_coeffs` helper, so the fused select chain and
    the f32-accumulating positive dot apply to this lowering by
    construction — the two lowerings cannot drift. The per-data-shard
    [Bl, P] chain shrinks exactly like the single-program [B, P] one; the
    collective schedule is untouched (the fusion is local elementwise
    restructuring, no new cross-shard values).

    ``sync_every`` (config.sync_every — local-SGD, docs/sharding.md
    §Local-SGD): 1 (default) returns the synchronous step above, byte-for-byte
    the pre-knob program. k > 1 returns a WINDOW function with the same outer
    signature over k-stacked inputs — ``batch`` leaves ``[k, B]``,
    ``negatives [k, nd·P]`` (each data shard consumes its own DISJOINT
    ``[k, P]`` pool slice, so merged runs are deterministic per
    (seed, mesh, k)), ``alpha [k]`` — that runs k OWNER-LOCAL steps per data
    shard (forward assembly psum over ``model`` per step as above, but the
    backward applies ONLY this shard's own payload: zero bytes cross the data
    axis inside the window) and then reconciles the data axis with ONE
    delta-merge collective (:func:`..parallel.distributed.local_sgd_delta_merge`:
    mean of per-shard deltas against the window-start state). Metrics come
    back as ``[k]`` vectors (per-step, data-psum'd once per window). The
    window's k-step loop is PYTHON-UNROLLED, not a lax.scan — deliberately:
    the HLO collective audit (tools/collectives.py) counts ops textually and
    a scan body would hide k−1 of the per-step assembly psums, making the
    priced schedule a lie. In-window stabilizer passes run owner-locally on
    the LOCAL touched mask (no mask all_gather); the merge preserves the
    clamp invariant (a convex combination of rows each with ‖row‖ ≤ c stays
    in the ball).
    """
    nd = mesh.shape[DATA_AXIS]
    nm = mesh.shape[MODEL_AXIS]

    def local_step(syn0, syn1, centers, contexts, mask, negatives, alpha):
        # per-device blocks: syn0/syn1 [Vs, D]; centers/contexts/mask [Bl];
        # negatives [P] and alpha replicated.
        #
        # SERIALIZATION PROPERTY (learned from a live rendezvous-starvation
        # deadlock on the 8-device CPU mesh — trainer._sync_collectives has
        # the full story): every collective in this program should data-
        # depend on the params carry. The index all_gather and the elided
        # twin's `pairs` psum otherwise depend only on the FEED, so a run
        # dispatched behind another collective-bearing program could start
        # those collectives early and race it on XLA:CPU's shared rendezvous
        # pool. The barrier ties the batch inputs to syn0/syn1 so every
        # collective waits for the carry; params are program inputs, so
        # within-program TPU/GPU stream scheduling is untouched.
        centers, contexts, mask, negatives, syn0, syn1 = (
            jax.lax.optimization_barrier(
                (centers, contexts, mask, negatives, syn0, syn1)))
        vs = syn0.shape[0]
        bl = centers.shape[0]
        pool = negatives.shape[0]
        row_offset = (jax.lax.axis_index(MODEL_AXIS) * vs).astype(jnp.int32)

        # (1) forward assembly: owner-local gathers, ONE psum over `model`
        with jax.named_scope("sgns.gather"):
            cat = jnp.concatenate([
                _owned_rows(syn0, centers, row_offset),
                _owned_rows(syn1, contexts, row_offset),
                _owned_rows(syn1, negatives, row_offset),
            ], axis=0)                               # [2·Bl + P, D] param dtype
        if nm > 1:
            cat = jax.lax.psum(cat, MODEL_AXIS)
        e_in = cat[:bl].astype(compute_dtype)
        e_pos = cat[bl:2 * bl].astype(compute_dtype)
        Z = cat[2 * bl:].astype(compute_dtype)

        # (2) the shared coefficient/update math — literally the same helpers
        # the GSPMD step runs (ops/sgns.py), per data shard
        with jax.named_scope("sgns.pool_matmul"):
            f_pos, f_neg, neg_valid, g_pos, g_neg = shared_pool_coeffs(
                e_in, e_pos, Z, contexts, negatives, mask, alpha,
                num_negatives, sigmoid_mode, logits_dtype,
                fused=fused, bf16_chain=bf16_chain)
            gn = g_neg.astype(compute_dtype)
            d_in = g_pos[:, None].astype(compute_dtype) * e_pos + gn @ Z
            d_pos = g_pos[:, None].astype(compute_dtype) * e_in
            d_Z = gn.T @ e_in                        # [P, D] partial over Bl pairs
        if stabilizers is not None and stabilizers.update_clip:
            # the per-pair rows only, never the (shard-partial) d_Z — the
            # exact scoping the single-program lowering applies (ops/sgns.py
            # Stabilizers docstring), so the lowerings cannot drift
            d_in = clip_update_rows(d_in, stabilizers.update_clip)
            d_pos = clip_update_rows(d_pos, stabilizers.update_clip)

        # (3) data-axis payload exchange: deltas in param dtype + int32 indices,
        # ONE all_gather each (the index list is 4 bytes/row — noise next to
        # the D·b-byte delta rows). nd == 1 skips the collective entirely.
        dtype = syn0.dtype
        payload = jnp.concatenate(
            [d_in, d_pos, d_Z], axis=0).astype(dtype)  # [2·Bl + P, D]
        idx = jnp.concatenate([centers, contexts, negatives])
        if nd > 1:
            payload = jax.lax.all_gather(payload, DATA_AXIS, tiled=True)
            idx = jax.lax.all_gather(idx, DATA_AXIS, tiled=True)
        # split back into per-matrix streams: every data shard's first Bl rows
        # target syn0 (centers), the rest target syn1 (contexts + pool; the
        # nd pool copies are partial d_Z sums — scatter-add accumulates them)
        seg = payload.reshape(nd, 2 * bl + pool, -1)
        seg_idx = idx.reshape(nd, 2 * bl + pool)
        upd0 = seg[:, :bl].reshape(nd * bl, -1)
        idx0 = seg_idx[:, :bl].reshape(-1)
        upd1 = seg[:, bl:].reshape(nd * (bl + pool), -1)
        idx1 = seg_idx[:, bl:].reshape(-1)

        # (4) owner-local scatters — ZERO update bytes cross the model axis
        with jax.named_scope("sgns.scatter_syn0"):
            new_syn0 = _owner_local_scatter_add(syn0, idx0, upd0, row_offset)
        with jax.named_scope("sgns.scatter_syn1"):
            new_syn1 = _owner_local_scatter_add(syn1, idx1, upd1, row_offset)

        # (4b) owner-local touched-row stabilizer pass (config.max_row_norm /
        # row_l2): the rows layout owns FULL rows per shard, so the clamp's
        # norm math runs locally on the just-updated block — the same
        # gathered index lists drive it, with masked batch slots mapped to a
        # global OOB sentinel (their placeholder index 0 must not drag row 0
        # into the pass) and non-owned/sentinel rows dropping at the scatter-
        # set exactly like the update scatter. One extra [B]-float all_gather
        # of the mask funds the gating — only compiled in when a stabilizer
        # is ON, so the stabilizers-off program is untouched.
        if stabilizers is not None and stabilizers.post_pass:
            gmask = mask
            if nd > 1:
                gmask = jax.lax.all_gather(gmask, DATA_AXIS, tiled=True)
            enable = (gmask.sum() > 0).astype(jnp.float32)
            sent = jnp.int32(vs * nm)                # global OOB sentinel
            stab0 = jnp.where(gmask > 0, idx0, sent)  # [nd·bl] centers
            gm = gmask.reshape(nd, bl)
            m1 = jnp.concatenate(
                [gm, jnp.ones((nd, pool), jnp.float32)], axis=1).reshape(-1)
            stab1 = jnp.where(m1 > 0, idx1, sent)

            def loc(i):
                li = i - row_offset
                return jnp.where((li >= 0) & (li < vs), li, vs)

            new_syn0 = stabilize_rows(
                new_syn0, loc(stab0), alpha, stabilizers, enable)
            new_syn1 = stabilize_rows(
                new_syn1, loc(stab1), alpha, stabilizers, enable)

        # metrics: three scalars psum'd over `data` (loss/mean_f_pos follow
        # the GSPMD step's masked-mean: global numerators / global pair count)
        if with_metrics:
            loss_num, fpos_num = shared_pool_loss_terms(
                f_pos, f_neg, neg_valid, mask, num_negatives)
            stats = jnp.stack([loss_num, fpos_num, mask.sum()])
            if nd > 1:
                stats = jax.lax.psum(stats, DATA_AXIS)
            denom = jnp.maximum(stats[2], 1.0)
            loss, mean_f_pos, pairs = stats[0] / denom, stats[1] / denom, stats[2]
        else:
            pairs = mask.sum()
            if nd > 1:
                pairs = jax.lax.psum(pairs, DATA_AXIS)
            loss = mean_f_pos = jnp.float32(0.0)
        return new_syn0, new_syn1, loss, mean_f_pos, pairs

    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), P(MODEL_AXIS, None),
                  P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=(P(MODEL_AXIS, None), P(MODEL_AXIS, None), P(), P(), P()),
        # outputs ARE replicated where the specs say so (every data replica
        # applies the identical all-gathered payload to the identical block;
        # scalars ride a psum) — but the tracer cannot prove it through the
        # scatters, so replication checking is off
        check_vma=False)

    def step(params, batch, negatives, alpha):
        syn0, syn1 = params.syn0, params.syn1
        v, b = syn0.shape[0], batch["centers"].shape[0]
        if v % nm:
            raise ValueError(
                f"shard_map step needs the padded vocab ({v}) divisible by "
                f"num_model={nm} (pad_vocab_for_sharding guarantees this in "
                "the trainer)")
        if b % nd:
            raise ValueError(
                f"shard_map step needs the batch ({b}) divisible by "
                f"num_data={nd}")
        s0, s1, loss, mean_f_pos, pairs = mapped(
            syn0, syn1, batch["centers"], batch["contexts"], batch["mask"],
            negatives, alpha)
        return EmbeddingPair(s0, s1), StepMetrics(
            loss=loss, mean_f_pos=mean_f_pos, pairs=pairs)

    if sync_every == 1:
        return step

    # ---- local-SGD window (sync_every = k > 1): k owner-local steps per
    # data shard, then ONE delta-merge collective over the data axis ----
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    k = int(sync_every)

    def owner_local_step(syn0, syn1, centers, contexts, mask, negatives,
                         alpha, row_offset):
        """One step of the in-window schedule on THIS shard's diverged
        replica: same forward assembly (steps 1–2 of the module schedule, the
        one model-axis psum included) but the backward applies only the
        shard's OWN payload — no data-axis all_gather, so the window crosses
        the data axis zero times until the merge. ``negatives`` is this
        shard's disjoint [P] pool slice; its d_Z rows therefore accumulate
        only this shard's partials (exactly what the per-shard oracle
        replays). Returns the updated blocks + the [3] local stat numerators
        (summed over `data` once per window, not per step)."""
        vs = syn0.shape[0]
        bl = centers.shape[0]
        pool = negatives.shape[0]

        with jax.named_scope("sgns.gather"):
            cat = jnp.concatenate([
                _owned_rows(syn0, centers, row_offset),
                _owned_rows(syn1, contexts, row_offset),
                _owned_rows(syn1, negatives, row_offset),
            ], axis=0)                               # [2·Bl + P, D] param dtype
        if nm > 1:
            cat = jax.lax.psum(cat, MODEL_AXIS)
        e_in = cat[:bl].astype(compute_dtype)
        e_pos = cat[bl:2 * bl].astype(compute_dtype)
        Z = cat[2 * bl:].astype(compute_dtype)

        with jax.named_scope("sgns.pool_matmul"):
            f_pos, f_neg, neg_valid, g_pos, g_neg = shared_pool_coeffs(
                e_in, e_pos, Z, contexts, negatives, mask, alpha,
                num_negatives, sigmoid_mode, logits_dtype,
                fused=fused, bf16_chain=bf16_chain)
            gn = g_neg.astype(compute_dtype)
            d_in = g_pos[:, None].astype(compute_dtype) * e_pos + gn @ Z
            d_pos = g_pos[:, None].astype(compute_dtype) * e_in
            d_Z = gn.T @ e_in
        if stabilizers is not None and stabilizers.update_clip:
            d_in = clip_update_rows(d_in, stabilizers.update_clip)
            d_pos = clip_update_rows(d_pos, stabilizers.update_clip)

        dtype = syn0.dtype
        idx0 = centers
        upd0 = d_in.astype(dtype)
        idx1 = jnp.concatenate([contexts, negatives])
        upd1 = jnp.concatenate([d_pos, d_Z], axis=0).astype(dtype)
        with jax.named_scope("sgns.scatter_syn0"):
            new_syn0 = _owner_local_scatter_add(syn0, idx0, upd0, row_offset)
        with jax.named_scope("sgns.scatter_syn1"):
            new_syn1 = _owner_local_scatter_add(syn1, idx1, upd1, row_offset)

        if stabilizers is not None and stabilizers.post_pass:
            # owner-local in-window form: the LOCAL touched mask gates the
            # pass (no data-axis mask all_gather — the window's whole point);
            # each shard clamps the rows IT touched, and the merge preserves
            # the clamp ball (convexity — see local_sgd_delta_merge)
            enable = (mask.sum() > 0).astype(jnp.float32)
            sent = jnp.int32(vs * nm)
            stab0 = jnp.where(mask > 0, idx0, sent)
            m1 = jnp.concatenate([mask, jnp.ones((pool,), jnp.float32)])
            stab1 = jnp.where(m1 > 0, idx1, sent)

            def loc(i):
                li = i - row_offset
                return jnp.where((li >= 0) & (li < vs), li, vs)

            new_syn0 = stabilize_rows(
                new_syn0, loc(stab0), alpha, stabilizers, enable)
            new_syn1 = stabilize_rows(
                new_syn1, loc(stab1), alpha, stabilizers, enable)

        if with_metrics:
            loss_num, fpos_num = shared_pool_loss_terms(
                f_pos, f_neg, neg_valid, mask, num_negatives)
            stats = jnp.stack([loss_num, fpos_num, mask.sum()])
        else:
            stats = jnp.stack(
                [jnp.float32(0.0), jnp.float32(0.0), mask.sum()])
        return new_syn0, new_syn1, stats

    def local_window(syn0, syn1, centers, contexts, mask, negatives, alphas):
        # per-device blocks: syn0/syn1 [Vs, D]; centers/contexts/mask
        # [k, Bl]; negatives [k, P] (this shard's disjoint lattice); alphas
        # [k] replicated. Same serialization barrier as the k=1 step: every
        # collective in the window (the per-step assembly psums, the merge
        # psum, the stats psum) must data-depend on the params carry.
        centers, contexts, mask, negatives, syn0, syn1 = (
            jax.lax.optimization_barrier(
                (centers, contexts, mask, negatives, syn0, syn1)))
        vs = syn0.shape[0]
        row_offset = (jax.lax.axis_index(MODEL_AXIS) * vs).astype(jnp.int32)
        start0, start1 = syn0, syn1
        stats_steps = []
        # Python-unrolled on purpose (see make_shard_map_sgns_step docstring):
        # the HLO bytes audit must see all k assembly psums
        for i in range(k):
            syn0, syn1, st = owner_local_step(
                syn0, syn1, centers[i], contexts[i], mask[i], negatives[i],
                alphas[i], row_offset)
            stats_steps.append(st)

        # the ONE data-axis collective of the window
        merged0, merged1 = local_sgd_delta_merge(
            (start0, start1), (syn0, syn1), DATA_AXIS, nd)

        stats = jnp.stack(stats_steps)               # [k, 3]
        if nd > 1:
            stats = jax.lax.psum(stats, DATA_AXIS)
        pairs = stats[:, 2]
        if with_metrics:
            denom = jnp.maximum(pairs, 1.0)
            loss, mean_f_pos = stats[:, 0] / denom, stats[:, 1] / denom
        else:
            loss = mean_f_pos = jnp.zeros((k,), jnp.float32)
        return merged0, merged1, loss, mean_f_pos, pairs

    mapped_window = jax.shard_map(
        local_window, mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), P(MODEL_AXIS, None),
                  P(None, DATA_AXIS), P(None, DATA_AXIS), P(None, DATA_AXIS),
                  P(None, DATA_AXIS), P()),
        out_specs=(P(MODEL_AXIS, None), P(MODEL_AXIS, None), P(), P(), P()),
        # replication holds BY the merge (bitwise-identical psum result +
        # replicated start on every data replica), but the tracer cannot
        # prove it through the scatters — same waiver as the k=1 step
        check_vma=False)

    def window(params, batch, negatives, alphas):
        syn0, syn1 = params.syn0, params.syn1
        v, b = syn0.shape[0], batch["centers"].shape[1]
        if v % nm:
            raise ValueError(
                f"shard_map window needs the padded vocab ({v}) divisible "
                f"by num_model={nm} (pad_vocab_for_sharding guarantees this "
                "in the trainer)")
        if b % nd:
            raise ValueError(
                f"shard_map window needs the batch ({b}) divisible by "
                f"num_data={nd}")
        if batch["centers"].shape[0] != k:
            raise ValueError(
                f"sync_every={k} window needs [k, B]-stacked batch leaves, "
                f"got leading dim {batch['centers'].shape[0]}")
        if negatives.shape[1] % nd:
            raise ValueError(
                f"sync_every={k} window needs the pool axis "
                f"({negatives.shape[1]}) divisible by num_data={nd} (each "
                f"data shard consumes a disjoint slice)")
        s0, s1, loss, mean_f_pos, pairs = mapped_window(
            syn0, syn1, batch["centers"], batch["contexts"], batch["mask"],
            negatives, alphas)
        return EmbeddingPair(s0, s1), StepMetrics(
            loss=loss, mean_f_pos=mean_f_pos, pairs=pairs)

    return window
