"""Banded CBOW step: O(B) context gather/scatter via sentence-ordered prefix sums.

The scatter formulation (:func:`glint_word2vec_tpu.ops.sgns.cbow_step_shared_core`)
treats each example's context window as an unordered [B, C] index set: it gathers
``syn0[contexts]`` and scatters ``d_ctx`` as **B·C rows** (~655k at B=64k, C≈10).
XLA's TPU scatter into f32[3000000,384] costs 0.04 ms + 0.104 ms per 1,000 update
rows handed over, whether they repeat or are dropped out of bounds (TPU v5 lite;
PERF.md §6, PRs 28, 30 and 46), so those rows, not compute, are that form's step:
0.85 M examples/s on the chip where this form ran 2.17 M (PERF.md §6, PR 27).

But CBOW batches are sliding windows over the *kept-token stream*: when batch
position b holds kept token b (sentence-contiguous feed), both directions of the
context traffic are **banded sums over batch positions**:

- forward: ``hidden_b = (Σ_{j=b-l_b}^{b+r_b} e_j − e_b) / n_b`` — an interval sum,
  i.e. one difference of an inclusive prefix sum ``S`` over the gathered rows:
  ``S[b+r_b] − S[b−l_b−1] − e_b``;
- backward: position j receives ``Σ_{b: j ∈ [b−l_b, b+r_b]} d_hidden_b / n_b`` —
  the classic difference-array trick: add ``g_b = d_hidden_b/n_b`` at interval
  start ``b−l_b``, subtract it at ``b+r_b+1``, prefix-sum, then remove the
  self-term ``g_b`` at b.

Cost: ONE [T]-row ``syn0`` gather + two [T, D] prefix sums (the two-level
triangular-matmul form from ops/pairgen) + the interval-endpoint accumulation +
two [T]-row scatters back into syn0 and syn1: ~2·B update rows instead of ~11·B.
Measured at B = 65,536, D = 384, V = 3M (TPU v5 lite; PERF.md §5): a 21.7 ms
step of which the two 65,546-row token scatters were 13.7 ms and everything
else under 0.4 ms an op (the context mean and its spread 1.0, the backward
prefix at HIGHEST 0.39, the pool rows 0.34). Since PR 46 the two scatters are
handed one row per piece of a word's run of the block's tokens sorted inside
the step (``token_runs``; PERF.md §6 has the probe's prices and the step's
account since).

Window intervals never cross sentence boundaries (``device_cbow_windows`` clamps
them via the start bits), so prefix-sum *differences* are exact per sentence even
though the prefix runs over the whole block; the same argument makes one flat
prefix correct across the [Sd, T] → [Sd·T] segment concatenation the trainer
feeds (intervals are in-block by construction, so cross-segment prefix mass
cancels in every difference).

Precision: prefix sums accumulate in ``promote_types(param_dtype, float32)`` —
a bf16 prefix over 64k rows would lose the interval in the cancellation; float32
keeps the relative error of an ~10-row interval at ~1e-5, far below SGD noise
(the float64 CPU equivalence suite in tests/test_cbow_banded.py pins the math).

``duplicate_scaling=True`` is NOT supported here — its mean-update bookkeeping
is per-occurrence-count over the materialized context sets; config validation
routes that combination to the scatter path (the selection matrix lives at
trainer._build_step).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    StepMetrics,
    Stabilizers,
    _log_sigmoid,
    _mask_sentinel,
    _sigmoid,
    clip_update_rows,
    scatter_add_by_runs,
    stabilize_rows,
)

# above this window the unrolled shifted-add endpoint accumulation (2·window
# fused [T, D] terms) loses to two plain scatter-adds of T rows each
_SHIFT_UNROLL_MAX_WINDOW = 16


def cumsum_rows(x: jax.Array, one_pass: bool = False) -> jax.Array:
    """Inclusive prefix sum along axis 0 of a [T, D] array.

    The float twin of ops/pairgen._cumsum_i32: XLA's 1-D cumulative ops cost
    ~0.45 ms at 28k elements on TPU, so the within-chunk prefix runs as a
    [128, 128] triangular matmul on the MXU and only the [T/128, D] chunk
    totals take the (tiny) native cumsum. Unlike the int variant there is no
    exactness window — callers pick an accumulation dtype wide enough for
    their cancellation needs (the banded step uses ≥ float32).

    The matmul asks for ``Precision.HIGHEST``: at the default the TPU's MXU
    takes float32 operands ROUNDED to bfloat16, and a prefix sum of rounded
    difference-array entries never cancels — the errors walk over the block
    (on the v5e syn0's update came out ~30% off, its norm 5-6% over the
    float32 reference's, where the CPU, which computes a float32 matmul
    exactly, had shown nothing; PERF.md §6, PR 27). HIGHEST is six MXU passes,
    ~0.24 ms of a 23 ms step at T = 64k, D = 384. ``one_pass=True`` is the
    caller's word that every entry of ``x`` is a bfloat16 value already: the
    default precision's single pass is then exact.
    """
    T, D = x.shape
    chunk = 128
    rows = -(-T // chunk)
    xp = jnp.pad(x, ((0, rows * chunk - T), (0, 0))).reshape(rows, chunk, D)
    tri = jnp.tril(jnp.ones((chunk, chunk), x.dtype))  # [i, j] = 1 iff j <= i
    within = jnp.einsum(                               # inclusive within-chunk
        "ij,rjd->rid", tri, xp,
        precision=None if one_pass else jax.lax.Precision.HIGHEST)
    totals = within[:, -1, :]                          # [rows, D]
    # graftlint: disable=R4 -- accumulation dtype is the CALLER's contract (docstring above); both call sites pass >=f32 and are R4-checked there
    offs = jnp.cumsum(totals, axis=0) - totals         # exclusive chunk offsets
    return (within + offs[:, None, :]).reshape(rows * chunk, D)[:T]


def _band_endpoint_delta(
    g: jax.Array,      # [T, D] per-example spread gradient (masked rows are 0)
    left: jax.Array,   # int32 [T]
    right: jax.Array,  # int32 [T]
    window: int,
) -> jax.Array:
    """The difference array of the banded backward accumulation: +g_b at each
    interval start ``b−left_b``, −g_b at each one-past-end ``b+right_b+1``
    (ends falling at T are dropped — their prefix mass is never read).

    Since ``left ∈ [0, window)`` and ``right+1 ∈ [1, window]``, small windows
    realize both endpoint adds as 2·window statically-unrolled shifted
    masked adds (pure elementwise — XLA fuses them into one pass, no scatter
    rows at all); large windows fall back to one 2T-row scatter-add, still
    ~5× fewer scatter rows than the B·C formulation."""
    T, D = g.shape
    t = jnp.arange(T, dtype=jnp.int32)
    if window > _SHIFT_UNROLL_MAX_WINDOW:
        idx = jnp.concatenate([t - left, t + right + 1])
        upd = jnp.concatenate([g, -g])
        return jnp.zeros((T + 1, D), g.dtype).at[idx].add(
            upd, mode="drop")[:T]
    # start marks: g_b lands at j = b − left_b  ⇔  left[j+d] == d, d ∈ [0, W)
    gs = jnp.pad(g, ((0, window), (0, 0)))
    ls = jnp.pad(left, (0, window), constant_values=-1)
    delta = jnp.zeros((T, D), g.dtype)
    for d in range(window):
        sel = (ls[d:d + T] == d).astype(g.dtype)[:, None]
        delta = delta + gs[d:d + T] * sel
    # end marks: g_b removed at j = b + right_b + 1  ⇔  right[j−d] == d−1,
    # d ∈ [1, W] (legacy right ≤ W−2, symmetric ≤ W−1 — both covered)
    ge = jnp.pad(g, ((window, 0), (0, 0)))
    re = jnp.pad(right, (window, 0), constant_values=-2)
    for d in range(1, window + 1):
        sel = (re[window - d:window - d + T] == d - 1).astype(g.dtype)[:, None]
        delta = delta - ge[window - d:window - d + T] * sel
    return delta


def _tap_masks(left: jax.Array, right: jax.Array, window: int):
    """For each relative position p of −window..window without 0, in the order
    of the position weights' rows: (p, int32 [T + 2·window] extent to compare
    with |p|, padded so that a shifted slice reads "no window" past the
    block's ends). Slot t has position p where ``|p| <= extent[t]``."""
    lp = jnp.pad(left, (window, window), constant_values=-1)
    rp = jnp.pad(right, (window, window), constant_values=-1)
    return [(p, lp if p < 0 else rp)
            for p in list(range(-window, 0)) + list(range(1, window + 1))]


def position_taps(x: jax.Array, weights: jax.Array, left: jax.Array,
                  right: jax.Array, window: int,
                  transpose: bool = False) -> jax.Array:
    """The window sum under per-position weights, a depthwise correlation
    along the token axis with 2·window taps masked per (slot, position) by the
    drawn extents (no interval sum: the weights differ by position):

        forward    out[t] = Σ_p [p ∈ P_t] · weights[p] ⊙ x[t + p]
        transpose  out[j] = Σ_p [p ∈ P_{j−p}] · weights[p] ⊙ x[j − p]

    ``x`` [T, D] and ``weights`` [2·window, D] in the accumulation dtype;
    2·window shifted masked multiply-adds over static slices of one
    zero-padded copy, which XLA fuses into one pass (as
    :func:`_band_endpoint_delta`'s)."""
    T = x.shape[0]
    xp = jnp.pad(x, ((window, window), (0, 0)))
    out = jnp.zeros_like(x)
    for row, (p, extent) in enumerate(_tap_masks(left, right, window)):
        if transpose:       # slot j − p gives, under ITS window
            at = window - p
            has = extent[at:at + T] >= abs(p)
        else:               # slot t takes from t + p, under its own window
            at = window + p
            has = extent[window:window + T] >= abs(p)
        out = out + jnp.where(has[:, None], xp[at:at + T] * weights[row], 0)
    return out


def position_weight_sums(g: jax.Array, x: jax.Array, live: jax.Array,
                         left: jax.Array, right: jax.Array, window: int):
    """``(Σ_t [p ∈ P_t] g[t] ⊙ x[t + p], #{live t : p ∈ P_t})`` for every
    position p: the position weights' summed gradient [2·window, D] and the
    examples that have each position [2·window]: 2·window product-reductions
    over the block."""
    T = x.shape[0]
    xp = jnp.pad(x, ((window, window), (0, 0)))
    sums, counts = [], []
    for p, extent in _tap_masks(left, right, window):
        has = extent[window:window + T] >= abs(p)
        sums.append(jnp.sum(jnp.where(
            has[:, None], g * xp[window + p:window + p + T], 0), axis=0))
        counts.append(jnp.sum(jnp.where(has, live, 0)))
    return jnp.stack(sums), jnp.stack(counts)


def cbow_step_banded_core(
    params: EmbeddingPair,
    tokens: jax.Array,       # int32 [T] — kept tokens, sentence-contiguous
    left: jax.Array,         # int32 [T] — context extent left (in-sentence)
    right: jax.Array,        # int32 [T] — context extent right
    center_mask: jax.Array,  # float32 [T] — 1.0 for slots trained as centers
    token_mask: jax.Array,   # float32 [T] — 1.0 for valid token slots
    negatives: jax.Array,    # int32 [P] — pre-drawn shared pool
    alpha: jax.Array,
    num_negatives: int,
    window: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    logits_dtype: jnp.dtype = jnp.float32,
    with_metrics: bool = True,
    stabilizers: Optional[Stabilizers] = None,
    subword: Optional[tuple] = None,
    token_runs: Optional[Tuple[int, int, int]] = None,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """Banded CBOW update — mathematically the shared-pool scatter step
    (:func:`~glint_word2vec_tpu.ops.sgns.cbow_step_shared_core`) on the example
    set {slot b : center_mask_b = 1, left_b + right_b > 0} with contexts
    ``tokens[b−left_b : b+right_b+1] \\ {b}``, identical up to floating-point
    summation order (asserted by tests/test_cbow_banded.py in float64).

    (left, right) come from :func:`~glint_word2vec_tpu.ops.pairgen.device_cbow_windows`
    and are guaranteed in-range (``b−left_b ≥ 0``, ``b+right_b < T``) and
    in-sentence. Halo slots carry ``center_mask 0`` but ``token_mask 1``: they
    train no example this block yet still receive their context gradient from
    this block's core centers (their remaining gradient arrives in the block
    where they are core — each (center, context) link is applied exactly once
    across the overlapping feed).

    ``params.pos`` (config.cbow_position_weights; Mikolov et al. 2018,
    arXiv:1712.09405 §2.2): the window is summed under a learned vector per
    relative position, ``hidden_b = (1/n_b) Σ_p pos[p] ⊙ e_{b+p}``. No
    interval sum holds for unequal weights, so both prefix sums give way to
    :func:`position_taps`, and ``pos`` moves by the MEAN of its per-example
    updates over the live examples that have each position (it is the one
    parameter every example touches: their sum at the per-example rate would
    be a step tens of thousands of times one example's).

    ``subword`` ``(SubwordTable, SubwordShape)`` (config.subword;
    :mod:`.subword`): a TOKEN's vector is the mean of the rows its word's
    list names, ``e = u[tokens]`` composed from the lists, and ``d_ctx`` is
    divided and spread back over them in one scatter; syn0 then holds the
    vocabulary's rows and the bucket rows. Every token slot reads its own
    word's list (``shape`` is ``(max_groups, 1, T)``): reading a list once per
    distinct word of the block, the tokens sorted by word inside the step,
    came in at 0.86 of this form's step on the chip where the bar was 0.7
    (PERF.md §6, PR 33). Where ``shape.slot_cap`` is set the scatter takes the
    lists' slots sorted by row and cut to that many (a block with more live
    slots takes them all; :mod:`.subword`), and ``StepMetrics.subword_slots``
    says how many it was handed; where ``shape.tail_cap`` is set the gather
    reads every token's first group of rows and, that many tokens a pass, the
    later groups of the tokens that have them, and
    ``StepMetrics.subword_gather_slots`` says how many it was handed. With
    neither, the program is the one it was.

    ``token_runs`` ``(max_run, cap0, cap1)``: both token scatters are keyed
    by ``tokens`` (syn0's rows by token as context, syn1's by token as
    center), and a block of kept tokens holds about half as many distinct
    words as slots (65,546 slots, ~30,700 words at V = 3M: PERF.md §6, PR
    46). Each goes through
    :func:`~glint_word2vec_tpu.ops.sgns.scatter_add_by_runs` on the block's
    tokens sorted by word inside the step: one summed row per piece of a
    word's run, cut every ``max_run``. syn0's by every slot's token, under
    ``cap0`` (not beside the token row source, whose lists are syn0's
    update); syn1's by the tokens of the slots that train an example alone
    (``live``: the others' rows are zero), under ``cap1``. A block with more
    pieces than a cap takes that table's plain scatter on the unsorted rows;
    the trainer derives the three numbers from the vocabulary's counts, and
    ``StepMetrics.syn0_rows`` / ``.syn1_rows`` say what each scatter was
    handed. Masked slots still give syn0 zero rows (in word 0's run); the
    pool rows' scatter, the stabilizers' post-pass and the taps are as
    without it.
    None: the program it was.
    """
    syn0, syn1, pos_w = params
    T = tokens.shape[0]
    P = negatives.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    pf = jnp.promote_types(syn0.dtype, jnp.float32)  # prefix accumulation dtype

    ctx_n_i = left + right
    has_ctx = (ctx_n_i > 0).astype(jnp.float32)
    live = center_mask * has_ctx                                    # [T]

    # named scopes: the scatter form's five (ops/sgns.cbow_step_shared_core),
    # metadata only
    # -- forward: windowed context mean via one prefix-sum difference ---------
    rows_in_bf16 = jnp.dtype(compute_dtype) == jnp.bfloat16
    if subword is not None:
        from glint_word2vec_tpu.ops import subword as sw
        sw_table, sw_shape = subword
        # a masked slot is no word: it lists nothing and receives nothing
        words = jnp.where(token_mask > 0, tokens, sw_table.counts.shape[0] - 1)
        sw_plan = sw.plan_centers(words, sw_table, sw_shape, dtype=pf)
    with jax.named_scope("cbow.gather"):
        if subword is None:
            e = syn0[tokens].astype(pf)                             # [T, D]
        else:
            e = sw.center_vectors(syn0, words, sw_table, sw_shape, sw_plan, pf)
        if rows_in_bf16:
            # the rows enter the sum at compute_dtype, as the scatter form's
            # do, rounded to nearest HERE: the forward prefix is then one MXU
            # pass and exact on them (reduce_precision, because XLA may drop
            # an astype round trip as excess precision)
            e = jax.lax.reduce_precision(e, exponent_bits=8, mantissa_bits=7)
    with jax.named_scope("cbow.context_sum" if pos_w is None
                         else "cbow.position_taps"):
        if pos_w is None:
            S = cumsum_rows(e.astype(pf), one_pass=rows_in_bf16)    # [T, D]
            Spad = jnp.concatenate([jnp.zeros((1, S.shape[1]), pf), S])  # S[<i]
            ctx_sum = Spad[t + right + 1] - Spad[t - left] - e
        else:
            weights = pos_w.astype(pf)
            ctx_sum = position_taps(e, weights, left, right, window)
        ctx_n = jnp.maximum(ctx_n_i, 1).astype(pf)
        hidden = (ctx_sum / ctx_n[:, None]).astype(compute_dtype)   # [T, D]

    # -- shared-pool positive/negative chain, unchanged from the scatter step
    tok_i = tokens.astype(jnp.int32)
    with jax.named_scope("cbow.gather"):
        e_out = syn1[tokens].astype(compute_dtype)                  # [T, D]
        Z = syn1[negatives].astype(compute_dtype)                   # [P, D]
    with jax.named_scope("cbow.pool_matmul"):
        f_pos = jnp.sum(hidden * e_out, axis=-1).astype(jnp.float32)
        f_neg = (hidden @ Z.T).astype(logits_dtype)                 # [T, P]
    neg_valid = (negatives[None, :] != tok_i[:, None]).astype(logits_dtype) \
        * center_mask[:, None].astype(logits_dtype)

    g_pos = (1.0 - _sigmoid(f_pos, sigmoid_mode)) * alpha * live
    g_neg = ((0.0 - _sigmoid(f_neg, sigmoid_mode))
             * jnp.asarray(alpha, logits_dtype) * neg_valid
             * has_ctx[:, None].astype(logits_dtype)
             * jnp.asarray(num_negatives / P, logits_dtype))

    with jax.named_scope("cbow.pool_matmul"):
        gp = g_pos[:, None].astype(compute_dtype)
        gn = g_neg.astype(compute_dtype)
        d_hidden = gp * e_out + gn @ Z                              # [T, D]
        d_out = gp * hidden
        d_Z = gn.T @ hidden                                         # [P, D]
    if stabilizers is not None and stabilizers.update_clip:
        # clip BEFORE the mean-convention split/spread — the same quantity
        # the scatter formulation clips (ops/sgns.py), so the two CBOW
        # formulations stay equivalent with stabilizers on. d_Z never clips
        # (Stabilizers docstring).
        d_hidden = clip_update_rows(d_hidden, stabilizers.update_clip)
        d_out = clip_update_rows(d_out, stabilizers.update_clip)

    # -- backward: banded spread of d_hidden/n via difference array + prefix --
    g_row = d_hidden.astype(pf) / ctx_n[:, None]                    # [T, D]
    new_pos = None
    if pos_w is None:
        with jax.named_scope("cbow.context_sum"):
            delta = _band_endpoint_delta(g_row, left, right, window)
            d_ctx = cumsum_rows(delta) - g_row
    else:
        with jax.named_scope("cbow.position_taps"):
            d_ctx = position_taps(g_row, weights, left, right, window,
                                  transpose=True)
            d_pos, having = position_weight_sums(
                g_row, e, live.astype(pf), left, right, window)
            new_pos = pos_w + (d_pos / jnp.maximum(having, 1)[:, None]
                               ).astype(pos_w.dtype)
    d_ctx = d_ctx * token_mask[:, None].astype(pf)

    dtype = syn0.dtype
    syn0_rows = syn1_rows = None
    if token_runs is not None:
        max_run, cap0, cap1 = token_runs
        # a conditional updates its table in place only where every read of
        # that table is ordered before it: d_out does not depend on the pool
        # rows' gather (Z), so tie the two (ops/sgns.py has the same barrier
        # for the same reason; PERF.md §6, PR 30)
        d_out, _ = jax.lax.optimization_barrier((d_out, Z))
    with jax.named_scope("cbow.scatter_syn0"):
        if subword is not None:
            new_syn0 = sw.scatter_center_updates(
                syn0, words, d_ctx, sw_table, sw_shape, sw_plan)
        elif token_runs is None:
            new_syn0 = syn0.at[tokens].add(d_ctx.astype(dtype))
        else:
            new_syn0, syn0_rows, _ = scatter_add_by_runs(
                syn0, tok_i, d_ctx, max_run, cap0, sort=True)
    with jax.named_scope("cbow.scatter_syn1"):
        if token_runs is None:
            new_syn1 = syn1.at[tokens].add(d_out.astype(dtype))
        else:
            new_syn1, syn1_rows, _ = scatter_add_by_runs(
                syn1, tok_i, d_out, max_run, cap1, sort=True, keep=live > 0)
        new_syn1 = new_syn1.at[negatives].add(d_Z.astype(dtype))
    if stabilizers is not None and stabilizers.post_pass:
        # touched sets of THIS formulation: syn0 at every valid token slot
        # (each is a potential context row of the band — a context-less token
        # sees a zero update but is still in the scatter's index list, so it
        # clamps/decays here where the scatter formulation would skip it: the
        # one documented touched-set difference between the formulations),
        # syn1 at the live centers plus the whole shared pool
        V = syn0.shape[0]
        enable = (token_mask.sum() > 0).astype(jnp.float32)
        new_syn0 = stabilize_rows(
            new_syn0, _mask_sentinel(tokens, token_mask, V), alpha,
            stabilizers, enable)
        idx1 = jnp.concatenate(
            [_mask_sentinel(tokens, live, V), negatives])
        new_syn1 = stabilize_rows(new_syn1, idx1, alpha, stabilizers, enable)

    if with_metrics:
        denom = jnp.maximum(live.sum(), 1.0)
        loss = (-_log_sigmoid(f_pos) * live
                - jnp.sum(_log_sigmoid(-f_neg) * neg_valid
                          * has_ctx[:, None].astype(logits_dtype), axis=-1,
                          dtype=jnp.float32)
                * (num_negatives / P)).sum() / denom
        mean_f_pos = (f_pos * live).sum() / denom
    else:
        loss = mean_f_pos = jnp.float32(0.0)
    metrics = StepMetrics(
        loss=loss,
        mean_f_pos=mean_f_pos,
        pairs=live.sum(),
        syn0_rows=syn0_rows,
        syn1_rows=syn1_rows,
        subword_rows=None if subword is None else sw_plan.live_rows,
        subword_slots=(None if subword is None
                       else sw.scatter_slots(sw_plan, sw_shape)),
        subword_gather_slots=(None if subword is None
                              else sw.gather_slots(sw_plan, sw_shape)),
    )
    return EmbeddingPair(new_syn0, new_syn1, new_pos), metrics
