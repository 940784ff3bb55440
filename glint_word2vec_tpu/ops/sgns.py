"""Fused SGNS/CBOW training step — the TPU-native replacement for the reference's hot loop.

In the reference, one minibatch costs two network round-trips to the parameter servers:
``dotprod(wInput, wOutput, seed)`` computes positive/negative dot products server-side
(G3, mllib:419-421), the client turns them into scalar gradient coefficients through a
1000-entry sigmoid LUT (``getSigmoid``, mllib:292-302), and ``adjust(gPlus, gMinus,
cacheKeys)`` applies the scatter-updates server-side (G4, mllib:423-425), pipelined at most
one minibatch deep (mllib:428-429).

Here the whole thing is one jitted function: embedding gather → batched dots → sigmoid →
scatter-add updates, with negatives sampled on-device (:mod:`..ops.sampler`). Under jit the
``dotprod``/``adjust`` split disappears; under pjit the per-shard partial dot products of the
CIKM'16 scheme become XLA collectives inserted by GSPMD.

Update rule (SGD on the SGNS objective, identical to the reference's coefficients):

    f_pos = syn0[c]·syn1[x]          g_pos = (1 − σ(f_pos))·α
    f_neg = syn0[c]·syn1[z_k]        g_neg = (0 − σ(f_neg))·α
    syn0[c]    += g_pos·syn1[x] + Σ_k g_neg_k·syn1[z_k]
    syn1[x]    += g_pos·syn0[c]
    syn1[z_k]  += g_neg_k·syn0[c]

using the *pre-update* values on both sides, exactly like the server-side cache in the
reference (the ``cacheKeys`` minibatch cache exists to reuse the dotprod-time rows in
adjust). Duplicate indices within a batch accumulate via scatter-add — deterministic,
unlike the reference's accepted Hogwild races (README.md:17-19).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.ops.sampler import AliasTable, sample_negatives

MAX_EXP = 6.0  # the reference's LUT clipping range (mllib:247, EXP_TABLE_SIZE/MAX_EXP)

# divide guard for the stabilizer norm ratios — far below any row norm a
# trained embedding can reach in f32 (min normal ~1.2e-38) yet nonzero, so a
# zero row clamps with scale min(1, max/eps) = 1 instead of NaN
_STAB_EPS = 1e-30


class Stabilizers(NamedTuple):
    """In-step numeric stabilizers (config.max_row_norm / update_clip /
    row_l2 — docs/robustness.md escalation ladder). All 0.0 = OFF, and an
    off knob elides its ops from the compiled step entirely, so the
    stabilizers-off step is bit-identical to the pre-stabilizer step (tested).

    - ``max_row_norm``: per-TOUCHED-row L2 clamp applied after the scatter
      update — never a dense [V, D] renorm pass. The direct counter to the
      measured finite norm blowup (EVAL.md round-5: hot rows run orders of
      magnitude past the healthy 1-15 band while isfinite stays true).
    - ``update_clip``: per-row L2 ceiling on each pair's/example's update
      contribution (the d_in/d_pos rows of SGNS, d_hidden/d_out of CBOW),
      applied BEFORE the scatter-add. Pool-row deltas (d_Z) are deliberately
      NOT clipped: under shard_map each data shard holds only a partial d_Z
      sum, so clipping there would diverge from the single-program lowering —
      pool rows are bounded by the n/P reweight plus ``max_row_norm`` instead.
    - ``row_l2``: L2 weight decay on touched rows — each touched row scales
      by (1 − α·row_l2) once per step regardless of in-batch multiplicity.

    All norm/scale math runs in float32 regardless of param/compute dtype
    (the R4 accumulation discipline: bf16 squared norms underflow exactly
    where the blowup channel saturates).
    """

    max_row_norm: float = 0.0
    update_clip: float = 0.0
    row_l2: float = 0.0

    @property
    def enabled(self) -> bool:
        return bool(self.max_row_norm or self.update_clip or self.row_l2)

    @property
    def post_pass(self) -> bool:
        """Whether the post-scatter touched-row pass (clamp/decay) runs."""
        return bool(self.max_row_norm or self.row_l2)


def clip_update_rows(d: jax.Array, clip: float) -> jax.Array:
    """Per-row L2 ceiling on an update-row block ``[..., D]``: rows whose L2
    norm exceeds ``clip`` rescale to exactly ``clip``; shorter rows pass
    through bit-exact (scale 1.0 round-trips the dtype). Norm math in
    ``promote_types(d.dtype, float32)`` — never below f32 (R4), never below
    the data's own precision (the f64 oracle suite holds this path exact)."""
    if not clip:
        return d
    pf = jnp.promote_types(d.dtype, jnp.float32)
    dp = d.astype(pf)
    n2 = jnp.sum(dp * dp, axis=-1, keepdims=True)
    scale = jnp.minimum(
        jnp.asarray(1.0, pf),
        jnp.asarray(clip, pf) / jnp.maximum(jnp.sqrt(n2),
                                            jnp.asarray(_STAB_EPS, pf)))
    return (dp * scale).astype(d.dtype)


def stabilize_rows(
    mat: jax.Array,       # [Vs, D] — a just-updated param matrix (or shard)
    idx: jax.Array,       # int32 [N] — touched rows; >= Vs = drop sentinel
    alpha: jax.Array,     # scalar learning rate (already decayed)
    stab: Stabilizers,
    enable: jax.Array,    # f32 scalar 1.0/0.0 — 0 on all-masked padded batches
) -> jax.Array:
    """Post-scatter touched-row stabilizer pass: gather the just-updated rows
    at ``idx``, apply the touched-row weight decay ``(1 − α·row_l2)`` then the
    ``max_row_norm`` clamp (clamping the DECAYED norm), and write the rows
    back with one scatter-set. Duplicate indices are safe by construction:
    every duplicate computes the identical replacement value (same gathered
    row → same scale), so the unordered scatter writes agree. Indices at or
    past ``mat.shape[0]`` (the caller's mask/ownership sentinel) drop — vocab
    padding rows are never touched. ``enable=0`` pins every scale to 1.0, so
    a fully-masked padded batch stays a bit-level no-op."""
    if not stab.post_pass:
        return mat
    vs = mat.shape[0]
    # norm/scale math in promote_types(dtype, f32): never below f32 (bf16
    # squared norms underflow exactly where the blowup saturates — R4),
    # never below the data's own precision (f64 oracle exactness)
    pf = jnp.promote_types(mat.dtype, jnp.float32)
    rows = mat[jnp.minimum(idx, vs - 1)].astype(pf)
    scale = jnp.ones(rows.shape[:-1], pf)
    if stab.row_l2:
        scale = scale * (jnp.asarray(1.0, pf)
                         - alpha.astype(pf) * jnp.asarray(stab.row_l2, pf))
    if stab.max_row_norm:
        norm = jnp.sqrt(jnp.sum(rows * rows, axis=-1)) * scale
        scale = scale * jnp.minimum(
            jnp.asarray(1.0, pf),
            jnp.asarray(stab.max_row_norm, pf)
            / jnp.maximum(norm, jnp.asarray(_STAB_EPS, pf)))
    scale = jnp.where(enable > 0, scale, jnp.asarray(1.0, pf))
    return mat.at[idx].set(
        (rows * scale[..., None]).astype(mat.dtype), mode="drop")


def _mask_sentinel(idx: jax.Array, gate: jax.Array, vs: int) -> jax.Array:
    """Touched-index list with gated-off slots mapped to the drop sentinel
    ``vs`` (one past the last row): a masked batch slot's placeholder index
    (0) must not drag a real row into the clamp/decay pass."""
    return jnp.where(gate > 0, idx, jnp.int32(vs))


def run_positions(idx: jax.Array, max_run: int) -> jax.Array:
    """Position of every entry inside its run of equal neighbouring ``idx``
    (int32 [N]; 0 = the run's head). A run longer than ``max_run`` is cut into
    pieces of ``max_run``, each with a head of its own, so :func:`run_sums`
    needs ``max_run − 1`` shifts whatever the input holds (equal neighbouring
    words, a masked tail of zeros). Cheap 1-D work on ``idx`` alone."""
    at = jnp.arange(idx.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), idx[1:] != idx[:-1]])
    # distance to the last change of idx, cut every max_run
    return (at - jax.lax.cummax(jnp.where(first, at, 0))) % max_run


def run_sums(rows: jax.Array, pos: jax.Array, max_run: int,
             dtype: jnp.dtype) -> jax.Array:
    """``[N, D]`` in ``dtype`` holding AT each run's head (``pos == 0``) the
    sum of the run's ``rows`` (the other rows hold partial sums nobody reads):
    ``max_run − 1`` shifted, masked adds. No sort, no scatter, no matmul (a
    prefix sum through the MXU rounds float32 to bfloat16 at the TPU's default
    precision): each sum is the plain left-to-right sum of the run's rows,
    each cast to ``dtype`` first.

    The shifts are static slices of ONE zero-padded copy: the TPU compiler
    fuses them, the casts and the adds into a single pass that writes the
    result once (``jnp.roll`` instead materialises every shifted block)."""
    n = rows.shape[0]
    # the padding's position 0 belongs to no run headed before it
    pos_pad = jnp.pad(pos, (0, max_run - 1))
    rows_pad = jnp.pad(rows, ((0, max_run - 1), (0, 0)))
    sums = rows.astype(dtype)
    for j in range(1, max_run):
        # row i + j belongs to the run headed at i exactly when its position is j
        member = pos_pad[j:j + n] == j
        sums = sums + jnp.where(member[:, None],
                                rows_pad[j:j + n].astype(dtype),
                                jnp.zeros((), dtype))
    return sums


# the sort key of an entry plan_runs leaves out (``keep``): past every row id,
# so such entries sort last
_NO_KEY = int(jnp.iinfo(jnp.int32).max)


class RunPlan(NamedTuple):
    """One table's runs of a batch (:func:`plan_runs`): what its coalesced
    scatter (:func:`scatter_add_by_runs`) and its coalesced gather
    (:func:`gather_by_runs`) both read, so a step that does both makes the
    sort, the heads and their compaction once."""

    at: jax.Array     # int32 [N] — 0..N−1, the entries' places in the batch
    keys: jax.Array   # int32 [N] — ``idx`` in run order (sorted where asked)
    # int32 [N] — the batch place of every entry in run order; None = the
    # batch's own order
    order: Optional[jax.Array]
    pos: jax.Array    # int32 [N] — place inside the piece (run_positions)
    head: jax.Array   # bool [N] — the entry heads a piece
    heads: jax.Array  # int32 scalar — pieces in the batch
    # int32 [cap] — the first ``cap`` heads' places in run order, N for
    # padding, and the same clamped into the batch (:func:`compact_heads`);
    # None until a caller asks for them
    live: Optional[jax.Array] = None
    src: Optional[jax.Array] = None


def plan_runs(idx: jax.Array, max_run: int, sort: bool = False,
              keep: Optional[jax.Array] = None) -> RunPlan:
    """The runs of equal neighbouring ``idx``, cut every ``max_run``; with
    ``sort`` those a stable 1-D sort of ``idx`` makes (it carries the places,
    so rows can be read in that order). ``keep`` (bool [N], with ``sort``):
    the other entries sort last under a key no word has and head no run."""
    at = jnp.arange(idx.shape[0], dtype=jnp.int32)
    by = idx if keep is None else jnp.where(keep, idx, _NO_KEY)
    keys, order = (jax.lax.sort((by, at), num_keys=1, is_stable=True)
                   if sort else (by, None))
    pos = run_positions(keys, max_run)
    head = pos == 0 if keep is None else (pos == 0) & (keys != _NO_KEY)
    return RunPlan(at, keys, order, pos, head, head.sum(dtype=jnp.int32))


def compact_heads(plan: RunPlan, cap: int) -> RunPlan:
    """``plan`` with its heads compacted to a static ``cap`` places (a sort of
    their positions; ``jnp.nonzero`` is itself a 65,536-row scatter); the plan
    itself where it already holds them (under a ladder of caps: its last
    rung's, which every rung before it is a prefix of)."""
    if plan.live is not None:
        return plan
    n = plan.at.shape[0]
    live = jnp.sort(jnp.where(plan.head, plan.at, n))[:cap]
    return plan._replace(live=live, src=jnp.minimum(live, n - 1))


def _ladder(cap) -> Tuple[int, ...]:
    """A cap as the ascending ladder of caps it stands for: one rung for an int."""
    return (cap,) if isinstance(cap, int) else tuple(cap)


def ladder_rung(plan: RunPlan, caps: Tuple[int, ...]) -> jax.Array:
    """The first rung of the ascending ``caps`` that holds ``plan``'s heads
    (int32 scalar: the count of rungs they exceed; ``len(caps)`` = none does)."""
    return sum((plan.heads > cap).astype(jnp.int32) for cap in caps)


def scatter_add_by_runs(mat: jax.Array, idx: jax.Array, rows: jax.Array,
                        max_run: int, cap: Union[int, Tuple[int, ...]],
                        sort: bool = False,
                        keep: Optional[jax.Array] = None,
                        plan: Optional[RunPlan] = None,
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``mat.at[idx].add(rows)`` that hands the scatter ONE row per run of
    equal neighbouring ``idx``: ``(new_mat, rows_handed_over, slots)``, the
    live rows the scatter was handed and the static rows, padding and all, of
    the entry the batch took.

    XLA's TPU scatter is priced per update row, ~100 ns each into
    f32[3000000,384], whether rows repeat or are dropped out of bounds (PERF.md
    §6, PR 28: 65,536 rows 6.65 ms, 24,576 rows 2.52 ms, 65,536 rows with 74%
    sent out of bounds 6.66 ms). So the runs are summed first
    (:func:`run_sums`), the heads are compacted to a static ``cap`` rows
    (:func:`compact_heads`) and only those reach the scatter, the padding
    dropped out of bounds.

    The native pair feed emits a center's pairs consecutively
    (native/pairgen.cpp), so by ``centers`` a batch has ~1/4 as many heads as
    pairs at window 5. ``contexts`` come in no order: ``sort`` makes the runs
    here, by a stable 1-D sort of ``idx`` that carries the positions, and the
    coalesced branch reads ``rows`` in that order (one row gather).
    The step decides from its own batch: one with more heads than ``cap``
    (rows that all differ, a feed that does not emit runs) takes the plain
    scatter, the same op on the same unsorted ``rows`` as without this
    function. A row of ``mat`` receives the same sum of the same ``rows``
    either way, in the order the batch holds them (the sort is stable);
    coalesced, the float additions run over the run first, then into the row.

    ``cap`` as an ascending tuple is a ladder of caps, and the padding is
    priced like the live rows, so the step takes the FIRST rung that holds
    its batch's heads (:func:`ladder_rung`): one flat ``lax.switch`` whose
    entry *i* is the coalesced scatter at ``cap[i]`` and whose last entry is
    the plain scatter. The heads are compacted once, to the last rung, and
    every rung reads a prefix; a row receives the same run sums in the same
    order on every rung. ``slots`` is ``cap[i]`` of the entry the batch TOOK,
    N for the plain one: what the scatter's time follows. A ladder of one
    rung is the int's program.

    ``keep`` (bool [N], with ``sort``): the entries whose ``rows`` are not
    zero by construction (the banded CBOW step's slots that train an example,
    four fifths of a block). The others sort last under a key no word has and
    head no run, so the coalesced scatter is handed the kept entries' runs
    alone; the plain branch is the same either way.

    ``plan``: :func:`plan_runs` of the same ``idx``, ``max_run``, ``sort``
    and ``keep``, where the caller made it already (a step whose gather goes
    by the same runs); made here where not."""
    n, v = idx.shape[0], mat.shape[0]
    caps = _ladder(cap)
    if plan is None:
        plan = plan_runs(idx, max_run, sort, keep)

    def coalesced(rung: int):
        def entry(mat):
            by_run = rows if plan.order is None else rows[plan.order]
            sums = run_sums(by_run, plan.pos, max_run, mat.dtype)
            at_heads = compact_heads(plan, rung)
            live, src = at_heads.live[:rung], at_heads.src[:rung]
            return mat.at[jnp.where(live < n, plan.keys[src], v)
                          ].add(sums[src], mode="drop")
        return entry

    def plain(mat):
        return mat.at[idx].add(rows.astype(mat.dtype))

    if len(caps) == 1:
        fits = plan.heads <= caps[0]
        new_mat = jax.lax.cond(fits, coalesced(caps[0]), plain, mat)
        slots = jnp.where(fits, caps[0], n)
    else:
        # the one sort of the heads' places, ahead of the switch
        plan = compact_heads(plan, caps[-1])
        rung = ladder_rung(plan, caps)
        fits = rung < len(caps)
        new_mat = jax.lax.switch(
            rung, [coalesced(c) for c in caps] + [plain], mat)
        slots = jnp.asarray(caps + (n,))[rung]
    return (new_mat, jnp.where(fits, plan.heads, n).astype(jnp.float32),
            slots.astype(jnp.float32))


def gather_by_runs(tables, dtype: jnp.dtype) -> Tuple[tuple, jax.Array]:
    """``mat[idx].astype(dtype)`` for every ``(mat, idx, plan, cap)`` of
    ``tables``, made from ONE gathered row per piece of ``plan``'s runs:
    ``(the [N, D] blocks, the rows handed to their assembly)``.

    Over a model axis a table's rows lie on several chips, and a gather of
    ``mat[idx]`` is assembled by an all-reduce of the whole ``[N, D]`` block,
    every chip adding the rows it owns and zeros for the rest (GSPMD's
    schedule; docs/sharding.md). That collective is bandwidth-bound and
    carries a repeated row once per repeat: ~3.8 times at window 5. Here the
    heads' rows alone are gathered and cast (``[cap, D]``: that block is what
    crosses the mesh), and every entry then reads its piece's row out of the
    replicated block, a gather local to each chip. For a sorted plan the
    pieces' ids go back to the batch's order by a second sort (a 1-D scatter
    of N ids is priced like a row scatter on the TPU, PERF.md §6, PR 28). A
    piece of a cut run gathers its row again: the cap counts pieces.

    One flat switch holds every table's gather, so the compiler can combine
    an entry's all-reduces. ``cap`` is an int or an ascending ladder of caps
    (:func:`scatter_add_by_runs`; a shorter ladder repeats its last rung):
    entry *i* gathers ``[cap[i], D]`` of every table, the batch takes the
    first whose rungs hold every table's pieces, and one with more pieces
    than a table's last rung takes the plain gathers, the same ops as without
    this function. The rows are the same either way, bit for bit (the other
    chips add zeros). The second result counts what crossed the mesh: the
    entry's caps summed (float32 scalar), ``N`` a table on the plain one.
    Plans made with ``keep`` are not for this: an entry left out has no piece."""
    mats = tuple(mat for mat, _, _, _ in tables)
    ladders = [_ladder(cap) for _, _, _, cap in tables]
    rungs = max(len(caps) for caps in ladders)
    ladders = [caps + caps[-1:] * (rungs - len(caps)) for caps in ladders]
    # each table's one sort of its heads' places, ahead of the switch
    plans = [compact_heads(plan, caps[-1])
             for (_, _, plan, _), caps in zip(tables, ladders)]

    def by_runs(at: int):
        def entry(mats):
            out = []
            for mat, plan, caps in zip(mats, plans, ladders):
                # the padding reads the last entry's row, which no piece names
                block = mat[plan.keys[plan.src[:caps[at]]]].astype(dtype)
                piece = jnp.cumsum(plan.head.astype(jnp.int32)) - 1
                if plan.order is not None:
                    _, piece = jax.lax.sort((plan.order, piece), num_keys=1)
                out.append(block[piece])
            return tuple(out)
        return entry

    def plain(mats):
        return tuple(mat[idx].astype(dtype)
                     for mat, (_, idx, _, _) in zip(mats, tables))

    rung = jnp.int32(0)
    for plan, caps in zip(plans, ladders):
        rung = jnp.maximum(rung, ladder_rung(plan, caps))
    blocks = jax.lax.switch(
        rung, [by_runs(at) for at in range(rungs)] + [plain], mats)
    handed = [sum(caps[at] for caps in ladders) for at in range(rungs)]
    handed.append(sum(idx.shape[0] for _, idx, _, _ in tables))
    return blocks, jnp.asarray(handed, jnp.float32)[rung]


class EmbeddingPair(NamedTuple):
    """The two trainable matrices: input (syn0) and output (syn1neg) embeddings —
    the reference's ``BigWord2VecMatrix`` pair (G2, README.md:69)."""

    syn0: jax.Array  # [V, D] input embeddings — the word vectors the model exports
    syn1: jax.Array  # [V, D] output embeddings — negative-sampling softmax weights
    # [2·window, D] position weights (config.cbow_position_weights;
    # ops/cbow_banded.py): row p + window for p < 0, p + window − 1 for p > 0.
    # None on every other model: the pytree then has the two leaves it always
    # had, so their compiled steps and checkpoints are what they were
    pos: Optional[jax.Array] = None


class StepMetrics(NamedTuple):
    """Per-step training telemetry — superset of the reference's heartbeat, which logs
    wordCount/alpha/fPlus(0) every 10k words (mllib:411-412)."""

    loss: jax.Array       # masked mean SGNS loss
    mean_f_pos: jax.Array  # mean positive dot product (gradient-health signal)
    pairs: jax.Array      # number of real (unmasked) pairs in the batch
    # update rows that reached syn0's scatter with a live index (the shared-pool
    # SGNS step: B plain, one per center run coalesced; the banded CBOW step
    # under ``token_runs``: T plain, one per piece of a word's run of the
    # block's tokens coalesced); None = not counted
    syn0_rows: Optional[jax.Array] = None
    # the same for syn1's context scatter (one per context run of the batch
    # sorted by context; the banded CBOW step: one per piece of a word's run
    # of the tokens that train an example); the pool rows' scatter is not counted
    syn1_rows: Optional[jax.Array] = None
    # rows of the centers' subword lists that reached syn0's scatter with a
    # live index (config.subword; ops/subword.py); None = not a subword step
    subword_rows: Optional[jax.Array] = None
    # slots the subword lists' scatter was handed (ops/subword.py
    # scatter_slots: the slot capacity, or every slot of the heads' block: a
    # CBOW token block's, the word heads' of a skip-gram batch); None = not a
    # subword step
    subword_slots: Optional[jax.Array] = None
    # slots the same block's list gather was handed (ops/subword.py
    # gather_slots: every token's first group and the tail capacity's later
    # groups a pass, or every slot of the block); None everywhere but that step
    subword_gather_slots: Optional[jax.Array] = None
    # live (pair, node) terms of a hierarchical-softmax step: the summed path
    # lengths of its real pairs' contexts (config.loss="hs"; ops/hs.py); None
    # on every other step
    hs_nodes: Optional[jax.Array] = None
    # rows handed to the forward assembly of the gathered rows over a model
    # axis (sgns_step_shared_core ``assemble_by_runs``): both scatters' caps
    # and the pool where the batch went by runs, 2B + P where it did not; None
    # where that form is not compiled
    assembly_rows: Optional[jax.Array] = None
    # static rows each table's scatter was handed, padding and all (the
    # shared-pool SGNS step: the cap of the rung its batch took,
    # scatter_add_by_runs; B plain): what the scatter's time follows, where
    # ``syn0_rows`` / ``syn1_rows`` count the live ones; None = not counted
    syn0_slots: Optional[jax.Array] = None
    syn1_slots: Optional[jax.Array] = None


def init_embeddings(
    vocab_size: int,
    vector_size: int,
    key: jax.Array,
    dtype: jnp.dtype = jnp.float32,
) -> EmbeddingPair:
    """Classic word2vec init: syn0 ~ U(-0.5/D, 0.5/D), syn1 = 0 (fork-side in the
    reference; standard for SGNS — zero syn1 makes initial dots 0, σ=0.5)."""
    syn0 = jax.random.uniform(
        key, (vocab_size, vector_size), dtype=jnp.float32,
        minval=-0.5 / vector_size, maxval=0.5 / vector_size).astype(dtype)
    syn1 = jnp.zeros((vocab_size, vector_size), dtype=dtype)
    return EmbeddingPair(syn0=syn0, syn1=syn1)


def _sigmoid(f: jax.Array, mode: str) -> jax.Array:
    """σ(f); "clipped" mirrors the reference LUT saturation: σ=1 for f>6, σ=0 for f<-6
    (getSigmoid, mllib:292-302), which zeroes gradients outside ±6."""
    if mode == "clipped":
        return jnp.where(f > MAX_EXP, 1.0,
                         jnp.where(f < -MAX_EXP, 0.0, jax.nn.sigmoid(f)))
    return jax.nn.sigmoid(f)


def _log_sigmoid(f: jax.Array) -> jax.Array:
    return -jax.nn.softplus(-f)


def sgns_loss(
    params: EmbeddingPair,
    centers: jax.Array,     # int32 [B]
    contexts: jax.Array,    # int32 [B]
    negatives: jax.Array,   # int32 [B, n]
    mask: jax.Array,        # float32 [B]
    compute_dtype: jnp.dtype = jnp.float32,
) -> jax.Array:
    """Masked-mean SGNS negative log likelihood:
    −log σ(f_pos) − Σ_k log σ(−f_neg_k). ∂loss/∂f gives exactly the reference's gradient
    coefficients (up to the α scale), so SGD-via-autodiff on this loss and the manual
    :func:`sgns_step` agree — a property the unit tests assert.
    """
    e_in = params.syn0[centers].astype(compute_dtype)
    e_pos = params.syn1[contexts].astype(compute_dtype)
    e_neg = params.syn1[negatives].astype(compute_dtype)
    f_pos = jnp.sum(e_in * e_pos, axis=-1).astype(jnp.float32)
    f_neg = jnp.einsum("bd,bnd->bn", e_in, e_neg).astype(jnp.float32)
    neg_valid = (negatives != contexts[:, None]).astype(jnp.float32) * mask[:, None]
    per_pair = -_log_sigmoid(f_pos) * mask - jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1)
    denom = jnp.maximum(mask.sum(), 1.0)
    return per_pair.sum() / denom


def sgns_step(
    params: EmbeddingPair,
    centers: jax.Array,    # int32 [B]
    contexts: jax.Array,   # int32 [B]
    mask: jax.Array,       # float32 [B]
    key: jax.Array,
    alpha: jax.Array,      # scalar learning rate (already decayed)
    table: AliasTable,
    num_negatives: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    duplicate_scaling: bool = False,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """One synchronous SGNS update on a fixed-shape batch of (center, context) pairs.

    Negatives equal to their pair's positive context word are skipped (zero gradient), the
    classic word2vec rule the fork's server-side sampler follows. Padded pairs (mask 0)
    contribute nothing: their coefficients are multiplied by the mask before scatter.

    ``duplicate_scaling``: divide each row's accumulated update by the number of times the
    row occurs in the batch. The reference never faces this — its async 50-pair minibatches
    apply sequentially (mllib:417-429), so a frequent word's updates interleave; in one
    large synchronous batch they *sum*, and at extreme duplicate density (tiny vocab ×
    large batch) the effective per-row step is duplicates × α, which can diverge. Scaling
    makes each row take the *mean* of its pair updates — stable at any batch size, at the
    cost of slower differentiation (frequent rows see one averaged step per batch). Default
    off: textbook accumulate semantics, the reference's math.
    """
    negatives = sample_negatives(table, key, (centers.shape[0], num_negatives))
    return sgns_step_core(params, centers, contexts, mask, negatives, alpha,
                          sigmoid_mode, compute_dtype, duplicate_scaling)


def sgns_step_core(
    params: EmbeddingPair,
    centers: jax.Array,    # int32 [B]
    contexts: jax.Array,   # int32 [B]
    mask: jax.Array,       # float32 [B]
    negatives: jax.Array,  # int32 [B, n] — pre-drawn (hot path: ops.sampler.sample_negatives_hash)
    alpha: jax.Array,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    duplicate_scaling: bool = False,
    stabilizers: Optional[Stabilizers] = None,
    fused: bool = False,
    bf16_chain: bool = False,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """:func:`sgns_step` with the negatives supplied by the caller — the form the
    trainer jits (sampling happens once per dispatch chunk, outside the scan, because
    in-program threefry is catastrophically slow on TPU; see ops/prng.py).

    ``stabilizers`` (None/all-zero = off, bit-identical step): ``update_clip``
    caps every per-pair update row (d_in, d_pos, and — per-pair negatives
    being per-pair rows — d_neg); the post-scatter pass clamps/decays the
    touched rows: syn0 at the unmasked centers, syn1 at the unmasked contexts
    plus the negatives of unmasked pairs (see :class:`Stabilizers`).

    ``fused``/``bf16_chain``: the per-pair forms of the ISSUE-14
    step restructurings (see :func:`sgns_step_shared_core` for semantics):
    fused folds validity+mask+α into one [B, n] select with a precomputed
    scalar; bf16_chain accumulates both logit dots in promote(compute, f32)
    via ``preferred_element_type`` (the per-pair chain previously ran the
    einsum in compute dtype and upcast AFTER — chain mode is the stricter R4
    form). Both default off; off elides the new ops entirely (bit-identical
    step)."""
    syn0, syn1 = params.syn0, params.syn1
    V = syn0.shape[0]
    if duplicate_scaling and fused:
        raise ValueError("duplicate_scaling has no fused form "
                         "(refused at config construction)")
    if not fused:
        neg_valid = (negatives != contexts[:, None]).astype(jnp.float32) \
            * mask[:, None]

    with jax.named_scope("sgns.gather"):
        e_in = syn0[centers].astype(compute_dtype)          # [B, D]
        e_pos = syn1[contexts].astype(compute_dtype)        # [B, D]
        e_neg = syn1[negatives].astype(compute_dtype)       # [B, n, D]

    if bf16_chain:
        pf = jnp.promote_types(compute_dtype, jnp.float32)
        f_pos = jnp.einsum("bd,bd->b", e_in, e_pos,
                           preferred_element_type=pf).astype(jnp.float32)
        f_neg = jnp.einsum("bd,bnd->bn", e_in, e_neg,
                           preferred_element_type=pf).astype(jnp.float32)
    else:
        f_pos = jnp.sum(e_in * e_pos, axis=-1).astype(jnp.float32)        # [B]
        f_neg = jnp.einsum("bd,bnd->bn", e_in, e_neg).astype(jnp.float32)  # [B, n]

    # Gradient coefficients, exactly the reference's client-side math (mllib:421-425):
    # gPlus = (1 − σ(f))·α for label 1, gMinus = (0 − σ(f))·α for label 0.
    g_pos = (1.0 - _sigmoid(f_pos, sigmoid_mode)) * alpha * mask               # [B]
    if fused:
        valid = (negatives != contexts[:, None]) & (mask[:, None] > 0)
        g_neg = jnp.where(valid, _sigmoid(f_neg, sigmoid_mode) * (-alpha),
                          jnp.zeros((), f_neg.dtype))                  # [B, n]
        neg_valid = valid
    else:
        g_neg = (0.0 - _sigmoid(f_neg, sigmoid_mode)) * alpha * neg_valid  # [B, n]

    if duplicate_scaling:
        cnt0 = jnp.zeros(V, jnp.float32).at[centers].add(mask)
        cnt1 = (jnp.zeros(V, jnp.float32).at[contexts].add(mask)
                .at[negatives.reshape(-1)].add(neg_valid.reshape(-1)))
        g_pos_in = g_pos / jnp.maximum(cnt0[centers], 1.0)
        g_neg_in = g_neg / jnp.maximum(cnt0[centers], 1.0)[:, None]
        g_pos_out = g_pos / jnp.maximum(cnt1[contexts], 1.0)
        g_neg_out = g_neg / jnp.maximum(cnt1[negatives], 1.0)
    else:
        g_pos_in = g_pos_out = g_pos
        g_neg_in = g_neg_out = g_neg

    d_in = (g_pos_in[:, None].astype(compute_dtype) * e_pos
            + jnp.einsum("bn,bnd->bd", g_neg_in.astype(compute_dtype), e_neg))
    d_pos = g_pos_out[:, None].astype(compute_dtype) * e_in          # [B, D]
    d_neg = g_neg_out[..., None].astype(compute_dtype) * e_in[:, None, :]  # [B, n, D]
    if stabilizers is not None and stabilizers.update_clip:
        d_in = clip_update_rows(d_in, stabilizers.update_clip)
        d_pos = clip_update_rows(d_pos, stabilizers.update_clip)
        d_neg = clip_update_rows(d_neg, stabilizers.update_clip)

    dtype = syn0.dtype
    D = syn1.shape[1]
    with jax.named_scope("sgns.scatter_syn0"):
        new_syn0 = syn0.at[centers].add(d_in.astype(dtype))
    with jax.named_scope("sgns.scatter_syn1"):
        new_syn1 = syn1.at[contexts].add(d_pos.astype(dtype))
        new_syn1 = new_syn1.at[negatives.reshape(-1)].add(
            d_neg.reshape(-1, D).astype(dtype))
    if stabilizers is not None and stabilizers.post_pass:
        enable = (mask.sum() > 0).astype(jnp.float32)
        new_syn0 = stabilize_rows(
            new_syn0, _mask_sentinel(centers, mask, V), alpha,
            stabilizers, enable)
        idx1 = jnp.concatenate([
            _mask_sentinel(contexts, mask, V),
            _mask_sentinel(negatives,
                           jnp.broadcast_to(mask[:, None], negatives.shape),
                           V).reshape(-1)])
        new_syn1 = stabilize_rows(new_syn1, idx1, alpha, stabilizers, enable)

    denom = jnp.maximum(mask.sum(), 1.0)
    if fused:
        neg_loss = jnp.sum(
            jnp.where(neg_valid, _log_sigmoid(-f_neg),
                      jnp.zeros((), f_neg.dtype)), axis=-1)
    else:
        neg_loss = jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1)
    loss = (-_log_sigmoid(f_pos) * mask - neg_loss).sum() / denom
    metrics = StepMetrics(
        loss=loss,
        mean_f_pos=(f_pos * mask).sum() / denom,
        pairs=mask.sum(),
    )
    return EmbeddingPair(new_syn0, new_syn1), metrics


def shared_pool_coeffs(
    e_in: jax.Array,       # [B, D] compute_dtype
    e_pos: jax.Array,      # [B, D] compute_dtype
    Z: jax.Array,          # [P, D] compute_dtype
    contexts: jax.Array,   # int32 [B]
    negatives: jax.Array,  # int32 [P]
    mask: jax.Array,       # float32 [B]
    alpha: jax.Array,
    num_negatives: int,
    sigmoid_mode: str,
    logits_dtype: jnp.dtype,
    fused: bool = False,
    bf16_chain: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The shared-pool logit chain: (f_pos, f_neg, neg_valid, g_pos, g_neg).

    Extracted so the GSPMD step (:func:`sgns_step_shared_core`) and the
    explicit shard_map lowering (:mod:`.sgns_shard`) run op-for-op identical
    coefficient math — the two lowerings must never drift in anything but
    collective placement.

    ``fused`` (config.fused_logits): collapse the [B, P] chain to ONE
    coefficient expression — validity (pool entry == pair's positive) and
    the batch mask fold into a single select predicate, and the
    α·negatives/P reweight folds into one precomputed scalar, so the chain
    materializes only f_neg (the dot output) and g_neg instead of also the
    float neg_valid array and its mask/α/reweight elementwise passes
    (PERF.md §11). ``neg_valid`` is then returned as the BOOL predicate —
    consumed only by the metrics twin's loss pass (dead code in the elided
    production twin). Off (default) keeps the pre-fusion chain op-for-op.

    ``bf16_chain`` (config.bf16_chain): compute the positive logit as a
    dot_general accumulating in promote(compute, f32) via
    ``preferred_element_type`` instead of a multiply + convert-to-f32 +
    reduce — same R4 accumulation discipline WITHOUT the dense f32 [B, D]
    product the sum-based form materializes in bf16 mode (the new stepaudit
    dtype-contract row pins this on the lowered module)."""
    P = negatives.shape[0]
    if bf16_chain:
        pf = jnp.promote_types(e_in.dtype, jnp.float32)
        f_pos = jnp.einsum("bd,bd->b", e_in, e_pos,
                           preferred_element_type=pf).astype(jnp.float32)
    else:
        f_pos = jnp.sum(e_in * e_pos, axis=-1).astype(jnp.float32)
    f_neg = (e_in @ Z.T).astype(logits_dtype)           # [B, P] — MXU
    g_pos = (1.0 - _sigmoid(f_pos, sigmoid_mode)) * alpha * mask
    if fused:
        valid = ((negatives[None, :] != contexts[:, None])
                 & (mask[:, None] > 0))                 # bool [B, P]
        neg_scale = (alpha * (0.0 - num_negatives / P)).astype(logits_dtype)
        g_neg = jnp.where(valid, _sigmoid(f_neg, sigmoid_mode) * neg_scale,
                          jnp.zeros((), logits_dtype))
        return f_pos, f_neg, valid, g_pos, g_neg
    neg_valid = (negatives[None, :] != contexts[:, None]).astype(logits_dtype) \
        * mask[:, None].astype(logits_dtype)
    g_neg = ((0.0 - _sigmoid(f_neg, sigmoid_mode))
             * jnp.asarray(alpha, logits_dtype) * neg_valid
             * jnp.asarray(num_negatives / P, logits_dtype))
    return f_pos, f_neg, neg_valid, g_pos, g_neg


def shared_pool_loss_terms(
    f_pos: jax.Array,      # [B] float32
    f_neg: jax.Array,      # [B, P] logits_dtype
    neg_valid: jax.Array,  # [B, P] logits_dtype
    mask: jax.Array,       # float32 [B]
    num_negatives: int,
) -> Tuple[jax.Array, jax.Array]:
    """Pre-division loss/mean_f_pos numerators (scalars). Shared by both
    lowerings; the shard_map step psums these across data shards before
    dividing by the global pair count, the single-program step divides
    directly — same math either way. ``neg_valid`` may be the classic float
    validity array or the fused chain's bool predicate (a select replaces
    the multiply — identical masking, one fewer [B, P] float array)."""
    P = f_neg.shape[-1]
    if neg_valid.dtype == jnp.bool_:
        neg_term = jnp.sum(
            jnp.where(neg_valid, _log_sigmoid(-f_neg),
                      jnp.zeros((), f_neg.dtype)),
            axis=-1, dtype=jnp.float32)
    else:
        neg_term = jnp.sum(_log_sigmoid(-f_neg) * neg_valid, axis=-1,
                           dtype=jnp.float32)
    loss_num = (-_log_sigmoid(f_pos) * mask
                - neg_term * (num_negatives / P)).sum()
    return loss_num, (f_pos * mask).sum()


def sgns_step_shared(
    params: EmbeddingPair,
    centers: jax.Array,    # int32 [B]
    contexts: jax.Array,   # int32 [B]
    mask: jax.Array,       # float32 [B]
    key: jax.Array,
    alpha: jax.Array,
    table: AliasTable,
    num_negatives: int,
    negative_pool: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """SGNS step with a batch-shared negative pool — the TPU fast path.

    Per-pair negative sampling makes the step row-access-bound: 5·B extra row gathers and
    5·B row scatters per batch dominate the step (measured ~4× the positive-pair traffic).
    Sharing ONE pool of ``negative_pool`` negatives across the whole batch turns all
    negative compute into MXU matmuls — ``f_neg = E_in @ Zᵀ`` and ``dZ = g_negᵀ @ E_in`` —
    leaving only ``negative_pool`` scatter rows. Each negative term is reweighted by
    ``num_negatives / negative_pool`` so the expected gradient matches the per-pair
    objective (the standard shared-negative estimator used by batched word2vec systems;
    the reference's own shared-seed trick, G3 mllib:419-421, is the RPC-era cousin —
    negatives shared across PS shards to avoid communicating them).

    Pool entries equal to a pair's positive context are masked per (pair, pool) entry.
    """
    negatives = sample_negatives(table, key, (negative_pool,))
    return sgns_step_shared_core(params, centers, contexts, mask, negatives, alpha,
                                 num_negatives, sigmoid_mode, compute_dtype)


def sgns_step_shared_core(
    params: EmbeddingPair,
    centers: jax.Array,    # int32 [B]
    contexts: jax.Array,   # int32 [B]
    mask: jax.Array,       # float32 [B]
    negatives: jax.Array,  # int32 [P] — pre-drawn shared pool
    alpha: jax.Array,
    num_negatives: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    duplicate_scaling: bool = False,
    logits_dtype: jnp.dtype = jnp.float32,
    with_metrics: bool = True,
    stabilizers: Optional[Stabilizers] = None,
    fused: bool = False,
    bf16_chain: bool = False,
    center_runs: Optional[Tuple[int, int]] = None,
    context_runs: Optional[Tuple[int, int]] = None,
    subword: Optional[tuple] = None,
    assemble_by_runs: bool = False,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """:func:`sgns_step_shared` with the pool supplied by the caller (see
    :func:`sgns_step_core` for why sampling lives outside the jitted scan).

    ``subword`` ``(SubwordTable, SubwordShape)`` (config.subword;
    :mod:`.subword`): the center's row source. syn0 then has the vocabulary's
    rows and the bucket rows; a center's ``e_in`` is the mean of the rows its
    word's list names, made once per distinct center word of the batch (once
    per center run where the batch has too many), and ``d_in``, summed per run
    and per word and divided by the list's length, is spread back over them in
    one scatter.
    Everything after ``e_in`` and before syn0's scatter is the same code;
    ``center_runs`` is not read (the shape carries the run length and cap).

    ``center_runs`` ``(max_run, cap)``: syn0's update goes through
    :func:`scatter_add_by_runs` — the pair feed emits a center's pairs
    consecutively, so a batch hands the scatter one summed row per center run
    (about a quarter of B at window 5) where it holds at most ``cap`` runs,
    and takes the plain scatter where it does not. The trainer derives both
    numbers from ``config.window``; nothing else in the step changes.

    ``context_runs`` ``(max_run, cap)``: the same for syn1's context update,
    whose runs the helper makes by sorting the batch's pairs by context inside
    the step (a batch holds ~0.18 distinct contexts a pair at V = 3M, PERF.md
    §6, PR 30); the trainer derives the cap from the vocabulary's counts. The
    pool rows' scatter, the stabilizers' post-pass and ``duplicate_scaling``
    read ``contexts``, not the order, and are as without it.

    ``assemble_by_runs`` (with both of the above): ``e_in`` and ``e_pos`` are
    made through :func:`gather_by_runs` under the two scatters' own plans and
    caps, one gathered row a piece and an expansion by piece id. For tables
    sharded by rows over a model axis, where the gathered rows are assembled
    by an all-reduce and a batch holds each center's row ~3.8 times and each
    context's ~3.9 (the trainer engages it there and nowhere else: on one
    chip there is no collective to shrink and the expansion costs what the
    gather saves). The values are the same rows, so every later op, the
    tables and the loss are bit for bit those of the step without it;
    ``StepMetrics.assembly_rows`` says which branch a batch took.

    ``fused``/``bf16_chain`` (config.fused_logits / config.bf16_chain —
    ISSUE 14): the fused coefficient chain and the f32-accumulating dot
    restructurings of :func:`shared_pool_coeffs`; both default off, and off
    elides the new ops entirely (the step is bit-identical to the
    pre-restructure release — tested). Neither supports
    ``duplicate_scaling`` (the mean-update scaling reads the per-pair
    coefficient arrays the fusion eliminates; refused at config).

    ``stabilizers`` (None/all-zero = off, bit-identical step): ``update_clip``
    caps the per-pair d_in/d_pos rows (NOT the pool deltas d_Z — see
    :class:`Stabilizers` for the shard_map-parity rationale); the post-scatter
    pass clamps/decays the touched rows — syn0 at the unmasked centers, syn1
    at the unmasked contexts plus the whole shared pool (every pool row is
    part of the step's touched set by construction). The explicit shard_map
    lowering (ops/sgns_shard.py) applies the identical math owner-locally, so
    the two lowerings agree to the usual f32-reassociation tolerance.

    ``duplicate_scaling`` extends :func:`sgns_step_core`'s mean-update semantics to
    this path: each embedding row moves by the MEAN of its per-pair updates instead of
    their sum — centers/contexts divide by their occurrence count in the batch, and
    each pool row divides by its number of contributing (valid) pairs times its
    within-pool multiplicity. This bounds the per-row step at any batch size without
    subsampling, at the cost of slower differentiation of frequent rows (and, for pool
    rows, a much smaller effective negative step, since their contribution count is
    ~B). Frequency subsampling (subsample_ratio ≈ 1e-4) is usually the better fix —
    see EVAL.md.

    ``logits_dtype`` is the dtype of the [B, P] negative-logit chain (f_neg → sigmoid
    → g_neg). The default float32 matches the reference's client-side float math
    (mllib:421-425). At pool ≥ 512 the f32 chain is several full passes over a
    [B, P] array (~268 MB at B=64k/P=1024) and becomes a measurable slice of the
    step (PERF.md §4); ``bfloat16`` keeps it in half precision — gradient
    coefficients are O(α·n/P) and tolerate ~0.4% relative noise. Loss/metric
    reductions still accumulate in f32.

    ``with_metrics=False`` skips the loss/mean_f_pos side-channel (the negative
    loss term is an extra full [B, P] pass — measured ~0.3 ms at B=64k/P=512
    bf16, PERF.md §4); ``pairs`` stays exact (it is load-bearing for the
    trainer's pair accounting). The trainer dispatches this variant for chunks
    no heartbeat will sample."""
    syn0, syn1 = params.syn0, params.syn1
    V = syn0.shape[0]
    if duplicate_scaling and fused:
        raise ValueError("duplicate_scaling has no fused form "
                         "(refused at config construction)")
    if assemble_by_runs and (subword is not None or not center_runs
                             or not context_runs):
        raise ValueError("assemble_by_runs goes by both scatters' runs: it "
                         "needs center_runs and context_runs, and no subword")
    # named scopes are metadata for a profile's reader (docs/observability.md
    # §4); the compiled step is the same program without them (tested)
    if subword is not None:
        from glint_word2vec_tpu.ops import subword as sw
        sw_table, sw_shape = subword
        sw_plan = sw.plan_centers(centers, sw_table, sw_shape)
        e_in = sw.center_vectors(syn0, centers, sw_table, sw_shape, sw_plan,
                                 compute_dtype)              # [B, D]
    plan0 = plan1 = assembly_rows = None
    with jax.named_scope("sgns.gather"):
        if assemble_by_runs:
            # the plans the two scatters go by, compacted here once for both
            (run0, cap0), (run1, cap1) = center_runs, context_runs
            plan0 = compact_heads(plan_runs(centers, run0), _ladder(cap0)[-1])
            plan1 = compact_heads(plan_runs(contexts, run1, sort=True),
                                  _ladder(cap1)[-1])
            (e_in, e_pos), handed = gather_by_runs(
                ((syn0, centers, plan0, cap0), (syn1, contexts, plan1, cap1)),
                compute_dtype)
            assembly_rows = handed + negatives.shape[0]
        else:
            if subword is None:
                e_in = syn0[centers].astype(compute_dtype)      # [B, D]
            e_pos = syn1[contexts].astype(compute_dtype)        # [B, D]
        Z = syn1[negatives].astype(compute_dtype)               # [P, D]

    with jax.named_scope("sgns.pool_matmul"):
        f_pos, f_neg, neg_valid, g_pos, g_neg = shared_pool_coeffs(
            e_in, e_pos, Z, contexts, negatives, mask, alpha,
            num_negatives, sigmoid_mode, logits_dtype,
            fused=fused, bf16_chain=bf16_chain)

    if duplicate_scaling:
        cnt0 = jnp.zeros(V, jnp.float32).at[centers].add(mask)
        cnt1 = jnp.zeros(V, jnp.float32).at[contexts].add(mask)
        in_scale = 1.0 / jnp.maximum(cnt0[centers], 1.0)
        g_pos_in = g_pos * in_scale
        # keep the [B, P] chain in logits_dtype (bf16 x f32 would promote and
        # materialize the f32 array this option exists to avoid); 1/count is safe
        g_neg_in = g_neg * in_scale[:, None].astype(logits_dtype)
        g_pos_out = g_pos / jnp.maximum(cnt1[contexts], 1.0)
        # pool row p: mean over its contributing pairs, then divided by how many
        # pool slots hold the same word (their scatter-adds would otherwise sum)
        pool_mult = jnp.zeros(V, jnp.float32).at[negatives].add(1.0)[negatives]
        z_scale = 1.0 / (jnp.maximum(neg_valid.sum(axis=0, dtype=jnp.float32), 1.0)
                         * pool_mult)
    else:
        g_pos_in, g_neg_in, g_pos_out = g_pos, g_neg, g_pos
        z_scale = None

    with jax.named_scope("sgns.pool_matmul"):
        gp_in = g_pos_in[:, None].astype(compute_dtype)
        gn_in = g_neg_in.astype(compute_dtype)
        gn = g_neg.astype(compute_dtype)
        d_in = gp_in * e_pos + gn_in @ Z                     # [B, D] — MXU
        d_pos = g_pos_out[:, None].astype(compute_dtype) * e_in
        d_Z = gn.T @ e_in                                    # [P, D] — MXU
    if z_scale is not None:
        d_Z = d_Z * z_scale[:, None].astype(compute_dtype)
    if stabilizers is not None and stabilizers.update_clip:
        d_in = clip_update_rows(d_in, stabilizers.update_clip)
        d_pos = clip_update_rows(d_pos, stabilizers.update_clip)

    dtype = syn0.dtype
    subword_rows = subword_slots = syn0_slots = None
    with jax.named_scope("sgns.scatter_syn0"):
        if subword is not None:
            new_syn0 = sw.scatter_center_updates(
                syn0, centers, d_in, sw_table, sw_shape, sw_plan)
            syn0_rows = jnp.where(sw_plan.fits, sw_plan.heads,
                                  centers.shape[0]).astype(jnp.float32)
            subword_rows = sw_plan.live_rows
            subword_slots = sw.scatter_slots(sw_plan, sw_shape)
        elif center_runs is None:
            new_syn0 = syn0.at[centers].add(d_in.astype(dtype))
            syn0_rows = syn0_slots = jnp.float32(centers.shape[0])
        else:
            new_syn0, syn0_rows, syn0_slots = scatter_add_by_runs(
                syn0, centers, d_in, *center_runs, plan=plan0)
    with jax.named_scope("sgns.scatter_syn1"):
        if context_runs is None:
            new_syn1 = syn1.at[contexts].add(d_pos.astype(dtype))
            syn1_rows = syn1_slots = jnp.float32(contexts.shape[0])
        else:
            # a conditional updates its table in place only where every read
            # of that table is ordered before it. d_pos does not depend on the
            # pool rows' gather (Z), so tie the two: left unordered, XLA
            # copies syn1 into the branch and back, 14 ms a step at V = 3M
            # (PERF.md §6, PR 30)
            d_pos, _ = jax.lax.optimization_barrier((d_pos, Z))
            new_syn1, syn1_rows, syn1_slots = scatter_add_by_runs(
                syn1, contexts, d_pos, *context_runs, sort=True, plan=plan1)
        new_syn1 = new_syn1.at[negatives].add(d_Z.astype(dtype))
    if stabilizers is not None and stabilizers.post_pass:
        enable = (mask.sum() > 0).astype(jnp.float32)
        new_syn0 = stabilize_rows(
            new_syn0, _mask_sentinel(centers, mask, V), alpha,
            stabilizers, enable)
        idx1 = jnp.concatenate(
            [_mask_sentinel(contexts, mask, V), negatives])
        new_syn1 = stabilize_rows(new_syn1, idx1, alpha, stabilizers, enable)

    if with_metrics:
        denom = jnp.maximum(mask.sum(), 1.0)
        loss_num, fpos_num = shared_pool_loss_terms(
            f_pos, f_neg, neg_valid, mask, num_negatives)
        loss = loss_num / denom
        mean_f_pos = fpos_num / denom
    else:
        loss = mean_f_pos = jnp.float32(0.0)
    metrics = StepMetrics(
        loss=loss,
        mean_f_pos=mean_f_pos,
        pairs=mask.sum(),
        syn0_rows=syn0_rows,
        syn1_rows=syn1_rows,
        subword_rows=subword_rows,
        subword_slots=subword_slots,
        assembly_rows=assembly_rows,
        syn0_slots=syn0_slots,
        # the subword step reports its lists' slots (above), not its tables'
        syn1_slots=None if subword is not None else syn1_slots,
    )
    return EmbeddingPair(new_syn0, new_syn1), metrics


def cbow_step(
    params: EmbeddingPair,
    centers: jax.Array,     # int32 [B] — predicted (output) words
    contexts: jax.Array,    # int32 [B, C] — context window, padded
    ctx_mask: jax.Array,    # float32 [B, C]
    mask: jax.Array,        # float32 [B]
    key: jax.Array,
    alpha: jax.Array,
    table: AliasTable,
    num_negatives: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    duplicate_scaling: bool = False,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """CBOW variant (BASELINE config 5): input = mean of context vectors, output = center.

    hidden = mean_c syn0[context_c]; positives are the centers, negatives sampled per
    example. Context-vector gradients are the hidden gradient divided equally (mean
    convention), scattered back to every context position.
    """
    negatives = sample_negatives(table, key, (centers.shape[0], num_negatives))
    return cbow_step_core(params, centers, contexts, ctx_mask, mask, negatives, alpha,
                          sigmoid_mode, compute_dtype, duplicate_scaling)


def cbow_step_core(
    params: EmbeddingPair,
    centers: jax.Array,     # int32 [B]
    contexts: jax.Array,    # int32 [B, C]
    ctx_mask: jax.Array,    # float32 [B, C]
    mask: jax.Array,        # float32 [B]
    negatives: jax.Array,   # int32 [B, n] — pre-drawn
    alpha: jax.Array,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    duplicate_scaling: bool = False,
    stabilizers: Optional[Stabilizers] = None,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """:func:`cbow_step` with the negatives supplied by the caller (see
    :func:`sgns_step_core` for why sampling lives outside the jitted scan).

    ``stabilizers``: ``update_clip`` caps the per-example d_hidden (before the
    mean-convention split into per-context rows — so the banded formulation
    applies the identical clipped quantity), d_out, and per-example d_neg
    rows; the post pass clamps/decays syn0 at the live context slots and syn1
    at the live centers plus the negatives of unmasked examples."""
    syn0, syn1 = params.syn0, params.syn1
    B, C = contexts.shape
    neg_valid = (negatives != centers[:, None]).astype(jnp.float32) * mask[:, None]

    e_ctx = syn0[contexts].astype(compute_dtype)                      # [B, C, D]
    ctx_m = ctx_mask.astype(compute_dtype)[..., None]
    ctx_n = jnp.maximum(ctx_mask.sum(axis=-1), 1.0).astype(compute_dtype)  # [B]
    hidden = (e_ctx * ctx_m).sum(axis=1) / ctx_n[:, None]             # [B, D]

    e_out = syn1[centers].astype(compute_dtype)                       # [B, D]
    e_neg = syn1[negatives].astype(compute_dtype)                     # [B, n, D]
    f_pos = jnp.sum(hidden * e_out, axis=-1).astype(jnp.float32)
    f_neg = jnp.einsum("bd,bnd->bn", hidden, e_neg).astype(jnp.float32)

    has_ctx = (ctx_mask.sum(axis=-1) > 0).astype(jnp.float32)
    g_pos = (1.0 - _sigmoid(f_pos, sigmoid_mode)) * alpha * mask * has_ctx
    g_neg = (0.0 - _sigmoid(f_neg, sigmoid_mode)) * alpha * neg_valid * has_ctx[:, None]

    V = syn0.shape[0]
    live_ctx = ctx_mask * (mask * has_ctx)[:, None]
    if duplicate_scaling:
        cnt0 = jnp.zeros(V, jnp.float32).at[contexts.reshape(-1)].add(
            live_ctx.reshape(-1))
        cnt1 = (jnp.zeros(V, jnp.float32).at[centers].add(mask * has_ctx)
                .at[negatives.reshape(-1)].add(
                    (neg_valid * has_ctx[:, None]).reshape(-1)))
        ctx_scale = (1.0 / jnp.maximum(cnt0[contexts], 1.0)).astype(compute_dtype)
        g_pos_out = g_pos / jnp.maximum(cnt1[centers], 1.0)
        g_neg_out = g_neg / jnp.maximum(cnt1[negatives], 1.0)
    else:
        ctx_scale = jnp.ones_like(contexts, compute_dtype)
        g_pos_out, g_neg_out = g_pos, g_neg

    gp = g_pos[:, None].astype(compute_dtype)
    d_hidden = gp * e_out + jnp.einsum("bn,bnd->bd", g_neg.astype(compute_dtype), e_neg)
    d_out = g_pos_out[:, None].astype(compute_dtype) * hidden
    d_neg = g_neg_out[..., None].astype(compute_dtype) * hidden[:, None, :]
    if stabilizers is not None and stabilizers.update_clip:
        d_hidden = clip_update_rows(d_hidden, stabilizers.update_clip)
        d_out = clip_update_rows(d_out, stabilizers.update_clip)
        d_neg = clip_update_rows(d_neg, stabilizers.update_clip)
    # mean convention: each context word gets d_hidden / |context|
    d_ctx = (d_hidden / ctx_n[:, None])[:, None, :] * ctx_m * ctx_scale[..., None]

    dtype = syn0.dtype
    D = syn0.shape[1]
    new_syn0 = syn0.at[contexts.reshape(-1)].add(d_ctx.reshape(-1, D).astype(dtype))
    new_syn1 = syn1.at[centers].add(d_out.astype(dtype))
    new_syn1 = new_syn1.at[negatives.reshape(-1)].add(d_neg.reshape(-1, D).astype(dtype))
    if stabilizers is not None and stabilizers.post_pass:
        enable = (mask.sum() > 0).astype(jnp.float32)
        new_syn0 = stabilize_rows(
            new_syn0,
            _mask_sentinel(contexts, live_ctx, V).reshape(-1), alpha,
            stabilizers, enable)
        idx1 = jnp.concatenate([
            _mask_sentinel(centers, mask * has_ctx, V),
            _mask_sentinel(negatives,
                           jnp.broadcast_to(mask[:, None], negatives.shape),
                           V).reshape(-1)])
        new_syn1 = stabilize_rows(new_syn1, idx1, alpha, stabilizers, enable)

    denom = jnp.maximum((mask * has_ctx).sum(), 1.0)
    neg_live = neg_valid * has_ctx[:, None]
    loss = (-_log_sigmoid(f_pos) * mask * has_ctx
            - jnp.sum(_log_sigmoid(-f_neg) * neg_live, axis=-1)).sum() / denom
    metrics = StepMetrics(
        loss=loss,
        mean_f_pos=(f_pos * mask * has_ctx).sum() / denom,
        pairs=(mask * has_ctx).sum(),
    )
    return EmbeddingPair(new_syn0, new_syn1), metrics


def cbow_step_shared_core(
    params: EmbeddingPair,
    centers: jax.Array,     # int32 [B]
    contexts: jax.Array,    # int32 [B, C]
    ctx_mask: jax.Array,    # float32 [B, C]
    mask: jax.Array,        # float32 [B]
    negatives: jax.Array,   # int32 [P] — pre-drawn shared pool
    alpha: jax.Array,
    num_negatives: int,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    logits_dtype: jnp.dtype = jnp.float32,
    with_metrics: bool = True,
    stabilizers: Optional[Stabilizers] = None,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """CBOW with a batch-shared negative pool — the CBOW analog of
    :func:`sgns_step_shared_core` (same estimator: each negative term reweighted by
    ``num_negatives / pool`` so the expected gradient matches per-example sampling;
    pool entries equal to an example's center are masked). All negative compute rides
    the MXU: ``f_neg = hidden @ Zᵀ`` and ``dZ = g_negᵀ @ hidden``. ``logits_dtype``
    and ``with_metrics`` as in :func:`sgns_step_shared_core` (the [B, P] chain /
    the trainer's metrics-elided fast twin). ``stabilizers``: clips d_hidden
    (pre mean-split, so the banded formulation matches) and d_out, never d_Z;
    post pass over the live context slots, live centers, and the whole pool."""
    syn0, syn1 = params.syn0, params.syn1
    P = negatives.shape[0]
    neg_valid = (negatives[None, :] != centers[:, None]).astype(logits_dtype) \
        * mask[:, None].astype(logits_dtype)

    # named scopes are metadata for a profile's reader (docs/observability.md
    # §4), the same five on both CBOW step forms; the compiled step is the
    # same program without them (tested)
    with jax.named_scope("cbow.gather"):
        e_ctx = syn0[contexts].astype(compute_dtype)                  # [B, C, D]
    with jax.named_scope("cbow.context_sum"):
        ctx_m = ctx_mask.astype(compute_dtype)[..., None]
        ctx_n = jnp.maximum(ctx_mask.sum(axis=-1), 1.0).astype(compute_dtype)  # [B]
        hidden = (e_ctx * ctx_m).sum(axis=1) / ctx_n[:, None]         # [B, D]

    with jax.named_scope("cbow.gather"):
        e_out = syn1[centers].astype(compute_dtype)                   # [B, D]
        Z = syn1[negatives].astype(compute_dtype)                     # [P, D]
    with jax.named_scope("cbow.pool_matmul"):
        f_pos = jnp.sum(hidden * e_out, axis=-1).astype(jnp.float32)
        f_neg = (hidden @ Z.T).astype(logits_dtype)                   # [B, P] — MXU

    has_ctx = (ctx_mask.sum(axis=-1) > 0).astype(jnp.float32)
    g_pos = (1.0 - _sigmoid(f_pos, sigmoid_mode)) * alpha * mask * has_ctx
    g_neg = ((0.0 - _sigmoid(f_neg, sigmoid_mode))
             * jnp.asarray(alpha, logits_dtype) * neg_valid
             * has_ctx[:, None].astype(logits_dtype)
             * jnp.asarray(num_negatives / P, logits_dtype))

    with jax.named_scope("cbow.pool_matmul"):
        gp = g_pos[:, None].astype(compute_dtype)
        gn = g_neg.astype(compute_dtype)
        d_hidden = gp * e_out + gn @ Z                                # [B, D] — MXU
        d_out = gp * hidden
        d_Z = gn.T @ hidden                                           # [P, D] — MXU
    if stabilizers is not None and stabilizers.update_clip:
        d_hidden = clip_update_rows(d_hidden, stabilizers.update_clip)
        d_out = clip_update_rows(d_out, stabilizers.update_clip)
    with jax.named_scope("cbow.context_sum"):
        # mean convention: each context word gets d_hidden / |context|
        d_ctx = (d_hidden / ctx_n[:, None])[:, None, :] * ctx_m

    dtype = syn0.dtype
    D = syn0.shape[1]
    with jax.named_scope("cbow.scatter_syn0"):
        new_syn0 = syn0.at[contexts.reshape(-1)].add(
            d_ctx.reshape(-1, D).astype(dtype))
    with jax.named_scope("cbow.scatter_syn1"):
        new_syn1 = syn1.at[centers].add(d_out.astype(dtype))
        new_syn1 = new_syn1.at[negatives].add(d_Z.astype(dtype))
    if stabilizers is not None and stabilizers.post_pass:
        V = syn0.shape[0]
        enable = (mask.sum() > 0).astype(jnp.float32)
        live_ctx = ctx_mask * (mask * has_ctx)[:, None]
        new_syn0 = stabilize_rows(
            new_syn0,
            _mask_sentinel(contexts, live_ctx, V).reshape(-1), alpha,
            stabilizers, enable)
        idx1 = jnp.concatenate(
            [_mask_sentinel(centers, mask * has_ctx, V), negatives])
        new_syn1 = stabilize_rows(new_syn1, idx1, alpha, stabilizers, enable)

    if with_metrics:
        denom = jnp.maximum((mask * has_ctx).sum(), 1.0)
        loss = (-_log_sigmoid(f_pos) * mask * has_ctx
                - jnp.sum(_log_sigmoid(-f_neg) * neg_valid
                          * has_ctx[:, None].astype(logits_dtype), axis=-1,
                          dtype=jnp.float32)
                * (num_negatives / P)).sum() / denom
        mean_f_pos = (f_pos * mask * has_ctx).sum() / denom
    else:
        loss = mean_f_pos = jnp.float32(0.0)
    metrics = StepMetrics(
        loss=loss,
        mean_f_pos=mean_f_pos,
        pairs=(mask * has_ctx).sum(),
    )
    return EmbeddingPair(new_syn0, new_syn1), metrics


def alpha_schedule(
    words_processed,
    total_words: float,
    learning_rate: float,
    min_alpha_factor: float = 1e-4,
):
    """Linear lr decay with floor — the reference's schedule (mllib:405-413):
    ``alpha = lr · (1 − words_processed/total)``, floored at ``lr · 1e-4``, where
    ``total = numIterations · trainWordsCount + 1`` and words_processed is the global clock
    (the reference approximates it as ``numPartitions · wordCount_partition + prior_iters``).
    Works on Python floats and jnp scalars alike.
    """
    progress = words_processed / total_words
    alpha = learning_rate * (1.0 - progress)
    floor = learning_rate * min_alpha_factor
    if isinstance(alpha, (float, int)):
        return max(float(alpha), floor)
    return jnp.maximum(alpha, floor)
