"""The subword row source of the skip-gram step's center side.

Where the model is subword (config.subword; Bojanowski et al. 2017), a
center word's input vector is the mean of the rows its list names
(data/subword.py: its own row and its hashed n-gram rows, 1 to ~40 of them)
and its update is spread back over them:

    h_w = (1/|G(w)|) Σ_{r ∈ G(w)} syn0[r]        syn0[r] += d_h / |G(w)|

:func:`sgns_step_shared_core` stays one body; this module makes its ``e_in``
and applies its ``d_in``. The work is done once per DISTINCT CENTER WORD of
the batch, not per pair and not per center run. The pair feed emits a
center's pairs consecutively (~0.26 runs a pair at window 5), and a batch's
center tokens repeat their words (58% of them distinct at the published
shape), so a pair's own ~19 listed rows are ~5 a pair per run and ~3.7 per
word. Two levels of heads:

- the run heads are compacted to a static capacity as
  :func:`..ops.sgns.scatter_add_by_runs` compacts them (``head_cap``);
- the compacted run heads are sorted by word inside the step (a stable 1-D
  sort that carries their rank), a word's heads are cut every ``word_run``
  into pieces, and the pieces' heads are compacted to ``word_cap``. A word
  with more heads than ``word_run`` has several pieces that list the same
  rows again; the scatter-add sums them.

Every word head's list is read as one block padded to the longest list
([word_cap, max_groups · 8] row ids; lists are stored in groups of
:data:`GROUP` rows, padding slots out of bounds), gathered, summed and
divided, and handed to every pair through one composed index (pair → run
head → word head): the values are the per-run form's bit for bit. ``d_in`` is
summed per run (:func:`run_sums`), the run sums are read in word order,
summed again per piece and divided; the block's slots are sorted by row
inside the step and scattered once, each with its head's update row (XLA
sorts a scatter's indices itself only above an eighth of the table's rows:
:func:`scatter_center_updates`).

The block padded per head, and not a batch's lists laid end to end under a
second capacity: the chip read 78.1 ms a step for the padded form (per run)
and 66.5 for the flat one (58.3 with no room in its capacity), where ISSUE
31 asked the flat form to come in under half before its index work (a
search over group ends, a second run sum, a second overflow case) was worth
having (PERF.md §6, PR 31: XLA sorts this form's scatter indices, so its
padding rows cost it little). What was built of it instead (PR 47) is the
flat form's capacity without its index work: the padded block's 491,520 slots
are sorted by row inside the step anyway, NO_ROW sorts last, so the ~241,000
live ones are a prefix, and the scatter takes the first ``slot_cap`` = 278,528
of them (the trainer derives it from the counts and the lists' lengths:
train/trainer.py ``_word_slot_cap``; 2,000 feed batches hold 235,178-247,787
live). On the chip a step reads 52.31 ms whole, 48.54 cut (17.7 ns a padding
slot gone; 48.70 at 286,720) and 48.29 with the cut slots scattered WITHOUT
``indices_are_sorted``, which is what runs: under an eighth of the table's
rows XLA takes every slot at one price, and with the padding gone that price
is the lower (PERF.md §6, PR 47). A batch with more live slots than the
capacity takes the whole form, the fourth entry of the scatter's switch: a
conditional or a loop of passes inside the per-word entry made the TPU's
compiler copy the table in the plain entry's scan, 6.46 GB it has no room for.

Three forms, chosen by the step from its own batch. More word pieces than
``word_cap`` (a batch whose centers hardly repeat): the per-RUN form, a run
head's list once a run, at that form's cost plus the sort of the heads. More
center runs than ``head_cap`` (centers that all differ): the plain form,
every pair its own list, in chunks of pairs under ``lax.map`` /
``lax.scan``, so that no [B, G, D] block is ever made. Same sums, same rows.
``word_cap`` 0 (the trainer's rule found no saving; a CBOW token block)
builds the last two alone, as before there were three. The per-word form's
scatter has two entries where ``slot_cap`` is set beside ``word_cap``: cut to
the capacity, and whole for a batch over it.

A CBOW token block (ops/cbow_banded.py; ``max_run`` 1 with room for every
token slot, so the per-run form alone is built) has no runs and no word
level: every token slot reads its own word's list, [T, max_groups · 8] slots
of which two thirds are padding at cc.en.300's shape (1,048,736 slots, ~330,000
live). Its scatter takes ``slot_cap`` where the trainer derives one from the
counts: the block's (row, slot) keys sorted inside the step (NO_ROW sorts
last, so the live slots are a prefix), the first ``slot_cap`` of them
scattered with ``indices_are_sorted``, each with its token's update row read
in that order. A sorted scatter's padding slot costs ~19.6 ns on the chip and
a live one ~82: 28.2 ms a step at 393,216 slots where all 1,048,736 cost 41.7
and the broadcast block 3.0 more (PERF.md §6, PR 36). A block with more live
slots than the capacity (``plan.live_rows`` counts them) takes the whole form
under one ``lax.cond``: same rows, same sums.

Its gather takes ``tail_cap`` where the trainer derives one. The sum needs
token order, so the scatter's sort cannot serve it; but a list's FIRST group of
8 rows holds nearly all that is live (n-grams of 5: only a word of 10 letters
or more has a second, 5% of the kept tokens), so every token's first group is
gathered densely ([T, 8] slots) and summed, and the tokens with later groups
(``plan.tails`` counts them) are compacted as the heads are, their later
groups' row ids read from the block, gathered as a second small block
([tail_cap, (max_groups - 1) · 8] slots), summed, and added into their tokens'
sums by a scatter-add of sorted rows, before the one division by |G(w)|:
557,136 slots handed to the gather where 1,048,736 were, 7.5 ms of a 63.7 ms
step (PERF.md §6, PR 43). The second block sits in a loop of dynamic trip
count, ``tail_cap`` tokens a pass: one pass where the trainer's rule held,
another for a block with more, so no block has a whole form of its own and no
second branch is built (the loop came in 0.05 ms under the same block beside a
whole-gather branch under ``lax.cond``, and 0.4 under passes of 2,048).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.data.subword import GROUP, NO_ROW
from glint_word2vec_tpu.parallel.mesh import pad_dim_to_lanes

# pairs in a chunk of the plain form: [chunk, max_groups · 8, D] float32 is
# 31 MB at 512 pairs, 5 groups, D = 384
_PLAIN_CHUNK = 512


class SubwordTable(NamedTuple):
    """data/subword.SubwordRows on the device (jit ARGUMENTS, never closure
    constants: ops/prng.py has the reason)."""

    offsets: jax.Array   # int32 [V + 2]
    rows: jax.Array      # int32 [N, GROUP]
    counts: jax.Array    # int32 [V + 1]


class SubwordShape(NamedTuple):
    """What the step needs to know at trace time."""

    max_groups: int      # groups of the longest list
    max_run: int         # center runs are cut every max_run pairs
    head_cap: int        # center runs of a batch the per-run form holds
    word_run: int = 1    # a word's run heads are cut every word_run into pieces
    word_cap: int = 0    # word pieces of a batch the per-word form holds; 0: not built
    # slots the list scatter takes, sorted by row and cut to their live
    # prefix: of the word heads' block where word_cap is set (the subword
    # skip-gram step), else of the run heads' (a CBOW token block); 0: not
    # built. The trainer derives either from the counts and the lists' lengths
    slot_cap: int = 0
    # heads whose later groups a pass of the per-run form's gather reads as a
    # second block, beside every head's first group; 0: not built, the gather
    # reads the whole block (the trainer sets it for a CBOW token block; it
    # is not read beside word_cap)
    tail_cap: int = 0


# CenterPlan.form: which of the three forms the batch takes
PLAIN, PER_RUN, PER_WORD = 0, 1, 2


class WordPlan(NamedTuple):
    """The second level of one batch's index work: the run heads by word."""

    src: jax.Array       # int32 [H] batch position of each run head, in word order
    pos: jax.Array       # int32 [H] position of each run head in its word's piece
    head: jax.Array      # int32 [W] where (in word order) each piece starts
    pair_head: jax.Array  # int32 [B] which word head (by rank) a pair belongs to
    pieces: jax.Array    # int32: word pieces of the batch


class CenterPlan(NamedTuple):
    """One batch's index work, shared by the gather and the scatter."""

    fits: jax.Array      # bool: the batch's center runs are inside head_cap
    pos: jax.Array       # int32 [B] position of each pair in its center run
    pair_head: jax.Array  # int32 [B] which head (by rank) a pair belongs to
    src: jax.Array       # int32 [H] batch position of each head
    rows: jax.Array      # int32 [H, max_groups · GROUP] syn0 rows, NO_ROW where none
    inv: jax.Array       # float32 [H] 1 / |G(head word)|, 0 past the heads
    heads: jax.Array     # int32: center runs of the batch (its word pieces, PER_WORD)
    live_rows: jax.Array  # float32: rows with a live index the scatter gets
    # where shape.word_cap is set: the form the batch takes (PLAIN, PER_RUN,
    # PER_WORD), and the run heads by word; ``rows`` / ``inv`` then hold the
    # lists of that form's heads (the first word_cap of them per word)
    form: Optional[jax.Array] = None
    words: Optional[WordPlan] = None
    # where shape.tail_cap is set (and lists have more than one group): int32,
    # heads whose list has a second group
    tails: Optional[jax.Array] = None


def _acc(syn0: jax.Array):
    """The dtype the lists' sums run in: float32, or the table's if wider."""
    return jnp.promote_types(syn0.dtype, jnp.float32)


def _lists(words: jax.Array, table: SubwordTable, max_groups: int,
           dtype: jnp.dtype = jnp.float32):
    """Every word's list padded to the longest: [C, max_groups · GROUP] row
    ids (NO_ROW past the list), and 1 / |G| in ``dtype`` (0 for index V, "no
    word")."""
    lo = table.offsets[words]
    g = jnp.arange(max_groups, dtype=jnp.int32)
    has = g[None, :] < (table.offsets[words + 1] - lo)[:, None]
    rows = table.rows.at[
        jnp.where(has, lo[:, None] + g[None, :], table.rows.shape[0])
    ].get(mode="fill", fill_value=NO_ROW)
    count = table.counts[words]
    inv = jnp.where(count > 0, 1.0 / jnp.maximum(count, 1).astype(dtype), 0.0)
    return rows.reshape(words.shape[0], max_groups * GROUP), inv


def _plan_words(word: jax.Array, src: jax.Array, pair_head: jax.Array,
                shape: SubwordShape, v: int):
    """The run heads' words ([H], ``v`` past the heads) sorted, cut into
    pieces of ``word_run`` heads and compacted to ``word_cap``: the
    :class:`WordPlan` and each word head's word ([W], ``v`` past them). 1-D
    work on 24,576 entries where the batch has 65,536 pairs."""
    from glint_word2vec_tpu.ops.sgns import run_positions

    h = word.shape[0]
    at = jnp.arange(h, dtype=jnp.int32)
    keys, order = jax.lax.sort((word, at), num_keys=1, is_stable=True)
    pos = run_positions(keys, shape.word_run)
    head = (pos == 0) & (keys < v)              # the padding sorts last: no piece
    live = jnp.sort(jnp.where(head, at, h))[:shape.word_cap]
    at_head = jnp.minimum(live, h - 1)
    # a run head's word head, by the head's rank in the batch: through the
    # sort's inverse (a second sort; a scatter of 24,576 indices costs more)
    of_sorted = jnp.minimum(jnp.cumsum(head.astype(jnp.int32)) - 1,
                            shape.word_cap - 1)
    _, back = jax.lax.sort((order, at), num_keys=1)
    plan = WordPlan(src=src[order], pos=pos, head=at_head,
                    pair_head=of_sorted[back][pair_head],
                    pieces=head.sum(dtype=jnp.int32))
    return plan, jnp.where(live < h, keys[at_head], v)


def plan_centers(centers: jax.Array, table: SubwordTable,
                 shape: SubwordShape,
                 dtype: jnp.dtype = jnp.float32) -> CenterPlan:
    """Heads of the batch's center runs, compacted (as
    :func:`..ops.sgns.scatter_add_by_runs` compacts them), by word where
    ``shape.word_cap`` is set (:func:`_plan_words`), and the lists of the
    heads of the form the batch takes: 1-D index work and one small gather of
    row ids. ``dtype``: the lists' sums run in it (float32, or the tables'
    dtype if wider).

    ``shape.max_run == 1`` with room for every entry is the row source of a
    CBOW token block (ops/cbow_banded.py): a block has no runs (the same word
    recurs scattered over it), so every token slot is the head of its own
    list."""
    from glint_word2vec_tpu.ops.sgns import run_positions

    n, v = centers.shape[0], table.counts.shape[0] - 1
    hcap = shape.head_cap
    at = jnp.arange(n, dtype=jnp.int32)
    pos = run_positions(centers, shape.max_run)
    head = pos == 0
    heads = head.sum(dtype=jnp.int32)
    live = jnp.sort(jnp.where(head, at, n))[:hcap]
    src = jnp.minimum(live, n - 1)
    word = jnp.where(live < n, centers[src], v)             # v: no word
    if shape.word_cap:
        return _plan_by_word(centers, table, shape, dtype, pos, head, heads,
                             src, word)
    rows, inv = _lists(word, table, shape.max_groups, dtype)
    # every entry a head of its own and room for all of them: known while
    # tracing, so no second branch is built
    fits = (jnp.bool_(True) if shape.max_run == 1 and hcap >= n
            else heads <= hcap)
    return CenterPlan(
        fits=fits, pos=pos,
        pair_head=jnp.minimum(jnp.cumsum(head.astype(jnp.int32)) - 1, hcap - 1),
        src=src, rows=rows, inv=inv, heads=heads,
        live_rows=jnp.where(fits, table.counts[word].sum(),
                            table.counts[centers].sum()).astype(jnp.float32),
        tails=((table.counts[word] > GROUP).sum(dtype=jnp.int32)
               if shape.tail_cap and shape.max_groups > 1 else None))


def _plan_by_word(centers, table, shape, dtype, pos, head, heads, src, word):
    """:func:`plan_centers` where ``shape.word_cap`` is set: the run heads by
    word, the form the batch takes, and the lists of that form's heads."""
    v, hcap = table.counts.shape[0] - 1, shape.head_cap
    pair_head = jnp.minimum(jnp.cumsum(head.astype(jnp.int32)) - 1, hcap - 1)
    fits = heads <= hcap
    words, head_word = _plan_words(word, src, pair_head, shape, v)
    form = (fits.astype(jnp.int32)
            + (fits & (words.pieces <= shape.word_cap)).astype(jnp.int32))

    # the lists of the heads of the form that runs, and no others: reading
    # [24576, 5] groups of row ids is 2.5 ms on the chip (PERF.md §5)
    def lists_of(heads_words, pad):
        rows, inv = _lists(heads_words, table, shape.max_groups, dtype)
        return (jnp.pad(rows, ((0, pad), (0, 0)), constant_values=NO_ROW),
                jnp.pad(inv, (0, pad)), (rows != NO_ROW).sum(dtype=jnp.int32))

    def no_lists():
        return (jnp.full((hcap, shape.max_groups * GROUP), NO_ROW, jnp.int32),
                jnp.zeros((hcap,), dtype), table.counts[centers].sum(dtype=jnp.int32))

    rows, inv, live = jax.lax.switch(
        form, (no_lists, partial(lists_of, word, 0),
               partial(lists_of, head_word, hcap - shape.word_cap)))
    return CenterPlan(
        fits=fits, pos=pos, pair_head=pair_head, src=src, rows=rows, inv=inv,
        heads=jnp.where(form == PER_WORD, words.pieces, heads),
        live_rows=live.astype(jnp.float32), form=form, words=words)


def _either(plan: CenterPlan, shape: SubwordShape, n: int, per_word, per_run,
            plain, syn0: jax.Array, per_word_cut=None) -> jax.Array:
    """``per_word`` where both levels of the plan fit their capacities,
    ``per_run`` where the runs alone do, else ``plain``; only the branches
    that can run, where that is known while tracing. ``per_word_cut``: in
    ``per_word``'s place where the block's live slots fit ``slot_cap`` too."""
    if shape.max_run == 1 and shape.head_cap >= n:
        return per_run(syn0)
    if plan.words is None:
        return jax.lax.cond(plan.fits, per_run, plain, syn0)
    forms, form = (plain, per_run, per_word), plan.form
    if per_word_cut is not None:
        # a fourth entry, not a choice inside the third: under a conditional
        # nested there, or a loop of passes, the TPU's compiler copies the
        # table in every pass of the plain form's scan (PERF.md §6, PR 47)
        forms += (per_word_cut,)
        form += ((form == PER_WORD) & _cut_holds(plan, shape)).astype(jnp.int32)
    return jax.lax.switch(form, forms, syn0)


def _cut_holds(plan: CenterPlan, shape: SubwordShape) -> jax.Array:
    """bool: the live slots of the block ``slot_cap`` is the capacity of (the
    word heads' where the shape has a word level, else the run heads') fit
    it."""
    return plan.live_rows <= shape.slot_cap


def scatter_slots(plan: CenterPlan, shape: SubwordShape) -> jax.Array:
    """float32: slots the list scatter of the form the batch takes is handed,
    live or padding: ``slot_cap`` where the live slots of the block it is the
    capacity of fit it (the word heads', or the run heads' in a shape without
    a word level), else every slot of that block; every slot of the run
    heads' block beside a word level; every pair's list, plain."""
    plain = jnp.float32(plan.pos.shape[0] * shape.max_groups * GROUP)
    heads, slots = plan.rows.shape
    handed = jnp.float32((shape.word_cap or heads) * slots)
    if shape.slot_cap:
        handed = jnp.where(_cut_holds(plan, shape),
                           jnp.float32(shape.slot_cap), handed)
    if plan.form is None:
        return jnp.where(plan.fits, handed, plain)
    return jnp.select([plan.form == PER_WORD, plan.form == PER_RUN],
                      [handed, jnp.float32(plan.rows.size)], plain)


def gather_slots(plan: CenterPlan, shape: SubwordShape) -> jax.Array:
    """float32: slots the list gather of the per-run or the plain form is
    handed, live or padding: every slot of the heads' block (of every pair's
    list, plain), or, where the plan counts the heads with later groups
    (``shape.tail_cap``), every head's first group and ``tail_cap`` heads'
    later groups a pass of the loop over them."""
    heads, slots = plan.rows.shape
    handed = jnp.float32(plan.rows.size)
    if plan.tails is not None:
        passes = -(-plan.tails // shape.tail_cap)
        handed = (heads * GROUP + passes * (shape.tail_cap * (slots - GROUP))
                  ).astype(jnp.float32)
    return jnp.where(plan.fits, handed, jnp.float32(
        plan.pos.shape[0] * shape.max_groups * GROUP))


def center_vectors(syn0: jax.Array, centers: jax.Array, table: SubwordTable,
                   shape: SubwordShape, plan: CenterPlan,
                   compute_dtype: jnp.dtype) -> jax.Array:
    """``e_in`` [B, D] in ``compute_dtype``: every pair's center vector, the
    mean of its word's listed rows (sums and the count in float32, or in
    the table's dtype if wider)."""
    d, acc = syn0.shape[1], _acc(syn0)

    def sums_of(syn0, rows):
        with jax.named_scope("subword.gather"):
            got = syn0.at[rows].get(mode="fill", fill_value=0)
        with jax.named_scope("subword.mean"):
            return got.astype(acc).sum(axis=1)

    def mean_of(syn0, rows, inv):
        return (sums_of(syn0, rows) * inv[:, None]).astype(compute_dtype)

    def per_word(syn0):
        w = shape.word_cap
        return mean_of(syn0, plan.rows[:w], plan.inv[:w])[plan.words.pair_head]

    def mean_by_groups(syn0):
        """:func:`mean_of` of the heads' block in two parts: every head's
        first group, gathered densely, and the later groups of the (few)
        heads that have them, ``tail_cap`` heads a pass of a loop that runs
        while there are more (one pass where the trainer's rule held; none
        for a block without one), each pass's sums added into its heads' by
        a scatter-add of sorted rows."""
        n, c, g = plan.rows.shape[0], shape.tail_cap, shape.max_groups
        sums = sums_of(syn0, plan.rows[:, :GROUP])
        has_later = plan.rows[:, GROUP] != NO_ROW       # lists are contiguous
        heads = jnp.pad(jnp.sort(jnp.where(
            has_later, jnp.arange(n, dtype=jnp.int32), n)), (0, c), constant_values=n)
        # whole rows of the block's [H · max_groups, GROUP] view, the gather
        # the lists themselves were read by: a window of a row's columns is a
        # loop of slices to the TPU's compiler, an iteration a head
        groups = plan.rows.reshape(-1, GROUP)
        later = jnp.arange(1, g, dtype=jnp.int32)

        def one_pass(i, sums):
            at = jax.lax.dynamic_slice_in_dim(heads, i * c, c)      # n past them
            rows = groups.at[at[:, None] * g + later[None, :]].get(
                mode="fill", fill_value=NO_ROW).reshape(c, -1)
            return sums.at[at].add(sums_of(syn0, rows), mode="drop",
                                   indices_are_sorted=True, unique_indices=True)

        sums = jax.lax.fori_loop(0, -(-plan.tails // c), one_pass, sums)
        return (sums * plan.inv[:, None]).astype(compute_dtype)

    def per_run(syn0):
        mean = (mean_of(syn0, plan.rows, plan.inv) if plan.tails is None
                else mean_by_groups(syn0))
        return mean[plan.pair_head]

    def plain(syn0):
        c = math.gcd(centers.shape[0], _PLAIN_CHUNK)
        return jax.lax.map(
            lambda words: mean_of(
                syn0, *_lists(words, table, shape.max_groups, acc)),
            centers.reshape(-1, c)).reshape(-1, d)

    return _either(plan, shape, centers.shape[0], per_word, per_run, plain, syn0)


def scatter_center_updates(syn0: jax.Array, centers: jax.Array, d_in: jax.Array,
                           table: SubwordTable, shape: SubwordShape,
                           plan: CenterPlan) -> jax.Array:
    """syn0 with every pair's ``d_in`` row, divided by |G(center)|, added to
    each row of the center's list, duplicate rows summed: per run first
    (:func:`run_sums`), per word piece where the plan has them, then one
    scatter of the heads' blocks."""
    from glint_word2vec_tpu.ops.sgns import run_sums

    d, acc = syn0.shape[1], _acc(syn0)

    def spread(syn0, rows, d_h):
        return syn0.at[rows].add(
            jnp.broadcast_to(d_h.astype(syn0.dtype)[:, None, :], rows.shape + (d,)),
            mode="drop")

    def spread_sorted(syn0, rows, d_h, cut=None, told=True):
        """:func:`spread` with the slots handed over sorted by row, each with
        its head's update row read in that order: what XLA's TPU scatter makes
        of a scatter of more update rows than an eighth of the table's rows,
        and does not below that (PERF.md §6, PR 34: unsorted, 491,520 slots
        cost 95 ns each, dropped or not, 46.8 ms; sorted, the padding sorts
        last and costs little, 32.1 ms, and no broadcast block is made).
        ``cut``: the first ``cut`` sorted slots alone, for a caller who knows
        the live ones (NO_ROW sorts last) are no more. ``told`` False: the
        scatter is not told that they are sorted (the sort still finds the
        prefix): with the padding gone, at one price a slot."""
        keys, slot = jax.lax.sort(
            (rows.reshape(-1), jnp.arange(rows.size, dtype=jnp.int32)), num_keys=1)
        if cut is not None:
            keys, slot = keys[:cut], slot[:cut]
        return syn0.at[keys].add(
            d_h.astype(syn0.dtype)[slot // rows.shape[1]], mode="drop",
            indices_are_sorted=told)

    def per_word(syn0, cut=None):
        w, by = shape.word_cap, plan.words
        with jax.named_scope("subword.mean"):
            sums = run_sums(d_in, plan.pos, shape.max_run, acc)
            # the run sums in word order (one gather: the sort's order is
            # composed into the heads' batch positions), summed per piece
            sums = run_sums(sums[by.src], by.pos, shape.word_run, acc)
            d_h = sums[by.head] * plan.inv[:w, None]
        with jax.named_scope("subword.scatter"):
            # the cut slots untold: 48.29 ms a step on the chip against 48.54
            # told, and 52.31 for all 491,520 told (PERF.md §6, PR 47)
            return spread_sorted(syn0, plan.rows[:w], d_h, cut, told=cut is None)

    def per_run(syn0):
        with jax.named_scope("subword.mean"):
            sums = run_sums(d_in, plan.pos, shape.max_run, acc)
            d_h = sums[plan.src] * plan.inv[:, None]
        with jax.named_scope("subword.scatter"):
            # beside a word level the capacity is the word heads' block's
            if not shape.slot_cap or shape.word_cap:
                return spread(syn0, plan.rows, d_h)
            # a block with more live slots than the capacity takes the whole
            # form: same rows, same sums
            return jax.lax.cond(
                _cut_holds(plan, shape),
                partial(spread_sorted, cut=shape.slot_cap), spread,
                syn0, plan.rows, d_h)

    def plain(syn0):
        c = math.gcd(centers.shape[0], _PLAIN_CHUNK)

        def chunk(syn0, xs):
            words, rows_d = xs
            rows, inv = _lists(words, table, shape.max_groups, acc)
            return spread(syn0, rows, rows_d.astype(acc) * inv[:, None]), None
        return jax.lax.scan(
            chunk, syn0, (centers.reshape(-1, c), d_in.reshape(-1, c, d)))[0]

    cut = shape.slot_cap if shape.word_cap else 0
    return _either(plan, shape, centers.shape[0], per_word, per_run, plain, syn0,
                   per_word_cut=partial(per_word, cut=cut) if cut else None)


def lane_padded(rows: jax.Array) -> jax.Array:
    """``rows`` [N, D] with D widened to whole lanes of 128, zeros past D: the
    form in which the TPU gathers rows in place. It keeps a table whose D is
    no multiple of 128 column-major, and a gather from that first copies the
    whole table row-major (3.07 GB at 2,000,000 x 300, by the v5e compiler,
    every call; PERF.md §6, PR 40); one-row slices read in place but only
    unrolled, one a slot. The form lies as ``rows`` lies (one jitted pad,
    ``rows``' own sharding out): over a table partitioned by rows
    (ops/scan._row_shards) every chip widens the rows it holds and nothing
    crosses a chip."""
    extra = pad_dim_to_lanes(rows.shape[1]) - rows.shape[1]
    if not extra:
        return rows
    return jax.jit(lambda rows: jnp.pad(rows, ((0, 0), (0, extra))),
                   out_shardings=rows.sharding)(rows)


def list_vectors(buckets: jax.Array, lists: jax.Array, dim: int) -> jax.Array:
    """[Q, dim] float32: the mean of the bucket rows each row of ``lists``
    ([Q, L] ids into ``buckets``, :data:`NO_ROW` past a list) names; zeros
    for an empty list. The vector of a string the vocabulary has never seen
    (data/subword.ngram_lists), built inside the program that scans for it."""
    count = (lists != NO_ROW).sum(axis=1)
    inv = jnp.where(count > 0, 1.0 / jnp.maximum(count, 1).astype(jnp.float32), 0.0)
    return _listed_sums(buckets, lists, dim) * inv[:, None]


def _listed_sums(buckets: jax.Array, lists: jax.Array, dim: int) -> jax.Array:
    """[Q, dim] float32 sums of the rows of ``buckets`` that each row of
    ``lists`` names; an id out of bounds (:data:`NO_ROW`) adds nothing."""
    return (buckets.at[lists].get(mode="fill", fill_value=0)
            .astype(jnp.float32).sum(axis=1)[:, :dim])


@partial(jax.jit, static_argnames=("max_groups", "block"), donate_argnums=0)
def _compose_block(out: jax.Array, raw0: jax.Array, buckets: jax.Array,
                   table: SubwordTable, lo: jax.Array, max_groups: int,
                   block: int) -> jax.Array:
    """``out`` with rows ``lo .. lo + block`` composed, in place (``out`` is
    donated). A block that would pass the last word starts earlier and
    rewrites rows of the one before with the values they have. An ``out``
    wider than D (whole lanes: :func:`compose_vectors`) gets zeros past D."""
    v, d = raw0.shape
    lo = jnp.minimum(lo, v - block)
    rows, inv = _lists(lo + jnp.arange(block, dtype=jnp.int32), table, max_groups)
    # a list's first row is the word's own, and the words of a block lie side
    # by side: a slice of raw0, no gather; the rest are bucket rows
    listed = jnp.where((rows >= v) & (rows != NO_ROW), rows - v, NO_ROW)
    own = jax.lax.dynamic_slice_in_dim(raw0, lo, block)
    h = (own.astype(jnp.float32) + _listed_sums(buckets, listed, d)) * inv[:, None]
    if out.shape[1] > d:
        h = jnp.pad(h, ((0, 0), (0, out.shape[1] - d)))
    return jax.lax.dynamic_update_slice_in_dim(out, h, lo, 0)


# words in a block of the composed table's build: [block, max_groups · 8, 384]
# float32 gathered at a time (0.5 GB at 5 groups)
COMPOSE_BLOCK = 1 << 13


def compose_vectors(raw0: jax.Array, buckets: jax.Array, table: SubwordTable,
                    max_groups: int, block: int = COMPOSE_BLOCK,
                    whole_lanes: bool = False) -> jax.Array:
    """[V, D] float32: h_w of every word of the vocabulary, from a trained
    syn0 as its two parts, the words' own rows ``raw0`` [V, D] and the bucket
    rows ``buckets`` [K, D or more] (:func:`lane_padded` on a TPU), in blocks
    of words written in place into one result (the model's query table;
    models/word2vec.py). No [V + K, D] array is made: each part is read where
    it lies. ``whole_lanes``: the result is written straight at
    :func:`lane_padded`'s width, zeros past D (the form row reads gather from
    in place; a model that only reads rows never holds the [V, D] one)."""
    v, d = raw0.shape
    block = min(block, v)
    out = jnp.zeros((v, pad_dim_to_lanes(d) if whole_lanes else d), jnp.float32)
    for lo in range(0, v, block):
        out = _compose_block(out, raw0, buckets, table, jnp.int32(lo),
                             max_groups, block)
    return out


__all__: Tuple[str, ...] = (
    "SubwordTable", "SubwordShape", "CenterPlan", "WordPlan", "plan_centers",
    "center_vectors", "scatter_center_updates", "compose_vectors",
    "lane_padded", "list_vectors", "COMPOSE_BLOCK")
