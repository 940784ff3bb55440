"""The subword row source of the skip-gram step's center side.

Where the model is subword (config.subword; Bojanowski et al. 2017), a
center word's input vector is the mean of the rows its list names
(data/subword.py: its own row and its hashed n-gram rows, 1 to ~40 of them)
and its update is spread back over them:

    h_w = (1/|G(w)|) Σ_{r ∈ G(w)} syn0[r]        syn0[r] += d_h / |G(w)|

:func:`sgns_step_shared_core` stays one body; this module makes its ``e_in``
and applies its ``d_in``. The work is done once per center RUN, not per pair
(the pair feed emits a center's pairs consecutively: ~0.26 runs a pair at
window 5, so ~5 listed rows a pair where a pair's own list holds ~19): the
run heads are compacted to a static capacity as
:func:`..ops.sgns.scatter_add_by_runs` compacts them, every head's list is
read as one block padded to the longest list ([heads, max_groups · 8] row
ids; lists are stored in groups of :data:`GROUP` rows, padding slots out of
bounds), gathered, summed and divided; ``d_in`` is summed per run
(:func:`run_sums`), divided, broadcast over the block and scattered once.

The block padded per head, and not a batch's lists laid end to end under a
second capacity: the chip read 78.1 ms a step for this form and 66.5 for
the flat one (58.3 with no room in its capacity), where ISSUE 31 asked the
flat form to come in under half before its index work (a search over group
ends, a second run sum, a second overflow case) was worth having (PERF.md
§6, PR 31: XLA sorts this form's scatter indices, so its ~650,000 padding
rows cost it little).

A batch with more center runs than the capacity (centers that all differ)
takes the plain form instead: every pair its own list, in chunks of pairs
under ``lax.map`` / ``lax.scan``, so that no [B, G, D] block is ever made.
Same sums, same rows.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.data.subword import GROUP, NO_ROW

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


class CenterPlan(NamedTuple):
    """One batch's index work, shared by the gather and the scatter."""

    fits: jax.Array      # bool: the batch's center runs are inside head_cap
    pos: jax.Array       # int32 [B] position of each pair in its center run
    pair_head: jax.Array  # int32 [B] which head (by rank) a pair belongs to
    src: jax.Array       # int32 [H] batch position of each head
    rows: jax.Array      # int32 [H, max_groups · GROUP] syn0 rows, NO_ROW where none
    inv: jax.Array       # float32 [H] 1 / |G(head word)|, 0 past the heads
    heads: jax.Array     # int32: center runs of the batch
    live_rows: jax.Array  # float32: rows with a live index the scatter gets


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


def plan_centers(centers: jax.Array, table: SubwordTable,
                 shape: SubwordShape,
                 dtype: jnp.dtype = jnp.float32) -> CenterPlan:
    """Heads of the batch's center runs, compacted (as
    :func:`..ops.sgns.scatter_add_by_runs` compacts them), and each head's
    list: 1-D index work and one small gather of row ids. ``dtype``: the
    lists' sums run in it (float32, or the tables' dtype if wider).

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
                            table.counts[centers].sum()).astype(jnp.float32))


def _either(plan: CenterPlan, shape: SubwordShape, n: int, per_run, plain,
            syn0: jax.Array) -> jax.Array:
    """``per_run`` where the plan fits its capacity, else ``plain``; one
    branch alone where that is known while tracing."""
    if shape.max_run == 1 and shape.head_cap >= n:
        return per_run(syn0)
    return jax.lax.cond(plan.fits, per_run, plain, syn0)


def center_vectors(syn0: jax.Array, centers: jax.Array, table: SubwordTable,
                   shape: SubwordShape, plan: CenterPlan,
                   compute_dtype: jnp.dtype) -> jax.Array:
    """``e_in`` [B, D] in ``compute_dtype``: every pair's center vector, the
    mean of its word's listed rows (sums and the count in float32, or in
    the table's dtype if wider)."""
    d, acc = syn0.shape[1], _acc(syn0)

    def mean_of(syn0, rows, inv):
        with jax.named_scope("subword.gather"):
            got = syn0.at[rows].get(mode="fill", fill_value=0)
        with jax.named_scope("subword.mean"):
            return (got.astype(acc).sum(axis=1) * inv[:, None]
                    ).astype(compute_dtype)

    def per_run(syn0):
        return mean_of(syn0, plan.rows, plan.inv)[plan.pair_head]

    def plain(syn0):
        c = math.gcd(centers.shape[0], _PLAIN_CHUNK)
        return jax.lax.map(
            lambda words: mean_of(
                syn0, *_lists(words, table, shape.max_groups, acc)),
            centers.reshape(-1, c)).reshape(-1, d)

    return _either(plan, shape, centers.shape[0], per_run, plain, syn0)


def scatter_center_updates(syn0: jax.Array, centers: jax.Array, d_in: jax.Array,
                           table: SubwordTable, shape: SubwordShape,
                           plan: CenterPlan) -> jax.Array:
    """syn0 with every pair's ``d_in`` row, divided by |G(center)|, added to
    each row of the center's list, duplicate rows summed: per run first
    (:func:`run_sums`), then one scatter of the heads' blocks."""
    from glint_word2vec_tpu.ops.sgns import run_sums

    d, acc = syn0.shape[1], _acc(syn0)

    def spread(syn0, rows, d_h):
        return syn0.at[rows].add(
            jnp.broadcast_to(d_h.astype(syn0.dtype)[:, None, :], rows.shape + (d,)),
            mode="drop")

    def per_run(syn0):
        with jax.named_scope("subword.mean"):
            sums = run_sums(d_in, plan.pos, shape.max_run, acc)
            d_h = sums[plan.src] * plan.inv[:, None]
        with jax.named_scope("subword.scatter"):
            return spread(syn0, plan.rows, d_h)

    def plain(syn0):
        c = math.gcd(centers.shape[0], _PLAIN_CHUNK)

        def chunk(syn0, xs):
            words, rows_d = xs
            rows, inv = _lists(words, table, shape.max_groups, acc)
            return spread(syn0, rows, rows_d.astype(acc) * inv[:, None]), None
        return jax.lax.scan(
            chunk, syn0, (centers.reshape(-1, c), d_in.reshape(-1, c, d)))[0]

    return _either(plan, shape, centers.shape[0], per_run, plain, syn0)


def compose_vectors(syn0: jax.Array, table: SubwordTable, max_groups: int,
                    num_words: int, block: int = 1 << 13) -> jax.Array:
    """[num_words, D] float32: h_w of every word of the vocabulary from a
    trained syn0 ([V + K, D]), in row blocks on the device (the model's query
    table; models/word2vec.py)."""
    @jax.jit
    def rows_of(syn0, table, words):
        rows, inv = _lists(words, table, max_groups)
        return (syn0.at[rows].get(mode="fill", fill_value=0)
                .astype(jnp.float32).sum(axis=1) * inv[:, None])
    out = []
    for lo in range(0, num_words, block):
        ids = jnp.minimum(jnp.arange(lo, lo + block, dtype=jnp.int32),
                          num_words - 1)
        out.append(rows_of(syn0, table, ids)[:min(block, num_words - lo)])
    return jnp.concatenate(out)


__all__: Tuple[str, ...] = (
    "SubwordTable", "SubwordShape", "CenterPlan", "plan_centers",
    "center_vectors", "scatter_center_updates", "compose_vectors")
