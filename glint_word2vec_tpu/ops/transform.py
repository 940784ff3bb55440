"""The slide: the device programs of the read side's sentence operations.

One pass of one slide of ``Word2VecModel.transform_sentences``
(:func:`_segment_means`; over a table partitioned by rows its body under
``shard_map``, :func:`_sharded_segment_sums`) and of ``sentence_vectors``
(:func:`_sentence_means`), each ONE jitted program of fixed shapes. The
model's host halves (``models/word2vec.py``: ``_transform_begin``,
``_sentvec_begin``) encode the slide, derive its capacities and take the
spans; what is traced lives here. Imports: ``jax`` alone
(tests/test_read_layers.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _owned_block(block: jax.Array, ids: jax.Array, axis: str) -> jax.Array:
    """Inside ``shard_map`` over ``axis``: rows ``ids`` (GLOBAL rows) of the
    table whose ``[R, lanes]`` ``block`` this shard holds, gathered from the
    block in place; an id another shard owns, and a fill id past the padded
    rows, is moved out of the block's range and reads zeros (``mode="fill"``:
    a select over the ``[rows]`` indices, no mask over a ``[rows, lanes]``
    block)."""
    per = block.shape[0]
    at = ids - jax.lax.axis_index(axis) * per
    return block.at[jnp.where((at >= 0) & (at < per), at, per)].get(
        mode="fill", fill_value=0)


def _sharded_segment_sums(shards: NamedSharding, table: jax.Array,
                          ids: jax.Array, seg: jax.Array,
                          segments: int) -> jax.Array:
    """Rows ``ids`` (GLOBAL rows) of a table partitioned by rows (``shards``:
    its sharding, ops/scan._row_shards) summed into the sentences ``seg``
    names, ``[segments, lanes]`` float32 on every chip: the body runs under
    ``shard_map`` over the axis that partitions the rows (the parameter
    servers' ``pullAverage``: every server sums the rows IT holds). Shard j
    gathers the ids it owns from its own block (:func:`_owned_block`), sums
    them by sentence with the one-chip program's sorted ``segment_sum``, and
    ONE ``psum`` over the axis adds the ``[segments, lanes]`` partial sums.
    The table never moves and nothing R wide crosses a chip. Ids and
    sentence ids are replicated; along a data axis every replica does the
    whole slide."""
    axis = shards.spec[0]

    def shard(block, ids, seg):
        with jax.named_scope("transform.owner_gather"):
            rows = _owned_block(block, ids, axis)
            rows = rows.astype(jnp.promote_types(rows.dtype, jnp.float32))
        with jax.named_scope("transform.segment_mean"):
            partial_sums = jax.ops.segment_sum(
                rows, seg, num_segments=segments, indices_are_sorted=True)
        with jax.named_scope("transform.partial_sum"):
            return jax.lax.psum(partial_sums, axis)

    return jax.shard_map(shard, mesh=shards.mesh,
                         in_specs=(P(axis, None), P(), P()),
                         out_specs=P())(table, ids, seg)


@partial(jax.jit, static_argnames=("dim", "shards"))
def _sharded_rows(table: jax.Array, ids: jax.Array, dim: int,
                  shards: NamedSharding) -> jax.Array:
    """Rows ``ids`` of a table partitioned by rows, ``[len(ids), dim]`` in
    the table's dtype, replicated: every shard reads the ids it owns from
    its block in place (:func:`_owned_block`) and one ``psum`` of the
    ``[len(ids), lanes]`` block adds them. Exact: one addend of each row is
    not zero. (The scan's ops/scan._owner_rows does the same for its 64 query
    rows by one-row slices of the 300-wide block, unrolled one a slot: it
    must not build the whole-lane form, 3.84 GB a chip that a model which
    only answers ``find_synonyms*`` never holds, and a ``pull`` of thousands
    of ids cannot be unrolled.)"""
    axis = shards.spec[0]

    def shard(block, ids):
        with jax.named_scope("transform.owner_gather"):
            return jax.lax.psum(_owned_block(block, ids, axis), axis)

    return jax.shard_map(shard, mesh=shards.mesh, in_specs=(P(axis, None), P()),
                         out_specs=P())(table, ids)[:, :dim]


@partial(jax.jit, static_argnames=("segments", "dim", "shards"))
def _segment_means(table: jax.Array, ids: jax.Array, seg: jax.Array,
                   counts: Optional[jax.Array], carried: Optional[jax.Array],
                   segments: int, dim: int,
                   shards: Optional[NamedSharding] = None) -> jax.Array:
    """One pass of a transform slide, ONE program: rows ``ids`` of ``table``
    (an id past its rows reads zeros) summed into the sentences ``seg`` names
    (ascending, as the slide's ids lie; one past ``segments`` is dropped),
    on top of the sums ``carried`` from the pass before. The last pass is
    handed the sentences' ``counts`` and returns their means ``[segments,
    dim]`` float32 (zeros where the count is 0); a pass before it returns the
    sums at the table's width. The sums are taken in float32 (a wider
    table's in its own precision). On the TPU the gather is fused into the
    sorted scatter-add: the ``[rows, lanes]`` block is never written. Over a
    table partitioned by rows (``shards``), the sums are
    :func:`_sharded_segment_sums`' and what follows them is replicated."""
    if shards:
        sums = _sharded_segment_sums(shards, table, ids, seg, segments)
    else:
        with jax.named_scope("transform.gather"):
            rows = table.at[ids].get(mode="fill", fill_value=0)
            rows = rows.astype(jnp.promote_types(rows.dtype, jnp.float32))
        with jax.named_scope("transform.segment_mean"):
            sums = jax.ops.segment_sum(rows, seg, num_segments=segments,
                                       indices_are_sorted=True)
    with jax.named_scope("transform.segment_mean"):
        if carried is not None:
            sums = sums + carried
        if counts is None:
            return sums
        return (sums[:, :dim] / jnp.maximum(counts, 1)[:, None].astype(
            sums.dtype)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("segments", "dim"))
def _sentence_means(table: jax.Array, scale: jax.Array, ids: jax.Array,
                    seg: jax.Array, lists: Optional[tuple],
                    counts: Optional[jax.Array], carried: Optional[tuple],
                    segments: int, dim: int):
    """One pass of a ``sentence_vectors`` slide, ONE program, a two-level
    ragged reduction. Words, as :func:`_segment_means`: rows ``ids`` of
    ``table``, each times its slot of ``scale`` (``[len(ids)]``, 1 / the
    row's norm taken by the host's encode: a unit vector; the program
    gathers rows and nothing else), summed into the sentences ``seg``
    names. Composed tokens, where ``lists``
    is handed over (``buckets``, ``rows``, ``token``, ``token_seg``): rows
    ``rows`` of ``buckets`` (one past them reads zeros) summed into the
    tokens ``token`` names (ascending: a token's rows lie together; one past
    the token capacity is dropped), every token's sum divided by its own
    norm (h / |h| whatever |G| divided the mean by; a sum of zero norm is
    left out), the unit vectors summed into the sentences ``token_seg``
    names and the tokens kept counted there. ``carried``: the sums and that
    count from the pass before. The last pass is handed the sentences'
    ``counts`` of words and returns the means ``[segments, dim]`` float32
    over words and kept tokens together (zeros where there are none); a pass
    before it returns (sums, kept). Sums, norms and the division in float32
    (a wider table's in its own precision)."""
    with jax.named_scope("transform.gather"):
        rows = table.at[ids].get(mode="fill", fill_value=0)
        acc = jnp.promote_types(rows.dtype, jnp.float32)
        unit = rows.astype(acc) * scale.astype(acc)[:, None]
    with jax.named_scope("transform.segment_mean"):
        sums = jax.ops.segment_sum(unit, seg, num_segments=segments,
                                   indices_are_sorted=True)
    kept = None
    if lists is not None:
        buckets, list_rows, token, token_seg = lists
        with jax.named_scope("transform.list_gather"):
            listed = buckets.at[list_rows].get(mode="fill", fill_value=0).astype(acc)
        with jax.named_scope("transform.compose"):
            h = jax.ops.segment_sum(
                listed, token, num_segments=token_seg.shape[0],
                indices_are_sorted=True)[:, :sums.shape[1]]
            norm = jnp.sqrt((h * h).sum(axis=1))
            live = norm > 0
            h = jnp.where(live[:, None], h / jnp.where(live, norm, 1)[:, None], 0)
        with jax.named_scope("transform.segment_mean"):
            sums = sums + jax.ops.segment_sum(
                h.astype(acc), token_seg, num_segments=segments,
                indices_are_sorted=True)
            kept = jax.ops.segment_sum(
                live.astype(jnp.int32), token_seg, num_segments=segments,
                indices_are_sorted=True)
    if carried is not None:
        sums = sums + carried[0]
        if kept is not None:
            kept = kept + carried[1]
    if counts is None:
        return sums, kept
    if kept is not None:
        counts = counts + kept
    return (sums[:, :dim] / jnp.maximum(counts, 1)[:, None].astype(
        sums.dtype)).astype(jnp.float32)
