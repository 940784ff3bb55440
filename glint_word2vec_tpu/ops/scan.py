"""The scan: the device programs of the read side's neighbour and analogy searches.

What ``models/word2vec.Word2VecModel`` enqueues for ``find_synonyms*``,
``analogies`` and ``analogy_accuracy``, and the one host function that chooses
among them (:func:`_topk_dispatch`: the program and the TPU's tile rule are
one decision). The model's host halves resolve words to row ids, take the
spans and fetch the results; everything that is traced lives here, as the
training steps live beside it (ops/sgns.py, ops/subword.py,
ops/cbow_banded.py, ops/hs.py) under ``train/trainer.select_step``:

- one device: :func:`_gather_topk_batch`, the query rows built in the
  program (:func:`_query_block`), one ``[Q, V]`` cosine matmul
  (:func:`_cosine_batch`) and an exact top-k in two stages
  (:func:`_two_stage_topk`);
- a table partitioned by rows over a mesh: the same entry point, its body
  under ``shard_map`` (:func:`_sharded_scan`);
- the analogy test: :func:`_analogy_topk`, the table scored in blocks of rows.

A jitted function's name is its lowered module's name
(``module @jit__gather_topk_batch``), which tests/test_subword_query.py's
digests, the ledger's ``breakdown`` and docs/observability.md hold: the names
here are the ones the programs were written under. Imports: ``jax``,
``numpy``, ``data/``, ``parallel/`` and ``ops/`` only
(tests/test_read_layers.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# what ``ids[i]`` says of a query that is no word of the vocabulary: row i of
# the vector block, or the mean of row i of the list block's bucket rows
_VECTOR, _LISTED = -1, -2


def _row_slices(table: jax.Array, at: jax.Array) -> jax.Array:
    """Rows ``at`` (in range) of ``table`` as one-row slices, stacked: each
    reads its row in place, where a gather op first copies a table whose D
    is no multiple of 128 row-major (:func:`_query_block`)."""
    return jnp.concatenate([
        jax.lax.dynamic_slice_in_dim(
            table, at[i], 1, allow_negative_indices=False)
        for i in range(at.shape[0])])


def _query_block(syn0: jax.Array, ids: jax.Array,
                 block: Optional[jax.Array],
                 buckets: Optional[jax.Array] = None,
                 lists: Optional[jax.Array] = None) -> jax.Array:
    """The [Q, D] query rows, built inside the scan's own program: row
    ``ids[i]`` of the table; or row ``i`` of ``block`` where ``ids[i]`` is
    :data:`_VECTOR` (a vector query); or, where it is :data:`_LISTED` (a
    string a subword model's vocabulary lacks), the mean of the rows of
    ``buckets`` that row ``i`` of ``lists`` names (ops/subword.list_vectors).
    ``block`` is None for a batch without vectors and ``lists`` for one
    without such strings — other traces, without those operands: an all-word
    batch's program is the one it was before there were lists. The dtype is
    what stacking the rows gave: the table's for words alone, promoted with
    the float32 of either block otherwise.

    The rows are read as Q slices, not as one gather op: the TPU keeps a
    [V, D] table whose D is no multiple of 128 column-major, and its gather
    first copies the whole table row-major (4.6 GB and a second pass over it
    at 3M × 300, by the v5e compiler), where a slice reads a row in place.
    (A table partitioned by rows over a mesh is read the same way, each
    shard its own rows: :func:`_owner_rows`.) The listed bucket rows ARE
    gathered, from rows the model keeps at whole lanes of 128 for it
    (ops/subword.lane_padded), which the gather reads in place."""
    with jax.named_scope("scan.gather"):
        rows = _row_slices(syn0, jnp.maximum(ids, 0))
        if block is not None:
            rows = jnp.where((ids >= 0)[:, None], rows, block)
    if lists is None:
        return rows
    with jax.named_scope("scan.compose"):
        from glint_word2vec_tpu.ops.subword import list_vectors
        return jnp.where((ids == _LISTED)[:, None],
                         list_vectors(buckets, lists, syn0.shape[1]), rows)


@partial(jax.jit, static_argnames=("valid_rows",))
def _cosine_batch(syn0: jax.Array, norms: jax.Array, queries: jax.Array,
                  valid_rows: int) -> jax.Array:
    """The [Q, V] masked cosine matrix of :func:`_cosine_topk_batch` without
    the top-k."""
    with jax.named_scope("scan.cosine"):
        qn = jnp.linalg.norm(queries, axis=1, keepdims=True)
        q = queries / jnp.maximum(qn, 1e-12)
        dots = q @ syn0.T                                      # [Q, V]
        cos = jnp.where(norms[None, :] > 0,
                        dots / jnp.maximum(norms[None, :], 1e-12), 0.0)
        return jnp.where(jnp.arange(cos.shape[1])[None, :] < valid_rows,
                         cos, -jnp.inf)


# The two-stage selection's group sizes: whole lane tiles (128 columns) of
# the [Q, V] score block; PR 38's probe timed 256-4,096 at 3M rows (PERF.md §6)
_TOPK_GROUPS = (128, 256, 512, 1024, 2048, 4096)


def _topk_group(num_rows: int, k: int) -> int:
    """Columns a group of :func:`_two_stage_topk` holds over a ``[Q, num_rows]``
    score block (a whole table's, or one shard's of a table partitioned by
    rows), or 0 where the single ``lax.top_k`` ranks the whole block. The
    size is the grid's nearest to sqrt(num_rows / k), where the group
    maxima and the k winning groups' members are together fewest. Handed
    back: rows that k groups would hold whole anyway."""
    ideal = (num_rows / max(k, 1)) ** 0.5
    group = min(_TOPK_GROUPS, key=lambda g: abs(math.log(g / max(ideal, 1.0))))
    return group if k * group < num_rows else 0


def _topk_rows(num_rows: int, k: int) -> int:
    """Scores one query's selection ranks over ``num_rows`` rows (a table's,
    or one shard's) in the scan's program: the group maxima and the k
    winning groups' members where the two stages run, every row where the
    single top-k does (``serve.scan_enqueue``'s ``topk_rows``)."""
    group = _topk_group(num_rows, k)
    return -(-num_rows // group) + k * group if group else num_rows


def _scan_counts(table: jax.Array, k: int) -> Dict[str, int]:
    """What ``serve.scan_enqueue`` says of the program a scan of ``table``
    runs: ``shards``, the partitions of its rows the program runs over (1 on
    one device); ``topk_rows``, the scores one query's selection ranks on
    each (:func:`_topk_rows` of a shard's rows); ``merge_rows``, the
    candidates one query's merge ranks after the shards' selections
    (shards · k; 0 on one device, where nothing is merged)."""
    shards = _row_shards(table)
    n = shards.mesh.shape[shards.spec[0]] if shards else 1
    per = table.shape[0] // n
    return dict(shards=n, topk_rows=_topk_rows(per, k),
                merge_rows=n * min(k, per) if shards else 0)


def _grouped_scores(syn0: jax.Array, norms: jax.Array, queries: jax.Array,
                    valid_rows: int, group: int) -> jax.Array:
    """:func:`_cosine_batch`'s score block widened to whole groups of
    ``group`` columns, the added columns -inf as every column past
    ``valid_rows`` is. On the TPU it is the TABLE's rows that are padded,
    which the compiler folds into the matmul's read of it: no copy of the
    table, the scores are the unpadded block's bit for bit (PR 38's probe),
    and the block is written at its final width, where a pad or a slice of
    the block itself is a copy of 4·Q·V bytes. Elsewhere it is the padded
    table that would be the copy, and the block is padded."""
    extra = -syn0.shape[0] % group
    if jax.default_backend() != "tpu":
        return jnp.pad(_cosine_batch(syn0, norms, queries, valid_rows),
                       ((0, 0), (0, extra)), constant_values=-jnp.inf)
    return _cosine_batch(jnp.pad(syn0, ((0, extra), (0, 0))),
                         jnp.pad(norms, (0, extra)), queries, valid_rows)


def _two_stage_topk(cos: jax.Array, k: int,
                    group: int) -> Tuple[jax.Array, jax.Array]:
    """``lax.top_k(cos, k)``, scores and ids bit for bit, ties included,
    ranking G + k·group scores a row and not all of them: the maximum of
    each of the G runs of ``group`` columns, the k runs with the largest
    maxima, then those runs' members.

    Why it is exact: with t the k-th largest score, fewer than k runs have a
    maximum over t and all of them are taken; a score equal to t that
    ``lax.top_k`` returns (it breaks ties toward the lower index) lies in one
    of those or in one of the lowest-numbered runs whose maximum is t, and
    the runs are numbered in column order, so the k runs taken (ties toward
    the lower run) hold all k answers. The members are laid out in ascending
    column, so the last top-k breaks its ties as the single one does."""
    rows, width = cos.shape
    runs = width // group
    with jax.named_scope("scan.group_max"):
        # 8 rows by 128 columns is the tile the TPU keeps the block in, so
        # over whole tiles of rows (what _topk_dispatch hands over) or one
        # row this view is the block as it lies and the maxima read it once;
        # [rows, runs, group] is first copied into another tiling (4.9 ms of
        # a 9.3 ms scan at [32, 3M], PERF.md §6). Any split of the rows
        # gives the same maxima.
        sub = math.gcd(rows, 8)
        top = cos.reshape(rows // sub, sub, runs, group // 128, 128).max(
            axis=(3, 4)).reshape(rows, runs)
    with jax.named_scope("scan.topk"):
        _, won = jax.lax.top_k(top, k)
        won = jax.lax.sort(won, dimension=1)
        members = jax.vmap(lambda row, starts: jax.vmap(
            lambda s: jax.lax.dynamic_slice(row, (s,), (group,)))(starts))(
                cos, won * group)
        scores, pos = jax.lax.top_k(members.reshape(rows, k * group), k)
        run = jnp.take_along_axis(won, pos // group, axis=1)
        return scores, run * group + pos % group


@partial(jax.jit, static_argnames=("k", "valid_rows"))
def _cosine_topk_batch(syn0: jax.Array, norms: jax.Array, queries: jax.Array,
                       k: int, valid_rows: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """cosine(rows, q) top-k over a [Q, D] query matrix in ONE dispatch:
    normalize queries (snrm2/sscal analog, mllib:589-596), the [Q, V] cosine
    matrix as a single MXU matmul (mllib:598's matvec, batched), divide by row
    norms with zero-norm → 0 (mllib:601-609), batched device top-k instead of
    the client-side BoundedPriorityQueue scan (mllib:611-619). Rows past
    valid_rows are padding (a mesh's row count, rounded up), excluded
    outright. The top-k is taken in two exact stages
    (:func:`_two_stage_topk`) wherever :func:`_topk_group` names a group
    size: what it returns is ``lax.top_k``'s over the same scores."""
    group = _topk_group(syn0.shape[0], k)
    if not group:
        cos = _cosine_batch(syn0, norms, queries, valid_rows)
        with jax.named_scope("scan.topk"):
            return jax.lax.top_k(cos, k)
    return _two_stage_topk(
        _grouped_scores(syn0, norms, queries, valid_rows, group), k, group)


def _block_topk(cos: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """``lax.top_k(cos, k)`` of one block of the analogy scan, columns as
    block-local ids. ``k`` = 1 (the accuracy test) is one variadic reduce,
    the maximum and the lowest column that holds it in ONE pass over the
    block, which at thousands of rows is all the block's reading there is;
    a block narrower than ``k`` is widened with -inf."""
    if cos.shape[1] < k:
        cos = jnp.pad(cos, ((0, 0), (0, k - cos.shape[1])),
                      constant_values=-jnp.inf)
    if k > 1:
        with jax.named_scope("scan.topk"):
            return jax.lax.top_k(cos, k)

    def better(a, b):
        (sa, ia), (sb, ib) = a, b
        keep = (sa > sb) | ((sa == sb) & (ia < ib))
        return jnp.where(keep, sa, sb), jnp.where(keep, ia, ib)

    with jax.named_scope("scan.group_max"):
        col = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
        best, at = jax.lax.reduce(
            (cos, col), (jnp.array(-jnp.inf, cos.dtype), jnp.int32(2 ** 31 - 1)),
            better, (1,))
        return best[:, None], at[:, None]


@partial(jax.jit, static_argnames=("k", "candidates", "block_rows"))
def _analogy_topk(syn0: jax.Array, scanned: jax.Array, inv_norms: jax.Array,
                  words: jax.Array, num_words: jax.Array, pos: jax.Array,
                  k: int, candidates: int, block_rows: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """The analogy scan, ONE program a (question capacity, ``k``,
    ``candidates``): for each of ``pos.shape[0]`` questions the ``k`` best
    cosines of q = û_b − û_a + û_c over rows [0, ``candidates``) of ``syn0``,
    a, b and c excluded, and their row ids: ``lax.top_k``'s over the masked
    scores, ties toward the lower row. The question rows are read from
    ``syn0``; the matmul reads ``scanned``
    (:meth:`Word2VecModel._scan_table`: ``syn0`` itself, or on a TPU its
    bfloat16 rounding, which is what the MXU multiplies either way).

    ``words`` (``int32[3·capacity]``) holds the part's distinct row ids, the
    first ``num_words`` live; ``pos`` (``int32[capacity, 3]``) names each
    question's a, b and c by their place in it (padding questions: 0, 0, 0).
    The rows are read in place, each DISTINCT word once under a ``while``:
    the whole lane tile of 128 rows that holds it, and the row picked out of
    that (a gather from a table whose D is no multiple of 128 first copies
    all of it, :func:`_row_slices`' reason, and so does a one-row slice
    under a ``while``); then scaled by ``inv_norms``, the
    cached 1 / norm (0 for a row of zero norm, whose score is then 0). The
    table is scored ``block_rows`` rows at a time, ``candidates //
    block_rows`` whole blocks under one ``fori_loop`` and the rest as a block
    of its own; rows past ``candidates`` are never read. Per block: the
    ``[capacity, block_rows]`` cosines at :func:`_cosine_batch`'s precision,
    a, b and c set to -inf by row id, the block's own top-k
    (:func:`_block_topk`), and a merge with the running answers (the
    earlier block's first, so that equal scores keep the lower row). No
    score block wider than ``block_rows`` is ever resident."""
    dim = syn0.shape[1]
    with jax.named_scope("scan.analogy_rows"):
        def read(i, rows):
            # the whole lane tile of 128 rows that holds the word's, as the
            # table lies, and the one row picked out of it: exact
            first = jnp.minimum(words[i] // 128 * 128, max(syn0.shape[0] - 128, 0))
            tile = jax.lax.dynamic_slice_in_dim(
                syn0, first, min(128, syn0.shape[0]), allow_negative_indices=False)
            mine = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == words[i] - first
            row = jnp.sum(jnp.where(mine, tile, 0), axis=0, keepdims=True)
            return jax.lax.dynamic_update_slice_in_dim(rows, row, i, 0)

        rows = jax.lax.fori_loop(
            0, num_words, read, jnp.zeros((words.shape[0], dim), syn0.dtype))
        unit = rows * inv_norms[words][:, None].astype(rows.dtype)
        q = unit[pos[:, 1]] - unit[pos[:, 0]] + unit[pos[:, 2]]
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        ids = words[pos]

    def merged(best, lo: jax.Array, table: jax.Array, inv: jax.Array,
               cut: bool):
        with jax.named_scope("scan.cosine"):
            cos = jax.lax.dot_general(
                q.astype(table.dtype), table, (((1,), (1,)), ((), ())),
                preferred_element_type=q.dtype) * inv[None, :].astype(q.dtype)
            col = lo + jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
            out = ((col == ids[:, 0:1]) | (col == ids[:, 1:2])
                   | (col == ids[:, 2:3]))
            if cut:
                out = out | (col >= candidates)
            cos = jnp.where(out, -jnp.inf, cos)
        scores, at = _block_topk(cos, k)
        with jax.named_scope("scan.block_merge"):
            scores = jnp.concatenate([best[0], scores], axis=1)
            at = jnp.concatenate([best[1], lo + at.astype(jnp.int32)], axis=1)
            scores, won = jax.lax.top_k(scores, k)
            return scores, jnp.take_along_axis(at, won, axis=1)

    whole = candidates // block_rows
    best = (jnp.full((pos.shape[0], k), -jnp.inf, q.dtype),
            jnp.zeros((pos.shape[0], k), jnp.int32))
    if whole:
        best = jax.lax.fori_loop(0, whole, lambda i, best: merged(
            best, i * block_rows,
            jax.lax.dynamic_slice_in_dim(scanned, i * block_rows, block_rows),
            jax.lax.dynamic_slice_in_dim(inv_norms, i * block_rows, block_rows),
            False), best)
    if candidates > whole * block_rows:
        lo = whole * block_rows
        # the last block's rows up to a whole lane tile: a slice of the
        # table's rows folds into the matmul's read; what lies past
        # ``candidates`` in it is masked
        hi = min(-(-candidates // 128) * 128, syn0.shape[0])
        best = merged(best, jnp.int32(lo), scanned[lo:hi], inv_norms[lo:hi],
                      hi > candidates)
    return best[0].astype(jnp.float32), best[1]


def _row_shards(table: jax.Array) -> Optional[NamedSharding]:
    """The sharding of a table whose rows are partitioned over one axis of a
    mesh (``MeshPlan.embedding`` where the model axis holds more than one
    device), else None: the table lies on one device, or whole on each."""
    sh = table.sharding
    if (isinstance(sh, NamedSharding) and len(sh.spec) > 0
            and isinstance(sh.spec[0], str) and sh.mesh.shape[sh.spec[0]] > 1
            and all(axis is None for axis in sh.spec[1:])):
        return sh
    return None


def _owner_rows(syn0: jax.Array, ids: jax.Array, first: jax.Array,
                axis: str) -> jax.Array:
    """The [Q, D] rows ``ids`` name (by GLOBAL row; zeros for a negative
    id), inside ``shard_map``: this shard, whose block ``syn0`` starts at
    row ``first``, reads the ids it owns as one-row slices of its block, in
    place as :func:`_query_block` does on one device (no gather op, so no
    row-major copy of a 300-wide shard), and contributes zeros for the
    others; one ``psum`` over ``axis`` hands every shard the whole block.
    Exact: one addend of each row is not zero."""
    at = ids - first
    mine = (at >= 0) & (at < syn0.shape[0])
    rows = _row_slices(syn0, jnp.clip(at, 0, syn0.shape[0] - 1))
    return jax.lax.psum(jnp.where(mine[:, None], rows, 0), axis)


def _sharded_scan(shards: NamedSharding, syn0: jax.Array, norms: jax.Array,
                  ids: jax.Array, block: Optional[jax.Array],
                  valid_rows: int, k: int):
    """The scan over a table partitioned by rows (``shards``: its sharding),
    as ONE program whose body runs under ``shard_map`` over the axis that
    partitions them, each shard on its own ``[V/n, D]`` rows and ``[V/n]``
    norms; the queries are replicated (along a data axis too). The table
    never moves and nothing V wide leaves a chip:

    - the query rows by :func:`_owner_rows` (the vector block's rows where
      ``ids[i]`` is :data:`_VECTOR`, as on one device);
    - the shard's ``[Q, V/n]`` scores as on one device
      (:func:`_grouped_scores` at the group size :func:`_topk_group` gives
      for the SHARD's rows), columns whose global row is past
      ``valid_rows`` at -inf;
    - the shard's own top-k in two exact stages (:func:`_two_stage_topk`;
      the single ``lax.top_k`` where its rows are too few, all of them where
      they are fewer than k), its ids moved to global rows by the shard's
      first row; one ``all_gather`` each of the ``[Q, k]`` scores and ids;
      and ``lax.top_k`` over the ``[Q, n·k]`` candidates, replicated.

    Why the merge is ``lax.top_k``'s over the whole [Q, V] block, ties
    included: that one orders by (score, lower row first), each of its k
    answers is among its own shard's best k under the same order, and the
    candidates lie shard by shard in ascending row, each shard's of equal
    score in ascending row too (its own top-k's order): among candidates of
    equal score a lower position is a lower global row."""
    axis = shards.spec[0]
    n = shards.mesh.shape[axis]
    per = syn0.shape[0] // n
    group = _topk_group(per, k)

    def shard(syn0, norms, ids, block):
        first = jax.lax.axis_index(axis) * per
        with jax.named_scope("scan.owner_rows"):
            queries = _owner_rows(syn0, ids, first, axis)
            if block is not None:
                queries = jnp.where((ids >= 0)[:, None], queries, block)
        cos = (_grouped_scores(syn0, norms, queries, per, group) if group
               else _cosine_batch(syn0, norms, queries, per))
        if valid_rows < n * per:
            # the mesh's padding rows, at the end of the last shards; where
            # the vocabulary divides there are none and no pass is added
            with jax.named_scope("scan.cosine"):
                cos = jnp.where(
                    first + jnp.arange(cos.shape[1])[None, :] < valid_rows,
                    cos, -jnp.inf)
        if group:
            scores, rows = _two_stage_topk(cos, k, group)
        else:
            with jax.named_scope("scan.topk"):
                scores, rows = jax.lax.top_k(cos, min(k, per))
        with jax.named_scope("scan.merge"):
            scores = jax.lax.all_gather(scores, axis, axis=1, tiled=True)
            rows = jax.lax.all_gather(rows + first, axis, axis=1, tiled=True)
            best, at = jax.lax.top_k(scores, k)
            return best, jnp.take_along_axis(rows, at, axis=1)

    return jax.shard_map(
        shard, mesh=shards.mesh,
        in_specs=(P(axis, None), P(axis), P(), P()),
        out_specs=(P(), P()),
        # every shard holds the same gathered candidates and ranks them
        # alike, but an all_gather's result is typed as varying
        check_vma=False)(syn0, norms, ids, block)


@partial(jax.jit, static_argnames=("k", "valid_rows", "shards"))
def _gather_topk_batch(syn0: jax.Array, norms: jax.Array, ids: jax.Array,
                       block: Optional[jax.Array], k: int, valid_rows: int,
                       shards: Optional[NamedSharding],
                       buckets: Optional[jax.Array] = None,
                       lists: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Word ids (and lists, and vectors) in, top-k out, ONE program:
    :func:`_cosine_topk_batch` over the rows :func:`_query_block` reads from
    the tables the model already holds; over a table partitioned by rows
    (``shards``: :func:`_row_shards`'s answer), :func:`_sharded_scan`."""
    if shards:
        return _sharded_scan(shards, syn0, norms, ids, block, valid_rows, k)
    return _cosine_topk_batch(
        syn0, norms, _query_block(syn0, ids, block, buckets, lists), k,
        valid_rows)


def _topk_dispatch(syn0: jax.Array, norms: jax.Array, ids: np.ndarray,
                   block: Optional[np.ndarray], k: int, valid_rows: int,
                   buckets: Optional[jax.Array] = None,
                   lists: Optional[np.ndarray] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """The cosine top-k of one chunk, enqueued: ``ids`` (and ``block``, where
    the chunk holds vector queries; and ``lists``, where it holds strings a
    subword model composes from the ``buckets`` it keeps on the device) are
    host arrays, transferred by the one call that runs the program, the
    top-k in the same program as the gather and the matmul
    (:func:`_gather_topk_batch`; over a table partitioned by rows,
    :func:`_sharded_scan`). On a TPU over whole tiles of 8 query rows, so the
    result may hold padding rows after the chunk's own."""
    extra = -ids.shape[0] % 8
    if jax.default_backend() == "tpu" and ids.shape[0] > 1 and extra:
        # a TPU keeps the [Q, V] score block in tiles of 8 rows, so whole
        # tiles cost the scan nothing; they are what the two-stage
        # selection reads in place, and 9 programs serve the 64 batch
        # sizes a full batcher sends where 64 did (each ~0.15 s to load
        # and 1-4 s to compile: the benchmark's set-up). The last query
        # is repeated; the caller keeps the first ``len(ids)`` rows. A
        # single query is a matrix-vector product of its own and stays.
        ids = np.concatenate([ids, np.repeat(ids[-1:], extra)])
        if block is not None:
            block = np.concatenate(
                [block, np.zeros((extra, block.shape[1]), block.dtype)])
        if lists is not None:
            lists = np.concatenate([lists, np.repeat(lists[-1:], extra, 0)])
    # device arrays: this returns once the program is enqueued, and the
    # caller's fetch is where the host waits for it
    return _gather_topk_batch(
        syn0, norms, ids, block, k, valid_rows, _row_shards(syn0),
        *(() if lists is None else (buckets, lists)))
