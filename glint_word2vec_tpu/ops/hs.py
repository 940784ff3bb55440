"""Skip-gram under hierarchical softmax: the step whose OUTPUT side is a list.

``word2vec.c -hs 1 -negative 0`` (config.loss="hs"; Mikolov et al. 2013,
arXiv:1301.3781 §2.1, arXiv:1310.4546 eq. 3). syn1's rows are the V − 1 inner
nodes of the vocabulary's Huffman tree (data/huffman.py), not words; a pair's
output side is the context word's path, 6 to 26 signed rows, and there is no
sampler and no pool. For a pair (center c, context x), L = L(x):

    h      = syn0[c]
    f_d    = h · syn1[point_d(x)]                      d = 0 … L − 1
    loss   = − Σ_d log σ((1 − 2·code_d(x)) · f_d)
    g_d    = 1 − code_d(x) − σ(f_d)
    syn0[c]          += α Σ_d g_d · syn1[point_d(x)]   (from the values before the step)
    syn1[point_d(x)] += α g_d · h

One step applies a batch's pairs at once, duplicate rows summed, as every
step of this program does. (``word2vec.c`` reads the pair the other way
round, the path of the window's middle word and the row of its neighbour;
over a corpus every (a, b) also comes as (b, a), and syn0 stays the center's
table, which serving reads.)

The path table has data/subword.py's format, so a word's path is read as
ops/subword.py reads a list (:func:`..subword._lists`: a block of
``max_groups · 8`` slots a word, a slot ``2 · point + code``, padding
:data:`NO_ROW`). What differs from a subword list: no mean (every listed row
gets its own dot with the pair's ``h``, its own sigmoid and its own
coefficient, so a head's [slots, D] block is contracted against the pairs of
its head, not reduced), signs, and a skew no bucket list has: the root is in
every path, its children in half of them, and so on down.

Two forms, chosen by the step from its own batch under one ``lax.cond``:

- per WORD PIECE (``shape.word_cap``): the batch's pairs are sorted by context
  inside the step (a stable 1-D sort that carries their positions, as
  :func:`..sgns.scatter_add_by_runs` sorts them), a word's pairs are cut every
  ``max_run`` into pieces, and the pieces' heads are compacted to
  ``word_cap``. A piece's path is gathered once, [word_cap, slots, D];
  contracted with its pairs' center vectors [word_cap, max_run, D] (two small
  batched matmuls: the logits, and the pairs' ``d_in``); the node updates
  [word_cap, slots, D] are a third. The block's slots are sorted by node and
  the first ``slot_cap`` (the live ones: NO_ROW sorts last) scattered with
  ``indices_are_sorted``, each with its own update row. A node's duplicate
  slots are NOT summed before the scatter: sorted, a slot whose node is the
  one before it costs the chip ~57 ns and a new node ~156, so summing them
  first (run sums over the sorted keys, the runs' heads compacted) read 52.8
  ms a step against 45.3 (PERF.md §6, PR 37).
- per PAIR, where the batch has more pieces than ``word_cap`` or more live
  slots than ``slot_cap`` (contexts that hardly repeat), or the trainer
  built no capacities: every pair its own
  path, in chunks of pairs under ``lax.map`` / ``lax.scan`` so that no
  [B, slots, D] block is made; the coefficients of all pairs first, from the
  tables as they stood, then the scatters. Same rows, same sums.

**The rule for a node that many pairs share.** A batch of B pairs sends B
summed updates to the root, B/2 to each of its children. ``word2vec.c``
applies them one after another, each seeing the last; a batch sum is that only
to first order. Node j's summed update is scaled by ``min(1, M / m_j)``, m_j
the batch's live pairs whose path holds j, M = :data:`MAX_NODE_PAIRS`
(``None``: the plain sum, which at 65,536 pairs a step trains to NaN inside
32 steps: PERF.md §6, PR 37). syn0's side, a pair's own terms, is never
scaled. The per-word form reads m_j off its sorted slots (a running count
over each node's run); the per-pair form counts into a [nodes] vector.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from glint_word2vec_tpu.data.subword import GROUP, NO_ROW
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair, StepMetrics, _log_sigmoid, _sigmoid, run_positions,
    scatter_add_by_runs)
from glint_word2vec_tpu.ops.subword import SubwordTable, _lists

# M of the rule above; None = the plain sum (PERF.md §6, PR 37 has the curves
# that chose it). The reference (benchmark/reference/hs_ref.py) applies the same
MAX_NODE_PAIRS: Optional[int] = 2048

# pairs in a chunk of the per-pair form: [chunk, slots, D] float32 is 25 MB at
# 512 pairs, 32 slots, D = 384
_PAIR_CHUNK = 512


class HsShape(NamedTuple):
    """What the step needs to know at trace time."""

    max_groups: int      # groups of the longest path (4 of 8 slots at 3M words)
    max_run: int = 1     # a context word's pairs are cut every max_run into pieces
    word_cap: int = 0    # word pieces of a batch the per-word form holds; 0: not built
    slot_cap: int = 0    # sorted slots the per-word form's scatter takes


def _decode(slots: jax.Array):
    """(rows, codes, live) of a block of path slots: NO_ROW's row is out of
    bounds of any table (gathers fill 0 for it, scatters drop it)."""
    live = slots != NO_ROW
    return slots >> 1, (slots & 1).astype(jnp.float32), live


def _coefficients(f, code, live, alpha, sigmoid_mode, with_metrics):
    """g of every (pair, node) term, float32, 0 where the term does not
    exist, and the terms' summed loss and summed signed logit."""
    g = jnp.where(live, (1.0 - code - _sigmoid(f, sigmoid_mode)) * alpha, 0.0)
    if not with_metrics:
        return g, jnp.float32(0.0), jnp.float32(0.0)
    signed = (1.0 - 2.0 * code) * f
    return (g, jnp.where(live, -_log_sigmoid(signed), 0.0).sum(),
            jnp.where(live, signed, 0.0).sum())


def _node_scale(syn1: jax.Array, rows: jax.Array, pairs: jax.Array):
    """min(1, M / m_j) of every slot's node (None where M is None), counted
    into a [nodes] vector: ``pairs`` is the live pairs a slot stands for, m_j
    their sum over the batch. The per-pair form's; :func:`_sorted_node_scale`
    is the per-word form's."""
    if MAX_NODE_PAIRS is None:
        return None
    m = jnp.zeros((syn1.shape[0],), jnp.float32).at[rows.reshape(-1)].add(
        pairs.reshape(-1), mode="drop")
    m_slot = m.at[rows].get(mode="fill", fill_value=1.0)
    return jnp.minimum(1.0, MAX_NODE_PAIRS / jnp.maximum(m_slot, 1.0))


# a 1-D scan runs along the lanes of [rows, 128] blocks, then over the rows
_LANES = 128


def _scan(op, combine, x: jax.Array, identity, reverse: bool = False):
    """``op`` (``lax.cumsum`` / ``cummax`` / ``cummin``) over 1-D ``x`` in two
    levels: along the lanes of [rows, 128] blocks, then over the rows' ends,
    each row taking what came before it through ``combine``. The same values
    as the 1-D scan, which the chip runs 7-14 times slower at 311,296 entries
    (PERF.md §6, PR 37)."""
    n = x.shape[0]
    blocks = jnp.pad(x, (0, -n % _LANES), constant_values=identity
                     ).reshape(-1, _LANES)
    within = op(blocks, axis=1, reverse=reverse)
    ends = within[:, 0] if reverse else within[:, -1]
    upto = op(ends, axis=0, reverse=reverse)
    carried = (jnp.concatenate([upto[1:], jnp.full((1,), identity, x.dtype)])
               if reverse else
               jnp.concatenate([jnp.full((1,), identity, x.dtype), upto[:-1]]))
    return combine(within, carried[:, None]).reshape(-1)[:n]


def _sorted_node_scale(node: jax.Array, pairs: jax.Array):
    """min(1, M / m_j) of every slot of a block sorted by node (None where M
    is None): m_j is the sum of ``pairs`` (int32: the live pairs each sorted
    slot stands for) over the node's run, the running sum at the run's end
    less the one before its start, each spread over the run by a running
    maximum / minimum (the running sum never falls). 1-D work on the sorted
    keys; no [nodes] vector is made and nothing is gathered."""
    if MAX_NODE_PAIRS is None:
        return None
    edge = node[1:] != node[:-1]
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_), edge])
    last = jnp.concatenate([edge, jnp.ones((1,), jnp.bool_)])
    running = _scan(jax.lax.cumsum, jnp.add, pairs, 0)
    top = jnp.iinfo(jnp.int32).max
    before = _scan(jax.lax.cummax, jnp.maximum,
                   jnp.where(first, running - pairs, 0), 0)
    upto = _scan(jax.lax.cummin, jnp.minimum,
                 jnp.where(last, running, top), top, reverse=True)
    return jnp.minimum(1.0, MAX_NODE_PAIRS
                       / jnp.maximum(upto - before, 1).astype(jnp.float32))


def _read_per_word(syn1, e_in, keys, order, pos, head, table, shape, alpha,
                   sigmoid_mode, compute_dtype, with_metrics):
    """The per-word-piece form's reads of syn1: ``(d_in [B, D] in batch
    order, loss sum, signed-logit sum, the pieces' node updates
    [word_cap · slots, D], the block's slots sorted by node and cut to
    slot_cap (their nodes, their places in the block), the pairs of every
    piece [word_cap])``."""
    n, d = e_in.shape
    v = table.counts.shape[0] - 1
    w, r = shape.word_cap, shape.max_run
    at = jnp.arange(n, dtype=jnp.int32)
    with jax.named_scope("hs.paths"):
        live_at = jnp.sort(jnp.where(head, at, n))[:w]
        src = jnp.minimum(live_at, n - 1)
        word = jnp.where(live_at < n, keys[src], v)             # v: no word
        slots, _ = _lists(word, table, shape.max_groups)         # [W, S]
        rows, code, live = _decode(slots)
        # the pairs of a piece: the next max_run sorted positions whose place
        # in their run says they belong to it (run_sums' rule)
        j = jnp.arange(r, dtype=jnp.int32)
        member_at = src[:, None] + j[None, :]                    # [W, R]
        member = ((jnp.pad(pos, (0, r - 1))[member_at] == j[None, :])
                  & (live_at < n)[:, None])
    with jax.named_scope("hs.gather"):
        nodes = syn1.at[rows].get(mode="fill", fill_value=0).astype(compute_dtype)
        # the pieces' pairs in sorted order: the sort composed into the index
        h = jnp.where(member[:, :, None],
                      e_in[jnp.pad(order, (0, r - 1))[member_at]],
                      jnp.zeros((), compute_dtype))              # [W, R, D]
    with jax.named_scope("hs.logits"):
        f = jnp.einsum("wrd,wsd->wrs", h, nodes,
                       preferred_element_type=jnp.float32)
        term = member[:, :, None] & live[:, None, :]
        g, loss, signed = _coefficients(f, code[:, None, :], term, alpha,
                                        sigmoid_mode, with_metrics)
        d_h = jnp.einsum("wrs,wsd->wrd", g.astype(compute_dtype), nodes)
        update = jnp.einsum("wrs,wrd->wsd", g.astype(compute_dtype), h)
        # a pair's d_in sits at (its piece, its place in the piece): the
        # sort's inverse composed into one index (a second sort; a scatter of
        # B indices costs more), one gather of B rows
        piece = jnp.minimum(jnp.cumsum(head.astype(jnp.int32)) - 1, w - 1)
        _, back = jax.lax.sort((order, at), num_keys=1)
        d_in = jnp.where((keys < v)[back][:, None],
                         d_h.reshape(w * r, d)[(piece * r + pos)[back]],
                         jnp.zeros((), compute_dtype))
    with jax.named_scope("hs.scatter"):
        node, slot = jax.lax.sort(
            (jnp.where(live, rows, NO_ROW).reshape(-1),
             jnp.arange(slots.size, dtype=jnp.int32)), num_keys=1)
    return (d_in, loss, signed, update.reshape(-1, d), node[:shape.slot_cap],
            slot[:shape.slot_cap], member.sum(axis=1, dtype=jnp.int32))


def _write_per_word(syn1, flat, node, slot, pairs):
    """The per-word-piece form's scatter: (new syn1, rows handed over). Every
    sorted slot goes with its own update row, scaled by the rule for a node
    many pairs share."""
    with jax.named_scope("hs.scatter"):
        rows = flat[slot].astype(syn1.dtype)
        scale = _sorted_node_scale(node, pairs[slot // (flat.shape[0] // pairs.shape[0])])
        if scale is not None:
            rows = rows * scale[:, None]
        return (syn1.at[node].add(rows, mode="drop", indices_are_sorted=True),
                (node != NO_ROW).sum(dtype=jnp.float32))


def _read_per_pair(syn1, e_in, words, table, shape, alpha, sigmoid_mode,
                   compute_dtype, with_metrics):
    """The per-pair form's reads of syn1, in chunks under ``lax.map``:
    ``(d_in [B, D], loss sum, signed-logit sum, every pair's nodes
    [chunks, chunk, slots] (NO_ROW past its path), its coefficients)``."""
    n, d = e_in.shape
    c = math.gcd(n, _PAIR_CHUNK)

    def coefficients(xs):
        words, h = xs
        rows, code, live = _decode(_lists(words, table, shape.max_groups)[0])
        with jax.named_scope("hs.gather"):
            nodes = syn1.at[rows].get(mode="fill", fill_value=0).astype(compute_dtype)
        with jax.named_scope("hs.logits"):
            f = jnp.einsum("bd,bsd->bs", h, nodes,
                           preferred_element_type=jnp.float32)
            g, loss, signed = _coefficients(f, code, live, alpha, sigmoid_mode,
                                            with_metrics)
            d_in = jnp.einsum("bs,bsd->bd", g.astype(compute_dtype), nodes)
        return jnp.where(live, rows, NO_ROW), g, d_in, loss, signed

    rows, g, d_in, loss, signed = jax.lax.map(
        coefficients, (words.reshape(-1, c), e_in.reshape(-1, c, d)))
    scale = _node_scale(syn1, rows, (rows != NO_ROW).astype(jnp.float32))
    if scale is not None:
        g = g * scale
    return d_in.reshape(n, d), loss.sum(), signed.sum(), rows, g


def _write_per_pair(syn1, rows, g, e_in):
    """The per-pair form's scatters, a chunk at a time under ``lax.scan``."""
    def scatter(syn1, xs):
        rows, g, h = xs
        with jax.named_scope("hs.scatter"):
            return syn1.at[rows].add(
                (g.astype(h.dtype)[:, :, None] * h[:, None, :]
                 ).astype(syn1.dtype), mode="drop"), None

    chunks, c = rows.shape[:2]
    return (jax.lax.scan(scatter, syn1, (rows, g, e_in.reshape(chunks, c, -1)))[0],
            (rows != NO_ROW).sum(dtype=jnp.float32))


def hs_step_core(
    params: EmbeddingPair,
    centers: jax.Array,    # int32 [B]
    contexts: jax.Array,   # int32 [B]
    mask: jax.Array,       # float32 [B]
    alpha: jax.Array,
    table: SubwordTable,   # the path table (data/huffman.py) on the device
    shape: HsShape,
    sigmoid_mode: str = "exact",
    compute_dtype: jnp.dtype = jnp.float32,
    with_metrics: bool = True,
    center_runs: Optional[Tuple[int, int]] = None,
) -> Tuple[EmbeddingPair, StepMetrics]:
    """One hierarchical-softmax skip-gram step (the module's docstring has the
    equations and the forms). ``center_runs`` ``(max_run, cap)``: syn0's
    update goes through :func:`..sgns.scatter_add_by_runs`, as the shared-pool
    step's. The per-node logits, sigmoids and coefficients are float32 (a
    [pieces, max_run, slots] block, small beside the [B, P] chain
    ``logits_dtype`` exists for). ``StepMetrics.mean_f_pos`` is the mean
    signed logit (1 − 2·code)·f over the batch's (pair, node) terms.

    syn1 is read under one conditional and written under a second that the
    first's results order after it: one conditional that held a form's reads
    AND its writes made the compiler copy the table in and out of the
    per-pair branch's scatter loop (the compile for the described v5e,
    tests/test_hs_inplace_tpu.py)."""
    syn0, syn1 = params.syn0, params.syn1
    n, d = centers.shape[0], syn0.shape[1]
    v = table.counts.shape[0] - 1
    with jax.named_scope("sgns.gather"):
        e_in = syn0[centers].astype(compute_dtype)               # [B, D]
    with jax.named_scope("hs.paths"):
        # a masked pair's word is "no word": it sorts last and has no path
        words = jnp.where(mask > 0, contexts, v)
        hs_nodes = table.counts[words].sum(dtype=jnp.float32)

    def read_per_pair(syn1):
        return _read_per_pair(syn1, e_in, words, table, shape, alpha,
                              sigmoid_mode, compute_dtype, with_metrics)

    if not shape.word_cap:
        d_in, loss, signed, rows, g = read_per_pair(syn1)
        new_syn1, syn1_rows = _write_per_pair(syn1, rows, g, e_in)
    else:
        with jax.named_scope("hs.paths"):
            at = jnp.arange(n, dtype=jnp.int32)
            keys, order = jax.lax.sort((words, at), num_keys=1, is_stable=True)
            pos = run_positions(keys, shape.max_run)
            head = (pos == 0) & (keys < v)
            # live slots of the pieces' block: each piece lists its word's path
            live_slots = jnp.where(head, table.counts[keys], 0).sum()
            fits = ((head.sum() <= shape.word_cap)
                    & (live_slots <= shape.slot_cap))
        c = math.gcd(n, _PAIR_CHUNK)
        slots = shape.max_groups * GROUP
        block = shape.word_cap * slots
        cut = min(shape.slot_cap, block)

        # each half hands the other form's results over empty, so that the
        # two branches of a conditional return the same shapes
        def per_word(syn1):
            return (*_read_per_word(
                syn1, e_in, keys, order, pos, head, table, shape, alpha,
                sigmoid_mode, compute_dtype, with_metrics),
                    jnp.full((n // c, c, slots), NO_ROW, jnp.int32),
                    jnp.zeros((n // c, c, slots), jnp.float32))

        def per_pair(syn1):
            d_in, loss, signed, rows, g = read_per_pair(syn1)
            return (d_in, loss, signed, jnp.zeros((block, d), compute_dtype),
                    jnp.full((cut,), NO_ROW, jnp.int32),
                    jnp.zeros((cut,), jnp.int32),
                    jnp.zeros((shape.word_cap,), jnp.int32), rows, g)

        d_in, loss, signed, flat, node, slot, piece_pairs, rows, g = jax.lax.cond(
            fits, per_word, per_pair, syn1)
        new_syn1, syn1_rows = jax.lax.cond(
            fits, lambda syn1: _write_per_word(syn1, flat, node, slot, piece_pairs),
            lambda syn1: _write_per_pair(syn1, rows, g, e_in), syn1)

    with jax.named_scope("sgns.scatter_syn0"):
        if center_runs is None:
            new_syn0 = syn0.at[centers].add(d_in.astype(syn0.dtype))
            syn0_rows = jnp.float32(n)
        else:
            new_syn0, syn0_rows, _ = scatter_add_by_runs(
                syn0, centers, d_in, *center_runs)
    pairs = mask.sum()
    if with_metrics:
        loss = loss / jnp.maximum(pairs, 1.0)
        mean_f = signed / jnp.maximum(hs_nodes, 1.0)
    else:
        loss = mean_f = jnp.float32(0.0)
    return EmbeddingPair(new_syn0, new_syn1), StepMetrics(
        loss=loss, mean_f_pos=mean_f, pairs=pairs, syn0_rows=syn0_rows,
        syn1_rows=syn1_rows, hs_nodes=hs_nodes)


__all__: Tuple[str, ...] = ("HsShape", "MAX_NODE_PAIRS", "hs_step_core")
