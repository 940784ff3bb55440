"""Bytes and FLOPs of ONE hierarchical-softmax skip-gram step, from its shapes
and two counters.

What the step has to move for B (center, context) pairs on tables of padded
width D, a context's output side being its path of inner-node rows:

- the rows HANDED to the paths' gather and scatter: ``syn1_rows_per_pair`` (the
  program's own counter over the check's three feed batches: rows that reached
  syn1's scatter with a live index, over B) times B. It counts rows handed over
  and NOT distinct rows: the root, which every piece of every context word
  lists, is counted once a piece where the step works per word piece (~4.0 a
  pair), and once a pair where it works per pair (~15.4); a form that summed
  a node's duplicates first would move fewer and read above this share;
- 2 B center-side rows (B gathered from syn0; its scatter takes fewer where the
  center runs are summed first, so this side is counted at its plain size);
- three passes over all of them in the tables' dtype: the gather, and the
  update's read and its write;
- the pair indices and the paths' slot ids, 4 bytes each;
- three contractions of 2 * D FLOPs for every live (pair, node) term
  (``path_nodes_per_pair`` * B of them: the logit, the pair's d_in, the node's
  update).

Nothing that the step chooses to materialise is counted (the gathered block,
the update block, the sorted slots).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, pairs_per_batch: int, padded_dim: int, param_dtype: str,
         path_nodes_per_pair: float, syn1_rows_per_pair: float) -> dict:
    b, d = pairs_per_batch, padded_dim
    listed = syn1_rows_per_pair * b
    table_bytes = 3 * (listed + 2 * b) * d * _DTYPE_BYTES[param_dtype]
    index_bytes = 4 * (2 * b + listed)
    flops = 3 * 2.0 * path_nodes_per_pair * b * d
    return {"bytes": table_bytes + index_bytes, "flops": flops}
