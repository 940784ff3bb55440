"""Bytes and FLOPs of ONE subword skip-gram step, from its shapes and one counter.

What the step has to move for B (center, context) pairs against a shared pool
of P negatives, on tables of padded width D:

- the rows HANDED to the subword gather and scatter: ``subword_rows_per_pair``
  (the program's own counter over the check's three feed batches: rows of the
  centers' lists that reached syn0's scatter with a live index, over B) times
  B. It counts rows handed over and NOT distinct rows: a bucket row that a
  hundred center runs of the batch list is counted a hundred times, because
  the step reads and adds it a hundred times; a form that summed a batch's
  duplicate rows first would move fewer and read above this roofline's share.
  Where the step works once per distinct word of the batch (a piece of its run
  heads: what the window runs since PR 34) that is ~3.7 rows a pair, once per
  center run (a batch over the word cap) ~5, per pair ~19;
- B context rows and P pool rows of syn1;
- three passes over all of them in the tables' dtype: the gather, and the
  update's read and its write;
- the pair indices, the pool indices and the list's row ids, 4 bytes each;
- three matmuls of 2*B*P*D FLOPs (negative logits, d_in, d_Z), ~10*B*D
  elementwise FLOPs (positive logits, coefficients, positive updates) and the
  mean and its spread back (~2 FLOPs an element of every listed row).

Nothing that the step chooses to materialise is counted (the [B, P] logit
chain, the gathered block, the broadcast update).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, pairs_per_batch: int, pool: int, padded_dim: int, param_dtype: str,
         subword_rows_per_pair: float) -> dict:
    b, p, d = pairs_per_batch, pool, padded_dim
    listed = subword_rows_per_pair * b
    rows = listed + b + p
    table_bytes = 3 * rows * d * _DTYPE_BYTES[param_dtype]   # gather + RMW
    index_bytes = 4 * (2 * b + p + listed)
    flops = 3 * 2.0 * b * p * d + 10.0 * b * d + 2.0 * listed * d
    return {"bytes": table_bytes + index_bytes, "flops": flops}
