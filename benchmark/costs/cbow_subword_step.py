"""Bytes and FLOPs of ONE subword CBOW step with position weights, from its
shapes and one counter.

What the step has to move for a block of T token slots (each a context token,
the core ones centers too) against a shared pool of P negatives, on tables of
padded width D:

- the rows HANDED to the list gather and scatter: ``subword_rows_per_block``
  (the program's own counter over the check's three feed blocks: rows of the
  tokens' lists that reached syn0's scatter with a live index). It counts rows
  handed over and NOT distinct rows: a bucket row that a hundred tokens of the
  block list is counted a hundred times where the step reads every token's
  list, and once a distinct word where it reads a word's list once; a form
  that handed over fewer would move fewer bytes and be held to fewer;
- T center rows and P pool rows of syn1, and the 2*window rows of ``d``;
- three passes over all of them in the tables' dtype: the gather, and the
  update's read and its write;
- the token indices, the pool indices and the lists' row ids, 4 bytes each;
- three matmuls of 2*T*P*D FLOPs (negative logits, d_hidden, d_Z), the taps'
  three passes of 2*window multiply-adds over [T, D] (the forward window, its
  transpose, ``d``'s product-reductions: 3 * 2*window * 2*T*D), ~10*T*D
  elementwise FLOPs (positive logits, coefficients, positive updates) and the
  lists' mean and its spread back (~2 FLOPs an element of every listed row).

Nothing that the step chooses to materialise is counted (the [T, P] logit
chain, the gathered block, the broadcast update, the shifted copies).
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, tokens_per_block: int, window: int, pool: int, padded_dim: int,
         param_dtype: str, subword_rows_per_block: float) -> dict:
    t, w, p, d = tokens_per_block, window, pool, padded_dim
    listed = subword_rows_per_block
    rows = listed + t + p + 2 * w
    table_bytes = 3 * rows * d * _DTYPE_BYTES[param_dtype]   # gather + RMW
    index_bytes = 4 * (t + p + listed)
    flops = (3 * 2.0 * t * p * d + 3 * 2 * w * 2.0 * t * d + 10.0 * t * d
             + 2.0 * listed * d)
    return {"bytes": table_bytes + index_bytes, "flops": flops}
