"""Least bytes and FLOPs of ONE shared-pool CBOW step, from its shapes alone.

What the algorithm has to do for B example slots (consecutive kept tokens, each
a center with up to 2*window context words around it) against a shared pool of
P negatives, on tables of padded width D:

- the distinct rows it touches, each once a pass: a batch's examples are
  consecutive kept tokens, so their windows cover the same B + 2*window rows of
  syn0 (every example's context words are its neighbours, which are the other
  examples' centers); B center rows and P pool rows of syn1;
- three passes over them in the tables' dtype: the gather, and the update's read
  and its write;
- the token indices and the pool indices, 4 bytes each;
- three matmuls of 2*B*P*D FLOPs (negative logits, d_hidden, d_Z) and the
  elementwise work of the context mean and its gradient (each a sum of up to
  2*window rows an example, whatever the form: ~2 * 2*window*B*D), the positive
  logits, coefficients and positive updates (~10*B*D).

Nothing that a form chooses to materialise is counted (the [B, P] logit chain,
prefix sums, gathered copies): the roofline is what the arithmetic needs. The
scatter form (``cbow_update="scatter"``) gathers and scatters B*2*window context
rows where this counts B + 2*window, so its share reads low by design.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, pairs_per_batch: int, window: int, pool: int, padded_dim: int,
         param_dtype: str) -> dict:
    b, w, p, d = pairs_per_batch, window, pool, padded_dim
    rows = (b + 2 * w) + b + p
    table_bytes = 3 * rows * d * _DTYPE_BYTES[param_dtype]   # gather + RMW
    index_bytes = 4 * (b + 2 * w + p)
    flops = 3 * 2.0 * b * p * d + 2 * 2.0 * w * b * d + 10.0 * b * d
    return {"bytes": table_bytes + index_bytes, "flops": flops}
