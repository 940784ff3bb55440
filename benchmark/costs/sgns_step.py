"""Least bytes and FLOPs of ONE shared-pool SGNS step, from its shapes alone.

What the algorithm has to do for B (center, context) pairs against a shared pool
of P negatives, on tables of padded width D:

- read B center rows, B context rows and P pool rows, in the tables' dtype;
- read-modify-write the same rows (the update): another read and a write each;
- the pair indices and pool indices, 4 bytes each;
- three matmuls of 2*B*P*D FLOPs (negative logits, d_in, d_Z) and ~10*B*D
  elementwise FLOPs (positive logits, coefficients, positive updates).

Nothing that an implementation chooses to materialise (the [B, P] logit chain,
gathered copies) is counted: the roofline is what the arithmetic needs. With
``chips`` > 1 each chip does 1/chips of it and collectives are NOT counted, so
the share on several chips is against perfect scaling.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, pairs_per_batch: int, pool: int, padded_dim: int, param_dtype: str,
         chips: int = 1) -> dict:
    b, p, d = pairs_per_batch, pool, padded_dim
    rows = 2 * b + p
    table_bytes = 3 * rows * d * _DTYPE_BYTES[param_dtype]   # gather + RMW
    index_bytes = 4 * (2 * b + p)
    flops = 3 * 2.0 * b * p * d + 10.0 * b * d
    return {"bytes": (table_bytes + index_bytes) / chips, "flops": flops / chips}
