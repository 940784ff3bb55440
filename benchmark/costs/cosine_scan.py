"""Least bytes and FLOPs of ONE exact cosine top-k dispatch, from its shapes.

Q queries against a [V, D] table with cached row norms: the table is read once
(V*D elements), the norms once (V float32), and the [Q, V] product takes
2*Q*V*D FLOPs. The [Q, V] score block is not counted, though the program's scan
writes it whole and reads it again for the run maxima and the members: it is
what the scan chooses to materialise, not what the answer needs, so a share of
100% would be a scan that never writes it. Top-k selection is comparisons, not
FLOPs. Over a mesh ``chips`` is the shards: one chip's share of the table.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, queries: int, vocab: int, dim: int, table_dtype: str,
         chips: int = 1) -> dict:
    table_bytes = vocab * dim * _DTYPE_BYTES[table_dtype] + 4 * vocab
    query_bytes = 4 * queries * dim
    flops = 2.0 * queries * vocab * dim
    return {"bytes": (table_bytes + query_bytes) / chips, "flops": flops / chips}
