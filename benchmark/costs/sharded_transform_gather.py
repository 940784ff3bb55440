"""Least bytes and FLOPs of ONE sharded transform slide's program ON THE BUSIEST
CHIP, from its shapes: whatever implements the program must move them.

The table is partitioned by rows and never moves, so the chip that owns the most
of a slide's live ids (``owned_rows``: the mean over the traced slice of
``transform.enqueue``'s ``owned_max``) must read that many rows once, at the bytes
a row has as the program holds it (D widened to whole lanes of 128: 300 -> 384
float32 = 1,536 B). Every chip reads the slide's ids and the sentence of each id
once (``rows`` live ids, the padding to the row capacity not counted), writes its
``[sentences, lanes]`` float32 partial sums once and the collective reads them
once, and writes the ``[sentences, dim]`` float32 means; the counts are read once.
NOT counted, as costs/transform_gather.py: the gathered ``[rows, lanes]`` block's
write and re-read (a program that sums a row into its sentence as it reads it
never writes it), the zeros a chip reads for ids it does not own, and what the
collective moves between chips beyond that one read of the partial. One add a
gathered element, ``chips - 1`` adds an element of the partials and one divide a
result element are the FLOPs; bytes bind by three orders of magnitude.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, owned_rows: float, rows: float, sentences: int, dim: int,
         table_dtype: str, chips: int) -> dict:
    lanes = -(-dim // 128) * 128
    table_bytes = owned_rows * lanes * _DTYPE_BYTES[table_dtype]
    index_bytes = 2 * 4 * rows + 4 * sentences
    partial_bytes = 2 * 4 * sentences * lanes
    result_bytes = 4 * sentences * dim
    return {"bytes": table_bytes + index_bytes + partial_bytes + result_bytes,
            "flops": owned_rows * lanes + (chips - 1) * sentences * lanes
                     + sentences * dim}
