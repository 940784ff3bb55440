"""Least bytes and FLOPs of ONE transform slide's program, from its shapes.

``rows`` live word ids (the slide's in-vocabulary tokens, the padding to the row
capacity not counted) are each read once from the table at the bytes a row has
as the program holds it: D widened to whole lanes of 128 (300 -> 384 float32 =
1,536 B; the form the gather reads in place). The ``[sentences, dim]`` float32
means are written once; the ids, the sentence of each id and the counts are read
once. The gathered ``[rows, lanes]`` block's write and re-read are NOT counted: a
program that sums a row into its sentence as it reads it never writes the block.
One add a gathered element and one divide a result element are the FLOPs; bytes
bind by three orders of magnitude.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, rows: float, sentences: int, dim: int, table_dtype: str) -> dict:
    lanes = -(-dim // 128) * 128
    table_bytes = rows * lanes * _DTYPE_BYTES[table_dtype]
    index_bytes = 2 * 4 * rows + 4 * sentences
    result_bytes = 4 * sentences * dim
    return {"bytes": table_bytes + index_bytes + result_bytes,
            "flops": rows * lanes + sentences * dim}
