"""Least bytes and FLOPs of ONE sentence-vector slide's program, from its shapes.

``rows`` live word rows (the slide's in-vocabulary tokens) and ``list_rows``
live bucket rows (the n-grams of its ``unseen`` composed tokens), the padding to
either capacity not counted, are each read once at the bytes a row has as the
program holds it: D widened to whole lanes of 128 (300 -> 384 float32 = 1,536
B; the form the gathers read in place). Read beside them, 4 B each: a word
row's id, its sentence and its inverse norm; a list row's id and its token; a
composed token's sentence; a sentence's count. The ``[sentences, dim]`` float32
means are written once. NOT counted: the write and re-read of the two gathered
blocks and of the ``[unseen, lanes]`` token block (a program that scales and
sums a row as it reads it writes none of them; the TPU's compiler fuses both
gathers into their sorted scatter-adds). A multiply and an add a gathered word
element, an add a list element, a square, an add, a divide and an add a token
element and a divide a result element are the FLOPs; bytes bind by three orders
of magnitude.
"""

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def cost(*, rows: float, list_rows: float, unseen: float, sentences: int, dim: int,
         table_dtype: str) -> dict:
    lanes = -(-dim // 128) * 128
    table_bytes = (rows + list_rows) * lanes * _DTYPE_BYTES[table_dtype]
    index_bytes = 4 * (3 * rows + 2 * list_rows + unseen + sentences)
    result_bytes = 4 * sentences * dim
    return {"bytes": table_bytes + index_bytes + result_bytes,
            "flops": (2 * rows + list_rows + 4 * unseen) * lanes + sentences * dim}
