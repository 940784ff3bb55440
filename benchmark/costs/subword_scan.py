"""Least bytes and FLOPs of ONE exact cosine top-k dispatch over a subword
model's composed table, from its shapes.

Q queries against the composed [V, D] float32 table with cached row norms, as
``cosine_scan.py`` counts them: the table read once (V*D elements), the norms
once (V float32), 2*Q*V*D FLOPs for the [Q, V] product, the score block not
counted. Added: the bucket rows that the dispatch's unseen strings list
(``list_rows`` of them, live ones alone, D float32 each as the model defines
them, however wide the program keeps them), their ids, and one add an element
for their sums. The same work whatever reads the rows: a form that copies the
bucket table to gather from it reads a smaller share of this.
"""


def cost(*, queries: float, vocab: int, dim: int, list_rows: float,
         chips: int = 1) -> dict:
    table_bytes = 4 * vocab * dim + 4 * vocab
    query_bytes = 4 * queries * dim
    list_bytes = list_rows * (4 * dim + 4)
    flops = 2.0 * queries * vocab * dim + list_rows * dim
    return {"bytes": (table_bytes + query_bytes + list_bytes) / chips,
            "flops": flops / chips}
