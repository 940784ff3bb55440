"""The tests' reference ranking: top-k of one score row on the host.

Independent of ``ops/scan.py``'s programs (``np.partition`` and a sort of k
candidates, no ``lax`` call), it is what the scan's replies are compared with
where a whole ``[Q, V]`` score block is at hand. Not collected: no test lives
here (``tests/test_parallel_host.py`` holds it to ``lax.top_k``, ties
included)."""

from typing import Tuple

import numpy as np


def _cpu_topk_row(row: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k of one score row: O(V) selection + a k-element sort, scratch
    bounded to one float copy of the row (``np.partition``). Tie handling is
    EXACT to ``lax.top_k``: everything strictly above the k-th value is in,
    and entries EQUAL to it fill the remaining slots in ascending index order
    (a plain ``argpartition`` leaves that boundary choice arbitrary — it
    returned different neighbors than the device route on tied scores)."""
    V = row.shape[0]
    if k >= V:
        cand = np.arange(V)
    else:
        kth = np.partition(row, V - k)[V - k]        # the k-th largest value
        above = np.flatnonzero(row > kth)
        need = k - above.shape[0]
        ties = np.flatnonzero(row == kth)[:need]     # lowest tied indices win
        cand = np.concatenate([above, ties])
    sc = row[cand]
    order = np.lexsort((cand, -sc))
    return sc[order], cand[order]


def host_topk(cos: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`_cpu_topk_row` of every row of a fetched ``[Q, V]`` block:
    float32 ``[Q, k]`` scores and their columns."""
    scores = np.empty((cos.shape[0], k), np.float32)
    idxs = np.empty((cos.shape[0], k), np.int64)
    for r in range(cos.shape[0]):
        scores[r], idxs[r] = _cpu_topk_row(cos[r], k)
    return scores, idxs
