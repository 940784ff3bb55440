"""The Huffman tree of a vocabulary, and every word's path through it:
hierarchical softmax's output side (config.loss="hs"; ops/hs.py).

``word2vec.c``'s ``CreateBinaryTree``, letter for letter, because Huffman
trees are not unique under ties and most of a large vocabulary sits at one
count: the words come sorted by count descending (the vocabulary's contract),
the V − 1 inner nodes get ``count = 1e15`` until made, two cursors run down the
words and up the nodes, the two least are taken V − 1 times
(``count[pos1] < count[pos2]`` takes the word, else the node), node ``V + a``
becomes their parent and the SECOND taken is its ``1`` child. Node ids are in
order of creation, so the root is node V − 2 and the nodes nearest the root
are the highest ids.

A word's path, root first, is the inner nodes from the root down to its
parent (``point``, ids into syn1's V − 1 rows) and at each the bit of the
child the path takes (``code``); 6 to 26 nodes at 3M words under Zipf counts.

:func:`build_path_table` lays the paths out as data/subword.py lays out its
row lists (``offsets``, groups of :data:`GROUP` slots, ``counts``), so the step
reads a word's path as ops/subword.py reads a list: a slot holds
``2 · point + code``, padding slots :data:`NO_ROW`. The table is a function of
the counts alone: a checkpoint saves none, and a loaded model builds it again.
"""

from typing import Tuple

import numpy as np

from glint_word2vec_tpu.data.subword import GROUP, NO_ROW, SubwordRows


def huffman_parents(counts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(parent, binary)`` int32 [2V − 1] over words 0 .. V − 1 then nodes (the
    root, 2V − 2, has no parent). The two-cursor loop is sequential by nature
    (a node's count is known only once made): plain Python over lists, 3.5 s
    at 3M words."""
    counts = np.asarray(counts)
    v = counts.shape[0]
    if v < 2:
        raise ValueError("a Huffman tree needs at least two words")
    if (counts[1:] > counts[:-1]).any():
        raise ValueError("the Huffman tree is built from counts sorted "
                         "descending (the vocabulary's order)")
    count = counts.astype(np.int64).tolist() + [10 ** 15] * (v - 1)
    parent = [0] * (2 * v - 1)
    binary = [0] * (2 * v - 1)
    pos1, pos2 = v - 1, v
    for node in range(v, 2 * v - 1):
        if pos1 >= 0 and count[pos1] < count[pos2]:
            min1 = pos1
            pos1 -= 1
        else:
            min1 = pos2
            pos2 += 1
        if pos1 >= 0 and count[pos1] < count[pos2]:
            min2 = pos1
            pos1 -= 1
        else:
            min2 = pos2
            pos2 += 1
        count[node] = count[min1] + count[min2]
        parent[min1] = parent[min2] = node
        binary[min2] = 1
    return np.asarray(parent, np.int32), np.asarray(binary, np.int32)


def build_path_table(counts: np.ndarray) -> SubwordRows:
    """Every word's path as a :class:`..subword.SubwordRows`: word w's slots
    are ``2 · point_d + code_d`` for d = 0 .. L(w) − 1, root first; ``counts``
    holds L(w). The fill climbs from every word at once, one pass of NumPy a
    level (26 at 3M words)."""
    parent, binary = huffman_parents(counts)
    v = (parent.shape[0] + 1) // 2
    root = 2 * v - 2
    # lengths first: how many steps every word is from the root
    length = np.zeros(v, np.int64)
    at = np.arange(v)
    up = parent[:v].astype(np.int64)
    while at.size:
        length[at] += 1
        keep = up != root
        at, up = at[keep], parent[up[keep]].astype(np.int64)
    groups = -(-length // GROUP)
    goff = np.zeros(v + 2, np.int64)
    np.cumsum(groups, out=goff[1:v + 1])
    goff[v + 1] = goff[v]
    flat = np.full(int(goff[v]) * GROUP, NO_ROW, np.int32)
    base = goff[:v] * GROUP
    # the climb again, writing: the member s steps above the word's leaf sits
    # at depth L − 1 − s of the path, its parent the node and its bit the code
    at, node, step = np.arange(v), np.arange(v, dtype=np.int64), 0
    while at.size:
        above = parent[node].astype(np.int64)
        flat[base[at] + length[at] - 1 - step] = 2 * (above - v) + binary[node]
        keep = above != root
        at, node, step = at[keep], above[keep], step + 1
    return SubwordRows(
        offsets=goff.astype(np.int32), rows=flat.reshape(-1, GROUP),
        counts=np.append(length, 0).astype(np.int32),
        max_groups=int(groups.max()))


def decode_path(slots: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``(points, codes)`` of a word's live slots (the host's view)."""
    slots = np.asarray(slots)
    return slots >> 1, slots & 1
