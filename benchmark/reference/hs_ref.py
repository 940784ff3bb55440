"""Plain reference: one skip-gram step under hierarchical softmax
(``word2vec.c -hs 1 -negative 0``), from the written loss.

Straightforward float32 ``jax.numpy``, matmul precision "highest", no lane
padding, no bfloat16, no runs, no groups, no sort, no twins. It imports nothing
of the program, its tree is ``word2vec.c``'s ``CreateBinaryTree`` as a Python
loop, and it takes its updates from ``jax.grad`` of the loss below, not from a
hand-derived chain: it shares no algebra with the program's step.

Mikolov, Chen, Corrado, Dean, "Efficient Estimation of Word Representations in
Vector Space" (arXiv:1301.3781 §2.1, §3.2); Mikolov et al., "Distributed
Representations of Words and Phrases" (arXiv:1310.4546 §2.1, eq. 3), after
Morin & Bengio 2005. ``syn0`` has V rows (words), ``syn1`` V − 1 (the tree's
inner nodes).

The tree, from the words' counts sorted descending (ties by rank):
``count[V .. 2V−2] = 1e15``; two cursors ``pos1 = V − 1`` (down the words) and
``pos2 = V`` (up the nodes made so far); V − 1 times the two least are taken
(``count[pos1] < count[pos2]`` takes ``pos1``, else ``pos2``; twice), node
``V + a`` becomes their parent with the sum of their counts, and the SECOND
taken gets ``binary = 1``. A word's path, root first: ``point_0 = V − 2`` (the
root), ``point_d`` the d-th node below the root minus V; ``code_d`` the binary
of the path's (d + 1)-th member (the child of ``point_d`` on the way to the
word); L(w) its length.

For a pair (center c, context x):

    h      = syn0[c]
    f_d    = h . syn1[point_d(x)]                      d = 0 .. L(x) - 1
    loss   = - sum_d log s((1 - 2 code_d(x)) f_d)

and one SGD step moves syn0[c] and every syn1[point_d(x)] by -alpha times the
gradient of the batch's SUMMED loss, updates of duplicate rows summed. The
reported loss is the mean over the batch's pairs.

The rule for a node that many pairs share: node j's summed update is scaled by
``min(1, M / m_j)``, m_j the batch's pairs whose path holds j; M = None is the
plain sum (M infinite). syn0's side is never scaled.

Departures from ``word2vec.c``:

- the pair is read the other way round: ``word2vec.c`` takes the path of the
  window's middle word and the row of its neighbour; here the center's row
  and the neighbour's path (over a corpus every (a, b) also comes as (b, a),
  and syn0 stays the center's table);
- the exact sigmoid: ``word2vec.c`` skips a node whose |f| >= 6 (its table);
- batched SGD: a batch's pairs all read the tables as they stood before it,
  where ``word2vec.c`` applies them one after another; hence the rule above.

A pair is given as ``centers[i]`` and the first ``lengths[i]`` entries of
``points[i]`` / ``codes[i]`` (row ids into the table handed in; the rest of
the row is ignored): ragged paths in one rectangular array, which is a storage
format and not padding of the model.
"""

import jax
import jax.numpy as jnp
import numpy as np

# pairs whose paths' rows are gathered at once: [block, longest, D] float32
_BLOCK = 8192


def create_binary_tree(counts) -> tuple:
    """``word2vec.c``'s CreateBinaryTree over ``counts`` (descending):
    ``(parent, binary)``, lists of 2V − 1 entries (words 0 .. V − 1, then
    nodes; the root, 2V − 2, has no parent)."""
    v = len(counts)
    count = [int(c) for c in counts] + [10 ** 15] * (v - 1)
    parent = [0] * (2 * v - 1)
    binary = [0] * (2 * v - 1)
    pos1, pos2 = v - 1, v
    for a in range(v - 1):
        least = []
        for _ in range(2):
            if pos1 >= 0 and count[pos1] < count[pos2]:
                least.append(pos1)
                pos1 -= 1
            else:
                least.append(pos2)
                pos2 += 1
        count[v + a] = count[least[0]] + count[least[1]]
        parent[least[0]] = parent[least[1]] = v + a
        binary[least[1]] = 1
    return parent, binary


def word_path(tree: tuple, word: int) -> tuple:
    """``(points, codes)`` of a word, root first, as ``word2vec.c`` fills
    ``vocab[a].point`` / ``vocab[a].code``."""
    parent, binary = tree
    v = (len(parent) + 1) // 2
    code, point = [], []
    b = word
    while b != 2 * v - 2:
        code.append(binary[b])
        point.append(b)
        b = parent[b]
    points = [v - 2] + [p - v for p in reversed(point[1:])]
    return points, list(reversed(code))


def batch_loss(tables, centers, points, codes, lengths):
    """Summed loss of a batch on float32 (syn0, syn1)."""
    syn0, syn1 = tables
    on_path = jnp.arange(points.shape[1])[None, :] < lengths[:, None]
    f = jnp.einsum("bd,bld->bl", syn0[centers], syn1[points])
    sign = 1.0 - 2.0 * codes.astype(jnp.float32)
    return jnp.sum(jnp.where(on_path, -jax.nn.log_sigmoid(sign * f), 0.0))


def node_pairs(num_nodes: int, points, lengths) -> np.ndarray:
    """m_j: pairs of the batch whose path holds node j."""
    points, lengths = np.asarray(points), np.asarray(lengths)
    on_path = np.arange(points.shape[1])[None, :] < lengths[:, None]
    return np.bincount(points[on_path], minlength=num_nodes)


def hs_step(syn0, syn1, centers, points, codes, lengths, alpha,
            max_node_pairs=None):
    """One step on float32 tables, the gradient summed over blocks of pairs.
    Returns (syn0, syn1, mean loss per pair)."""
    grad = jax.jit(jax.value_and_grad(batch_loss))
    total, g0, g1 = 0.0, jnp.zeros_like(syn0), jnp.zeros_like(syn1)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, centers.shape[0], _BLOCK):
            cut = slice(lo, lo + _BLOCK)
            loss, (b0, b1) = grad((syn0, syn1), centers[cut], points[cut],
                                  codes[cut], lengths[cut])
            total, g0, g1 = total + float(loss), g0 + b0, g1 + b1
    if max_node_pairs is not None:
        m = node_pairs(syn1.shape[0], points, lengths)
        g1 = g1 * jnp.asarray(np.minimum(1.0, max_node_pairs / np.maximum(m, 1)),
                              jnp.float32)[:, None]
    return syn0 - alpha * g0, syn1 - alpha * g1, total / centers.shape[0]


def leaf_norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def follow_steps(rows0_init, rows1_init, centers, points, codes, lengths, alphas,
                 hot_rows_mask, max_node_pairs=None):
    """Follow len(centers) steps on compact tables (indices are into them).
    ``hot_rows_mask`` [rows of rows1_init] says which rows of the compact
    node table are the tree's top nodes (the others are the rest). Returns
    per-step losses and, after the first step and after all, the change norms
    of three leaves: syn0, syn1's top nodes, syn1's other nodes."""
    is_hot = jnp.asarray(hot_rows_mask)[:, None]

    def norms(s0, s1):
        d1 = s1 - rows1_init
        return (leaf_norm(s0 - rows0_init), leaf_norm(jnp.where(is_hot, d1, 0.0)),
                leaf_norm(jnp.where(is_hot, 0.0, d1)))

    s0, s1 = rows0_init, rows1_init
    losses, first = [], None
    for k in range(len(centers)):
        s0, s1, loss = hs_step(s0, s1, centers[k], points[k], codes[k], lengths[k],
                               jnp.float32(alphas[k]), max_node_pairs)
        losses.append(float(loss))
        if k == 0:
            first = norms(s0, s1)
    return {"losses": losses, "first_change_norm": first,
            "change_norm": norms(s0, s1), "syn0": s0, "syn1": s1}
