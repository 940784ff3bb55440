"""Plain reference: one subword skip-gram step (fastText's ``skipgram`` with
negative sampling), from the written loss.

Straightforward float32 ``jax.numpy``, matmul precision "highest", no lane
padding, no bfloat16, no runs, no groups, no twins. It imports nothing of the
program, its n-gram function is a Python loop written from the paper, and it
takes its updates from ``jax.grad`` of the loss below, not from a hand-derived
chain: it shares no algebra with the program's step.

Bojanowski, Grave, Joulin, Mikolov, "Enriching Word Vectors with Subword
Information", TACL 2017 (arXiv:1607.04606). The input table syn0 has V + K
rows (V words, K buckets); the output table syn1 has V. For a word w, G(w) is
the list of w's own row and of V + (fnv1a(g) mod K) for every substring g of
``min_n`` to ``max_n`` characters of ``"<" + w + ">"`` (characters are code
points, the hash runs over their UTF-8 bytes, each byte sign-extended as
fastText's ``int8_t`` cast does; the whole ``"<w>"`` is one of them where it is
that short); a bucket that two n-grams of w share is listed twice, as fastText
lists it. For a pair (center w, context c) and a pool Z of P words drawn from
count^0.75,

    h_w  = (1 / |G(w)|) * sum_{r in G(w)} syn0[r]
    loss = -log s(h_w . syn1[c]) - (n / P) * sum_{z in Z, z != c} log s(-h_w . syn1[z])

and one SGD step moves every row of G(w), syn1[c] and every syn1[z] by -alpha
times the gradient of the batch's SUMMED loss, updates of duplicate rows
summed. The reported loss is the mean over the batch's pairs.

Departures from the published description:

- the pool Z is shared by the whole batch and each negative term is weighted
  n / P, where the paper and ``fasttext`` draw n negatives per pair (the
  objective the program's default path trains; as ``sgns_ref.py``);
- each row of G(w) gets the true gradient, d loss / d h_w divided by |G(w)|,
  where ``fasttext`` adds the whole gradient to every row of the list (the same
  departure ``cbow_ref.py`` notes for ``word2vec.c``'s context rows);
- batched SGD: a batch's pairs all read the tables as they stood before it.

A pair is given as ``centers[i]``, ``contexts[i]`` and the first ``nrows[i]``
entries of ``lists[i]`` (row ids into the table handed in; the rest of the row
is ignored): ragged lists in one rectangular array, which is a storage format
and not padding of the model.
"""

import jax
import jax.numpy as jnp
import numpy as np

FNV_OFFSET, FNV_PRIME = 2166136261, 16777619


def fnv1a(data: bytes) -> int:
    h = FNV_OFFSET
    for byte in data:
        signed = byte - 256 if byte >= 128 else byte      # int8_t(byte)
        h = ((h ^ (signed & 0xFFFFFFFF)) * FNV_PRIME) & 0xFFFFFFFF
    return h


def ngrams(word: str, min_n: int = 3, max_n: int = 6) -> list:
    """Every substring of min_n..max_n characters of "<word>"."""
    marked = "<" + word + ">"
    out = []
    for start in range(len(marked)):
        for n in range(min_n, max_n + 1):
            if start + n <= len(marked):
                out.append(marked[start:start + n])
    return out


def word_rows(word: str, word_id, vocab_size: int, buckets: int,
              min_n: int = 3, max_n: int = 6) -> list:
    """G(w): the word's own row (none for a string the vocabulary has never
    seen: ``word_id`` None) and the bucket row of each of its n-grams."""
    own = [] if word_id is None else [int(word_id)]
    return own + [vocab_size + fnv1a(g.encode("utf-8")) % buckets
                  for g in ngrams(word, min_n, max_n)]


def batch_loss(tables, centers_lists, nrows, contexts, negatives, num_negatives):
    """Summed loss of a batch on float32 (syn0, syn1)."""
    syn0, syn1 = tables
    p = negatives.shape[0]
    in_list = jnp.arange(centers_lists.shape[1])[None, :] < nrows[:, None]
    h = (jnp.sum(jnp.where(in_list[:, :, None], syn0[centers_lists], 0.0), axis=1)
         / nrows[:, None].astype(jnp.float32))
    f_pos = jnp.sum(h * syn1[contexts], axis=-1)
    f_neg = h @ syn1[negatives].T
    other = negatives[None, :] != contexts[:, None]
    per_pair = (-jax.nn.log_sigmoid(f_pos)
                - (num_negatives / p)
                * jnp.sum(jnp.where(other, jax.nn.log_sigmoid(-f_neg), 0.0), axis=-1))
    return jnp.sum(per_pair)


def subword_step(syn0, syn1, lists, nrows, contexts, negatives, alpha,
                 num_negatives):
    """One step on float32 tables. Returns (syn0, syn1, mean loss per pair)."""
    with jax.default_matmul_precision("highest"):
        total, (g0, g1) = jax.value_and_grad(batch_loss)(
            (syn0, syn1), lists, nrows, contexts, negatives, num_negatives)
    return syn0 - alpha * g0, syn1 - alpha * g1, total / contexts.shape[0]


def leaf_norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def follow_steps(rows0_init, rows1_init, lists, nrows, contexts, negatives, alphas,
                 num_negatives, word_rows_mask):
    """Follow len(lists) steps on compact tables (indices are into them).
    ``word_rows_mask`` [rows of rows0_init] says which rows of the compact
    input table are words' own rows (the others are bucket rows). Returns
    per-step losses and, after the first step and after all, the change norms
    of three leaves: syn0's word rows, syn0's bucket rows, syn1."""
    step = jax.jit(subword_step, static_argnums=(7,))
    is_word = jnp.asarray(word_rows_mask)[:, None]

    def norms(s0, s1):
        d0 = s0 - rows0_init
        return (leaf_norm(jnp.where(is_word, d0, 0.0)),
                leaf_norm(jnp.where(is_word, 0.0, d0)),
                leaf_norm(s1 - rows1_init))

    s0, s1 = rows0_init, rows1_init
    losses, first = [], None
    for k in range(len(lists)):
        s0, s1, loss = step(s0, s1, lists[k], nrows[k], contexts[k], negatives[k],
                            jnp.float32(alphas[k]), num_negatives)
        losses.append(float(loss))
        if k == 0:
            first = norms(s0, s1)
    return {"losses": losses, "first_change_norm": first,
            "change_norm": norms(s0, s1), "syn0": s0, "syn1": s1}


def word_vector(syn0, string: str, word_id, vocab_size: int, buckets: int,
                min_n: int = 3, max_n: int = 6) -> np.ndarray:
    """fastText's ``get_word_vector``: the mean of the rows of G(w), for a word
    of the vocabulary (own row and n-grams) and for a string it has never seen
    (``word_id`` None: n-grams alone; zeros where it has none)."""
    rows = word_rows(string, word_id, vocab_size, buckets, min_n, max_n)
    table = np.asarray(syn0, np.float32)
    if not rows:
        return np.zeros(table.shape[1], np.float32)
    return table[np.asarray(rows)].sum(axis=0) / np.float32(len(rows))
