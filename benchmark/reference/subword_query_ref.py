"""Plain reference: fastText's ``nn`` over composed vectors, from the written
definition.

Straightforward float32, matmul precision "highest", no lane padding, no
bfloat16, no tiles, no groups, no two-stage selection. It imports nothing of
the program. A query's own vector and the held sample come from
``subword_ref.py``'s Python loop written from the paper (``fnv1a``, ``ngrams``,
``word_rows``); the lists of all V words come from a NumPy hasher of this
file's own, which every caller holds to that loop on a seeded sample
(:func:`hasher_mismatches`): 78 M hashes in a Python loop are minutes.

Bojanowski et al. 2017 (arXiv:1607.04606) §3.2 and fastText's
``get_nearest_neighbors`` / ``precomputeWordVectors``. ``A`` is the trained
input table, V word rows then K bucket rows, x D. For a string s, ``N(s)`` is
the list of buckets of every n-gram (min_n <= n <= max_n, by code point) of
``"<" + s + ">"``, FNV-1a with fastText's signed-byte xor, mod K, a bucket
listed as often as it occurs.

    G(w) = [w] ++ [V + b for b in N(word_w)]        a word of the vocabulary: its own row, then its n-grams'
    G(s) = [V + b for b in N(s)]                    a string the vocabulary lacks: n-grams alone
    h(x) = (1 / |G(x)|) * sum_{r in G(x)} A[r]      (zeros where G is empty)
    H    = [h(w) for w in 0 .. V-1]                 the composed table, made once at load
    score(x, w) = h(x) . H[w] / (|h(x)| |H[w]|)     0 where either norm is 0
    reply(x, k) = the k words of highest score, x itself left out where x is a word of the vocabulary

``A`` is never handed over whole: ``rows_fn(ids) -> [len(ids), D] float32``
makes the rows asked for (the benchmark's tables are a formula of the seed);
``ids`` is a NumPy array from :func:`vector` and a traced one inside
:func:`composed_table`'s jitted block.
"""

import jax
import jax.numpy as jnp
import numpy as np

from reference.subword_ref import FNV_OFFSET, FNV_PRIME, word_rows

NO_BUCKET = -1


def bucket_lists(strings, buckets: int, min_n: int = 3, max_n: int = 6) -> np.ndarray:
    """``N(s)`` of every string as one int32 array [len(strings), slots], by
    start then by length as the loop lists them, :data:`NO_BUCKET` where a
    start has no n-gram of that length. One-byte characters only (the
    harness's strings are lower-case ASCII; anything else raises): a character
    is then a byte and the signed-byte xor is the plain one."""
    n = len(strings)
    length = np.fromiter((len(s) for s in strings), np.int64, n) + 2
    width = int(length.max())
    raw = np.frombuffer("".join(strings).encode("utf-8"), np.uint8)
    if raw.shape[0] != int(length.sum()) - 2 * n or (raw.shape[0] and raw.max() > 127):
        raise ValueError("bucket_lists: one-byte characters only")
    # "<s>" of every string, one row each, zero past its end
    marked = np.zeros((n, width), np.uint32)
    at = np.arange(width)[None, :]
    marked[:, 0] = ord("<")
    marked[(at >= 1) & (at < length[:, None] - 1)] = raw
    marked[np.arange(n), length - 1] = ord(">")
    spans = max_n - min_n + 1
    out = np.full((n, width * spans), NO_BUCKET, np.int32)
    prime, mod = np.uint32(FNV_PRIME), np.uint32(buckets)
    for start in range(width):
        h = np.full(n, FNV_OFFSET, np.uint32)
        for size in range(1, max_n + 1):
            if start + size > width:
                break
            h = (h ^ marked[:, start + size - 1]) * prime
            if size >= min_n:
                fits = start + size <= length
                out[fits, start * spans + size - min_n] = (h[fits] % mod).astype(np.int32)
    return out


def hasher_mismatches(strings, lists: np.ndarray, sample, vocab_size: int,
                      buckets: int, min_n: int = 3, max_n: int = 6) -> int:
    """Strings of ``sample`` (positions into ``strings``) whose row of
    ``lists`` is not, in order, what the paper's loop lists for them."""
    bad = 0
    for i in sample:
        loop = [r - vocab_size for r in
                word_rows(strings[int(i)], None, vocab_size, buckets, min_n, max_n)]
        mine = lists[int(i)]
        bad += int(mine[mine != NO_BUCKET].tolist() != loop)
    return bad


def vector(rows_fn, string: str, word_id, vocab_size: int, buckets: int,
           min_n: int = 3, max_n: int = 6) -> np.ndarray:
    """h(x): the mean of the rows of G(x), by the paper's loop. ``word_id``
    None: a string the vocabulary lacks, which has no row of its own."""
    rows = word_rows(string, word_id, vocab_size, buckets, min_n, max_n)
    got = np.asarray(rows_fn(np.asarray(rows, np.int32).reshape(-1)), np.float32)
    return got.sum(axis=0) / np.float32(max(len(rows), 1))


def composed_table(rows_fn, lists: np.ndarray, vocab_size: int,
                   block: int = 1 << 14) -> jax.Array:
    """H [V, D] float32 from the words' lists (row w is word w's), in blocks
    of words: every listed row made by ``rows_fn``, summed with the word's
    own, divided by how many there are."""
    @jax.jit
    def rows_of(words, listed):
        live = listed != NO_BUCKET
        count = 1 + live.sum(axis=1)
        ids = jnp.where(live, vocab_size + listed, 0).reshape(-1)
        got = rows_fn(ids).reshape(listed.shape + (-1,))
        total = rows_fn(words) + jnp.where(live[:, :, None], got, 0.0).sum(axis=1)
        return total / count[:, None].astype(jnp.float32)

    out = []
    for lo in range(0, vocab_size, block):
        words = np.minimum(np.arange(lo, lo + block), vocab_size - 1).astype(np.int32)
        out.append(rows_of(jnp.asarray(words), jnp.asarray(lists[words]))
                   [:min(block, vocab_size - lo)])
    return jnp.concatenate(out)


def cosine_scores(table, query_rows, block: int = 1 << 19) -> np.ndarray:
    """[Q, V] exact cosines of ``query_rows`` [Q, D] against ``table`` [V, D],
    in row blocks; 0 where either norm is 0."""
    @jax.jit
    def block_scores(q, t):
        with jax.default_matmul_precision("highest"):
            qn, tn = jnp.linalg.norm(q, axis=1), jnp.linalg.norm(t, axis=1)
            dots = q @ t.T
            return jnp.where((qn[:, None] > 0) & (tn[None, :] > 0),
                             dots / jnp.maximum(qn[:, None] * tn[None, :], 1e-30), 0.0)

    q = jnp.asarray(query_rows, jnp.float32)
    return np.concatenate([np.asarray(block_scores(q, table[lo:lo + block]))
                           for lo in range(0, table.shape[0], block)], axis=1)


def reply(scores: np.ndarray, k: int, word_id) -> list:
    """The k words of highest score, best first; ``word_id`` (the query's own
    word, None for a string the vocabulary lacks) left out."""
    row = scores.copy()
    if word_id is not None:
        row[int(word_id)] = -np.inf
    top = np.argpartition(-row, k)[:k]
    return [int(w) for w in top[np.argsort(-row[top], kind="stable")]]
