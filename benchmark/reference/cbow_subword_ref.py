"""Plain reference: one step of subword CBOW with position weights (fastText's
``cc.<lang>.300`` recipe), from the written loss.

Straightforward float32 ``jax.numpy``, matmul precision "highest", no lane
padding, no bfloat16, no prefix sums, no taps, no run heads, no row table, no
twins. It imports nothing of the program; its n-gram function is
``subword_ref.py``'s Python loop written from the paper, and it takes its
updates from ``jax.grad`` of the loss below, not from a hand-derived chain.

Grave, Bojanowski, Gupta, Joulin, Mikolov, "Learning Word Vectors for 157
Languages", LREC 2018 (arXiv:1802.06893): CBOW with position weights, character
n-grams of length 5, window 5, 10 negatives. Position weights: Mikolov, Grave,
Bojanowski, Puhrsch, Joulin, "Advances in Pre-Training Distributed Word
Representations", LREC 2018 (arXiv:1712.09405) section 2.2.

``syn0`` has V + K rows (V words, K buckets), ``syn1`` V rows, ``d`` 2c rows
(c the window), all of width D. G(w) is w's own row and V + (fnv1a(g) mod K)
for every substring g of ``min_n``..``max_n`` characters of ``"<w>"``
(``subword_ref.word_rows``; a word too short for an n-gram lists itself alone,
a bucket two n-grams share is listed twice). For a center w_t with drawn
in-sentence extents l_t, r_t <= c, P_t = {p : -l_t <= p <= r_t, p != 0},
n_t = |P_t| (an example with n_t = 0 trains nothing and counts for nothing),
and a pool Z of P words drawn from count^0.75,

    u_w    = (1 / |G(w)|) * sum_{r in G(w)} syn0[r]
    h_t    = (1 / n_t) * sum_{p in P_t} d_p * u_{w_{t+p}}        (elementwise *)
    loss_t = -log s(h_t . syn1[w_t]) - (n / P) * sum_{z in Z, z != w_t} log s(-h_t . syn1[z])

``d_p`` is row p + c of ``d`` for p < 0 and row p + c - 1 for p > 0. One SGD
step moves every row of every G(w_{t+p}), syn1[w_t] and every syn1[z] by -alpha
times the gradient of the block's SUMMED loss, updates of duplicate rows summed.
The reported loss is the mean over live examples.

Departures from the published description:

- ``d_p`` moves by -alpha times the MEAN of d loss_t / d d_p over the live
  examples that have position p, not their sum: it is the one parameter every
  example of a block touches, and tens of thousands of examples' updates summed
  at the per-example rate would be a step tens of thousands of times the
  published per-example step for that leaf;
- ``u_w`` is the mean over G(w) and the window is divided by n_t; released
  fastText's ``cbow`` averages all listed rows of a window flat, and its
  position-weighted branch was never released;
- the pool Z is shared by the whole block and each negative term is weighted
  n / P, where fastText draws n negatives an example (as ``cbow_ref.py``);
- each row gets the true gradient of the written loss (divided by |G(w)| and
  by n_t), where ``fasttext`` adds the whole hidden gradient to every row;
- batched SGD: a block's examples all read the tables as they stood before it;
- the windows are not drawn here: an example arrives as a center and a list of
  (position, word), the program's draws.

With ``train_positions`` off the same code is plain CBOW over composed token
vectors (``d`` stays ones); with every word's list its own row alone it is
position-weighted CBOW without subwords; with both, ``cbow_ref.py``'s model.

An example is ``centers[i]`` and the first ``nctx[i]`` entries of
``ctx_word[i]`` / ``ctx_pos[i]`` (a word as an index into ``lists``, a position
as its row of ``d``); a word is the first ``nrows[w]`` entries of ``lists[w]``
(row ids into the table handed in). Ragged lists in rectangular arrays: a
storage format, not padding of the model.
"""

import jax
import jax.numpy as jnp


def position_row(p: int, window: int) -> int:
    """The row of ``d`` that holds d_p, p in -window..window without 0."""
    return p + window if p < 0 else p + window - 1


def batch_loss(tables, centers, ctx_word, ctx_pos, nctx, lists, nrows, negatives,
               num_negatives):
    """(summed loss over live examples, their count) on float32 (syn0, syn1, d)."""
    syn0, syn1, d = tables
    p = negatives.shape[0]
    in_list = jnp.arange(lists.shape[1])[None, :] < nrows[:, None]          # [W, L]
    u = (jnp.sum(jnp.where(in_list[:, :, None], syn0[lists], 0.0), axis=1)
         / jnp.maximum(nrows, 1)[:, None].astype(jnp.float32))              # [W, D]
    in_window = jnp.arange(ctx_word.shape[1])[None, :] < nctx[:, None]      # [B, 2c]
    live = nctx > 0
    h = (jnp.sum(jnp.where(in_window[:, :, None], d[ctx_pos] * u[ctx_word], 0.0),
                 axis=1)
         / jnp.maximum(nctx, 1)[:, None].astype(jnp.float32))
    f_pos = jnp.sum(h * syn1[centers], axis=-1)
    f_neg = h @ syn1[negatives].T
    other = negatives[None, :] != centers[:, None]
    per_example = (-jax.nn.log_sigmoid(f_pos)
                   - (num_negatives / p)
                   * jnp.sum(jnp.where(other, jax.nn.log_sigmoid(-f_neg), 0.0), axis=-1))
    return jnp.sum(jnp.where(live, per_example, 0.0)), jnp.sum(live)


def step(syn0, syn1, d, centers, ctx_word, ctx_pos, nctx, lists, nrows, negatives,
         alpha, num_negatives, train_positions):
    """One step on float32 tables. Returns (syn0, syn1, d, mean loss per live
    example)."""
    with jax.default_matmul_precision("highest"):
        (total, count), (g0, g1, gd) = jax.value_and_grad(batch_loss, has_aux=True)(
            (syn0, syn1, d), centers, ctx_word, ctx_pos, nctx, lists, nrows,
            negatives, num_negatives)
    if train_positions:
        # the mean rule: a position's summed gradient over the live examples
        # that have it
        in_window = jnp.arange(ctx_pos.shape[1])[None, :] < nctx[:, None]
        having = jnp.zeros(d.shape[0], jnp.float32).at[ctx_pos].add(
            in_window.astype(jnp.float32))
        d = d - alpha * gd / jnp.maximum(having, 1.0)[:, None]
    return (syn0 - alpha * g0, syn1 - alpha * g1, d,
            total / jnp.maximum(count, 1).astype(jnp.float32))


def leaf_norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def follow_steps(rows0_init, rows1_init, d_init, centers, ctx_word, ctx_pos, nctx,
                 lists, nrows, negatives, alphas, num_negatives, word_rows_mask,
                 train_positions=True):
    """Follow len(centers) steps on compact tables (indices are into them; step
    k's words are ``lists[k]``). ``word_rows_mask`` [rows of rows0_init] says
    which rows of the compact input table are words' own rows (the others are
    bucket rows). Returns per-step losses and, after the first step and after
    all, the change norms of four leaves: syn0's word rows, syn0's bucket rows,
    syn1, d; and the tables it ends on."""
    one = jax.jit(step, static_argnums=(11, 12))
    is_word = jnp.asarray(word_rows_mask)[:, None]

    def norms(s0, s1, d):
        d0 = s0 - rows0_init
        return (leaf_norm(jnp.where(is_word, d0, 0.0)),
                leaf_norm(jnp.where(is_word, 0.0, d0)),
                leaf_norm(s1 - rows1_init), leaf_norm(d - d_init))

    s0, s1, d = rows0_init, rows1_init, d_init
    losses, first = [], None
    for k in range(len(centers)):
        s0, s1, d, loss = one(s0, s1, d, centers[k], ctx_word[k], ctx_pos[k], nctx[k],
                              lists[k], nrows[k], negatives[k],
                              jnp.float32(alphas[k]), num_negatives, train_positions)
        losses.append(float(loss))
        if k == 0:
            first = norms(s0, s1, d)
    return {"losses": losses, "first_change_norm": first,
            "change_norm": norms(s0, s1, d), "syn0": s0, "syn1": s1, "d": d}
