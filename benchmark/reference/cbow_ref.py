"""Plain reference: one CBOW-with-negative-sampling step, from the written loss.

Straightforward float32 ``jax.numpy``, matmul precision "highest", no lane
padding, no bfloat16, no prefix sums, no twins. It imports nothing of the program,
and it takes its updates from ``jax.grad`` of the loss below, not from a
hand-derived chain: it shares no algebra with either of the program's step forms.

CBOW (Mikolov et al. 2013, arXiv:1301.3781; ``word2vec.c -cbow 1``): for one
example with center w, live context words C (|C| >= 1; an example with no
context trains nothing and counts for nothing) and a pool Z of P words drawn
from count^0.75,

    h    = (1 / |C|) * sum_{c in C} u_c
    loss = -log s(h . v_w) - (n / P) * sum_{z in Z, z != w} log s(-h . v_z)

and one SGD step moves every u_c, v_w and v_z by -alpha times the gradient of the
batch's SUMMED loss, updates of duplicate rows summed (word2vec's Hogwild sum
semantics). The reported loss is the mean over live examples.

Departures from the published description:

- the pool Z is shared by the whole batch and each negative term is weighted
  n / P, where the paper and ``word2vec.c`` draw n negatives per example (the
  objective the program's default path trains; as ``sgns_ref.py``);
- each context row gets the true gradient, d loss / d h divided by |C|, where
  ``word2vec.c`` adds the whole ``neu1e`` to every context row (a step |C| times
  as long on syn0);
- the windows (which words are an example's context) are not drawn here: an
  example arrives as a center and a list of context words, the program's draws.

An example is given as ``centers[i]``, the first ``nctx[i]`` entries of
``contexts[i]`` (the rest of the row is ignored) and the pool: ragged lists in
one rectangular array, which is a storage format and not padding of the model.
"""

import jax
import jax.numpy as jnp


def batch_loss(tables, centers, contexts, nctx, negatives, num_negatives):
    """(summed loss over live examples, their count) on float32 (syn0, syn1)."""
    syn0, syn1 = tables
    p = negatives.shape[0]
    in_list = jnp.arange(contexts.shape[1])[None, :] < nctx[:, None]      # [B, C]
    live = nctx > 0
    h = (jnp.sum(jnp.where(in_list[:, :, None], syn0[contexts], 0.0), axis=1)
         / jnp.maximum(nctx, 1)[:, None].astype(jnp.float32))
    f_pos = jnp.sum(h * syn1[centers], axis=-1)
    f_neg = h @ syn1[negatives].T
    other = negatives[None, :] != centers[:, None]
    per_example = (-jax.nn.log_sigmoid(f_pos)
                   - (num_negatives / p)
                   * jnp.sum(jnp.where(other, jax.nn.log_sigmoid(-f_neg), 0.0), axis=-1))
    return jnp.sum(jnp.where(live, per_example, 0.0)), jnp.sum(live)


def cbow_step(syn0, syn1, centers, contexts, nctx, negatives, alpha, num_negatives):
    """One step on float32 tables. Returns (syn0, syn1, mean loss per live example)."""
    with jax.default_matmul_precision("highest"):
        (total, count), (g0, g1) = jax.value_and_grad(batch_loss, has_aux=True)(
            (syn0, syn1), centers, contexts, nctx, negatives, num_negatives)
    return (syn0 - alpha * g0, syn1 - alpha * g1,
            total / jnp.maximum(count, 1).astype(jnp.float32))


def leaf_norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def follow_steps(rows0_init, rows1_init, centers, contexts, nctx, negatives, alphas,
                 num_negatives):
    """Follow len(centers) steps on compact tables (indices are into them). Returns
    per-step losses, the per-leaf norms of the first step's change, and of the
    change after all."""
    step = jax.jit(cbow_step, static_argnums=(7,))
    s0, s1 = rows0_init, rows1_init
    losses, first = [], None
    for k in range(len(centers)):
        s0, s1, loss = step(s0, s1, centers[k], contexts[k], nctx[k], negatives[k],
                            jnp.float32(alphas[k]), num_negatives)
        losses.append(float(loss))
        if k == 0:
            first = (leaf_norm(s0 - rows0_init), leaf_norm(s1 - rows1_init))
    total = (leaf_norm(s0 - rows0_init), leaf_norm(s1 - rows1_init))
    return {"losses": losses, "first_change_norm": first, "change_norm": total}
