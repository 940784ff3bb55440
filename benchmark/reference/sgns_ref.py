"""Plain reference: shared-pool SGNS step and exact cosine top-k.

Straightforward float32 ``jax.numpy``, matmul precision "highest", no kernels,
no padding, no bfloat16 chain, no twins. It imports nothing of the program.

SGNS with a shared negative pool (the objective the program's default path
trains; departure from Mikolov et al. 2013, where each pair draws its own n
negatives): for a pair (c, x) and a pool Z of P words drawn from count^0.75,

    loss = -log s(u_c . v_x) - (n / P) * sum_{z in Z, z != x} log s(-u_c . v_z)

and one SGD step moves u_c, v_x and every v_z by -alpha * d loss, updates of
duplicate rows summed (word2vec's Hogwild sum semantics).
"""

import jax
import jax.numpy as jnp
import numpy as np


def sgns_step(syn0, syn1, centers, contexts, negatives, alpha, num_negatives):
    """One step on float32 tables. Returns (syn0, syn1, mean loss per pair)."""
    with jax.default_matmul_precision("highest"):
        p = negatives.shape[0]
        u, v, z = syn0[centers], syn1[contexts], syn1[negatives]
        f_pos = jnp.sum(u * v, axis=-1)
        f_neg = u @ z.T
        valid = (negatives[None, :] != contexts[:, None]).astype(jnp.float32)
        w = num_negatives / p
        g_pos = (1.0 - jax.nn.sigmoid(f_pos)) * alpha
        g_neg = -jax.nn.sigmoid(f_neg) * alpha * valid * w
        d_u = g_pos[:, None] * v + g_neg @ z
        d_v = g_pos[:, None] * u
        d_z = g_neg.T @ u
        loss = jnp.mean(-jax.nn.log_sigmoid(f_pos)
                        - w * jnp.sum(jax.nn.log_sigmoid(-f_neg) * valid, axis=-1))
        syn0 = syn0.at[centers].add(d_u)
        syn1 = syn1.at[contexts].add(d_v).at[negatives].add(d_z)
    return syn0, syn1, loss


def leaf_norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def follow_steps(rows0_init, rows1_init, centers, contexts, negatives, alphas,
                 num_negatives):
    """Follow len(centers) steps on compact tables. Returns per-step losses, the
    per-leaf norms of the first step's change, and of the change after all."""
    step = jax.jit(sgns_step, static_argnums=(6,))
    s0, s1 = rows0_init, rows1_init
    losses, first = [], None
    for k in range(len(centers)):
        s0, s1, loss = step(s0, s1, centers[k], contexts[k], negatives[k],
                            jnp.float32(alphas[k]), num_negatives)
        losses.append(float(loss))
        if k == 0:
            first = (leaf_norm(s0 - rows0_init), leaf_norm(s1 - rows1_init))
    total = (leaf_norm(s0 - rows0_init), leaf_norm(s1 - rows1_init))
    return {"losses": losses, "first_change_norm": first, "change_norm": total}


def cosine_scores(table_rows_fn, num_rows: int, query_rows, block: int = 1 << 19):
    """[Q, num_rows] exact cosines of ``query_rows`` ([Q, D] float32) against a
    table given as ``table_rows_fn(row_ids) -> [R, D] float32``, in row blocks."""
    @jax.jit
    def block_scores(q, ids):
        with jax.default_matmul_precision("highest"):
            t = table_rows_fn(ids)
            qn = q / jnp.maximum(jnp.linalg.norm(q, axis=1, keepdims=True), 1e-12)
            tn = jnp.linalg.norm(t, axis=1)
            dots = qn @ t.T
            return jnp.where(tn[None, :] > 0, dots / jnp.maximum(tn[None, :], 1e-12), 0.0)

    out = []
    for lo in range(0, num_rows, block):
        ids = jnp.arange(lo, lo + block, dtype=jnp.int32)
        sc = np.asarray(block_scores(query_rows, jnp.minimum(ids, num_rows - 1)))
        out.append(sc[:, :min(block, num_rows - lo)])
    return np.concatenate(out, axis=1)
