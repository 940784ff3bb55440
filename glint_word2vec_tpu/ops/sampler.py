"""On-device unigram negative sampler (replaces reference component G7).

The reference materializes a server-resident unigram table of ``unigramTableSize`` entries
(default 10^8 — 400 MB of int32; mllib:81,234-244, built fork-side from broadcast vocab
counts, mllib:317,355-359) and draws negatives by indexing it with a shared seed so every
parameter-server shard samples identical negatives without communicating them (G3 contract,
mllib:419-421).

TPU-native replacement: a **Walker alias table** over the counts^0.75 unigram distribution —
O(2·vocab) memory instead of O(table_size), *exact* (no quantization), sampled fully
on-device with ``jax.random`` in O(1) per draw. The shared-seed trick survives as ordinary
functional PRNG: every device derives the same per-step key, so data-parallel replicas and
model shards agree on negatives for free.

A quantized table-based sampler (:func:`build_unigram_table`) is kept for distribution-parity
tests against the classic word2vec table semantics.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AliasTable(NamedTuple):
    """Walker alias method tables for a categorical distribution over vocab rows.

    prob[i] ∈ [0,1]: probability of keeping bucket i's own index; alias[i]: the index drawn
    otherwise. Both shape [vocab_size]; small and replicable across the mesh.
    """

    prob: jax.Array   # float32 [V]
    alias: jax.Array  # int32 [V]

    @property
    def vocab_size(self) -> int:
        return self.prob.shape[0]


# Fixed partition fan-out of the parallel alias build. A CONSTANT (never a
# function of the worker count), so the table is deterministic per
# (counts, power) — a worker knob that changed the realized negative-sample
# stream would make throughput settings quality-relevant.
_ALIAS_PARTITIONS = 16
_ALIAS_PARTITION_MIN_V = 1 << 18


def _alias_pair_sweep(scaled: np.ndarray, prob: np.ndarray, alias: np.ndarray,
                      small: np.ndarray, large: np.ndarray):
    """Vose pairing over the given small/large index queues, vectorized by
    CUMULATIVE MATCHING: one round assigns EVERY coverable small bucket to a
    large donor by aligning the cumulative deficit (1 − scaled[small]) against
    the cumulative surplus (scaled[large] − 1) with a searchsorted — O(V log V)
    across a handful of rounds, vs the old one-small-per-large round pairing
    whose 10k+ rounds of queue concatenation dominated the 10M-vocab build
    (PERF.md §10). A donor pushed below residual 1 demotes to the small queue
    (classic Vose), and the fp endgame — total remaining surplus smaller than
    the first deficit — falls back to one literal Vose pairing round, which
    absorbs the rounding imbalance exactly like the old builder. Mutates
    prob/alias/scaled in place; returns the leftover (small, large) queues
    (numerically ≈1 entries, finalized by the caller).

    Exactness: any pairing order yields an exact table — correctness only
    needs each bucket's kept probability plus its inbound alias mass to equal
    ``scaled`` — and both branches maintain that invariant; the construction
    is deterministic (fixed queue orders, no RNG)."""
    while small.size and large.size:
        d = 1.0 - scaled[small]
        j = np.searchsorted(np.cumsum(scaled[large] - 1.0), np.cumsum(d),
                            side="left")
        assigned = j < large.size
        if assigned.any():
            sa, ja = small[assigned], j[assigned]
            prob[sa] = scaled[sa]
            alias[sa] = large[ja]
            take = np.bincount(ja, weights=d[assigned], minlength=large.size)
            scaled[large] -= take
            now_small = scaled[large] < 1.0
            small = np.concatenate([small[~assigned], large[now_small]])
            large = large[~now_small]
        else:
            k = min(small.size, large.size)
            s, small = small[:k], small[k:]
            l = large[:k]
            prob[s] = scaled[s]
            alias[s] = l
            scaled[l] -= 1.0 - scaled[s]
            now_small = scaled[l] < 1.0
            small = np.concatenate([small, l[now_small]])
            large = np.concatenate([l[~now_small], large[k:]])
    return small, large


def build_alias_table(counts: np.ndarray, power: float = 0.75,
                      workers: int = 1) -> AliasTable:
    """Build alias tables for p(w) ∝ counts[w]^power (classic word2vec 3/4 power).

    Host-side Vose construction, vectorized by cumulative matching
    (:func:`_alias_pair_sweep`). Above ``_ALIAS_PARTITION_MIN_V`` rows the
    build is PARTITIONED: a fixed ``_ALIAS_PARTITIONS``-way strided split (the
    stride interleaves the Zipf head so every partition gets a balanced
    small/large mix) is swept per partition — independently, on ``workers``
    threads when ``workers > 1`` (numpy releases the GIL in the hot ops) —
    and the per-partition leftovers merge through one final sweep. The
    partition count is a constant, never the worker count, so the table is
    deterministic per (counts, power) at ANY ``workers``; partitions touch
    disjoint index sets, so concurrent in-place writes never overlap.

    **Rebuild vs incremental (continual training, docs/continual.md):** a
    vocab extension / counts merge REBUILDS the table from the merged
    counts rather than patching the old one — there is no incremental
    update path, by design. The rebuilt table is *distribution-exact* for
    the merged counts (the alias construction is exact for any counts;
    pinned by the implied-distribution equality test at an extended vocab,
    tests/test_continual.py), but the (prob, alias) PAIRING differs from
    the old table's, so the REALIZED negative-sample stream after an
    increment is not a continuation of the pre-increment stream — the same
    cross-release caveat as the round-8 vectorized builder (PERF.md §10,
    config.io_workers note). Continual increments may legally change the
    negative stream; only the sampled distribution is contractual.
    """
    counts = np.asarray(counts, dtype=np.float64)
    if counts.ndim != 1 or counts.size == 0:
        raise ValueError("counts must be a nonempty 1-D array")
    weights = np.power(np.maximum(counts, 0.0), power)
    total = weights.sum()
    if total <= 0:
        raise ValueError("all counts are zero")
    V = counts.size
    scaled = weights * (V / total)  # mean 1.0
    prob = np.ones(V, dtype=np.float64)
    alias = np.arange(V, dtype=np.int64)

    if V >= _ALIAS_PARTITION_MIN_V:
        P = _ALIAS_PARTITIONS

        def sweep_partition(c: int):
            idx = np.arange(c, V, P)
            sc = scaled[idx]
            return _alias_pair_sweep(
                scaled, prob, alias, idx[sc < 1.0], idx[sc >= 1.0])

        # R1 determinism audit (ISSUE 5): this fan-out is ordered-merge safe —
        # partitions mutate disjoint strided index sets and the leftovers are
        # consumed in partition order below — so it routes through the one
        # blessed pool primitive instead of an ad-hoc executor. workers<=1
        # degrades to the same serial loop as before inside ordered_pool_map.
        from glint_word2vec_tpu.data.pipeline import ordered_pool_map
        leftovers = list(ordered_pool_map(
            sweep_partition, range(P), workers=min(workers, P)))
        small = np.concatenate([s for s, _ in leftovers])
        large = np.concatenate([l for _, l in leftovers])
    else:
        small = np.flatnonzero(scaled < 1.0)
        large = np.flatnonzero(scaled >= 1.0)
    small, large = _alias_pair_sweep(scaled, prob, alias, small, large)
    # leftovers are numerically ≈1: keep their own index
    prob[small] = 1.0
    prob[large] = 1.0
    return AliasTable(
        prob=jnp.asarray(prob, dtype=jnp.float32),
        alias=jnp.asarray(alias, dtype=jnp.int32),
    )


def sample_negatives(
    table: AliasTable, key: jax.Array, shape: Tuple[int, ...]
) -> jax.Array:
    """Draw negative word indices with p ∝ counts^power, fully on-device, any shape.

    Two uniforms per draw: bucket u1·V, then keep-vs-alias on u2 < prob[bucket].

    NOTE: uses ``jax.random`` (threefry): for one-off draws and tests. The training
    programs draw through :func:`sample_negatives_hash`, whose stream is a pure
    function of (seed, step) on every backend (ops/prng.py).
    """
    k1, k2 = jax.random.split(key)
    V = table.vocab_size
    buckets = jax.random.randint(k1, shape, 0, V, dtype=jnp.int32)
    u = jax.random.uniform(k2, shape, dtype=jnp.float32)
    keep = u < table.prob[buckets]
    return jnp.where(keep, buckets, table.alias[buckets])


def sample_negatives_hash(
    prob: jax.Array,    # [V] or [V, 1] float32 — pass as a jit ARGUMENT, not a closure
    alias: jax.Array,   # [V] or [V, 1] int32 — same
    seed,
    counter: jax.Array,
    shape: Tuple[int, ...],
) -> jax.Array:
    """Hot-path sampler: same alias-method draw as :func:`sample_negatives`, but from
    the counter-based hash PRNG (ops/prng.py) — deterministic in (seed, counter).

    The two look-ups compile, on a TPU v5 lite, to element gathers
    (``gather(f32[V], s32[N]), slice_sizes={1}``, each behind a prefetch of the
    whole table into fast memory; the ``reshape(V, 1)`` below is folded away).
    Measured there (PERF.md §6, PR 32): the shared pool's (16, 2048) draw alone
    1.10 ms a call at V = 3M and at 10M, and inside the trainer's own chunk
    0.234 ms a look-up a dispatch of 16 steps, 0.03 ms a step for both. Reading
    the tables as 128-lane rows halves that (0.55 ms a call) for 0.02 ms a step
    and a second arm for draws too large for a ``[N, 128]`` block: not built.

    The tables must be passed into the enclosing jit as arguments: a closure-
    captured table is baked into the program as a constant.
    """
    from glint_word2vec_tpu.ops.prng import randint_mod, uniform01

    V = prob.shape[0]
    with jax.named_scope("sgns.sample"):
        prob2 = prob.reshape(V, 1)
        alias2 = alias.reshape(V, 1)
        buckets = randint_mod(seed, 0, counter, shape, V)
        u = uniform01(seed, 1, counter, shape)
        flat = buckets.reshape(-1)
        keep = u < prob2[flat][:, 0].reshape(shape)
        return jnp.where(keep, buckets, alias2[flat][:, 0].reshape(shape))


def sampled_probabilities(counts: np.ndarray, power: float = 0.75) -> np.ndarray:
    """Exact target distribution, for tests: p(w) = counts^power / Σ counts^power."""
    w = np.power(np.asarray(counts, dtype=np.float64), power)
    return w / w.sum()


def build_unigram_table(counts: np.ndarray, table_size: int, power: float = 0.75) -> np.ndarray:
    """Classic word2vec quantized unigram table (the reference's G7 semantics,
    unigramTableSize entries, mllib:81,234-244): entry j holds the word whose cumulative
    counts^power mass covers j/table_size. Kept for parity testing only — the alias sampler
    is exact and O(vocab)."""
    p = sampled_probabilities(counts, power)
    cdf = np.cumsum(p)
    grid = (np.arange(table_size, dtype=np.float64) + 0.5) / table_size
    return np.searchsorted(cdf, grid).astype(np.int32)
