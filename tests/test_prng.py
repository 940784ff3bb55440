"""Tests for the counter-based hash PRNG (ops/prng.py) and the hot-path negative
sampler built on it (ops/sampler.sample_negatives_hash) — the source of every
production training negative, so its distribution and determinism are load-bearing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.ops.prng import hash_bits, randint_mod, uniform01
from glint_word2vec_tpu.ops.sampler import (
    build_alias_table,
    sample_negatives_hash,
    sampled_probabilities,
)


def test_hash_bits_deterministic_and_stream_separated():
    a = hash_bits(7, 0, jnp.int32(3), (256,))
    b = hash_bits(7, 0, jnp.int32(3), (256,))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # different seed / stream / counter each give a different grid
    for other in (hash_bits(8, 0, jnp.int32(3), (256,)),
                  hash_bits(7, 1, jnp.int32(3), (256,)),
                  hash_bits(7, 0, jnp.int32(4), (256,))):
        assert not np.array_equal(np.asarray(a), np.asarray(other))


def test_uniform01_range_and_mean():
    u = np.asarray(uniform01(1, 0, jnp.int32(0), (100_000,)))
    assert (u >= 0).all() and (u < 1).all()
    # mean/variance of U(0,1): 0.5 / 1/12 — loose 5-sigma bounds
    assert abs(u.mean() - 0.5) < 5 * (1 / np.sqrt(12 * u.size))
    # all 8 leading bits exercised (no stuck-bit degeneracy)
    assert len(np.unique((u * 256).astype(np.int32))) == 256


def test_randint_mod_uniformity_chi2():
    bound = 97  # prime, adversarial to power-of-two structure in the hash
    n = 200_000
    draws = np.asarray(randint_mod(3, 0, jnp.int32(5), (n,), bound))
    freq = np.bincount(draws, minlength=bound)
    expected = n / bound
    chi2 = ((freq - expected) ** 2 / expected).sum()
    # chi2 dof=96: mean 96, sd ~13.9; 5 sigma ≈ 165
    assert chi2 < 165, f"chi2 {chi2:.1f} too high — hash not uniform mod {bound}"


def test_sample_negatives_hash_matches_target_distribution():
    counts = np.array([1000, 400, 150, 60, 25, 10, 4, 1], dtype=np.float64)
    table = build_alias_table(counts, 0.75)
    draws = np.asarray(sample_negatives_hash(
        table.prob, table.alias, 11, jnp.int32(0), (200_000,)))
    freq = np.bincount(draws, minlength=counts.size) / 200_000
    np.testing.assert_allclose(freq, sampled_probabilities(counts, 0.75), atol=0.01)


def test_sample_negatives_hash_counter_advances():
    counts = np.arange(1, 101)
    table = build_alias_table(counts)
    a = sample_negatives_hash(table.prob, table.alias, 5, jnp.int32(1), (64, 5))
    b = sample_negatives_hash(table.prob, table.alias, 5, jnp.int32(1), (64, 5))
    c = sample_negatives_hash(table.prob, table.alias, 5, jnp.int32(2), (64, 5))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.shape == (64, 5)
    assert a.dtype == jnp.int32


def test_sample_negatives_hash_same_under_jit_and_eager():
    counts = np.arange(1, 51)
    table = build_alias_table(counts)
    eager = sample_negatives_hash(table.prob, table.alias, 9, jnp.int32(4), (128,))
    jitted = jax.jit(
        lambda p, a, c: sample_negatives_hash(p, a, 9, c, (128,))
    )(table.prob, table.alias, jnp.int32(4))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))


# The draw, held to NumPy bit for bit over the same lattice: whatever form the two
# look-ups take on a device (an element gather today; PERF.md §6, PR 32, has the
# 128-lane row form's probe), the negatives are `where(u < prob[b], b, alias[b])`.
# V under a lane row, a whole row, ragged, a multiple of 128, large and ragged;
# the shared pool's [K, P], a flat draw, and a per-example [K, B, n].
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("shape", [(16, 2048), (200_000,), (4, 4096, 5)],
                         ids=["pool", "flat", "per_example"])
@pytest.mark.parametrize("V", [100, 128, 1_001, 3 * 2 ** 14, 300_007])
def test_sample_negatives_hash_is_numpys_draw_bit_for_bit(V, shape, jitted):
    rng = np.random.default_rng(V)
    prob = rng.random(V, dtype=np.float32)
    prob[rng.random(V) < 0.2] = 1.0          # leftovers keep their own index
    alias = rng.integers(0, V, V, dtype=np.int32)
    seed, counter = np.uint32(0x9E3779B9), jnp.int32(48)
    buckets = np.asarray(randint_mod(seed, 0, counter, shape, V))
    u = np.asarray(uniform01(seed, 1, counter, shape))
    want = np.where(u < prob[buckets], buckets, alias[buckets])

    def draw(p, a, c):
        return sample_negatives_hash(p, a, seed, c, shape)
    got = (jax.jit(draw) if jitted else draw)(jnp.asarray(prob), jnp.asarray(alias), counter)
    assert got.dtype == jnp.int32 and got.shape == shape
    np.testing.assert_array_equal(np.asarray(got), want)
    # the [V, 1] form of the tables draws the same
    if not jitted:
        np.testing.assert_array_equal(
            np.asarray(draw(jnp.asarray(prob)[:, None], jnp.asarray(alias)[:, None], counter)),
            want)
