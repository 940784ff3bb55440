"""Counter-based stateless PRNG for the training hot path.

Why not ``jax.random``: the negative sampler only needs statistically-good,
reproducible draws, not crypto-strength ones, and the training programs need them to
be a pure function of (seed, step) that is identical on every backend and every
device. So the hot path uses a murmur3-finalizer hash over a (seed, stream, counter,
lane) lattice — pure vectorizable integer ops (the reference's shared-seed trick, G3
mllib:419-421, survives as: all shards derive the same negatives from the same step
counter for free). What it costs on this installation's chip (TPU v5 lite; PERF.md §6,
PR 32): the whole (16, 2048) draw of a dispatch, hash lattice and both alias look-ups,
1.10 ms alone and under 0.5 ms inside the trainer's chunk, 0.03 ms a step. The
"~2.2 ms a step with one ``jax.random.randint``" of this header's earlier versions was
another installation's reading and has not been taken again.

``jax.random`` remains in use for one-time work outside the step (embedding init).
"""

from __future__ import annotations

from typing import Tuple, Union

import jax
import jax.numpy as jnp

_GOLDEN = 0x9E3779B9  # 2^32 / phi — Weyl-sequence increment


def mix32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer: full avalanche on uint32."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def hash_bits(
    seed: Union[int, jax.Array],
    stream: int,
    counter: jax.Array,
    shape: Tuple[int, ...],
) -> jax.Array:
    """uint32 grid of pseudo-random bits, a pure function of
    (seed, stream, counter, flat index).

    ``stream`` separates independent uses at the same counter (e.g. bucket draw vs
    keep/alias draw); ``counter`` is typically the global step.
    """
    n = 1
    for d in shape:
        n *= d
    i = jax.lax.iota(jnp.uint32, n)
    s = jnp.asarray(seed).astype(jnp.uint32) * jnp.uint32(_GOLDEN)
    c = jnp.asarray(counter).astype(jnp.uint32)
    base = mix32(c ^ mix32(s ^ jnp.uint32(stream * 0x7FEB352D + 0x68E31DA4)))
    return mix32(i ^ base).reshape(shape)


def uniform01(
    seed: Union[int, jax.Array],
    stream: int,
    counter: jax.Array,
    shape: Tuple[int, ...],
) -> jax.Array:
    """float32 uniforms in [0, 1) with 24 bits of mantissa entropy."""
    bits = hash_bits(seed, stream, counter, shape)
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def randint_mod(
    seed: Union[int, jax.Array],
    stream: int,
    counter: jax.Array,
    shape: Tuple[int, ...],
    bound: int,
) -> jax.Array:
    """int32 draws in [0, bound) via modulo. Bias is ≤ bound/2^32 relative
    (2e-3 ppm at bound = 10M) — negligible for negative sampling."""
    bits = hash_bits(seed, stream, counter, shape)
    return (bits % jnp.uint32(bound)).astype(jnp.int32)
