"""Tables made on the device from the seed, by a formula the benchmark owns.

Every element is a pure function of (seed, stream, row, column): a murmur3
finalizer over the flat index. So the program's tables are made in one jitted
call, shard by shard under ``out_shardings`` (no [V, D] array ever sits on one
chip or on the host), and the plain reference makes the rows IT needs from the
same formula without taking anything the program holds.
"""

import jax
import jax.numpy as jnp
import numpy as np


def _mix32(x):
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def seed32(seed: int) -> np.uint32:
    """--seed may pass 2**31; the hash takes its low 32 bits, as an ARGUMENT of
    the jitted call (a closed-over seed would compile a new program per seed)."""
    return np.uint32(int(seed) & 0xFFFFFFFF)


def rows_uniform(seed, stream: int, rows, dim: int, padded_dim: int,
                 half_width: float, dtype=jnp.float32):
    """[len(rows), padded_dim]: U(-half_width, half_width) in the first ``dim``
    columns, zeros in the lane padding. ``rows`` are int32 row ids, ``seed`` a
    uint32 scalar (traced or not)."""
    key = _mix32(jnp.asarray(seed).astype(jnp.uint32)
                 ^ jnp.uint32((stream * 0x7FEB352D + 0x68E31DA4) & 0xFFFFFFFF))
    cols = jax.lax.iota(jnp.uint32, padded_dim)
    flat = rows.astype(jnp.uint32)[:, None] * jnp.uint32(padded_dim) + cols[None, :]
    bits = _mix32(flat ^ key)
    u = (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    vals = (u - 0.5) * jnp.float32(2.0 * half_width)
    return jnp.where(cols[None, :] < dim, vals, 0.0).astype(dtype)


def make_table(seed, stream: int, num_rows: int, dim: int, padded_dim: int,
               half_width: float, dtype, sharding=None):
    """The whole [num_rows, padded_dim] table in one jitted call."""
    fn = jax.jit(
        lambda s: rows_uniform(s, stream, jax.lax.iota(jnp.int32, num_rows),
                               dim, padded_dim, half_width, dtype),
        out_shardings=sharding)
    return fn(seed32(seed))


def make_zeros(num_rows: int, padded_dim: int, dtype, sharding=None):
    return jax.jit(lambda: jnp.zeros((num_rows, padded_dim), dtype),
                   out_shardings=sharding)()
