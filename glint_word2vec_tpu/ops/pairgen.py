"""On-device pair generation: subsample + dynamic-window expansion inside jit.

The host pipeline (data/pipeline.py `_block_pairs`, the C4/C5/C6 replacement) ships
4 bytes per training pair (packed uint16 centers+contexts). Where the host→device link
is thin — a DCN-fed multi-host pod, a slow PCIe hop — the *feed*, not the host CPU and
not the device step, caps end-to-end throughput. Moving the last two pipeline stages into the
jitted step shrinks the wire format to raw token blocks (~2.1 bytes per token ≈ 1 byte
per pair): the device re-derives every random decision from the same position-keyed
murmur3 lattice as the host (:mod:`glint_word2vec_tpu.data.hashrng`, mirrored by
``native/pairgen.cpp``), so the device stream is **bit-identical** to the host stream
(asserted by tests/test_device_pairgen.py).

Reference parity: this computes the same subsample rule (mllib:371-379, intended float
semantics — see pipeline.py module docstring for the reference's integer-division
no-op) and the same legacy asymmetric window (``b = nextInt(window)``, context span
``[max(0, i-b), min(i+b, len))`` exclusive of ``i``, mllib:381-390), keyed by the raw
token ordinal within (seed, stream, iteration, shard).

Shape discipline: everything is fixed-shape. A step receives T token slots (whole
sentences, zero-padded, ``n_valid`` real) and emits exactly B pair slots; if the drawn
windows yield more than B pairs the tail pairs of the block are dropped (counted and
reported by the trainer), if fewer the tail slots are masked. The host packer targets
~0.85 fill so drops stay rare.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = 0x9E3779B9


def _u32(x) -> jax.Array:
    return jnp.asarray(x, jnp.uint32)


def mix32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer — jnp twin of data/hashrng.mix32 (bit-identical)."""
    x = _u32(x)
    x = (x ^ (x >> 16)) * _u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * _u32(0xC2B2AE35)
    return x ^ (x >> 16)


def hash_bits_at(base: jax.Array, ord_lo: jax.Array, ord_hi: jax.Array) -> jax.Array:
    """uint32 bits for 64-bit ordinals given as (lo, hi) uint32 halves — twin of
    data/hashrng.hash_bits_at (which takes uint64; jax runs without x64)."""
    return mix32(ord_lo ^ mix32(ord_hi ^ _u32(0xDEADBEEF)) ^ base)


def hash_u01_at(base, ord_lo, ord_hi) -> jax.Array:
    """float32 uniforms in [0, 1) with 24 mantissa bits — twin of hashrng.hash_u01_at.
    Exact: (bits >> 8) ≤ 2^24 is exactly representable, 2^-24 is a power of two."""
    bits = hash_bits_at(base, ord_lo, ord_hi)
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def hash_mod_at(base, ord_lo, ord_hi, bound: int) -> jax.Array:
    """draws in [0, bound) — twin of hashrng.hash_mod_at (same modulo bias)."""
    return (hash_bits_at(base, ord_lo, ord_hi) % _u32(bound)).astype(jnp.int32)


def _cumsum_i32(x: jax.Array) -> jax.Array:
    """Inclusive int32 cumsum via a two-level (row-matmul + row-offset) decomposition.

    XLA's 1-D cumulative ops on TPU cost ~0.45 ms at 28k elements (measured);
    reshaping to [rows, 128] and doing the within-row prefix sum as a triangular
    matmul cuts that ~4x. Exactness: every use here sums counts bounded by the
    block size (< 2^24), so the f32 matmul is exact.
    """
    n = x.shape[0]
    rows = -(-n // 128)
    xp = jnp.pad(x, (0, rows * 128 - n)).reshape(rows, 128).astype(jnp.float32)
    tri = jnp.tril(jnp.ones((128, 128), jnp.float32)).T  # [i, j] = 1 iff i <= j
    within = xp @ tri                                    # inclusive row prefix sums
    row_offs = jnp.cumsum(within[:, -1]) - within[:, -1]  # tiny [rows] scan
    return (within + row_offs[:, None]).reshape(-1)[:n].astype(jnp.int32)


class DevicePairs(NamedTuple):
    centers: jax.Array    # int32 [B]
    contexts: jax.Array   # int32 [B]
    mask: jax.Array       # float32 [B] — 1.0 for real pairs
    kept_words: jax.Array  # int32 [] — tokens surviving subsampling this step
    dropped_pairs: jax.Array  # int32 [] — pairs beyond the B slots (lost to overflow)


def device_block_pairs(
    tokens: jax.Array,      # int32/uint16 [T] — raw (NOT subsampled) token ids,
                            # whole sentences, zero-padded past n_valid
    start_bits: jax.Array,  # uint8 [ceil(T/8)] — bit t set ⟺ sentence starts at slot t
    n_valid: jax.Array,     # int32 [] — real token count
    ord_lo: jax.Array,      # uint32 [] — raw-token ordinal of slot 0, low 32 bits
    ord_hi: jax.Array,      # uint32 [] — high 32 bits
    keep_prob: jax.Array,   # float32 [V_pad] — per-word keep probability (C5)
    sub_base: jax.Array,    # uint32 [] — hashrng stream base for STREAM_SUBSAMPLE
    win_base: jax.Array,    # uint32 [] — stream base for STREAM_WINDOW
    window: int,
    num_pairs: int,         # B — output pair slots
    legacy_asymmetric_window: bool = True,
    presubsampled: bool = False,
) -> DevicePairs:
    """One step's (centers, contexts, mask) from a raw token block — C5+C6 on device.

    Mirrors data/pipeline._block_pairs stage for stage; every intermediate is
    fixed-shape [T] or [B]:

      1. subsample: keep ⟺ hash_u01(ordinal) ≤ keep_prob[token]   (mllib:371-379)
      2. compact kept tokens to the front (cumsum + scatter)
      3. segmented positions: pos-in-sentence and distance-to-sentence-end of the
         *subsampled* sentence (windows span the compacted sentence, like the host)
      4. window draw b = hash % window keyed by the raw ordinal    (mllib:384-388)
      5. ragged pair expansion inverted with searchsorted over the cumulative
         per-token pair counts (the jit-able form of numpy's repeat())

    ``presubsampled=True`` is the trainer's production mode: the host packer already
    applied the subsample rule (same hashrng draws on raw ordinals), so the block
    contains only kept tokens — stages 1–2 vanish (no compaction scatter/cumsums),
    the wire carries ~keep_ratio× fewer tokens, and the lr clock is exact. Window
    draws are then keyed by the KEPT-token ordinal (contiguous across blocks);
    statistically identical to raw-ordinal keying, and bit-identical to the host
    ``_block_pairs`` run on the same kept stream with keep ≡ 1.
    """
    T = tokens.shape[0]
    B = num_pairs
    t = jnp.arange(T, dtype=jnp.int32)
    valid = t < n_valid
    tok = tokens.astype(jnp.int32)

    # -- ordinals of each slot as uint32 (lo, hi) with carry ------------------------
    lo = ord_lo + t.astype(jnp.uint32)
    hi = ord_hi + (lo < ord_lo).astype(jnp.uint32)

    # -- sentence ids on the raw stream ---------------------------------------------
    is_start = ((start_bits[t >> 3] >> (t & 7).astype(jnp.uint8)) & 1).astype(
        jnp.bool_) & valid
    sid = _cumsum_i32(is_start.astype(jnp.int32))      # [T] raw sentence id (≥1)

    if presubsampled:
        # host already dropped subsampled tokens — the block IS the kept stream
        n_kept = n_valid
        comp_tok, comp_lo, comp_hi = tok, lo, hi
        ck = valid
        comp_sid = jnp.where(ck, sid, -1)
    else:
        # -- 1. subsample ------------------------------------------------------------
        u = hash_u01_at(sub_base, lo, hi)
        kept = valid & (u <= keep_prob[tok])
        kept_i = kept.astype(jnp.int32)
        n_kept = kept_i.sum()

        # -- 2. compact kept tokens (ONE scatter of the source permutation; scatters
        # are the expensive op on TPU — PERF.md — everything else routes via gathers)
        kpos = _cumsum_i32(kept_i) - 1                 # compact index of kept slots
        dst = jnp.where(kept, kpos, T)                 # OOB → dropped
        comp_src = jnp.zeros(T, jnp.int32).at[dst].set(t, mode="drop")
        comp_tok = tok[comp_src]
        comp_lo = lo[comp_src]
        comp_hi = hi[comp_src]
        # a kept token opens a compacted sentence iff it is the first kept token of
        # its raw sentence: diff the raw sentence ids on the compacted stream
        comp_sid = sid[comp_src]
        ck = t < n_kept                                # valid compacted slots
        comp_sid = jnp.where(ck, comp_sid, -1)
    prev_sid = jnp.concatenate([jnp.full(1, -2, jnp.int32), comp_sid[:-1]])
    new_sent = (comp_sid != prev_sid) & ck

    # -- 3. segmented position / distance-to-end on the compacted stream -------------
    seg_base = jax.lax.cummax(jnp.where(new_sent, t, 0))
    pos = t - seg_base                                 # kept-position in sentence
    # next sentence start at or after t+1 (sentinel n_kept) → distance to sentence end
    ns = jnp.where(new_sent, t, T)
    ns_next = jnp.concatenate([ns[1:], jnp.full(1, T, jnp.int32)])
    seg_end = jnp.flip(jax.lax.cummin(jnp.flip(ns_next)))
    seg_end = jnp.minimum(seg_end, n_kept)             # [T] one-past-last of sentence
    right_avail = seg_end - 1 - t

    # -- 4. window draw (keyed by RAW ordinal, like the host) -------------------------
    b = hash_mod_at(win_base, comp_lo, comp_hi, window)
    left = jnp.minimum(b, pos)
    right_extent = b - 1 if legacy_asymmetric_window else b
    right = jnp.clip(jnp.minimum(right_extent, right_avail), 0, None)
    total = jnp.where(ck, left + right, 0)

    # -- 5. ragged expansion: invert the cumulative pair counts ----------------------
    # The queries are arange(B), so the searchsorted inverse collapses to a scatter
    # of +1 marks at each token's first pair slot followed by a cumsum — one [T]-row
    # scatter (ascending indices) + one [B] cumsum, ~10x cheaper than searchsorted's
    # sequential scan method on TPU (measured; empty groups resolve correctly
    # because their marks stack on the next group's start slot).
    offs = _cumsum_i32(total)                          # [T] inclusive
    total_pairs = offs[-1]
    k = jnp.arange(B, dtype=jnp.int32)
    group_start = offs - total
    marks = jnp.zeros(B, jnp.int32).at[group_start].add(
        1, mode="drop", indices_are_sorted=True)
    src = _cumsum_i32(marks) - 1                       # [B] source token per slot
    src_c = jnp.clip(src, 0, T - 1)
    # one [B, 3] row gather instead of three [B] scalar gathers (group start,
    # window left bound, center token travel together)
    packed = jnp.stack([group_start, left, comp_tok], axis=1)   # [T, 3]
    g = packed[src_c]                                  # [B, 3]
    j = k - g[:, 0]
    left_s = g[:, 1]
    ctx = src_c - left_s + j + (j >= left_s)
    ctx_c = jnp.clip(ctx, 0, T - 1)
    mask = (k < jnp.minimum(total_pairs, B)).astype(jnp.float32)
    centers = jnp.where(mask > 0, g[:, 2], 0)
    contexts = jnp.where(mask > 0, comp_tok[ctx_c], 0)
    return DevicePairs(
        centers=centers, contexts=contexts, mask=mask,
        kept_words=n_kept,
        dropped_pairs=jnp.maximum(total_pairs - B, 0))


class CbowBand(NamedTuple):
    """Per-slot CBOW window geometry over a sentence-contiguous token block —
    the device-side contract of the banded CBOW step (ops/cbow_banded.py)."""

    left: jax.Array    # int32 [T] — context extent to the left of each slot
    right: jax.Array   # int32 [T] — context extent to the right
    center: jax.Array  # float32 [T] — 1.0 where the slot is a CORE center
                       # (trains an example this block; halo slots are 0)
    token: jax.Array   # float32 [T] — 1.0 for valid token slots (slots that may
                       # receive context gradient; zero-padding is 0)


def device_cbow_windows(
    tokens: jax.Array,      # int32/uint16 [T] — KEPT (presubsampled) tokens,
                            # sentence-contiguous, ±halo overlap at block edges
    start_bits: jax.Array,  # uint8 [ceil(T/8)] — bit t set ⟺ sentence starts at t
    n_valid: jax.Array,     # int32 [] — real token slots (prefix)
    ord_lo: jax.Array,      # uint32 [] — kept-token ordinal of slot 0, low 32 bits
    ord_hi: jax.Array,      # uint32 [] — high 32 bits
    win_base: jax.Array,    # uint32 [] — hashrng stream base for STREAM_WINDOW
    window: int,
    halo: int,              # core slots are [halo, T - halo); needs halo >= window
    legacy_asymmetric_window: bool = True,
) -> CbowBand:
    """Per-slot CBOW window extents from the hash lattice — the banded analog of
    :func:`device_block_pairs` stages 3–4, skipping the ragged pair expansion:
    the banded step (:func:`glint_word2vec_tpu.ops.cbow_banded.cbow_step_banded_core`)
    consumes (left, right) intervals directly instead of materialized pairs.

    The block is the kept-token stream cut with a ±``halo`` overlap
    (:func:`glint_word2vec_tpu.data.pipeline.pack_halo_token_blocks`), so window
    clamping is EXACT for every core slot with ``halo >= window``:

    - left: ``l = min(b, pos)`` with pos measured from the last in-block sentence
      start (slot 0 as implicit base). If the sentence started before the block,
      ``pos >= t >= halo > b`` and the clamp never binds — identical to the true
      stream. If it started in-block the start bit makes pos exact.
    - right: ``r`` is clamped by the next in-block start bit or ``n_valid``. A
      sentence end within reach of a core slot (r ≤ window-1 < halo) always has
      its successor's start bit (or the stream end) inside the block, so the
      clamp is exact too.

    Window draws are keyed by the kept-token ordinal (``ord_base + t``), the same
    key :func:`device_block_pairs` uses under ``presubsampled=True`` — a token
    draws the same window in every block that holds it (halo or core).
    """
    T = tokens.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    valid = t < n_valid

    lo = ord_lo + t.astype(jnp.uint32)
    hi = ord_hi + (lo < ord_lo).astype(jnp.uint32)

    is_start = ((start_bits[t >> 3] >> (t & 7).astype(jnp.uint8)) & 1).astype(
        jnp.bool_) & valid
    seg_base = jax.lax.cummax(jnp.where(is_start, t, 0))
    pos = t - seg_base
    ns = jnp.where(is_start, t, T)
    ns_next = jnp.concatenate([ns[1:], jnp.full(1, T, jnp.int32)])
    seg_end = jnp.flip(jax.lax.cummin(jnp.flip(ns_next)))
    seg_end = jnp.minimum(seg_end, n_valid)
    right_avail = seg_end - 1 - t

    b = hash_mod_at(win_base, lo, hi, window)
    left = jnp.minimum(b, pos)
    right_extent = b - 1 if legacy_asymmetric_window else b
    right = jnp.clip(jnp.minimum(right_extent, right_avail), 0, None)
    left = jnp.where(valid, left, 0)
    right = jnp.where(valid, right, 0)
    core = (t >= halo) & (t < T - halo) & valid
    return CbowBand(
        left=left, right=right,
        center=core.astype(jnp.float32),
        token=valid.astype(jnp.float32))


def pack_start_bits(lengths: np.ndarray, T: int) -> np.ndarray:
    """Host-side: sentence lengths → the packed start-bit array a step ships.

    uint8 [ceil(T/8)], bit t set iff a sentence begins at token slot t. Padding
    slots carry no bits (they are already masked by n_valid on device).
    """
    bits = np.zeros((T + 7) // 8, np.uint8)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]]).astype(np.int64)
    starts = starts[starts < T]
    np.bitwise_or.at(bits, starts >> 3, (1 << (starts & 7)).astype(np.uint8))
    return bits
