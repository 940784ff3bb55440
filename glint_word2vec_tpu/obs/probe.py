"""On-device health probe: ONE pass over each table of the params carry.

Extends the trainer's round-6 finiteness probe (a tiny ``isfinite().all()``
jit) into the instrumentation ROADMAP item 2 presupposes: the 1.6M-vocab
quality collapse is a FINITE norm blowup (purity 0.99 → 0.14, no NaN —
EVAL.md round-5 ladder), so the finiteness bit alone observes nothing until
long after the geometry is wrecked. The probe reads each matrix once: ONE
``lax.reduce`` over two operands a table (``_row_sums``) gives, per PADDED
row, the float32 sum of squares and the "any element not finite" bit, one
fusion with two results on the TPU; everything after it reads those vectors
(12 MB at 3M rows against the table's 4.6 GB). Written as two reductions,
each table is read twice: the compiler keeps the finite bit's reduce and
the sum's in fusions of their own, also where the bit is taken row-wise
beside the sum (24.4 of 26.3 ms a probe at 3M rows went to four such
passes: PERF.md §6, PRs 32 and 56). It returns, per matrix, over the first
``vocab_size`` entries of the sums (padding rows are zero by construction and
would poison every channel):

- ``max_norm`` / ``mean_norm`` — extremes and scale of the L2 row norms;
- ``p99_norm`` — bucketed (quarter-octave log2 buckets): an exact
  p99 needs a top-k/sort over [V], which at 10M rows is the same class of
  device work tools/model_ops_10m.py exists to avoid; the bucketed value is
  exact to one bucket (ratio ≤ 2^(1/4) ≈ 1.19), plenty for a blowup that
  moves norms by orders of magnitude;
- ``frac_over`` — fraction of rows with norm above the watchdog threshold
  (the channel the round-5 collapse is visible in long before the max).

Plus a whole-carry ``finite`` bit (over the PADDED matrices and over their
ELEMENTS — identical semantics to the old probe; never ``isfinite(norm)``: a
finite row of elements over 1.8e19 squares to inf and is a finite row). The
update-magnitude proxy (delta of ``mean_norm`` between consecutive probes) is
computed host-side by the trainer — it needs cross-probe state the pure
device function cannot hold.

Collective discipline: on a sharded mesh the reductions lower to collectives,
so the caller must drain the params carry before dispatching the probe and
fetch the result explicitly ("one collective program at a time",
docs/sharding.md) — the trainer's ``_health_stats`` owns that protocol.
Norm accumulation is ≥f32 regardless of param dtype (bf16 squares underflow
and the blowup channel saturates exactly where precision matters).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

# quarter-octave log2 buckets covering 2^-12 .. 2^20 — row norms outside that
# span clamp to the edge buckets (a healthy embedding sits around 2^0..2^4;
# the measured blowup runs orders of magnitude past 2^20 only after the
# watchdog should long have fired)
_HIST_LO = -12.0          # log2 of the smallest bucket edge
_HIST_PER_OCTAVE = 4
_HIST_BUCKETS = (20 - (-12)) * _HIST_PER_OCTAVE  # 128


class MatrixStats(NamedTuple):
    """Row-norm channels of one embedding matrix (real vocab rows only)."""

    max_norm: jax.Array    # f32 scalar
    mean_norm: jax.Array   # f32 scalar
    p99_norm: jax.Array    # f32 scalar — upper edge of the p99 bucket
    frac_over: jax.Array   # f32 scalar — fraction of rows with norm > threshold


class HealthStats(NamedTuple):
    """The fused probe's full result pytree."""

    finite: jax.Array      # bool scalar over the PADDED carry (old probe bit)
    syn0: MatrixStats
    # under config.loss="hs" syn1's rows are the Huffman tree's inner nodes,
    # not words: max_norm is where a diverging root shows first (ops/hs.py's
    # rule for a node many pairs share), and the last of the vocab_size rows
    # read is the unused row V − 1, which stays zero
    syn1: MatrixStats


def _row_sums(m: jax.Array):
    """One pass over a PADDED table: per row, the float32 sum of squares and
    whether any element is not finite.

    Keep it ONE ``lax.reduce`` over two operands (one fusion with two results
    on the TPU). ``jnp.sum(x * x, axis=1)`` beside ``jnp.any(~isfinite(m),
    axis=1)`` reads the table twice: the compiler folds the row-wise ``any``
    and the ``any`` over rows into a whole-table ``reduce_or`` of its own
    (tests/test_step_inplace_tpu.py compiles both forms)."""
    x = m.astype(jnp.float32)
    return jax.lax.reduce(
        (x * x, ~jnp.isfinite(m)), (jnp.float32(0.0), jnp.bool_(False)),
        lambda a, b: (a[0] + b[0], a[1] | b[1]), dimensions=(1,))


def _matrix_stats(sums: jax.Array, vocab_size: int, threshold: float) -> MatrixStats:
    # the vector is sliced, not the table: 12 MB at 3M rows, and the padding
    # rows stay out of every channel
    norms = jnp.sqrt(sums[:vocab_size])
    # bucketed p99: a row's bucket from log2(norm); the p99 bucket is the first
    # k with #{rows: bucket <= k} >= 99% of rows. No [V] sort/top-k, and no
    # histogram either: k is found by bisection over the 128 buckets, 7 counts
    # over [V] (0.012 ms each at 3M rows on a TPU v5 lite, where the 3M-index
    # scatter-add into s32[128] that used to build the histogram took 26.2 ms a
    # matrix a probe, 1.64 ms a training step; PERF.md §6, PR 32).
    logn = jnp.log2(jnp.maximum(norms, jnp.float32(2.0 ** _HIST_LO)))
    idx = jnp.clip(
        jnp.floor((logn - _HIST_LO) * _HIST_PER_OCTAVE),
        0, _HIST_BUCKETS - 1).astype(jnp.int32)
    # int32 counts are exact to 2^31 rows — far past the 10M-row north star
    target = jnp.int32(-(-vocab_size * 99 // 100))
    k, hi = jnp.int32(0), jnp.int32(_HIST_BUCKETS - 1)
    for _ in range((_HIST_BUCKETS - 1).bit_length()):
        mid = (k + hi) // 2
        enough = jnp.sum(idx <= mid, dtype=jnp.int32) >= target
        k, hi = jnp.where(enough, k, mid + 1), jnp.where(enough, mid, hi)
    p99 = jnp.exp2((k.astype(jnp.float32) + 1.0) / _HIST_PER_OCTAVE + _HIST_LO)
    return MatrixStats(
        max_norm=jnp.max(norms),
        mean_norm=jnp.mean(norms),
        p99_norm=p99,
        frac_over=jnp.mean((norms > jnp.float32(threshold)).astype(jnp.float32)),
    )


def make_health_probe(vocab_size: int, threshold: float) -> Callable:
    """Build the jitted fused probe: ``fn(params) -> HealthStats``.

    ``vocab_size`` (static) masks the padding rows out of every norm channel;
    ``threshold`` (static — a config constant, so baking it in costs no
    recompile churn) defines the ``frac_over`` channel."""

    def probe(params) -> HealthStats:
        sums0, bad0 = _row_sums(params.syn0)
        sums1, bad1 = _row_sums(params.syn1)
        # a test of the ELEMENTS, never of the sums: a finite row whose squares
        # overflow float32 has an infinite norm and is a finite row
        finite = ~(bad0.any() | bad1.any())
        if params.pos is not None:
            # the position weights (config.cbow_position_weights) are part of
            # the carry: a diverged leaf is a non-finite carry
            finite = finite & jnp.isfinite(params.pos).all()
        return HealthStats(
            finite=finite,
            syn0=_matrix_stats(sums0, vocab_size, threshold),
            syn1=_matrix_stats(sums1, vocab_size, threshold),
        )

    return jax.jit(probe)


def stats_to_channels(stats: "HealthStats") -> dict:
    """Flatten a FETCHED (host-side) HealthStats into the plain-float channel
    dict the heartbeat/sink/watchdog layers consume."""
    out = {"finite": bool(stats.finite)}
    for name in ("syn0", "syn1"):
        ms = getattr(stats, name)
        out[name] = {
            "max_norm": float(ms.max_norm),
            "mean_norm": float(ms.mean_norm),
            "p99_norm": float(ms.p99_norm),
            "frac_over": float(ms.frac_over),
        }
    return out
