"""On-device health probe: ONE fused stats reduction over the params carry.

Extends the trainer's round-6 finiteness probe (a tiny ``isfinite().all()``
jit) into the instrumentation ROADMAP item 2 presupposes: the 1.6M-vocab
quality collapse is a FINITE norm blowup (purity 0.99 → 0.14, no NaN —
EVAL.md round-5 ladder), so the finiteness bit alone observes nothing until
long after the geometry is wrecked. The probe reads each matrix once and
returns, per matrix, over the REAL vocab rows (padding rows are zero by
construction and would poison every channel):

- ``max_norm`` / ``mean_norm`` — extremes and scale of the L2 row norms;
- ``p99_norm`` — bucketed (quarter-octave log2 buckets): an exact
  p99 needs a top-k/sort over [V], which at 10M rows is the same class of
  device work tools/model_ops_10m.py exists to avoid; the bucketed value is
  exact to one bucket (ratio ≤ 2^(1/4) ≈ 1.19), plenty for a blowup that
  moves norms by orders of magnitude;
- ``frac_over`` — fraction of rows with norm above the watchdog threshold
  (the channel the round-5 collapse is visible in long before the max).

Plus a whole-carry ``finite`` bit (over the PADDED matrices — identical
semantics to the old probe). The update-magnitude proxy (delta of
``mean_norm`` between consecutive probes) is computed host-side by the
trainer — it needs cross-probe state the pure device function cannot hold.

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


def _matrix_stats(m: jax.Array, vocab_size: int, threshold: float) -> MatrixStats:
    rows = m[:vocab_size]
    norms = jnp.sqrt(jnp.sum(
        rows.astype(jnp.float32) * rows.astype(jnp.float32), axis=1))
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
        finite = (jnp.isfinite(params.syn0).all()
                  & jnp.isfinite(params.syn1).all())
        if params.pos is not None:
            # the position weights (config.cbow_position_weights) are part of
            # the carry: a diverged leaf is a non-finite carry
            finite = finite & jnp.isfinite(params.pos).all()
        return HealthStats(
            finite=finite,
            syn0=_matrix_stats(params.syn0, vocab_size, threshold),
            syn1=_matrix_stats(params.syn1, vocab_size, threshold),
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
