"""Per-phase host time attribution: log2 histograms over per-chunk durations.

The trainer has carried exactly two aggregate timers since round 1
(``host_wait_time`` / ``dispatch_time``) — enough to say "the host starved
the device", not enough to say *which* stage did, or whether the tail of a
distribution (one slow chunk every N) is what ate the run. This module is
the host-side twin of the probe's quarter-octave log2 histogram trick
(obs/probe.py: a bucketed quantile is exact to one bucket, ratio ≤ 2^0.25,
with no sort) applied to wall-clock durations, so "where did the time go"
is answerable per-run from the telemetry JSONL alone — no Perfetto trace
load needed.

Phases (one histogram each, docs/observability.md):

- ``producer_wait`` — fit() blocked on the next chunk/round (the fit
  loop's wait, or a gathering feed's own for its local chunk);
- ``stage``         — feed device-put + transfer-forcing touch
  (``stage_put``) and the sharded handshake's ``allgather_fetch``;
- ``dispatch``      — per-round step dispatch (incl. meta staging);
- ``device_block``  — explicit device syncs: the fused health probe, the
  heartbeat metrics fetch, and the CPU-mesh collective-serialization drain.

Durations arrive two ways: the span tracer tees every span whose name maps
to a phase (``spans._PHASE_OF``) into the accumulator attached for the run,
and the trainer adds the non-span waits directly. Buckets cover 2^-20 s
(~1 µs) to 2^6 s (64 s) at 4 buckets/octave — 104 buckets; durations
outside clamp to the edge buckets. Thread-safe: producer/stager threads add
concurrently with the main loop under one lock (an add is an int increment
+ two float adds — never contended for longer than that).

Disabled accumulators (telemetry and statusd both off) cost one attribute
check per add — and the span tee skips even that when no accumulator is
attached, so the telemetry-off fit path is unchanged.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Optional
from glint_word2vec_tpu.lockcheck import make_rlock

# quarter-octave log2 buckets over 2^-20 .. 2^6 seconds (~1 µs .. 64 s);
# same bucketing discipline as obs/probe.py's norm histogram
HIST_LO = -20            # log2 seconds of the smallest bucket edge
HIST_PER_OCTAVE = 4
HIST_BUCKETS = (6 - HIST_LO) * HIST_PER_OCTAVE  # 104

PHASES = ("producer_wait", "stage", "dispatch", "device_block")


def bucket_index(seconds: float) -> int:
    """Bucket for one duration: ``floor((log2(s) - LO) * 4)``, edge-clamped."""
    if seconds <= 2.0 ** HIST_LO:
        return 0
    i = int(math.floor((math.log2(seconds) - HIST_LO) * HIST_PER_OCTAVE))
    return min(max(i, 0), HIST_BUCKETS - 1)


def bucket_upper_edge(index: int) -> float:
    """Upper duration edge (seconds) of bucket ``index`` — the value a
    bucketed quantile reports (exact to one bucket, ratio ≤ 2^0.25)."""
    return 2.0 ** ((index + 1) / HIST_PER_OCTAVE + HIST_LO)


class _Phase:
    __slots__ = ("count", "total_s", "max_s", "hist")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.hist: List[int] = [0] * HIST_BUCKETS


def _hist_quantile(hist: List[int], count: int, q: float) -> float:
    """Upper edge of the bucket where the CDF crosses ``q`` of ``count``."""
    if count <= 0:
        return 0.0
    need = max(1, math.ceil(q * count))
    acc = 0
    for i, c in enumerate(hist):
        acc += c
        if acc >= need:
            return bucket_upper_edge(i)
    return bucket_upper_edge(HIST_BUCKETS - 1)


class PhaseAccumulator:
    """Thread-safe per-phase duration histograms for one trainer."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        # RLock: the flight recorder's SIGTERM dump (main thread, any
        # bytecode boundary) snapshots these histograms — a plain Lock held
        # by the interrupted add() would deadlock the handler
        # (obs/blackbox.py has the full rationale)
        self._lock = make_rlock("obs.phases")
        self._phases: Dict[str, _Phase] = {p: _Phase() for p in PHASES}

    def configure(self, enabled: bool) -> None:
        self.enabled = enabled

    def clear(self) -> None:
        with self._lock:
            self._phases = {p: _Phase() for p in PHASES}

    def add(self, phase: str, seconds: float) -> None:
        if not self.enabled:
            return
        ph = self._phases.get(phase)
        if ph is None:
            return
        i = bucket_index(seconds)
        with self._lock:
            ph.count += 1
            ph.total_s += seconds
            if seconds > ph.max_s:
                ph.max_s = seconds
            ph.hist[i] += 1

    # -- snapshots --------------------------------------------------------------

    def raw_snapshot(self) -> Dict[str, tuple]:
        """Cheap copy for later delta(): {phase: (count, total_s, hist[:])}.
        ``max_s`` is deliberately cumulative-only (a per-window max needs
        per-window state the heartbeat path should not pay for)."""
        with self._lock:
            return {name: (ph.count, ph.total_s, list(ph.hist))
                    for name, ph in self._phases.items()}

    @staticmethod
    def _summarize(count: int, total_s: float, hist: List[int],
                   max_s: Optional[float] = None) -> dict:
        out = {
            "count": count,
            "total_s": round(total_s, 6),
            "p50_s": round(_hist_quantile(hist, count, 0.50), 9),
            "p99_s": round(_hist_quantile(hist, count, 0.99), 9),
            # sparse histogram: {bucket_index: count}; upper edge of bucket i
            # is 2^((i+1)/4 - 20) seconds (bucket_upper_edge)
            "hist": {str(i): c for i, c in enumerate(hist) if c},
        }
        if max_s is not None:
            out["max_s"] = round(max_s, 6)
        return out

    def summary(self) -> Dict[str, dict]:
        """Cumulative per-phase rollup (run_end / last_run_stats / statusd);
        phases that never ran are omitted."""
        with self._lock:
            return {
                name: self._summarize(ph.count, ph.total_s, ph.hist, ph.max_s)
                for name, ph in self._phases.items() if ph.count
            }

    def delta(self, prev: Dict[str, tuple]) -> Dict[str, dict]:
        """Per-phase rollup of everything added since ``prev``
        (:meth:`raw_snapshot`) — the heartbeat-window emission."""
        cur = self.raw_snapshot()
        out: Dict[str, dict] = {}
        for name, (count, total_s, hist) in cur.items():
            pc, pt, ph = prev.get(name, (0, 0.0, None))
            dcount = count - pc
            if dcount <= 0:
                continue
            dhist = (hist if ph is None
                     else [a - b for a, b in zip(hist, ph)])
            out[name] = self._summarize(dcount, total_s - pt, dhist)
        return out
