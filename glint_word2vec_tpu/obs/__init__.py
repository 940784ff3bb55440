"""Run-telemetry subsystem (docs/observability.md).

Seven layers, each usable alone, all off by default and zero-cost when off:

- :mod:`.probe` — the fused on-device health reduction over the params carry
  (finiteness + per-matrix row-norm channels), the instrumentation ROADMAP
  item 2 names as the first step against the measured finite norm blowup.
- :mod:`.watch` — the finite-blowup watchdog (``config.norm_watch``) that
  fires on the probe channels where the non-finite guardrail stays silent.
- :mod:`.sink` + :mod:`.schema` — the schema-versioned JSONL run log
  (rotating file, never stdout — graftlint R7).
- :mod:`.spans` — the one host span recorder (ids, parents, counts as
  args): on under telemetry or while a ``jax.profiler`` trace is live, when
  the spans are in the profiler's trace too; always on for its pinned
  spans, the once-a-process regions of the set-up (:mod:`.compile_spans`
  makes every compilation one); Chrome-trace JSON export.
- :mod:`.phases` — host-side per-phase log2 duration histograms (producer
  wait / stage / dispatch / device block), the "where did the time go"
  attribution without a trace viewer.
- :mod:`.blackbox` — the flight recorder: bounded rings of recent telemetry
  dumped atomically to ``<telemetry_path>.blackbox.json`` on fit death.
- :mod:`.statusd` — the read-only live-inspection HTTP endpoint
  (``config.status_port``): JSON + Prometheus gauges for a running fit;
  the serving tier reuses it with the ``glint_serve_*`` renderer
  (:func:`.statusd.serve_prometheus_text`, docs/serving.md).

Plus the FLEET plane above them (ISSUE 13, docs/observability.md §9):

- :mod:`.trace` — cross-process trace propagation: one ``trace_id`` per
  fleet query, span children across the router/replica boundary, the
  per-process clock anchor, and the publish-side correlation record.
- :mod:`.slo` — availability/latency objectives with multi-window burn
  rates over the router's per-query samples (``glint_serve_fleet_slo_*``).
- :mod:`.collect` — the offline collector: N per-process sinks + blackbox
  dumps merged into one causally ordered fleet timeline
  (``tools/obs_collect.py``; Perfetto export + slowest-K exemplars +
  offline SLO recompute).
"""

from glint_word2vec_tpu.obs.blackbox import FlightRecorder
from glint_word2vec_tpu.obs.phases import PhaseAccumulator
from glint_word2vec_tpu.obs.probe import HealthStats, make_health_probe
from glint_word2vec_tpu.obs.schema import (
    SCHEMA_VERSION,
    validate_blackbox,
    validate_blackbox_file,
    validate_file,
    validate_record,
)
from glint_word2vec_tpu.obs.sink import TelemetrySink
from glint_word2vec_tpu.obs.slo import SloObjectives, SloTracker
from glint_word2vec_tpu.obs.spans import Tracer, default_tracer
from glint_word2vec_tpu.obs.trace import SpanEmitter, clock_anchor
from glint_word2vec_tpu.obs.statusd import (
    StatusServer,
    prometheus_text,
    serve_prometheus_text,
)
from glint_word2vec_tpu.obs.watch import NormWatchdog

__all__ = [
    "HealthStats", "make_health_probe",
    "SCHEMA_VERSION", "validate_file", "validate_record",
    "validate_blackbox", "validate_blackbox_file",
    "TelemetrySink", "Tracer", "default_tracer", "NormWatchdog",
    "FlightRecorder", "PhaseAccumulator", "StatusServer", "prometheus_text",
    "serve_prometheus_text",
    "SpanEmitter", "clock_anchor", "SloObjectives", "SloTracker",
]
