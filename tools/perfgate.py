#!/usr/bin/env python
"""Throughput regression gate over the committed BENCH_r*.json trajectory.

The BENCH trajectory (r01 → r05: 3.44M → 11.18M step pairs/s) is the repo's
perf ground truth, but until now nothing CHECKED a fresh bench line against
it — a regression would land silently and surface rungs later as "huh, r06
is slower". This gate compares a fresh ``bench.py`` JSON line against the
committed trajectory with EXPLICIT per-metric tolerance bands and fails
loudly when a gated metric falls below band.

Gate rule, per metric: ``new >= (1 - band) * latest_rung`` — the latest
committed rung is the CURRENT claim a fresh line must hold. The historical
best is reported beside it as an advisory ``drift_from_best`` (the
committed trajectory itself is not monotonic: r03's f32 step row beats
r05's by ~12% — a real drift the rungs absorbed while the headline moved
to bf16 — so gating on the all-time best would fail the genuine current
line; the advisory keeps that drift visible instead of burying it).

Tolerance-band provenance (docs/observability.md has the full table): the
bands come from the measured trial spread of the bench harness itself —
bench.py step rows report min/median/max over 3 interleaved trials
(BENCH r04+), where the committed rungs show up to ~6% median-to-min spread
on the step metrics and wider spread on the e2e row (host-pipeline noise,
PERF.md §3/§5). Bands are set ≥ 2x the observed spread so the gate fires on
regressions, not on weather; tighten them on a quieter host, in the JSON,
with provenance.

Modes::

    python tools/perfgate.py --bench fresh_bench.json   # gate a real run
    python tools/perfgate.py --smoke                    # self-test (CI)

``--smoke`` is machine-independent (CI containers cannot reproduce
capable-host numbers): it proves the GATE works — the genuine latest
committed rung must pass against the trajectory, and a seeded regression
(every gated metric scaled by --seed-factor, default 0.7 — below every
band) must fire. A
real ``--bench`` run belongs on the host class the baselines came from.

Prints exactly ONE JSON line on stdout (graftlint R7); chatter to stderr.
Exit 0 iff the gate holds (or, under --smoke, iff genuine-passes AND
seeded-fires).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# gated metric -> tolerance band (fraction below trajectory-best tolerated).
# Provenance: >= 2x the observed cross-trial/cross-rung spread (module doc).
GATED: Dict[str, float] = {
    # headline single-chip step throughput; step_trials_ms spread <= ~6%
    "value": 0.12,
    # f32 step twin, same harness
    "step_f32_pairs_per_sec": 0.12,
    # e2e trainer row folds the host pipeline in — noisier (PERF.md §5)
    "e2e_pairs_per_sec": 0.25,
    # large-vocab step row (scatter-bound regime)
    "v1m_step_pairs_per_sec": 0.15,
    # CBOW step row
    "cbow_examples_per_sec": 0.20,
    # --- ISSUE-14 restructured step rows (gated only once a rung carries
    # them — r01-r05 predate the knobs). Same harness/trial structure as
    # the step rows above, so the same 0.12 band ---
    "step_fused_pairs_per_sec": 0.12,
    "step_bf16_chain_pairs_per_sec": 0.12,
    # --- flat per-row scalars (ISSUE 17 satellite): bench.py now emits one
    # `step_<row>_pairs_per_sec` per step row as a top-level scalar, the
    # PREFERRED gate names going forward — every step row gets gated by a
    # stable flat name instead of only the hand-picked subset above.
    # _load_parsed back-fills them for older rungs from the legacy aliases
    # (same harness, same number), so history exists from r04 on. Bands
    # mirror the per-row counterparts; the `step_<row>_step_ms` flats ride
    # in the bench line for dashboards but are NOT gated here (the gate
    # rule is higher-is-better) ---
    "step_f32_p512_pairs_per_sec": 0.12,
    "step_bf16_p512_pairs_per_sec": 0.12,
    "step_bf16_p1024_pairs_per_sec": 0.12,
    "step_bf16_fused_pairs_per_sec": 0.12,
}

# legacy top-level name -> flat per-row name (back-fill for rungs that
# predate the flats; the pairs are the SAME measurement, so aliasing is
# honest). bf16_chain already used the flat-style name, so it needs no alias.
_FLAT_ALIASES = {
    "step_f32_pairs_per_sec": "step_f32_p512_pairs_per_sec",
    "step_fused_pairs_per_sec": "step_bf16_fused_pairs_per_sec",
}

# the SERVING trajectory's bands (--kind serve, SERVEBENCH_r*.json from
# tools/servebench.py — ISSUE 10). All higher-is-better, same gate rule.
# Thread-scheduling noise on closed/offered-loop latency arms is wider than
# the step benches', hence the looser throughput bands; recall is a
# deterministic property of (matrix, seed, nprobe), so its band is tight —
# a recall drop means the index or its auto rules changed, not weather.
SERVE_GATED: Dict[str, float] = {
    # closed-loop ANN capacity (qps) through the full service path
    "ann_qps": 0.30,
    # the acceptance headline: exact per-query p50 / ANN operating-point p50
    "ann_speedup_p50": 0.35,
    # oracle-checked index recall at the auto operating point
    "ann_recall_at_10": 0.03,
    # highest offered load with < 1% refusals
    "offered_qps_sustained": 0.30,
    # --- fleet tier (ISSUE 12, servebench --fleet; gated only once a rung
    # carries them — r01 predates the fleet). Router-path N=3 ANN capacity,
    # and the hedge A/B's p99 cut under the injected straggler (off/on
    # ratio, higher is better; < 1 would mean hedging HURT) ---
    "fleet3_ann_qps": 0.35,
    "fleet_hedge_p99_cut": 0.35,
    # --- quantized arms (ISSUE 18, servebench arm 5; gated only once a
    # rung carries them — r01/r02 predate quantization). qps bands mirror
    # the f32 ANN arm's scheduling noise; recall is deterministic per
    # (matrix, seed, arm) so the bands stay tight — a drop means the
    # quantizer or its auto rules changed, not weather; bytes_cut (f32
    # bytes over quant bytes, higher is better) is a pure layout property,
    # tightest of all ---
    "int8_qps": 0.30,
    "pq_qps": 0.35,
    "int8_recall_at_10": 0.03,
    "pq_recall_at_10": 0.05,
    "int8_bytes_cut": 0.05,
    "pq_bytes_cut": 0.05,
    # the acceptance ratio: int8 closed-loop qps over the f32 ANN arm's
    # (both arms measured in the same process minutes apart, so the band
    # can be tighter than either qps alone)
    "int8_qps_ratio": 0.25,
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _load_parsed(path: str) -> dict:
    """A bench JSON: either the raw one-line bench.py output (the metric
    dict itself) or a driver capture wrapping it under 'parsed'. Back-fills
    the flat per-row scalars for rungs that predate them (BENCH r04-r06):
    legacy aliases are the same measurement under an older name, and
    `step_<row>_step_ms` is the nested trial median — so the aliased flat
    gates have history instead of silently skipping every old rung. Rows
    that never had a top-level name (bf16_p512/bf16_p1024) start gating at
    the first rung that carries the flats, like the ISSUE-14 rows did."""
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    doc = doc.get("parsed", doc)
    for old, new in _FLAT_ALIASES.items():
        if doc.get(new) is None and doc.get(old) is not None:
            doc[new] = doc[old]
    trials = doc.get("step_trials_ms") or {}
    for k, st in trials.items():
        if isinstance(st, dict) and st.get("ms_median") is not None:
            doc.setdefault(f"step_{k}_step_ms", st["ms_median"])
    return doc


def load_trajectory(pattern: str) -> List[dict]:
    paths = sorted(glob.glob(pattern))
    rungs = []
    for p in paths:
        try:
            parsed = _load_parsed(p)
        except (OSError, json.JSONDecodeError) as e:
            log(f"skipping unreadable baseline {p}: {e}")
            continue
        rungs.append({"path": os.path.basename(p), "parsed": parsed})
    return rungs


def gate(new: dict, rungs: List[dict],
         bands: Optional[Dict[str, float]] = None) -> dict:
    """Compare one fresh parsed bench dict against the trajectory. Metrics
    absent from the new line are reported (a vanished metric is itself
    suspicious) but only gated when at least one rung carries them."""
    bands = bands or GATED
    metrics = {}
    ok = True
    for name, band in bands.items():
        # None-valued metrics are treated as absent: servebench emits null
        # for legitimately unmeasurable values (recall below 11 rows, p50 of
        # an empty offered row) — the gate must FAIL on them with a report,
        # not crash on float(None) past the R7 one-JSON-line contract
        history = [(r["path"], float(r["parsed"][name]))
                   for r in rungs
                   if r["parsed"].get(name) is not None]
        if not history:
            continue
        ref_path, ref = history[-1]           # the latest rung: the claim
        best_path, best = max(history, key=lambda kv: kv[1])
        floor = (1.0 - band) * ref
        entry = {"ref": ref, "ref_rung": ref_path, "band": band,
                 # 4 decimals: serving gates fractional metrics (recall)
                 # where 1-decimal display rounded the floor to 1.0
                 "floor": round(floor, 4),
                 # advisory: how far the current claim itself sits below the
                 # all-time best (non-monotonic trajectory drift)
                 "best": best, "best_rung": best_path,
                 "drift_from_best": round(1.0 - ref / best, 4)}
        if new.get(name) is None:
            metrics[name] = {**entry, "new": None, "ok": False,
                             "why": "metric missing/null in the fresh line"}
            ok = False
            continue
        val = float(new[name])
        passed = val >= floor
        metrics[name] = {**entry, "new": val,
                         "ratio_to_ref": round(val / ref, 4), "ok": passed}
        ok = ok and passed
    return {"ok": ok, "metrics": metrics,
            "rungs": [r["path"] for r in rungs]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--bench", default="",
                    help="fresh bench.py/servebench.py JSON (raw line or "
                         "driver capture) to gate against the trajectory")
    ap.add_argument("--kind", choices=["train", "serve"], default="train",
                    help="which trajectory/bands: 'train' = bench.py vs "
                         "BENCH_r*.json (GATED), 'serve' = servebench.py vs "
                         "SERVEBENCH_r*.json (SERVE_GATED)")
    ap.add_argument("--baselines", default="",
                    help="glob of committed trajectory rungs (default "
                         "derives from --kind)")
    ap.add_argument("--smoke", action="store_true",
                    help="machine-independent self-test: the genuine latest "
                         "rung must pass, a seeded regression must fire")
    ap.add_argument("--seed-factor", type=float, default=0.7,
                    help="--smoke: scale factor of the seeded regression "
                         "(must sit below every band to prove firing)")
    args = ap.parse_args()

    result, rc = _run(args)
    print(json.dumps(result))  # the ONE stdout line (graftlint R7)
    return rc


def _run(args) -> tuple:
    """All modes funnel through here so main() keeps exactly one
    ``print(json.dumps(...))`` (the R7 stdout contract)."""
    bands = SERVE_GATED if args.kind == "serve" else GATED
    if not args.baselines:
        args.baselines = os.path.join(
            _REPO, "SERVEBENCH_r*.json" if args.kind == "serve"
            else "BENCH_r*.json")
    rungs = load_trajectory(args.baselines)
    # the serving trajectory legitimately starts at one rung (r01 is the
    # subsystem's birth); the training trajectory predates the gate and
    # must never regress to a single readable rung
    min_rungs = 1 if args.kind == "serve" else 2
    if len(rungs) < min_rungs:
        return {"ok": False,
                "error": f"need >= {min_rungs} baseline rungs at "
                         f"{args.baselines}, found {len(rungs)}"}, 2

    if args.smoke:
        genuine = rungs[-1]["parsed"]
        g = gate(genuine, rungs, bands)
        seeded = {k: float(genuine[k]) * args.seed_factor
                  for k in bands if genuine.get(k) is not None}
        s = gate(seeded, rungs, bands)
        fired_on = sorted(k for k, m in s["metrics"].items()
                          if not m["ok"])
        # the recall gates specifically must prove they fire (ISSUE 18):
        # a seeded RECALL regression is the silent-degradation failure
        # mode the quantized arms exist to refuse, so whenever the rungs
        # carry a recall metric, the seeded line must trip at least one
        recall_carried = sorted(
            k for k in bands if "recall" in k
            and any(r["parsed"].get(k) is not None for r in rungs))
        recall_fired = sorted(set(fired_on)
                              & set(recall_carried))
        recall_ok = not recall_carried or bool(recall_fired)
        result = {
            # the gate is proven iff the real current line is inside band
            # AND the seeded regression trips it (including its recall
            # gates, when the trajectory carries any)
            "ok": bool(g["ok"] and not s["ok"] and recall_ok),
            "mode": "smoke",
            "kind": args.kind,
            "genuine": {"rung": rungs[-1]["path"], "ok": g["ok"],
                        "metrics": g["metrics"]},
            "seeded": {"factor": args.seed_factor, "ok": s["ok"],
                       "fired_on": fired_on,
                       "recall_fired": recall_fired},
            "rungs": g["rungs"],
        }
        log(f"perfgate --smoke: genuine {rungs[-1]['path']} "
            f"{'PASS' if g['ok'] else 'FAIL'}; seeded x{args.seed_factor} "
            f"{'fired on ' + ','.join(fired_on) if fired_on else 'DID NOT FIRE'}"
            + (f"; recall gates fired: {','.join(recall_fired) or 'NONE'}"
               if recall_carried else ""))
        return result, 0 if result["ok"] else 1

    if not args.bench:
        return {"ok": False,
                "error": "pass --bench FRESH.json or --smoke"}, 2
    try:
        new = _load_parsed(args.bench)
    except (OSError, json.JSONDecodeError) as e:
        return {"ok": False,
                "error": f"unreadable --bench {args.bench}: {e}"}, 2
    result = gate(new, rungs, bands)
    result["mode"] = "gate"
    result["kind"] = args.kind
    result["bench"] = args.bench
    for name, m in result["metrics"].items():
        log(f"perfgate {name}: new {m['new']} vs ref {m['ref']} "
            f"({m['ref_rung']}), floor {m['floor']} -> "
            f"{'ok' if m['ok'] else 'REGRESSION'}")
    return result, 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
