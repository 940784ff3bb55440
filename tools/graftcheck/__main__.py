"""CLI: ``python -m tools.graftcheck [--smoke] [--json-out F]``.

Prints exactly ONE JSON line on stdout (graftlint R7); progress on stderr.
Exit 1 on any unexplained violation, baseline/registry drift, or
undocumented knob."""

from __future__ import annotations

import argparse
import json
import os
import sys

# CPU by design, set BEFORE jax initializes: the probe builds ~1.2k toy Trainers
# to check construction refusals, which no accelerator changes
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    """Parses args, runs the sweep, and emits via ``_run``'s single JSON
    print — exactly ONE line on stdout on every exit path (graftlint R7)."""
    payload, rc = _run(argv)
    print(json.dumps(payload))
    return rc


def _run(argv) -> tuple:
    from tools.graftcheck import checker

    ap = argparse.ArgumentParser(
        prog="graftcheck", description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="thinned lattice (the tier-1 wiring); the full "
                         "sweep (>= 1000 executed configs) runs in CI")
    ap.add_argument("--json-out", default="",
                    help="also write the JSON report to this path")
    ap.add_argument("--baseline", default="",
                    help="baseline file (default: the committed "
                         "tools/graftcheck/baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the committed baseline from this "
                         "(reviewed) run instead of gating against it")
    ap.add_argument("--root", default=_REPO)
    args = ap.parse_args(argv)

    mode = "smoke" if args.smoke else "full"
    if args.write_baseline and mode != "full":
        checker.log("graftcheck: refusing to write a baseline from a smoke "
                    "run — the full sweep is the inventory")
        return ({"tool": "graftcheck", "ok": False,
                 "error": "write-baseline requires the full sweep"}, 2)
    checker.log(f"graftcheck: enumerating the {mode} lattice ...")
    report = checker.run_sweep(mode)
    if args.write_baseline:
        path = checker.write_baseline(report, args.baseline)
        checker.log(f"graftcheck: baseline written to {path}")
    report = checker.apply_gates(report, args.root, args.baseline)

    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
    for v in report["violations"]:
        checker.log(f"  VIOLATION[{'baselined' if v['baselined'] else 'NEW'}]"
                    f" {v['key'][:100]}  counterexample="
                    f"{v['counterexample']}")
    for d in report["baseline_drift"]:
        checker.log(f"  DRIFT {d}")
    for d in report["registry_drift"]:
        checker.log(f"  REGISTRY {d}")
    if report["docs_missing"]:
        checker.log(f"  DOCS missing knob rows: {report['docs_missing']}")
    checker.log(
        f"graftcheck: {report['configs_executed']} configs executed "
        f"({report['accepted']} accepted, {report['refused_construction']} "
        f"refused), {report['probes_run']} dispatch probes, "
        f"{len(report['refusal_signatures'])} refusal signatures, "
        f"{report['unexplained_violations']} unexplained violation(s) -> "
        f"{'ok' if report['ok'] else 'FAIL'}")
    return (report, 0 if report["ok"] else 1)


if __name__ == "__main__":
    raise SystemExit(main())
