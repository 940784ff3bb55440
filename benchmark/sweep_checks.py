#!/usr/bin/env python3
"""Readings for the limits of ``correct``: the program's check numbers over many
seeds, and the lower-precision control's, in ONE process on the chip.

    python benchmark/sweep_checks.py --workload <cell> --seeds 101,102,... --control-seeds 201,202,203

The kind's ``check_readings`` builds the program at the cell's own size and
returns, for each seed, the numbers ``correct`` compares (no limit applied). With the
control on, the program runs with its tables in the nearest precision below the
configuration's (bfloat16 for float32). Not part of a benchmark run.
"""

import argparse
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [ROOT, BENCH_DIR]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from harness import loader
    cell = loader.resolve(loader.load_manifest(ROOT), args.workload, ROOT)
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    kind = importlib.import_module(f"kinds.{cell['kind']}")
    rows = []
    for control, seeds in ((False, args.seeds), (True, args.control_seeds)):
        seeds = [int(s) for s in seeds.split(",") if s]
        if not seeds:
            continue
        for seed, numbers in zip(seeds, kind.check_readings(
                cell, seeds, control=control, tiny=args.tiny)):
            rows.append({"seed": seed, "control": control, **numbers})
            print("reading", json.dumps(rows[-1]), flush=True)
    for name in [k for k in rows[0] if k not in ("seed", "control")]:
        sound = [r[name] for r in rows if not r["control"]]
        ctrl = [r[name] for r in rows if r["control"]]
        print(f"summary {name}: sound max {max(sound) if sound else None} over "
              f"{len(sound)} seeds; control min {min(ctrl) if ctrl else None} over "
              f"{len(ctrl)} seeds", flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
