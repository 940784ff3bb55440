#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell of BENCHMARK.json to its files (harness/loader.py), runs its
kind of traffic (kinds/<kind>.py: set-up, warm-up, window, check) against the
program, and prints as the LAST line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and, in
a traced run, ``breakdown``.

It fails, and prints no result, where JAX finds no TPU, a ``device_kind`` that
harness/peaks.py does not know, or another number of chips than the cell asks
for. ``--tiny`` is for rehearsal only: the same code at the ``tiny`` sizes of
the configuration and traffic files, on whatever platform there is, stamped so.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

# stand-in peaks for --tiny rehearsals off the chip; never a device number
_REHEARSAL_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at toy sizes on any platform; not a measurement")
    args = ap.parse_args(argv)

    from harness import loader
    from harness.peaks import peaks_for
    cell = loader.resolve(loader.load_manifest(ROOT), args.workload, ROOT)

    import jax
    from glint_word2vec_tpu.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program of a run is worth keeping, the small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"benchmark: {args.workload} seed {args.seed} on {device}; "
          f"compile cache {cache_dir}", flush=True)
    if args.tiny:
        peaks = _REHEARSAL_PEAKS
        print("benchmark: --tiny rehearsal, NOT a measurement", flush=True)
    else:
        if device["platform"] != "tpu":
            print(f"benchmark: no TPU (platform {device['platform']!r}); the "
                  "benchmark does not fall back", file=sys.stderr)
            return 1
        peaks = peaks_for(device["kind"])
        if device["count"] != cell["chips"]:
            print(f"benchmark: {args.workload} asks for {cell['chips']} chip(s) and "
                  f"JAX sees {device['count']}", file=sys.stderr)
            return 1

    kind = importlib.import_module(f"kinds.{cell['kind']}")
    run = kind.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                   tiny=args.tiny, t_start=T_START)
    run["peaks"] = peaks

    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    if args.trace:
        if not run.get("slice"):
            print("benchmark: the traced slice never closed", file=sys.stderr)
            return 1
        device["busy_s"] = run["slice"]["busy_s"]
        device["window_s"] = run["slice"]["window_s"]
        metrics, run["layer_values"] = {}, {}
        for layer in cell["layers"]:
            reader = importlib.import_module(f"readers.{layer['reader']}")
            value = reader.read(layer.get("args", {}), run)
            if value is not None:
                run["layer_values"][layer["name"]] = value
                metrics[layer["name"]] = {"value": value, "unit": layer["unit"]}
    else:
        metrics = {m["name"]: {"value": run["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in run["end_to_end"]}

    line = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = {"device_ops": run["slice"]["device_ops"],
                             "idle_gaps": run["slice"]["idle_gaps"]}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the fit's feed threads and the service's workers are stopped by the kind;
    # os._exit keeps a straggling non-daemon thread from holding the chip
    os._exit(code)
