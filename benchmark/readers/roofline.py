"""Reader ``roofline``: the least time of one call over its measured device time.

args:
  "cost"     module under benchmark/costs/ whose ``cost(**shapes)`` gives the
             bytes and FLOPs of one call; the shapes are the kind's ``shapes``
  "time_ms"  the per-layer metric (already read in this run) that is the call's
             measured device time
The least time is the larger of bytes over the chip's peak bytes/s and FLOPs over
its peak FLOP/s (benchmark/harness/peaks.py); which of the two bounds is printed.
"""

import importlib


def read(args: dict, run: dict):
    measured_ms = run.get("layer_values", {}).get(args["time_ms"])
    if not measured_ms or "shapes" not in run:
        return None
    cost = importlib.import_module(f"costs.{args['cost']}").cost(**run["shapes"])
    peaks = run["peaks"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_flops = cost["flops"] / peaks["flops_per_s"]
    print(f"roofline {args['cost']}: bytes {cost['bytes']:.4g} -> {1e3 * t_bytes:.4f} ms, "
          f"flops {cost['flops']:.4g} -> {1e3 * t_flops:.4f} ms, bound by "
          f"{'bytes' if t_bytes >= t_flops else 'flops'}; measured {measured_ms:.4f} ms",
          flush=True)
    return 100.0 * 1e3 * max(t_bytes, t_flops) / measured_ms
