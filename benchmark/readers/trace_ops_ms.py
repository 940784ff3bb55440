"""Reader ``trace_ops_ms``: device-op time of the traced slice, from the trace.

args:
  "pattern"  optional regex on op names; without it, every op (the busy union)
  "per"      "call": milliseconds per call (steps or scan dispatches in the
             slice, the kind's counter ``slice_calls``);
             "busy": share of the device's busy time
Averaged over the chips used. Nothing to read (no trace, no call) gives nothing.
"""

from harness import trace as tr


def read(args: dict, run: dict):
    sl = run.get("slice")
    if not sl:
        return None
    pattern = args.get("pattern")
    per_plane = [tr.pattern_seconds(evs, pattern) if pattern else tr.busy_seconds(evs)
                 for evs in sl["planes"].values()]
    seconds = sum(per_plane) / len(per_plane)
    if args["per"] == "busy":
        return seconds / sl["busy_s"] if sl["busy_s"] else None
    calls = run.get("counters", {}).get("slice_calls")
    return 1e3 * seconds / calls if calls else None
