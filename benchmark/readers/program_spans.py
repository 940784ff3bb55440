"""Reader ``program_spans``: the program's own spans of the traced slice.

The program's span recorder (``glint_word2vec_tpu/obs/spans.py``) follows the
profiler: its ring holds the spans taken while the slice's trace was live,
each with ``id``, ``parent`` and counts as args. This reduces ``events()`` in
the process that ran the kind. A slice that recorded none of the named spans (a
program whose recorder does not follow the profiler records none) gives nothing.

args:
  "span"  a span name, or a list of names
  "stat"  "mean_ms"     mean duration, in milliseconds
          "ms_per"      total duration over the count of the span "per"
          "arg_mean"    mean of the spans' arg "arg"
          "busy_share"  time the spans cover over the time from the first one's
                        start to the last one's end, on the thread that
                        recorded them
          "self_share"  by parent id: the spans' self time (duration minus what
                        their direct children cover of it) over the total
                        duration of the span "over"
"""


def _union_s(intervals) -> float:
    total, at = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > at:
            total += e - max(s, at)
            at = e
    return total


def reduce_events(args: dict, events: list):
    """The stat ``args`` names over ``events`` (``Tracer.events()`` dicts)."""
    names = [args["span"]] if isinstance(args["span"], str) else list(args["span"])
    mine = [e for e in events if e["name"] in names]
    if not mine:
        return None
    stat = args["stat"]
    if stat == "mean_ms":
        return 1e3 * sum(e["dur_s"] for e in mine) / len(mine)
    if stat == "ms_per":
        per = sum(e["name"] == args["per"] for e in events)
        return 1e3 * sum(e["dur_s"] for e in mine) / per if per else None
    if stat == "arg_mean":
        values = [e["args"][args["arg"]] for e in mine
                  if args["arg"] in e.get("args", {})]
        return sum(values) / len(values) if values else None
    if stat == "busy_share":
        covered = extent = 0.0
        for tid in {e["tid"] for e in mine}:
            spans = [(e["ts_s"], e["ts_s"] + e["dur_s"]) for e in mine if e["tid"] == tid]
            covered += _union_s(spans)
            extent += max(e for _, e in spans) - min(s for s, _ in spans)
        return covered / extent if extent else None
    if stat == "self_share":
        over = sum(e["dur_s"] for e in events if e["name"] == args["over"])
        if not over:
            return None
        children = {}
        for e in events:
            if e.get("parent") is not None:
                children.setdefault(e["parent"], []).append(e)
        self_s = 0.0
        for e in mine:
            lo, hi = e["ts_s"], e["ts_s"] + e["dur_s"]
            # a child covers only what lies inside its parent: a ticket's queue
            # wait names its batch as parent and ends where the batch starts
            kids = [(max(c["ts_s"], lo), min(c["ts_s"] + c["dur_s"], hi))
                    for c in children.get(e["id"], [])]
            self_s += e["dur_s"] - _union_s([k for k in kids if k[1] > k[0]])
        return self_s / over
    raise ValueError(f"program_spans: unknown stat {stat!r}")


def read(args: dict, run: dict):
    if not run.get("slice"):
        return None
    from glint_word2vec_tpu.obs.spans import default_tracer
    return reduce_events(args, default_tracer().events())
