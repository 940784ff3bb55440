"""Reader ``setup_spans``: the program's pinned spans of the set-up.

The program's span recorder (``glint_word2vec_tpu/obs/spans.py``) keeps the
once-a-process and once-an-object regions (the package's import, the
vocabulary, ``Trainer()`` and its children, every compilation, the fit up to
its first heartbeat, the model's constructor) in a store of their own that
records with tracing off and that a fit's ``clear()`` leaves:
``Tracer.setup_events()``, the dicts ``events()`` gives, on the ring's epoch.
This reduces them in the process that ran the kind.

Only what STARTED BEFORE THE WINDOW counts. In a traced run the ring holds
the spans of the slice, the slice lies inside the window, and the window
holds no compilation (``correct``), so "started before the ring's first
record" keeps every span of the set-up and cuts what the benchmark does
after the window (its reference's compilations, its reference model's
constructor). A program whose recorder keeps no such store gives nothing.

args:
  "span"   a span name
  "where"  optional {arg: value}: only the spans whose args hold these
  "stat"   "total_s"     their durations, summed
           "union_s"     the time they cover, a thread's intervals merged (a
                         compilation met while a function is traced lies
                         inside that trace)
           "count"       how many, as a float
           "self_share"  by parent id: their self time (duration minus what
                         their direct children cover of it) over their
                         duration
"""

from readers.program_spans import _union_s, reduce_events as _reduce_ring


def before_window(setup: list, ring: list) -> list:
    """The pinned spans that started before the ring's first record did."""
    if not ring:
        return []
    opened = min(e["ts_s"] for e in ring)
    return [e for e in setup if e["ts_s"] < opened]


def reduce_events(args: dict, setup: list, ring: list):
    """The stat ``args`` names over ``setup`` (``Tracer.setup_events()``
    dicts), cut at the first of ``ring`` (``Tracer.events()`` dicts)."""
    events = before_window(setup, ring)
    where = args.get("where", {})
    mine = [e for e in events if e["name"] == args["span"]
            and all(e.get("args", {}).get(k) == v for k, v in where.items())]
    if not mine:
        return None
    stat = args["stat"]
    if stat == "total_s":
        return sum(e["dur_s"] for e in mine)
    if stat == "union_s":
        return sum(_union_s([(e["ts_s"], e["ts_s"] + e["dur_s"])
                             for e in mine if e["tid"] == tid])
                   for tid in {e["tid"] for e in mine})
    if stat == "count":
        return float(len(mine))
    if stat == "self_share":
        return _reduce_ring({"span": args["span"], "stat": "self_share",
                             "over": args["span"]}, events)
    raise ValueError(f"setup_spans: unknown stat {stat!r}")


def read(args: dict, run: dict):
    if not run.get("slice"):
        return None
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    if not hasattr(tracer, "setup_events"):
        return None
    return reduce_events(args, tracer.setup_events(), tracer.events())
