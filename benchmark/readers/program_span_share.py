"""Reader ``program_span_share``: the share of a thread's traced time that the
named program spans cover.

As ``program_spans`` (whose ring and whose union of intervals this uses): the
union of the named spans' time, over the extent, from the first start to the
last end, of EVERY event in the ring that the same thread recorded. The
denominator is the thread's whole traced stretch, so a span that is rare says
how rare: ``program_spans``' ``busy_share`` divides by the named spans' own
extent and would read the distance between two of them. A slice that recorded
none of the named spans gives nothing.

args:
  "span"  a span name, or a list of names
"""

from readers.program_spans import _union_s


def reduce_events(args: dict, events: list):
    names = [args["span"]] if isinstance(args["span"], str) else list(args["span"])
    mine = [e for e in events if e["name"] in names]
    covered = extent = 0.0
    for tid in {e["tid"] for e in mine}:
        thread = [e for e in events if e["tid"] == tid]
        covered += _union_s([(e["ts_s"], e["ts_s"] + e["dur_s"])
                             for e in thread if e["name"] in names])
        extent += (max(e["ts_s"] + e["dur_s"] for e in thread)
                   - min(e["ts_s"] for e in thread))
    return covered / extent if extent else None


def read(args: dict, run: dict):
    if not run.get("slice"):
        return None
    from glint_word2vec_tpu.obs.spans import default_tracer
    return reduce_events(args, default_tracer().events())
