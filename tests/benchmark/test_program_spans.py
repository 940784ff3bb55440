"""Reader ``program_spans`` on hand-made event lists: its five stats, self time
by parent id over two levels of nesting, and nothing where nothing was recorded."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from readers import program_spans  # noqa: E402


def ev(name, ts, dur, id_, parent=None, tid=1, **args):
    e = {"name": name, "tid": tid, "thread": f"t{tid}", "ts_s": ts, "dur_s": dur,
         "id": id_, "parent": parent}
    if args:
        e["args"] = args
    return e


# two batches on the worker thread (tid 1); a feed thread (tid 2)
EVENTS = [
    ev("serve.batch", 0.0, 10.0, 1, size=4),
    ev("serve.dispatch", 1.0, 8.0, 2, parent=1, size=4),
    ev("serve.row_fetch", 1.5, 4.0, 3, parent=2, ops=5),
    ev("serve.reply_build", 6.0, 2.0, 4, parent=2),
    # a ticket's wait names its batch as parent and lies before it
    ev("serve.queue_wait", -3.0, 3.0, 5, parent=1, request=1),
    ev("serve.queue_wait", -1.0, 1.0, 6, parent=1, request=2),
    ev("serve.batch", 20.0, 10.0, 7, size=2),
    ev("serve.dispatch", 20.0, 10.0, 8, parent=7, size=2),
    ev("serve.row_fetch", 21.0, 2.0, 9, parent=8, ops=3),
    ev("producer", 0.0, 1.0, 10, tid=2),
    ev("stage_put", 1.0, 1.0, 11, tid=2),
    ev("producer", 6.0, 2.0, 12, tid=2),
]


@pytest.mark.parametrize("args, want", [
    ({"span": "serve.queue_wait", "stat": "mean_ms"}, 2000.0),
    ({"span": "serve.row_fetch", "stat": "ms_per", "per": "serve.batch"}, 3000.0),
    ({"span": "serve.row_fetch", "stat": "arg_mean", "arg": "ops"}, 4.0),
    # 4 s inside the spans of the 8 s from the first one's start to the last's end
    ({"span": ["producer", "stage_put"], "stat": "busy_share"}, 0.5),
    # batch 1: 10 - 8 (its dispatch; the waits lie outside it); dispatch 2: 8 - 6;
    # batch 7: 0; dispatch 8: 10 - 2: 12 of the 20 s of serve.batch
    ({"span": ["serve.batch", "serve.dispatch"], "stat": "self_share",
      "over": "serve.batch"}, 0.6),
    # one level alone: what the batches' direct children leave
    ({"span": "serve.batch", "stat": "self_share", "over": "serve.batch"}, 0.1),
])
def test_stats_on_a_hand_made_event_list(args, want):
    assert program_spans.reduce_events(args, EVENTS) == pytest.approx(want)


def test_overlapping_children_are_counted_once():
    events = [ev("p", 0.0, 10.0, 1), ev("c", 1.0, 4.0, 2, parent=1),
              ev("c", 3.0, 4.0, 3, parent=1), ev("c", 9.0, 5.0, 4, parent=1)]
    got = program_spans.reduce_events({"span": "p", "stat": "self_share", "over": "p"},
                                      events)
    assert got == pytest.approx(0.3)        # 10 - [1, 7] - [9, 10]


@pytest.mark.parametrize("args", [
    {"span": "serve.idle", "stat": "mean_ms"},
    {"span": "serve.row_fetch", "stat": "ms_per", "per": "no.such.span"},
    {"span": "serve.reply_build", "stat": "arg_mean", "arg": "ops"},
    {"span": "serve.batch", "stat": "self_share", "over": "no.such.span"},
])
def test_nothing_recorded_gives_nothing(args):
    assert program_spans.reduce_events(args, EVENTS) is None
    assert program_spans.reduce_events(args, []) is None


def test_read_needs_a_traced_slice_and_reads_the_programs_ring():
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
    args = {"span": "t25.read", "stat": "mean_ms"}
    try:
        tracer.record("t25.read", 0.0, 0.25)
        assert program_spans.read(args, {"slice": None}) is None
        assert program_spans.read(args, {"slice": {"window_s": 1.0}}) == pytest.approx(250.0)
    finally:
        tracer.clear()
    assert program_spans.read(args, {"slice": {"window_s": 1.0}}) is None


def test_an_unknown_stat_is_an_error():
    with pytest.raises(ValueError, match="unknown stat"):
        program_spans.reduce_events({"span": "producer", "stat": "median"}, EVENTS)
