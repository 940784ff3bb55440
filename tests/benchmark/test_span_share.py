"""Reader ``program_span_share`` on a hand-made event list: the named spans'
time over the extent of everything their thread recorded, and nothing where
nothing was recorded or no slice was traced."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from readers import program_span_share  # noqa: E402


def ev(name, ts, dur, id_, parent=None, tid=1, **args):
    e = {"name": name, "tid": tid, "thread": f"t{tid}", "ts_s": ts, "dur_s": dur,
         "id": id_, "parent": parent}
    if args:
        e["args"] = args
    return e


# the fit thread (tid 1) from 10 s to 110 s: the refill of the round that opened
# the slice (its heartbeat was entered before the trace was live: no parent),
# two plain dispatches, one whole heartbeat round, and the round the slice's end
# cut (its heartbeat and refill are not kept). A feed thread (tid 2) beside it.
EVENTS = [
    ev("heartbeat.refill", 10.0, 4.0, 1),
    ev("dispatch", 11.0, 3.0, 2, parent=1, steps=16),
    ev("dispatch.enqueue", 12.0, 2.0, 3, parent=2),
    ev("dispatch", 30.0, 2.0, 4, steps=16),
    ev("heartbeat", 50.0, 16.0, 5, step=64, steps=32),
    ev("heartbeat.drain", 50.0, 8.0, 6, parent=5),
    ev("health_probe", 58.0, 1.0, 7, parent=5),
    ev("device_block", 59.0, 0.5, 8, parent=5),
    ev("heartbeat.callback", 59.5, 0.5, 9, parent=5),
    ev("heartbeat.refill", 60.0, 6.0, 10, parent=5),
    ev("dispatch", 61.0, 5.0, 11, parent=10, steps=16),
    ev("dispatch", 80.0, 2.0, 12, steps=16),
    ev("heartbeat.drain", 100.0, 9.0, 13, parent=99),
    ev("health_probe", 109.0, 1.0, 14, parent=99),
    ev("producer", 0.0, 200.0, 15, tid=2),
    ev("stage_put", 300.0, 100.0, 16, tid=2),
]


@pytest.mark.parametrize("args, want", [
    # 4 + 6 s of refill in the fit thread's 100 s, not in the 56 s from the first
    # refill's start to the last one's end, nor in the feed thread's 400 s
    ({"span": "heartbeat.refill"}, 0.10),
    # nested spans are counted once: the dispatches inside a refill add nothing
    ({"span": ["heartbeat.refill", "dispatch"]}, 0.14),
    ({"span": "heartbeat.drain"}, 0.17),
    # the feed thread's own extent, 0 to 400 s
    ({"span": "producer"}, 0.5),
    # two threads: each one's cover over each one's extent, summed
    ({"span": ["heartbeat.refill", "stage_put"]}, (10.0 + 100.0) / (100.0 + 400.0)),
])
def test_share_is_over_the_threads_whole_traced_stretch(args, want):
    assert program_span_share.reduce_events(args, EVENTS) == pytest.approx(want)


@pytest.mark.parametrize("events", [EVENTS, [], [ev("heartbeat.refill", 5.0, 0.0, 1)]])
def test_nothing_recorded_gives_nothing(events):
    args = {"span": "serve.idle"} if events is EVENTS else {"span": "heartbeat.refill"}
    assert program_span_share.reduce_events(args, events) is None


def test_read_needs_a_traced_slice_and_reads_the_programs_ring():
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    was = tracer.enabled
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        with tracer.span("t35.outer"):
            with tracer.span("t35.inner"):
                pass
        args = {"span": "t35.outer"}
        assert program_span_share.read(args, {"slice": None}) is None
        assert program_span_share.read(args, {}) is None
        # the outer span is the thread's whole recorded stretch
        assert program_span_share.read(args, {"slice": {"busy_s": 1.0}}) == pytest.approx(1.0)
        assert 0.0 < program_span_share.read({"span": "t35.inner"},
                                             {"slice": {"busy_s": 1.0}}) <= 1.0
        assert program_span_share.read({"span": "t35.none"},
                                       {"slice": {"busy_s": 1.0}}) is None
    finally:
        tracer.configure(enabled=was)
        tracer.clear()
