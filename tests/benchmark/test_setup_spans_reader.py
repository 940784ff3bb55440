"""Reader ``setup_spans`` on hand-made records: its four stats, the rule that
only what started before the window counts, and nothing where the name is
absent or the program keeps no pinned store."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from readers import setup_spans  # noqa: E402


def ev(name, ts, dur, id_, parent=None, tid=1, **args):
    e = {"name": name, "tid": tid, "thread": f"t{tid}", "ts_s": ts, "dur_s": dur,
         "id": id_, "parent": parent}
    if args:
        e["args"] = args
    return e


def compile_(ts, dur, id_, stage, fun="jit(chunk)", parent=None, tid=1, **args):
    return ev("xla.compile", ts, dur, id_, parent, tid, fun=fun, stage=stage, **args)


# the ring's epoch is the fit's clear(): the constructor lies before it
SETUP = [
    ev("import", -40.0, 9.0, 1, modules=34, jax_preloaded=True),
    ev("vocab.build", -30.0, 2.5, 2, words=3_000_000),
    ev("model.init", -28.0, 0.75, 20, words=3_000_000, subword=0),
    ev("trainer.resolve_auto", -27.0, 0.0, 4, parent=3, passes=0),
    # an eager operation of the alias table's put: a compile inside a child
    compile_(-26.5, 0.25, 6, "backend", "jit(convert)", parent=5, cache="hit"),
    ev("sampler.alias_table", -27.0, 3.0, 5, parent=3, words=3_000_000),
    ev("trainer.resolve_auto", -24.0, 7.0, 7, parent=3, passes=61),
    # and one under the constructor itself, outside every child
    compile_(-16.5, 0.5, 8, "backend", "jit(seed)", parent=3, cache="miss"),
    ev("trainer.build_step", -16.0, 1.0, 9, parent=3),
    ev("trainer.init", -27.0, 13.0, 3, words=3_000_000, mesh="1x1"),
    # the step program: its trace holds an eager operation's whole compile
    compile_(-10.0, 4.0, 10, "trace", "chunk", traced=900),
    compile_(-9.0, 1.0, 11, "backend", "jit(add)", cache="hit"),
    compile_(-6.0, 1.0, 12, "lower"),
    compile_(-5.0, 2.0, 13, "backend", cache="hit"),
    # the feed thread compiles its own, at the same time
    compile_(-9.5, 1.5, 14, "backend", "jit(touch)", tid=2, cache="hit"),
    ev("fit.first_heartbeat", 0.5, 6.0, 15, step=64, steps=64),
    ev("fit.first_dispatch", 0.5, 4.0, 16, parent=15),
    # after the window: the benchmark's reference, and its model
    compile_(60.0, 5.0, 17, "backend", "jit(follow_steps)", cache="miss"),
    ev("model.init", 70.0, 1.0, 18, words=64, subword=0),
]
# the traced slice's spans: the window holds them, and no compilation
RING = [ev("dispatch", 30.0, 0.01, 100, steps=8), ev("heartbeat", 31.0, 0.02, 101),
        ev("dispatch", 29.5, 0.01, 99, steps=8)]


def reduce(span, stat, setup=SETUP, ring=RING, **where):
    args = {"span": span, "stat": stat}
    if where:
        args["where"] = where
    return setup_spans.reduce_events(args, setup, ring)


@pytest.mark.parametrize("span, want", [
    ("import", 9.0), ("vocab.build", 2.5), ("trainer.init", 13.0),
    ("trainer.resolve_auto", 7.0), ("sampler.alias_table", 3.0),
    ("fit.first_heartbeat", 6.0)])
def test_total_s_sums_a_names_durations(span, want):
    assert reduce(span, "total_s") == pytest.approx(want)


def test_union_s_merges_a_threads_intervals_and_adds_the_threads():
    # thread 1: [-26.5, -26.25] [-16.5, -16] [-10, -6] (the eager compile is
    # inside the trace) [-6, -5] [-5, -3]; thread 2: [-9.5, -8]
    assert reduce("xla.compile", "union_s") == pytest.approx(
        0.25 + 0.5 + 4.0 + 1.0 + 2.0 + 1.5)
    # a plain sum would count the eager compile twice
    assert reduce("xla.compile", "total_s") == pytest.approx(
        0.25 + 0.5 + 4.0 + 1.0 + 1.0 + 2.0 + 1.5)


def test_count_takes_an_arg_filter_and_is_a_float():
    programs = reduce("xla.compile", "count", stage="backend")
    assert programs == 5.0 and isinstance(programs, float)
    assert reduce("xla.compile", "count", stage="backend", cache="miss") == 1.0
    assert reduce("xla.compile", "count", stage="trace") == 1.0
    assert reduce("xla.compile", "count", stage="none") is None


def test_a_compile_after_the_rings_first_record_is_not_counted():
    counted = setup_spans.before_window(SETUP, RING)
    funs = [e["args"]["fun"] for e in counted if e["name"] == "xla.compile"]
    assert "jit(follow_steps)" not in funs and len(funs) == 7
    # the reference's model is not the cell's either
    assert reduce("model.init", "total_s") == 0.75
    # the boundary is the ring's EARLIEST start, wherever it stands in the ring
    late = SETUP + [compile_(29.75, 0.1, 19, "backend", "jit(late)")]
    assert reduce("xla.compile", "count", setup=late, stage="backend") == 5.0
    early = SETUP + [compile_(29.25, 0.1, 19, "backend", "jit(early)")]
    assert reduce("xla.compile", "count", setup=early, stage="backend") == 6.0


def test_self_share_is_what_the_children_do_not_cover():
    # children by parent id: resolve_auto 0 + 7, alias table 3 (its compile is
    # ITS child, not the constructor's), the bare compile 0.5, build_step 1
    assert reduce("trainer.init", "self_share") == pytest.approx(
        (13.0 - (0.0 + 3.0 + 7.0 + 0.5 + 1.0)) / 13.0)
    # a span with no children is all self time
    assert reduce("vocab.build", "self_share") == pytest.approx(1.0)


@pytest.mark.parametrize("stat", ["total_s", "union_s", "count", "self_share"])
def test_nothing_where_the_name_is_absent(stat):
    assert reduce("service.start", stat) is None
    assert reduce("trainer.init", stat, setup=[]) is None


def test_nothing_without_a_ring_to_cut_at():
    # an untraced run, or a program whose recorder does not follow the profiler
    assert reduce("trainer.init", "total_s", ring=[]) is None
    assert setup_spans.read({"span": "import", "stat": "total_s"}, {}) is None
    assert setup_spans.read({"span": "import", "stat": "total_s"},
                            {"slice": None}) is None


def test_an_unknown_stat_is_an_error():
    with pytest.raises(ValueError):
        reduce("import", "median_s")


def test_a_program_without_the_pinned_store_gives_nothing(monkeypatch):
    """The parent commit's recorder has no ``setup_events``: the reader says
    nothing and does not raise."""
    from glint_word2vec_tpu.obs import spans

    class Old:
        def events(self):
            return RING

    monkeypatch.setattr(spans, "_default", Old())
    assert setup_spans.read({"span": "import", "stat": "total_s"},
                            {"slice": {"window_s": 1.0}}) is None


LAYERS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "layers"))
                if f.startswith("setup_"))


@pytest.mark.parametrize("name", LAYERS)
def test_each_setup_metric_is_declared_as_the_issue_has_it(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    with open(os.path.join(BENCH, "layers", name + ".json")) as f:
        layer = json.load(f)
    assert layer["reader"] == "setup_spans" and layer["what"]
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert entry["source"] == "program_span" and entry["layer"] == "set-up"
    assert entry["workloads"], "every set-up metric lists its cells"
    # on the synthetic run every one of them reads a float above 0
    value = setup_spans.reduce_events(layer["args"], SETUP, RING)
    assert isinstance(value, float) and value > 0


def test_there_are_ten_setup_metrics():
    assert len(LAYERS) == 10
