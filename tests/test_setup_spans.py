"""The pinned spans of the set-up, where the work happens (ISSUE 50,
docs/observability.md §4): the package's import, ``Trainer()`` and its
children, a fit up to its first heartbeat, every compilation, the model's
constructor and the service's start, all recorded with telemetry off and no
trace live, and none of them inside a steady-state round."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.obs.schema import validate_file
from glint_word2vec_tpu.obs.spans import default_tracer
from glint_word2vec_tpu.train.trainer import Trainer

V = 120


def _vocab(v=V):
    counts = (2000.0 / (np.arange(v) + 1.0)).astype(np.int64) + 5
    return Vocabulary.from_words_and_counts([f"word{i}" for i in range(v)], counts)


def _sentences(n, seed=0, v=V):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, v, 20).astype(np.int32) for _ in range(n)]


def _config(**kw):
    base = dict(vector_size=8, window=3, negatives=3, min_count=1,
                pairs_per_batch=128, steps_per_dispatch=2,
                heartbeat_every_steps=2, num_iterations=1, negative_pool=64,
                subsample_ratio=0.0, seed=3)
    base.update(kw)
    return Word2VecConfig(**base)


class _Since:
    """The pinned records made since this was built."""

    def __init__(self):
        self.tracer = default_tracer()
        self.tracer.configure(enabled=False)
        # ids are given where a span begins: a parent's is below its children's
        self.after = max((e["id"] for e in self.tracer.setup_events()),
                         default=0)

    def events(self, name=None):
        evs = [e for e in self.tracer.setup_events() if e["id"] > self.after]
        return evs if name is None else [e for e in evs if e["name"] == name]


def test_the_package_import_is_a_pinned_span():
    # recorded once, when the package was imported: the store's first record
    # unless a long test process has dropped it since
    imports = [e for e in default_tracer().setup_events() if e["name"] == "import"]
    if not imports:
        pytest.skip("the import's record has left the bounded store")
    (imp,) = imports
    assert imp["dur_s"] > 0 and imp["parent"] is None
    assert imp["args"]["modules"] >= 10
    assert imp["args"]["jax_preloaded"] in (True, False)


CHILDREN = {
    "ns": ["trainer.resolve_auto", "sampler.alias_table", "params.place",
           "trainer.build_step"],
    "hs": ["trainer.resolve_auto", "params.place", "vocab.huffman_tree",
           "trainer.capacities", "trainer.build_step"],
    "subword": ["trainer.resolve_auto", "sampler.alias_table", "params.place",
                "vocab.subword_table", "trainer.capacities",
                "trainer.build_step"],
}


@pytest.mark.parametrize("form", sorted(CHILDREN))
def test_trainer_init_has_its_children_in_order_and_they_cover_it(form):
    kw = {"ns": {}, "hs": dict(loss="hs", negatives=0, negative_pool=0),
          "subword": dict(subword=True, subword_buckets=500)}[form]
    vocab = _vocab()
    since = _Since()
    trainer = Trainer(_config(**kw), vocab)
    (init,) = since.events("trainer.init")
    assert init["args"] == {"words": V, "mesh": "1x1"}
    kids = [e for e in since.events() if e["parent"] == init["id"]
            and e["name"] != "xla.compile"]
    kids.sort(key=lambda e: e["ts_s"])
    first_seen = list(dict.fromkeys(e["name"] for e in kids))
    assert first_seen == CHILDREN[form]
    lo, hi = init["ts_s"], init["ts_s"] + init["dur_s"]
    assert all(lo <= k["ts_s"] and k["ts_s"] + k["dur_s"] <= hi + 1e-9
               for k in kids)
    # the reader's self share: what no child accounts for
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from readers import program_spans
    share = program_spans.reduce_events(
        {"span": "trainer.init", "stat": "self_share", "over": "trainer.init"},
        since.events())
    assert 0 < share < 0.5
    passes = [e["args"]["passes"] for e in since.events("trainer.resolve_auto")]
    assert passes[0] == 0 and sum(passes) >= 1
    placed = since.events("params.place")[0]["args"]["placed"]
    assert placed == 1
    # one clock read a region: the attributes are the spans' own durations
    if form == "hs":
        (tree,) = since.events("vocab.huffman_tree")
        assert trainer.hs_tree_time == tree["dur_s"] > 0
        assert tree["args"]["nodes"] == V - 1
    if form == "subword":
        (table,) = since.events("vocab.subword_table")
        assert trainer.subword_table_time == table["dur_s"] > 0
    # params that come in placed say so
    since = _Since()
    Trainer(trainer.config, vocab, plan=trainer.plan, params=trainer.params)
    assert since.events("params.place")[0]["args"]["placed"] == 0


def _fit(heartbeats):
    """A toy fit that ends after ``heartbeats`` heartbeats; the pinned
    records it left, its trainer's constructor's apart."""
    cfg = _config(num_iterations=50)
    trainer = Trainer(cfg, _vocab())
    seen = []

    class Enough(Exception):
        pass

    def on_heartbeat(rec):
        seen.append(rec.global_step)
        if len(seen) >= heartbeats:
            raise Enough()

    since = _Since()
    with pytest.raises(Enough):
        trainer.fit(_sentences(400), on_heartbeat=on_heartbeat)
    assert len(seen) == heartbeats
    return since.events()


def test_a_fit_of_any_length_leaves_the_same_pinned_records():
    # what a test file before this one on the same worker left in the ring
    # (a fit with telemetry on) is not these fits': clear() keeps the pinned
    default_tracer().clear()
    _fit(2)                       # the step programs' first compilation
    short, long = _fit(2), _fit(20)
    assert len(short) == len(long)
    assert ([e["name"] for e in short] == [e["name"] for e in long])
    for evs in (short, long):
        (beat,) = [e for e in evs if e["name"] == "fit.first_heartbeat"]
        (first,) = [e for e in evs if e["name"] == "fit.first_dispatch"]
        assert first["parent"] == beat["id"] and beat["parent"] is None
        assert first["ts_s"] == beat["ts_s"]
        assert 0 < first["dur_s"] < beat["dur_s"]
        assert beat["args"] == {"step": 2, "steps": 2}
    # nothing of the ring: telemetry is off and no trace is live
    assert default_tracer().events() == []


def test_a_freshly_jitted_function_leaves_its_compile_spans():
    since = _Since()

    @jax.jit
    def t50_fresh(x):
        return jnp.tanh(x) * 3.0 + jnp.cumsum(x)

    with default_tracer().span("t50.caller", pinned=True) as caller:
        t50_fresh(jnp.arange(7.0)).block_until_ready()
    mine = [e for e in since.events("xla.compile")
            if "t50_fresh" in e["args"]["fun"]]
    assert [e["args"]["stage"] for e in mine] == ["trace", "lower", "backend"]
    assert all(e["parent"] == caller.id and e["dur_s"] > 0 for e in mine)
    trace, lower, backend = mine
    # each ends where the next begins, on the recorder's clock
    assert trace["ts_s"] + trace["dur_s"] <= lower["ts_s"] + 1e-3
    assert lower["ts_s"] + lower["dur_s"] <= backend["ts_s"] + 1e-3
    # the functions it called while traced are folded into its own record
    assert trace["args"]["traced"] >= 2
    assert backend["args"].get("cache") in (None, "hit", "miss")
    before = len(since.events("xla.compile"))
    t50_fresh(jnp.arange(7.0)).block_until_ready()     # compiled: no event
    assert len(since.events("xla.compile")) == before
    digest = default_tracer().setup_summary()
    assert digest["compiles"]["programs"] >= 1
    assert digest["spans"]["xla.compile"]["count"] >= 3


def test_model_and_service_leave_their_spans():
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.serve.service import EmbeddingService
    vocab = _vocab()
    since = _Since()
    rng = np.random.default_rng(1)
    model = Word2VecModel(vocab, rng.standard_normal((V, 8)).astype(np.float32))
    (init,) = since.events("model.init")
    assert init["args"] == {"words": V, "subword": 0, "resident": "all"}
    assert not since.events("model.norms") and not since.events("model.row_table")
    service = EmbeddingService(model=model, ann=False)
    try:
        (start,) = since.events("service.start")
        assert start["args"] == {"words": V, "ann": 0}
        assert start["ts_s"] > init["ts_s"]
        service.synonyms("word3", 4)
        service.synonyms("word4", 4)
        model.pull([5, 6])
        model.pull([7])
    finally:
        service.close()
    # the lazily built structures: once an object, at their build site
    assert len(since.events("model.norms")) == 1
    assert len(since.events("model.row_table")) == 1
    assert len(since.events("service.start")) == 1


def test_a_subword_models_compose_time_is_its_spans_duration():
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    vocab = _vocab()
    cfg = _config(subword=True, subword_buckets=500)
    rng = np.random.default_rng(2)
    since = _Since()
    model = Word2VecModel(
        vocab, rng.standard_normal((V, 8)).astype(np.float32), config=cfg,
        subword_buckets=rng.standard_normal((500, 8)).astype(np.float32))
    (init,) = since.events("model.init")
    (compose,) = since.events("model.compose")
    assert init["args"]["subword"] == 1
    assert compose["parent"] == init["id"]
    assert model.compose_time == compose["dur_s"] > 0
    assert compose["dur_s"] <= init["dur_s"]


def test_the_native_table_is_built_once_in_a_pinned_span():
    from glint_word2vec_tpu.data import vocab as vocab_mod
    if vocab_mod._load_native() is None:
        pytest.skip("no native lookup library on this host")
    vocab = _vocab()
    since = _Since()
    tokens = ["word7"] * vocab_mod.NATIVE_LOOKUP_TOKENS
    assert (vocab.lookup(tokens) == 7).all()
    assert (vocab.lookup(tokens) == 7).all()
    (table,) = since.events("vocab.native_table")
    assert table["args"] == {"words": V}
    loads = [e for e in default_tracer().setup_events()
             if e["name"] == "native.load" and e["args"]["lib"] == "lookup"]
    assert len(loads) <= 1 and all(e["args"]["built"] in (True, False)
                                   for e in loads)


def test_run_start_carries_the_setup_digest(tmp_path):
    path = str(tmp_path / "run.jsonl")
    since = _Since()
    trainer = Trainer(_config(telemetry_path=path), _vocab())
    try:
        trainer.fit(_sentences(60))
    finally:
        default_tracer().configure(enabled=False)
        default_tracer().clear()
    import json
    with open(path) as f:
        records = [json.loads(line) for line in f]
    (start,) = [r for r in records if r["kind"] == "run_start"]
    setup = start["setup"]
    assert setup["spans"]["trainer.init"]["count"] >= 1
    assert setup["spans"]["trainer.init"]["total_s"] > 0
    assert set(setup["compiles"]) == {"programs", "cache_hits", "cache_misses"}
    assert validate_file(path)["ok"]
    assert trainer.status_snapshot()["setup"]["spans"]["trainer.init"]["count"] >= 1
    # the fit's clear() kept the constructor, and the export has it first
    with open(path + ".trace.json") as f:
        xs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in xs if e["args"]["id"] > since.after]
    assert "trainer.init" in names and "fit.first_heartbeat" in names
    assert names.index("trainer.init") < names.index("dispatch")
    assert names.count("fit.first_heartbeat") == 1


def test_a_profiled_fit_has_its_first_heartbeat_in_the_trace(tmp_path):
    """Under ``config.profile_dir`` the fit's trace is live from its
    bookkeeping on: ``fit.first_heartbeat`` is in the profiler's host plane,
    beside the first step's operations, and once in the ring."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import trace as htr
    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
    trainer = Trainer(_config(profile_dir=str(tmp_path)), _vocab())
    try:
        trainer.fit(_sentences(60))
        ring = [e["name"] for e in tracer.events()]
    finally:
        tracer.clear()
    host = htr.load(htr.newest_xplane(str(tmp_path)), "cpu")["host"]
    assert [n for n, _, _ in host].count("fit.first_heartbeat") == 1
    assert ring.count("fit.first_heartbeat") == 1
    assert ring.count("fit.first_dispatch") == 1
    (beat,) = [(s, e) for n, s, e in host if n == "fit.first_heartbeat"]
    firsts = sorted((s, e) for n, s, e in host if n == "dispatch.enqueue")
    assert beat[0] <= firsts[0][0] and firsts[0][1] <= beat[1]
