"""The heartbeat round as one span tree on the fit thread (docs/observability.md
§4): ``heartbeat`` over ``heartbeat.drain``, ``health_probe``, ``device_block``,
``heartbeat.callback`` and ``heartbeat.refill``, in every fit path a CPU run can
reach (the host feed, the device feed, and the two sharded loops as two
processes over one CPU mesh), at the edges of a traced slice, with probing off,
and with nothing recording."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.obs.spans import default_tracer
from glint_word2vec_tpu.train.trainer import Trainer
from test_obs import live_trace  # noqa: F401  (a fixture)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUND = ["heartbeat.drain", "health_probe", "device_block", "heartbeat.callback",
         "heartbeat.refill"]


def _toy_trainer(**cfg_kw):
    """tests/test_obs.py's toy fit, a heartbeat every dispatch of two steps
    unless ``cfg_kw`` says otherwise."""
    rng = np.random.default_rng(0)
    sents = [[f"w{i}" for i in rng.integers(0, 30, 20)] for _ in range(250)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(**{**dict(
        vector_size=8, pairs_per_batch=128, window=3, num_iterations=2,
        steps_per_dispatch=2, heartbeat_every_steps=2, subsample_ratio=0.0,
        prefetch_chunks=0, seed=1, heartbeat_ring=10_000), **cfg_kw})
    return Trainer(cfg, vocab), encode_sentences(sents, vocab, 1000)


def _ends(e):
    return e["ts_s"] + e["dur_s"]


def check_round_trees(events, heartbeats, last_round_was_one):
    """Every recorded ``heartbeat`` of ``events`` (dicts of ``Tracer.events()``,
    one fit, telemetry on) holds exactly the round's children, in order, each
    inside it; returns them by heartbeat."""
    by_parent = {}
    for e in events:
        by_parent.setdefault(e["parent"], []).append(e)
    beats = sorted((e for e in events if e["name"] == "heartbeat"),
                   key=lambda e: e["ts_s"])
    assert len(beats) == heartbeats
    assert all(e["parent"] is None and e["args"]["steps"] >= 1 for e in beats)
    assert len({e["tid"] for e in beats}) == 1
    assert [e["args"]["step"] for e in beats] == sorted(e["args"]["step"] for e in beats)
    trees = []
    for i, hb in enumerate(beats):
        kids = sorted(by_parent.get(hb["id"], []), key=lambda e: e["ts_s"])
        last = last_round_was_one and i == len(beats) - 1
        assert [k["name"] for k in kids] == (ROUND[:-1] if last else ROUND), (i, kids)
        for a, b in zip(kids, kids[1:]):
            assert _ends(a) <= b["ts_s"] + 1e-9
        assert hb["ts_s"] <= kids[0]["ts_s"] and _ends(kids[-1]) <= _ends(hb) + 1e-9
        if not last:
            refill = kids[-1]
            inside = by_parent.get(refill["id"], [])
            assert [k["name"] for k in inside].count("dispatch") == 1
            assert all(refill["ts_s"] <= k["ts_s"] and _ends(k) <= _ends(refill) + 1e-9
                       for k in inside)
            # the round ends with its refill, at the enqueue's return: whatever
            # the thread records next begins after both
            enqueue = next(e for e in events if e["name"] == "dispatch.enqueue"
                           and e["ts_s"] >= refill["ts_s"])
            assert _ends(enqueue) <= _ends(refill) <= _ends(hb)
            after = [e["ts_s"] for e in events
                     if e["tid"] == hb["tid"] and e["ts_s"] > _ends(enqueue)]
            assert not after or _ends(hb) <= min(after) + 1e-9
        trees.append(kids)
    # a dispatch that follows no heartbeat is nobody's child
    assert sum(e["name"] == "dispatch" and e["parent"] is None for e in events) >= 1
    return trees


def _fit_and_check(trainer, sents):
    trainer.fit(sents, on_heartbeat=lambda rec: None)
    assert trainer._round == []
    last_was_one = trainer.heartbeats[-1].global_step == trainer.global_step
    return check_round_trees(default_tracer().events(), len(trainer.heartbeats),
                             last_was_one)


@pytest.mark.parametrize("path, cfg_kw", [
    ("host_feed", dict(heartbeat_every_steps=4)),
    ("host_feed_prefetch", dict(heartbeat_every_steps=4, prefetch_chunks=2)),
    ("device_feed", dict(heartbeat_every_steps=4, device_pairgen=True, negative_pool=8)),
    ("banded_cbow_token_feed", dict(heartbeat_every_steps=4, cbow=True,
                                    cbow_update="banded", negative_pool=8)),
    ("host_feed_hierarchical_softmax", dict(heartbeat_every_steps=4, loss="hs",
                                            negatives=0)),
])
def test_round_tree_in_one_process(path, cfg_kw, tmp_path):
    trainer, sents = _toy_trainer(telemetry_path=str(tmp_path / "run.jsonl"),
                                  **cfg_kw)
    trees = _fit_and_check(trainer, sents)
    assert len(trees) >= 3
    # the ring's digest in run_end has the round's names too
    summary = trainer._tracer.span_summary()
    assert summary["heartbeat.drain"]["count"] == len(trees)


_WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
from glint_word2vec_tpu.parallel.distributed import initialize
pid, port, mode, workdir = int(sys.argv[1]), sys.argv[2], sys.argv[3], sys.argv[4]
initialize(coordinator_address="127.0.0.1:" + port, num_processes=2, process_id=pid)

import numpy as np
from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.obs.spans import default_tracer
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train.trainer import Trainer

rng = np.random.default_rng(0)
sentences = [[f"w{j}" for j in rng.integers(0, 64, 12)] for _ in range(200)]
vocab = build_vocab(sentences, min_count=1)
cfg = Word2VecConfig(vector_size=16, min_count=1, pairs_per_batch=128,
                     num_iterations=2, window=3, negatives=3, negative_pool=16,
                     steps_per_dispatch=2, heartbeat_every_steps=4, seed=7,
                     subsample_ratio=0.0, shard_input=True, heartbeat_ring=10000,
                     device_pairgen=(mode == "tokens"),
                     telemetry_path=os.path.join(workdir, f"run{pid}.jsonl"))
trainer = Trainer(cfg, vocab, plan=make_mesh(2, 4))
assert trainer._feed_segments == 2
trainer.fit(encode_sentences(sentences, vocab, cfg.max_sentence_length),
            on_heartbeat=lambda rec: None)
assert trainer._round == []
with open(os.path.join(workdir, f"spans{pid}.json"), "w") as f:
    json.dump({"events": default_tracer().events(),
               "heartbeats": len(trainer.heartbeats),
               "last_was_one": (trainer.heartbeats[-1].global_step
                                == trainer.global_step)}, f)
"""


@pytest.mark.parametrize("mode, feed", [("pairs", "GatheredPairs"),
                                        ("tokens", "GatheredTokenBlocks")])
def test_round_tree_in_the_sharded_loops(mode, feed, tmp_path):
    """Two processes of four virtual devices over one 2x4 CPU mesh, as
    tests/test_multiprocess.py runs them: ``shard_input`` takes the fit loop over
    ``feeds.<feed>``. On the CPU mesh ``_after_dispatch`` also drains after every
    dispatch (``device_block``), after the round has ended: nobody's child."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen([sys.executable, str(script), str(i), port, mode,
                               str(tmp_path)], stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for i in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-3000:]}"
    for pid in range(2):
        with open(tmp_path / f"spans{pid}.json") as f:
            got = json.load(f)
        trees = check_round_trees(got["events"], got["heartbeats"], got["last_was_one"])
        assert len(trees) >= 3
        drains = [e for e in got["events"] if e["name"] == "device_block"
                  and e["parent"] is None]
        assert len(drains) == sum(e["name"] == "dispatch" for e in got["events"])


def test_last_heartbeat_of_a_fit_has_no_refill(tmp_path):
    """Every round a heartbeat round: the last one is followed by no dispatch.
    Its refill is not kept, its ``heartbeat`` ends where that would have begun
    (before the fit's last save), and nothing stays open on the thread."""
    trainer, sents = _toy_trainer(telemetry_path=str(tmp_path / "run.jsonl"),
                                  heartbeat_every_steps=1)
    trainer.fit(sents, on_heartbeat=lambda rec: None,
                checkpoint_path=str(tmp_path / "ck"))
    tracer = default_tracer()
    evs = tracer.events()
    trees = check_round_trees(evs, len(trainer.heartbeats), last_round_was_one=True)
    assert sum(e["name"] == "heartbeat.refill" for e in evs) == len(trees) - 1
    last = max((e for e in evs if e["name"] == "heartbeat"), key=lambda e: e["ts_s"])
    save = next(e for e in evs if e["name"] == "checkpoint_save")
    assert save["parent"] is None and _ends(last) <= save["ts_s"]
    with tracer.span("t35.after") as after:
        pass
    assert after.parent is None


def test_round_that_raises_leaves_nothing_open(tmp_path):
    """The benchmark ends its fits by raising from the callback."""
    class Stop(Exception):
        pass

    def stop_at_the_third(rec):
        if len(trainer.heartbeats) == 3:
            raise Stop()

    trainer, sents = _toy_trainer(telemetry_path=str(tmp_path / "run.jsonl"))
    with pytest.raises(Stop):
        trainer.fit(sents, on_heartbeat=stop_at_the_third)
    assert trainer._round == []
    tracer = default_tracer()
    evs = tracer.events()
    beats = [e for e in evs if e["name"] == "heartbeat"]
    assert len(beats) == 3
    assert sum(e["name"] == "heartbeat.refill" for e in evs) == 2
    with tracer.span("t35.after") as after:
        pass
    assert after.parent is None


def test_drain_lands_in_the_device_block_phase(tmp_path):
    """``phases.device_block`` holds the explicit device syncs: the drain, the
    probe and the vector fetch, each once a heartbeat."""
    trainer, sents = _toy_trainer(telemetry_path=str(tmp_path / "run.jsonl"))
    trainer.fit(sents)
    evs = default_tracer().events()
    syncs = [e for e in evs
             if e["name"] in ("heartbeat.drain", "health_probe", "device_block")]
    drains = [e for e in syncs if e["name"] == "heartbeat.drain"]
    assert len(drains) == len(trainer.heartbeats) and len(syncs) == 3 * len(drains)
    phase = trainer.last_run_stats["phases"]["device_block"]
    assert phase["count"] == len(syncs)
    assert phase["total_s"] == pytest.approx(sum(e["dur_s"] for e in syncs), abs=1e-5)
    without = sum(e["dur_s"] for e in syncs if e["name"] != "heartbeat.drain")
    assert phase["total_s"] > without
    # the refill is no phase of its own: the dispatch inside it already is one
    assert trainer.last_run_stats["phases"]["dispatch"]["count"] == sum(
        e["name"] == "dispatch" for e in evs)


def test_nothing_recording_builds_no_span(monkeypatch):
    """Telemetry off and no live trace: a whole fit with heartbeats and a
    callback builds no ``_Span`` and keeps no round."""
    from glint_word2vec_tpu.obs import spans

    built = []

    class Counting(spans._Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            built.append(a[1])
            super().__init__(*a, **kw)

    monkeypatch.setattr(spans, "_Span", Counting)
    # a telemetry-on trainer of an earlier test leaves the recorder armed
    # until the next trainer is built, and the vocabulary is built before it
    default_tracer().configure(enabled=False)
    default_tracer().clear()
    trainer, sents = _toy_trainer()
    seen = []
    trainer.fit(sents, on_heartbeat=lambda rec: seen.append(list(trainer._round)))
    assert len(trainer.heartbeats) > 3 and seen == [[]] * len(seen)
    assert built == [] and default_tracer().events() == []
    assert trainer._round == []


def test_live_trace_puts_the_round_in_the_xplane(live_trace):
    trainer, sents = _toy_trainer()
    trainer.fit(sents, on_heartbeat=lambda rec: None)
    host = live_trace()
    assert not default_tracer().enabled
    assert {n for n, _, _ in host} >= {"heartbeat", *ROUND}
    check_round_trees(default_tracer().events(), len(trainer.heartbeats),
                      trainer.heartbeats[-1].global_step == trainer.global_step)


def test_probing_off_has_no_drain_and_blocks_on_the_fetch(live_trace):
    """No probe runs (``nonfinite_policy="none"``, ``norm_watch`` off, no sink):
    the heartbeat's first blocking call is the vector fetch, and it is the
    round's first child."""
    trainer, sents = _toy_trainer(nonfinite_policy="none")
    trainer.fit(sents, on_heartbeat=lambda rec: None)
    live_trace()
    evs = default_tracer().events()
    names = {e["name"] for e in evs}
    assert "heartbeat.drain" not in names and "health_probe" not in names
    beats = [e for e in evs if e["name"] == "heartbeat"]
    assert len(beats) == len(trainer.heartbeats) > 3
    for hb in beats[:-1]:
        kids = sorted((e for e in evs if e["parent"] == hb["id"]),
                      key=lambda e: e["ts_s"])
        assert [k["name"] for k in kids] == ["device_block", "heartbeat.callback",
                                             "heartbeat.refill"]


def test_edges_of_a_traced_slice(tmp_path):
    """The benchmark opens and closes its slice from inside the callback. The
    round that opens it has no recorded ``heartbeat`` (entered before the trace
    was live): its refill is recorded, with no parent. The round that closes it
    is open when the trace stops and is not kept; what it had closed by then is.
    Readers go by name."""
    import jax.profiler as jp
    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
    trainer, sents = _toy_trainer()
    state = {"live": False}

    def on_heartbeat(rec):
        if len(trainer.heartbeats) == 3:
            jp.start_trace(str(tmp_path))
            state["live"] = True
        elif len(trainer.heartbeats) == 6:
            jp.stop_trace()
            state["live"] = False

    try:
        trainer.fit(sents, on_heartbeat=on_heartbeat)
    finally:
        if state["live"]:
            jp.stop_trace()
    try:
        evs = tracer.events()
        count = lambda name: sum(e["name"] == name for e in evs)  # noqa: E731
        assert count("heartbeat") == 2              # rounds 4 and 5
        assert count("heartbeat.refill") == 3       # and round 3's
        assert count("heartbeat.drain") == count("health_probe") == 3   # 4, 5, 6
        assert count("heartbeat.callback") == 2
        first = min(evs, key=lambda e: e["ts_s"])
        assert first["name"] == "heartbeat.refill" and first["parent"] is None
        ids = {e["id"] for e in evs}
        cut = [e for e in evs if e["parent"] is not None and e["parent"] not in ids]
        assert sorted(e["name"] for e in cut) == ["device_block", "health_probe",
                                                  "heartbeat.drain"]
        assert trainer._round == []
    finally:
        tracer.clear()
