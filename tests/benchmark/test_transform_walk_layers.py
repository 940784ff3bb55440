"""The two metrics of the transform encode's walk (``layers/transform_encode_walk_ms``
and ``layers/transform_encode_by_objects``, PR 51): each file names a reader the
benchmark has and is declared in ``BENCHMARK.json`` for the transform cell (the
walk's time, since PR 54, for the sentence-vector cell too, whose slides take the
same walk; the ``by_objects`` arg is ``transform_sentences``' alone),
reduces hand-made records to the number it owes and to nothing where the
program has neither the span nor the arg, and reads a number from the spans the
program itself records around a slide (the cell's ``--tiny --trace 1`` run, which
``test_harness.py`` holds to the declared metrics, reports both as well).
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import zipf  # noqa: E402
from kinds import transform as kind  # noqa: E402
from readers import program_spans  # noqa: E402
from reference import transform_ref as ref  # noqa: E402

V, D, HALF_WIDTH, SEED = 5000, 40, 0.5, 2**31 + 51
TF = {"callers": 1, "slides_per_caller": 1, "slide_rows": 200, "oov_share": 0.05,
      "empty_share": 0.02, "check_rows_per_call": 4, "check_sentences": 12,
      "sentence_len": {"law": "lognormal", "median": 20, "sigma": 1.0, "min": 1, "max": 1000}}


@pytest.fixture(scope="module")
def table() -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(ref.seeded_rows(SEED, D, HALF_WIDTH)(jnp.arange(V, dtype=jnp.int32)))


TRANSFORM = "sgns-transform-3m-300.transform-slides10k-closed4"
SENTVEC = "subword-sentvec-2.5m-300.sentvec-slides10k-oov5-closed4"

WALK_LAYERS = {
    "transform_encode_walk_ms": dict(
        unit="ms", better="lower", source="program_span", cells=[TRANSFORM, SENTVEC],
        args={"span": "transform.encode.walk", "stat": "ms_per", "per": "transform.slide"}),
    "transform_encode_by_objects": dict(
        unit="share", better="higher", source="program_counter", cells=[TRANSFORM],
        args={"span": "transform.encode", "stat": "arg_mean", "arg": "by_objects"}),
}


def _layer(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    with open(os.path.join(BENCH, "layers", name + ".json")) as f:
        return entry, json.load(f)


@pytest.mark.parametrize("name", sorted(WALK_LAYERS))
def test_a_walk_metric_is_declared_as_the_issue_has_it(name):
    entry, layer = _layer(name)
    want = WALK_LAYERS[name]
    assert layer["reader"] == "program_spans"
    assert layer["args"] == want["args"] and layer["what"]
    assert {k: entry[k] for k in ("unit", "better", "source")} == {
        k: want[k] for k in ("unit", "better", "source")}
    assert entry["layer"] == "transform encode" and entry["moves"] == "query_per_s"
    assert entry["workloads"] == want["cells"]
    # two slides, one resolved by the walk (3 ms of it) and one by the dict
    events = [
        {"name": "transform.slide", "id": 1, "ts_s": 0.0, "dur_s": 0.05, "args": {}},
        {"name": "transform.encode", "id": 2, "parent": 1, "ts_s": 0.0, "dur_s": 0.01,
         "args": {"by_objects": 1}},
        {"name": "transform.encode.walk", "id": 3, "parent": 2, "ts_s": 0.001, "dur_s": 0.003},
        {"name": "transform.slide", "id": 4, "ts_s": 0.1, "dur_s": 0.05, "args": {}},
        {"name": "transform.encode", "id": 5, "parent": 4, "ts_s": 0.1, "dur_s": 0.04,
         "args": {"by_objects": 0}}]
    assert program_spans.reduce_events(layer["args"], events) == pytest.approx(
        {"transform_encode_walk_ms": 1.5, "transform_encode_by_objects": 0.5}[name])
    # a program from before the walk records neither the span nor the arg
    old = [dict(e, args={}) for e in events if e["name"] != "transform.encode.walk"]
    assert program_spans.reduce_events(layer["args"], old) is None


@pytest.mark.parametrize("name", sorted(WALK_LAYERS))
def test_a_walk_metric_reads_the_programs_own_spans(table, name, monkeypatch):
    """The model's slides, recorded by the program's recorder as a traced slice
    would hold them, through the metric's own file: above the lookup's
    threshold the walk answers every slide, under it none."""
    from glint_word2vec_tpu.data import vocab as vocab_module
    from glint_word2vec_tpu.data.vocab import Vocabulary
    from glint_word2vec_tpu.models.word2vec import Word2VecModel
    from glint_word2vec_tpu.obs.spans import default_tracer
    if vocab_module._load_native() is None:
        pytest.skip("no toolchain for native/lookup.cpp here: dict.get answers")
    _, layer = _layer(name)
    model = Word2VecModel(
        Vocabulary.from_words_and_counts(zipf.words_of(V), np.ones(V, np.int64)), table)
    slide = kind.make_slides(SEED, V, TF)[0][0][0]
    tracer = default_tracer()
    read = {}
    try:
        for route, threshold in (("walk", 1), ("dict", 1 << 30)):
            monkeypatch.setattr(vocab_module, "NATIVE_LOOKUP_TOKENS", threshold)
            tracer.clear()
            tracer.configure(True)
            model.transform_sentences(slide, batch_size=50)
            tracer.configure(False)
            read[route] = program_spans.reduce_events(layer["args"], tracer.events())
    finally:
        tracer.configure(False)
        tracer.clear()
        model.stop()
    if name == "transform_encode_by_objects":
        assert read == {"walk": 1.0, "dict": 0.0}
    else:
        assert isinstance(read["walk"], float) and 0 < read["walk"] < 1e3
        assert read["dict"] is None
