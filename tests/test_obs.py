"""Observability-layer suite (docs/observability.md): telemetry JSONL schema
round-trip + rotation, span nesting/thread-safety + Chrome-trace export, the
fused health probe vs a NumPy oracle, the finite-blowup watchdog under both
policies, the bounded heartbeat ring, and the compiled-step contract proof
that a probing fit adds no implicit transfers and no extra step-twin
recompile (the stepaudit discipline, exercised in-process with the probe
actually firing)."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import encode_sentences
from glint_word2vec_tpu.data.vocab import build_vocab
from glint_word2vec_tpu.obs.probe import make_health_probe, stats_to_channels
from glint_word2vec_tpu.obs.schema import (
    SCHEMA_VERSION,
    validate_file,
    validate_record,
)
from glint_word2vec_tpu.obs.sink import TelemetrySink
from glint_word2vec_tpu.obs.spans import Tracer
from glint_word2vec_tpu.obs.watch import NormWatchdog
from glint_word2vec_tpu.ops.sgns import EmbeddingPair
from glint_word2vec_tpu.train import faults
from glint_word2vec_tpu.train.faults import NormBlowupError
from glint_word2vec_tpu.train.trainer import Trainer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()
    # a telemetry-on trainer arms the process-wide recorder until the next
    # trainer is built; a vocabulary built before that one would put its
    # pinned span in the next test's ring
    from glint_word2vec_tpu.obs.spans import default_tracer
    default_tracer().configure(enabled=False)


def _toy_trainer(seed=0, n=250, **cfg_kw):
    rng = np.random.default_rng(seed)
    sents = [[f"w{i}" for i in rng.integers(0, 30, 20)] for _ in range(n)]
    vocab = build_vocab(sents, min_count=1)
    cfg = Word2VecConfig(vector_size=8, pairs_per_batch=128, window=3,
                         num_iterations=2, steps_per_dispatch=2,
                         heartbeat_every_steps=2, subsample_ratio=0.0,
                         prefetch_chunks=0, seed=1, **cfg_kw)
    return Trainer(cfg, vocab), encode_sentences(sents, vocab, 1000)


# -- schema + sink ---------------------------------------------------------------------


def test_sink_roundtrip_schema_valid(tmp_path):
    """Every record kind the trainer emits must validate against the
    catalogue, version field included, after a disk round-trip."""
    p = str(tmp_path / "run.jsonl")
    with TelemetrySink(p) as sink:
        sink.emit("run_start", run_id="r1", vocab_size=30, mesh=[1, 1],
                  config={"learning_rate": 0.02})
        sink.emit("heartbeat", step=4, words=100, alpha=0.02, loss=1.5,
                  mean_f_pos=0.4, pairs_per_sec=1e5, host_wait_s=0.1,
                  dispatch_s=0.2, recoveries=0, lr_scale=1.0,
                  norms={"finite": True})
        sink.emit("watchdog", step=4, policy="warn", reason="x",
                  channels={"syn0": {"max_norm": 1e4}})
        sink.emit("run_end", run_id="r1", status="ok", steps=4,
                  pairs_trained=512.0, host_wait_s_total=0.1,
                  dispatch_s_total=0.2, watchdog_fires=1)
    summary = validate_file(p)
    assert summary["ok"], summary["errors"]
    assert summary["kinds"] == {"run_start": 1, "heartbeat": 1,
                                "watchdog": 1, "run_end": 1}
    with open(p) as f:
        recs = [json.loads(line) for line in f]
    assert all(r["schema"] == SCHEMA_VERSION for r in recs)
    assert all("t" in r for r in recs)


def test_schema_rejects_drift():
    ok = {"schema": SCHEMA_VERSION, "kind": "heartbeat", "t": 1.0, "step": 1,
          "words": 10, "alpha": 0.1, "loss": 1.0, "mean_f_pos": 0.5,
          "pairs_per_sec": 1.0, "host_wait_s": 0.0, "dispatch_s": 0.0,
          "recoveries": 0, "lr_scale": 1.0}
    assert validate_record(ok) == []
    assert validate_record({**ok, "schema": SCHEMA_VERSION + 1})  # version drift
    bad = dict(ok)
    del bad["loss"]
    assert any("loss" in e for e in validate_record(bad))  # field removal
    assert validate_record({**ok, "step": "four"})         # type change
    assert validate_record({**ok, "kind": "mystery"})      # unknown kind
    # additive evolution stays legal
    assert validate_record({**ok, "new_field": 123}) == []
    # a pre-round-13 heartbeat (no recoveries/lr_scale/phases) still
    # validates — new fields are OPTIONAL under the unchanged version, so
    # archived run logs don't retroactively fail the drift gate
    old = dict(ok)
    del old["recoveries"], old["lr_scale"]
    assert validate_record(old) == []
    # ...but a present optional field is still type-checked
    assert any("lr_scale" in e
               for e in validate_record({**ok, "lr_scale": "half"}))


def test_sink_rotation_bounded(tmp_path):
    p = str(tmp_path / "run.jsonl")
    sink = TelemetrySink(p, rotate_bytes=2000, keep=2)
    for i in range(200):
        sink.emit("watchdog", step=i, policy="warn", reason="r" * 50,
                  channels={})
    sink.close()
    files = sorted(os.listdir(tmp_path))
    assert "run.jsonl" in files
    assert "run.jsonl.1" in files
    assert "run.jsonl.2" in files
    assert "run.jsonl.3" not in files  # keep=2 bounds the rotated segments
    for f in files:
        assert os.path.getsize(tmp_path / f) <= 2000 + 200
        assert validate_file(str(tmp_path / f))["ok"]


def test_sink_thread_safety(tmp_path):
    """Concurrent emitters must never interleave mid-line (each record is one
    write under the lock)."""
    p = str(tmp_path / "run.jsonl")
    sink = TelemetrySink(p)

    def emit_many(tid):
        for i in range(100):
            sink.emit("watchdog", step=i, policy="warn",
                      reason=f"t{tid}" * 20, channels={"tid": tid})

    threads = [threading.Thread(target=emit_many, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    summary = validate_file(p)
    assert summary["ok"], summary["errors"][:3]
    assert summary["records"] == 400


def test_sink_sanitizes_nonfinite(tmp_path):
    """Non-finite measured values (a diverging run's NaN loss) must land as
    null, never as RFC-8259-invalid bare NaN/Infinity tokens — strict
    consumers (jq) read the run log of exactly those runs."""
    p = str(tmp_path / "run.jsonl")
    with TelemetrySink(p) as sink:
        sink.emit("heartbeat", step=1, words=1, alpha=0.1, loss=float("nan"),
                  mean_f_pos=float("inf"), pairs_per_sec=1.0,
                  host_wait_s=0.0, dispatch_s=0.0, recoveries=0, lr_scale=1.0,
                  norms={"syn0": {"max_norm": float("-inf")}})
    line = open(p).read()
    assert "NaN" not in line and "Infinity" not in line
    rec = json.loads(line)
    assert rec["loss"] is None and rec["mean_f_pos"] is None
    assert rec["norms"]["syn0"]["max_norm"] is None
    assert validate_record(rec) == []


# -- spans -----------------------------------------------------------------------------


def test_span_nesting_and_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
    outer = evs[2]
    for inner in evs[:2]:
        # containment: inner spans sit inside the outer's [ts, ts+dur] window
        assert inner["ts_s"] >= outer["ts_s"] - 1e-9
        assert (inner["ts_s"] + inner["dur_s"]
                <= outer["ts_s"] + outer["dur_s"] + 1e-9)
    p = str(tmp_path / "trace.json")
    assert tr.export_chrome_trace(p) == 3
    with open(p) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    assert all(set(e) >= {"ph", "name", "pid", "tid", "ts", "dur"} for e in xs)
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"


def test_span_thread_safety_and_tids():
    tr = Tracer(enabled=True)
    barrier = threading.Barrier(4)  # all 4 alive at once: thread idents are
                                    # only unique among LIVE threads

    def work(i):
        barrier.wait()
        for _ in range(50):
            with tr.span(f"thread{i}"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 200
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], set()).add(e["tid"])
    # each span name was recorded on exactly its own thread
    assert all(len(tids) == 1 for tids in by_name.values())
    assert len({next(iter(t)) for t in by_name.values()}) == 4
    summary = tr.span_summary()
    assert all(summary[f"thread{i}"]["count"] == 50 for i in range(4))


def test_span_disabled_is_noop_and_bounded():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    assert tr.events() == []
    tr2 = Tracer(enabled=True, max_events=10)
    for i in range(25):
        with tr2.span(f"s{i}"):
            pass
    evs = tr2.events()
    assert len(evs) == 10
    assert evs[0]["name"] == "s15"  # oldest dropped, tail kept


# -- fused health probe vs NumPy oracle ------------------------------------------------


def test_probe_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    V, Vpad, D = 500, 512, 16
    threshold = 10.0
    syn0 = rng.normal(size=(Vpad, D)).astype(np.float32)
    syn1 = rng.normal(size=(Vpad, D)).astype(np.float32)
    syn0[50] *= 1e4    # a runaway row
    syn0[60:80] *= 40  # a hot subset past the threshold
    syn0[V:] = 0.0     # padding must not contaminate any channel
    syn1[V:] = 0.0
    params = EmbeddingPair(jax.numpy.asarray(syn0), jax.numpy.asarray(syn1))
    probe = make_health_probe(V, threshold)
    ch = stats_to_channels(jax.device_get(probe(params)))
    assert ch["finite"] is True
    for name, mat in (("syn0", syn0), ("syn1", syn1)):
        norms = np.linalg.norm(mat[:V].astype(np.float64), axis=1)
        got = ch[name]
        assert got["max_norm"] == pytest.approx(norms.max(), rel=1e-5)
        assert got["mean_norm"] == pytest.approx(norms.mean(), rel=1e-5)
        assert got["frac_over"] == pytest.approx(
            float((norms > threshold).mean()), abs=1e-7)
        # histogram p99 is exact to one quarter-octave bucket: the true p99
        # lies in (p99/2^0.25, p99]
        true_p99 = np.quantile(norms, 0.99, method="inverted_cdf")
        assert got["p99_norm"] >= true_p99 * (1 - 1e-6)
        assert got["p99_norm"] <= true_p99 * 2 ** 0.25 * (1 + 1e-6)


def _probe_rows(case: str, V: int, D: int = 8) -> np.ndarray:
    """Row norms laid out to stress the p99 bucket: where it falls, and the edges."""
    rng = np.random.default_rng(len(case) + V)
    unit = rng.normal(size=(V, D)).astype(np.float32)
    unit /= np.linalg.norm(unit, axis=1, keepdims=True)
    if case == "spread":            # many live buckets, 2^-14 .. 2^22: both clamps
        scale = np.exp2(rng.uniform(-14, 22, V))
    elif case == "one_bucket":      # every row in one bucket
        scale = np.full(V, 3.0)
    elif case == "zeros":           # all-zero rows clamp to bucket 0
        scale = np.zeros(V)
    elif case == "tail_of_one":     # 99% small, the rest huge: the crossing is at the edge
        scale = np.where(np.arange(V) < -(-V * 99 // 100), 0.5, 1e6)
    elif case == "tail_starts_early":   # one row short of 99% small
        scale = np.where(np.arange(V) < -(-V * 99 // 100) - 1, 0.5, 1e6)
    return (unit * scale[:, None]).astype(np.float32)


def _histogram_p99(rows: np.ndarray) -> float:
    """The p99 bucket's upper edge as the 128-bucket histogram's CDF gives it,
    built by NumPy from the probe's own float32 norms of ``rows``."""
    from glint_word2vec_tpu.obs import probe as P

    x = jax.numpy.asarray(rows)
    norms = np.asarray(jax.numpy.sqrt(jax.numpy.sum(x * x, axis=1)))
    logn = np.log2(np.maximum(norms, np.float32(2.0 ** P._HIST_LO)))
    idx = np.clip(np.floor((logn - np.float32(P._HIST_LO)) * P._HIST_PER_OCTAVE),
                  0, P._HIST_BUCKETS - 1).astype(np.int64)
    cdf = np.cumsum(np.bincount(idx, minlength=P._HIST_BUCKETS))
    k = int(np.argmax(cdf >= -(-rows.shape[0] * 99 // 100)))
    return float(np.exp2(np.float32((k + 1.0) / P._HIST_PER_OCTAVE + P._HIST_LO)))


@pytest.mark.parametrize("case", ["spread", "one_bucket", "zeros", "tail_of_one",
                                  "tail_starts_early"])
@pytest.mark.parametrize("V,pad", [(1, 0), (100, 28), (1000, 0), (4097, 31)])
def test_probe_p99_is_the_histograms_bucket(case, V, pad):
    """The p99 bucket found by bisection (7 counts) is the one the histogram's
    CDF gives: exactly, not to a bucket. syn1 is syn0 upside down, so its
    first V rows are the padding and the table's tail: another distribution."""
    rows = _probe_rows(case, V)
    m = np.concatenate([rows, np.zeros((pad, rows.shape[1]), np.float32)])
    flipped = m[::-1].copy()
    ch = stats_to_channels(jax.device_get(make_health_probe(V, 10.0)(
        EmbeddingPair(jax.numpy.asarray(m), jax.numpy.asarray(flipped)))))
    assert ch["syn0"]["p99_norm"] == pytest.approx(_histogram_p99(rows), rel=1e-6)
    assert ch["syn1"]["p99_norm"] == pytest.approx(_histogram_p99(flipped[:V]), rel=1e-6)


def test_probe_is_the_same_on_a_mesh():
    """Row-sharded tables (the 8 virtual devices of conftest.py as a 2x4 mesh):
    the counts become all-reduces and every channel is the one-device value."""
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    rows = _probe_rows("spread", 4096)
    plan = make_mesh(2, 4)
    probe = make_health_probe(4000, 10.0)
    one = stats_to_channels(jax.device_get(probe(
        EmbeddingPair(jax.numpy.asarray(rows), jax.numpy.asarray(rows * 2)))))
    put = lambda x: jax.device_put(jax.numpy.asarray(x), plan.embedding)  # noqa: E731
    many = stats_to_channels(jax.device_get(probe(
        EmbeddingPair(put(rows), put(rows * 2)))))
    for name in ("syn0", "syn1"):
        assert many[name]["p99_norm"] == one[name]["p99_norm"]
        assert many[name]["frac_over"] == pytest.approx(one[name]["frac_over"], abs=1e-7)
        assert many[name]["max_norm"] == one[name]["max_norm"]
    assert many["finite"] is True


def test_probe_finite_bit_matches_old_semantics():
    rng = np.random.default_rng(1)
    a = rng.normal(size=(64, 8)).astype(np.float32)
    params = EmbeddingPair(jax.numpy.asarray(a), jax.numpy.asarray(a))
    probe = make_health_probe(60, 100.0)
    assert stats_to_channels(jax.device_get(probe(params)))["finite"] is True
    b = a.copy()
    b[63, 7] = np.nan  # in the PADDING rows — finiteness covers the whole carry
    params = EmbeddingPair(jax.numpy.asarray(a), jax.numpy.asarray(b))
    assert stats_to_channels(jax.device_get(probe(params)))["finite"] is False


# What the probe's one pass a table (PR 56: the row sums and the "any element
# not finite" bit from ONE reduce over the PADDED table) must keep. Tables as
# the trainer pads them: V real rows of Vpad, D real lanes of Dpad.
_ONE_PASS = dict(V=500, Vpad=512, D=12, Dpad=16)


def _one_pass_tables():
    rng = np.random.default_rng(56)
    p = _ONE_PASS
    tables = []
    for _ in range(2):
        m = np.zeros((p["Vpad"], p["Dpad"]), np.float32)
        m[:p["V"], :p["D"]] = rng.normal(size=(p["V"], p["D"]))
        tables.append(m)
    return tables


@pytest.fixture(scope="module")
def one_pass_channels():
    """One compiled probe for every case below: tables -> channels."""
    probe = make_health_probe(_ONE_PASS["V"], 2.0)
    return lambda syn0, syn1: stats_to_channels(jax.device_get(probe(
        EmbeddingPair(jax.numpy.asarray(syn0), jax.numpy.asarray(syn1)))))


@pytest.fixture(scope="module")
def one_pass_clean(one_pass_channels):
    clean = one_pass_channels(*_one_pass_tables())
    assert clean["finite"] is True
    return clean


@pytest.mark.parametrize("table", [0, 1], ids=["syn0", "syn1"])
@pytest.mark.parametrize("lane", [0, _ONE_PASS["Dpad"] - 1], ids=["real_lane", "padding_lane"])
@pytest.mark.parametrize("row", [_ONE_PASS["V"], _ONE_PASS["Vpad"] - 1],
                         ids=["first_padding_row", "last_padding_row"])
@pytest.mark.parametrize("value", [np.nan, np.inf, -np.inf], ids=["nan", "inf", "minus_inf"])
def test_probe_a_padding_rows_element_clears_the_bit_and_moves_no_channel(
        one_pass_channels, one_pass_clean, value, row, lane, table):
    """The finite bit is over the PADDED carry; every norm channel is over the
    first V entries of the row sums, so a padding row reaches none of them:
    the channels are the clean tables' to the last bit."""
    tables, clean = _one_pass_tables(), one_pass_clean
    tables[table][row, lane] = value
    got = one_pass_channels(*tables)
    assert got["finite"] is False
    assert (got["syn0"], got["syn1"]) == (clean["syn0"], clean["syn1"])


@pytest.mark.parametrize("value", [np.nan, np.inf], ids=["nan", "inf"])
def test_probe_reads_a_real_rows_padding_lanes(one_pass_channels, one_pass_clean, value):
    """All Dpad lanes of a row are the row the probe reads: an element that is
    not finite in a padding lane of a REAL row clears the bit and is in that
    row's norm; the other table's channels are the clean ones."""
    tables, clean = _one_pass_tables(), one_pass_clean
    tables[0][7, _ONE_PASS["Dpad"] - 1] = value
    got = one_pass_channels(*tables)
    assert got["finite"] is False
    assert not np.isfinite(got["syn0"]["max_norm"])
    assert got["syn1"] == clean["syn1"]


@pytest.mark.parametrize("table", [0, 1], ids=["syn0", "syn1"])
def test_probe_a_finite_row_whose_squares_overflow_is_finite(
        one_pass_channels, one_pass_clean, table):
    """The bit tests the ELEMENTS, never the norms: 2e19 squared is past
    float32, so the row's norm is inf and the row is finite."""
    tables, clean = _one_pass_tables(), one_pass_clean
    tables[table][3, :_ONE_PASS["D"]] = 2e19
    got = one_pass_channels(*tables)
    name, other = ("syn0", "syn1") if table == 0 else ("syn1", "syn0")
    assert got["finite"] is True
    assert got[name]["max_norm"] == np.inf and got[name]["mean_norm"] == np.inf
    assert got[name]["frac_over"] >= clean[name]["frac_over"]
    assert got[other] == clean[other]


def test_probe_accumulates_a_bfloat16_carry_in_float32():
    """Rows of 0.1 over 4,096 lanes: a bfloat16 running sum of the squares
    stalls near 2 (0.01 is under half a place of it); the float32 sum of the
    bfloat16 elements' squares is 40.99."""
    import jax.numpy as jnp

    V, D = 64, 4096
    m = np.full((V, D), 0.1, np.float32)
    stats = jax.device_get(make_health_probe(V, 2.0)(
        EmbeddingPair(jnp.asarray(m, jnp.bfloat16), jnp.asarray(m * 2, jnp.bfloat16))))
    assert stats.syn0.max_norm.dtype == np.float32
    ch = stats_to_channels(stats)
    assert ch["finite"] is True
    for name, scale in (("syn0", 1.0), ("syn1", 2.0)):
        elem = float(jnp.asarray(0.1 * scale, jnp.bfloat16).astype(jnp.float32))
        want = np.sqrt(D) * elem
        assert ch[name]["max_norm"] == pytest.approx(want, rel=1e-5)
        assert ch[name]["mean_norm"] == pytest.approx(want, rel=1e-5)
        assert ch[name]["frac_over"] == 1.0


# -- watchdog --------------------------------------------------------------------------


def _channels(max_norm=1.0, frac=0.0):
    m = {"max_norm": max_norm, "mean_norm": 1.0, "p99_norm": 1.0,
         "frac_over": frac}
    return {"finite": True, "syn0": dict(m), "syn1": dict(m)}


def test_watchdog_unit_thresholds():
    wd = NormWatchdog("warn", threshold=100.0, max_norm=1000.0, frac=0.01)
    assert wd.check(_channels(), step=1) is None
    assert wd.check(_channels(max_norm=999.0, frac=0.0099), step=2) is None
    assert wd.fires == 0
    assert wd.check(_channels(frac=0.02), step=3)
    assert wd.check(_channels(max_norm=2000.0), step=4)
    assert wd.fires == 2
    wd_halt = NormWatchdog("halt", 100.0, 1000.0, 0.01)
    with pytest.raises(NormBlowupError, match="finite norm blowup"):
        wd_halt.check(_channels(max_norm=5000.0), step=5)
    wd_off = NormWatchdog("off", 100.0, 1000.0, 0.01)
    assert wd_off.check(_channels(max_norm=1e9), step=6) is None


def test_injected_blowup_warn_fires_nonfinite_silent(tmp_path):
    """The acceptance scenario: a scripted FINITE blowup
    (faults.scale_params_at_step). norm_watch='warn' fires and finishes;
    nonfinite_policy='halt' alone must never notice (no NaN exists)."""
    run_log = str(tmp_path / "run.jsonl")
    faults.configure(scale_params_at_step=8)
    trainer, enc = _toy_trainer(norm_watch="warn", nonfinite_policy="halt",
                                telemetry_path=run_log)
    trainer.fit(enc)  # no raise: the guardrail stays silent, warn continues
    assert trainer.norm_watchdog.fires >= 1
    assert np.isfinite(np.asarray(trainer.params.syn0)).all()
    summary = validate_file(run_log)
    assert summary["ok"], summary["errors"][:3]
    assert summary["kinds"].get("watchdog", 0) >= 1
    with open(run_log) as f:
        wd = [json.loads(line) for line in f
              if '"kind": "watchdog"' in line]
    assert wd[0]["policy"] == "warn"
    assert wd[0]["channels"]["syn0"]["max_norm"] > 1000.0


def test_injected_blowup_halt_raises(tmp_path):
    run_log = str(tmp_path / "run.jsonl")
    faults.configure(scale_params_at_step=8)
    trainer, enc = _toy_trainer(norm_watch="halt", telemetry_path=run_log)
    with pytest.raises(NormBlowupError, match="finite norm blowup"):
        trainer.fit(enc)
    # the halt record was emitted BEFORE the raise, and run_end carries error
    with open(run_log) as f:
        recs = [json.loads(line) for line in f]
    kinds = [r["kind"] for r in recs]
    assert "watchdog" in kinds
    assert recs[-1]["kind"] == "run_end" and recs[-1]["status"] == "error"


def test_norm_watch_off_default_and_validation():
    assert Word2VecConfig().norm_watch == "off"
    with pytest.raises(ValueError, match="norm_watch"):
        Word2VecConfig(norm_watch="panic")
    with pytest.raises(ValueError, match="norm_watch_frac"):
        Word2VecConfig(norm_watch_frac=0.0)
    with pytest.raises(ValueError, match="heartbeat_ring"):
        Word2VecConfig(heartbeat_ring=0)


# -- bounded heartbeat ring ------------------------------------------------------------


def test_heartbeat_ring_bounded(tmp_path):
    run_log = str(tmp_path / "run.jsonl")
    trainer, enc = _toy_trainer(heartbeat_ring=4, telemetry_path=run_log)
    trainer.fit(enc)
    assert trainer.heartbeats.maxlen == 4
    assert len(trainer.heartbeats) == 4
    # the ring keeps the newest records; the sink file keeps the full history
    summary = validate_file(run_log)
    assert summary["kinds"]["heartbeat"] > 4
    steps = [r.global_step for r in trainer.heartbeats]
    assert steps == sorted(steps)
    # the ring holds the NEWEST records (the final round may not reach the
    # next heartbeat cadence, so exact equality is not guaranteed)
    assert (trainer.global_step - trainer.heartbeats[-1].global_step
            < trainer.config.heartbeat_every_steps
            + trainer.config.steps_per_dispatch)
    # extended fields ride every record
    hb = trainer.heartbeats[-1]
    assert hb.norms is not None and "syn0" in hb.norms
    assert hb.host_wait_s >= 0.0 and hb.dispatch_s >= 0.0


def test_tracer_disarmed_by_telemetry_off_trainer(tmp_path):
    """The process-wide tracer must be DISARMED by a telemetry-off trainer
    constructed after a telemetry-on one — otherwise the overhead A/B's off
    arm silently records spans into the shared ring (biasing the very metric
    the acceptance bar reads) and long-lived processes accumulate events."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    _toy_trainer(telemetry_path=str(tmp_path / "a.jsonl"))
    assert default_tracer().enabled
    _toy_trainer()
    assert not default_tracer().enabled


def test_run_end_ok_when_fit_called_inside_except_block(tmp_path):
    """A successful fit launched from inside an except handler (the
    crash-recovery resume pattern) must emit run_end status='ok' — a
    sys.exc_info()-based abort check in the fit finally would see the OUTER
    handled exception and mislabel it."""
    run_log = str(tmp_path / "run.jsonl")
    try:
        raise RuntimeError("outer handled failure")
    except RuntimeError:
        trainer, enc = _toy_trainer(telemetry_path=run_log)
        trainer.fit(enc, checkpoint_path=str(tmp_path / "ck"),
                    checkpoint_every_steps=8)
    with open(run_log) as f:
        recs = [json.loads(line) for line in f]
    assert recs[-1]["kind"] == "run_end"
    assert recs[-1]["status"] == "ok"


# -- compiled-step contracts with the probe firing -------------------------------------


def test_probe_no_implicit_transfers_no_extra_recompile(tmp_path):
    """The stepaudit discipline with telemetry ON and the probe actually
    firing (the audit's scripted fits never reach a heartbeat, so this is the
    coverage for the probing path): the whole fit runs under
    jax.transfer_guard('disallow') — the probe's device fetch is explicit
    (jax.device_get) and its inputs are the already-staged params carry — and
    the two step twins still compile exactly once (the probe is its own tiny
    program, never a step-twin signature change)."""
    trainer, enc = _toy_trainer(
        telemetry_path=str(tmp_path / "run.jsonl"), norm_watch="warn")
    with jax.transfer_guard("disallow"):
        trainer.fit(enc)
    assert len(trainer.heartbeats) > 0  # the probe really ran under the guard
    compiles = trainer._step_fn._cache_size()
    if trainer._step_fn_fast is not trainer._step_fn:
        compiles += trainer._step_fn_fast._cache_size()
    assert compiles == 1


# -- scripted telemetry fit through the CLI driver -------------------------------------


def test_telemetry_run_smoke(tmp_path):
    """End-to-end acceptance: tools/telemetry_run.py --smoke produces a
    schema-valid JSONL run log + a Chrome trace with the required spans, and
    prints exactly one JSON line (R7)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "telemetry_run.py"),
         "--smoke", "--out", str(tmp_path / "art")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=_REPO, capture_output=True, timeout=500, text=True)
    assert proc.returncode == 0, proc.stdout[-1000:] + proc.stderr[-1000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    res = json.loads(lines[0])
    assert res["ok"] and res["schema_valid"]
    assert res["missing_spans"] == []
    assert os.path.exists(res["run_log"])
    assert os.path.exists(res["trace"])


# -- the recorder follows the profiler (ISSUE 25) --------------------------------------


@pytest.fixture
def live_trace(tmp_path):
    """A real jax.profiler trace on the CPU backend, telemetry off. Yields
    ``stop()``, which ends the trace once and returns its host spans as
    benchmark/harness/trace.py reads them."""
    import sys
    import jax.profiler as jp
    from glint_word2vec_tpu.obs.spans import default_tracer
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import trace as htr
    tracer = default_tracer()
    tracer.configure(enabled=False)
    tracer.clear()
    state = {"live": True}
    jp.start_trace(str(tmp_path))

    def stop():
        if state["live"]:
            state["live"] = False
            jp.stop_trace()
        return htr.load(htr.newest_xplane(str(tmp_path)), "cpu")["host"]

    try:
        yield stop
    finally:
        if state["live"]:
            jp.stop_trace()
        tracer.clear()


def test_trace_annotation_is_enabled_tracks_the_trace(tmp_path):
    """The rule the recorder rests on: a JAX upgrade that moves it must fail
    here and not blind the benchmark in silence."""
    import jax.profiler as jp
    assert not jp.TraceAnnotation.is_enabled()
    jp.start_trace(str(tmp_path))
    try:
        assert jp.TraceAnnotation.is_enabled()
    finally:
        jp.stop_trace()
    assert not jp.TraceAnnotation.is_enabled()


def test_live_trace_arms_spans_in_ring_and_xplane(live_trace):
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    assert not tracer.enabled
    with tracer.span("t25.outer", size=3):
        with tracer.span("t25.inner") as inner:
            inner.set(ops=7)
    host = live_trace()
    evs = {e["name"]: e for e in tracer.events()}
    assert set(evs) == {"t25.outer", "t25.inner"}
    assert evs["t25.outer"]["args"] == {"size": 3}
    assert evs["t25.inner"]["args"] == {"ops": 7}
    assert evs["t25.inner"]["parent"] == evs["t25.outer"]["id"]
    assert evs["t25.outer"]["parent"] is None
    # and in the profiler's own host plane, under their names
    assert {n for n, _, _ in host} >= {"t25.outer", "t25.inner"}
    # stop_trace disarmed it: nothing more is recorded
    with tracer.span("t25.late"):
        pass
    assert len(tracer.events()) == 2


def test_span_open_when_the_trace_stops_is_not_kept(live_trace):
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    with tracer.span("t25.cut") as sp:
        with tracer.span("t25.whole"):
            pass
        live_trace()
    assert [e["name"] for e in tracer.events()] == ["t25.whole"]
    assert not sp.recorded and sp.dur > 0


def test_fit_start_with_telemetry_off_keeps_following_the_profiler(live_trace):
    """``Trainer._start_run_bookkeeping`` configures telemetry off; under a
    live trace the fit's spans are recorded all the same, with the dispatch
    span's children by parent id and the caller's callback apart."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    trainer, sents = _toy_trainer()
    trainer.fit(sents, on_heartbeat=lambda rec: None)
    live_trace()
    tracer = default_tracer()
    assert not tracer.enabled
    evs = tracer.events()
    by_id = {e["id"]: e for e in evs}
    names = {e["name"] for e in evs}
    assert names >= {"producer", "dispatch", "dispatch.put", "dispatch.meta",
                     "dispatch.enqueue", "device_block", "heartbeat.callback"}
    for e in evs:
        if e["name"].startswith("dispatch."):
            assert by_id[e["parent"]]["name"] == "dispatch"
    assert all(e["args"]["steps"] >= 1 for e in evs if e["name"] == "dispatch")


def test_span_ids_parents_and_retroactive_record_across_threads():
    tr = Tracer(enabled=True)
    with tr.span("batch") as batch:
        def other():
            with tr.span("caused", parent=batch.id):
                with tr.span("nested"):
                    pass
            with tr.span("orphan"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    tr.record("waited", batch.t0 - 0.5, 0.5, parent=batch.id, request=11)
    evs = {e["name"]: e for e in tr.events()}
    ids = [e["id"] for e in evs.values()]
    assert len(set(ids)) == len(ids) == 5
    assert evs["caused"]["parent"] == evs["batch"]["id"]
    assert evs["nested"]["parent"] == evs["caused"]["id"]
    # the other thread's stack is its own: no parent leaks across threads
    assert evs["orphan"]["parent"] is None
    assert evs["waited"]["parent"] == evs["batch"]["id"]
    assert evs["waited"]["args"] == {"request": 11}
    assert abs(evs["waited"]["dur_s"] - 0.5) < 1e-9
    assert (evs["waited"]["ts_s"] + 0.5
            == pytest.approx(evs["batch"]["ts_s"], abs=1e-6))


def test_a_span_detached_on_one_thread_is_closed_on_another():
    """A region begun on one thread and ended on another (a serve batch): one
    record, the opener's start, the closer's thread; each thread's stack its
    own throughout; children name it by id."""
    tr = Tracer(enabled=True)
    assert tr.current() is None
    sp = tr.open("batch", timed=True, size=2)
    assert tr.current() == sp.id
    with tr.span("first_half"):
        pass
    sp.detach()
    sp.detach()                         # once is enough; twice changes nothing
    assert tr.current() is None
    with tr.span("next_on_the_opener"):
        pass

    def other():
        with tr.span("second_half", parent=sp.id):
            pass
        time.sleep(0.002)
        sp.set(inflight=1)
        sp.close()
        assert tr.current() is None

    t = threading.Thread(target=other, name="closer")
    t.start()
    t.join(timeout=30)
    assert not t.is_alive() and sp.recorded and sp.dur >= 0.002
    evs = {e["name"]: e for e in tr.events()}
    assert evs["batch"]["thread"] == "closer"
    assert evs["batch"]["args"] == {"size": 2, "inflight": 1}
    assert evs["batch"]["dur_s"] == pytest.approx(sp.dur)
    assert evs["first_half"]["parent"] == evs["second_half"]["parent"] == sp.id
    assert evs["next_on_the_opener"]["parent"] is None
    # a timed span that nothing records crosses threads the same way
    off = Tracer(enabled=False)
    quiet = off.open("batch", timed=True)
    quiet.detach()
    t = threading.Thread(target=quiet.close)
    t.start()
    t.join(timeout=30)
    assert quiet.dur >= 0 and not quiet.recorded and off.events() == []


def test_a_detached_spans_annotation_covers_the_openers_part(live_trace):
    """Under a live trace the annotation is left on the thread that entered
    it; the ring holds the whole region."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    sp = tracer.open("t41.batch")
    time.sleep(0.002)
    sp.detach()
    t = threading.Thread(target=lambda: (time.sleep(0.02), sp.close()))
    t.start()
    t.join(timeout=30)
    host = live_trace()
    (ring,) = [e for e in tracer.events() if e["name"] == "t41.batch"]
    (ann,) = [(s, e) for n, s, e in host if n == "t41.batch"]
    assert ring["dur_s"] >= 0.022 > 0.02 > ann[1] - ann[0] >= 0.002


def test_inactive_span_builds_nothing(monkeypatch):
    """Telemetry off and no live trace: the shared no-op, no annotation, no
    record; ``timed=True`` still hands its caller the region's times."""
    from glint_word2vec_tpu.obs import spans

    class Counting:
        built = 0
        is_enabled = staticmethod(lambda: False)

        def __init__(self, name):
            Counting.built += 1

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    tr = Tracer(enabled=False)
    assert tr.span("x") is tr.span("y", size=1)
    with tr.span("x") as sp:
        sp.set(ops=1)
    with tr.span("t", timed=True) as timed:
        time.sleep(0.002)
    assert timed.dur >= 0.002 and timed.t0 > 0 and not timed.recorded
    assert tr.events() == [] and Counting.built == 0


def test_wrap_iter_and_export_share_the_id_parent_path(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("consumer") as outer:
        assert list(tr.wrap_iter("item", iter([1, 2]))) == [1, 2]
    items = [e for e in tr.events() if e["name"] == "item"]
    assert len(items) == 3          # two items and the StopIteration probe
    assert all(e["parent"] == outer.id for e in items)
    p = str(tmp_path / "trace.json")
    tr.export_chrome_trace(p)
    with open(p) as f:
        xs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert {e["args"]["parent"] for e in xs} == {None, outer.id}
    assert len({e["args"]["id"] for e in xs}) == 4


# -- pinned spans: the once-a-process regions (ISSUE 50) -------------------------------


def test_pinned_span_records_with_telemetry_off_and_no_trace_live():
    tr = Tracer(enabled=False)
    with tr.span("setup.outer", pinned=True, words=3) as outer:
        assert tr.current() == outer.id
        with tr.span("round.inner"):        # unpinned, nothing on: a no-op
            pass
        with tr.span("setup.inner", pinned=True) as inner:
            inner.set(passes=2)
    tr.record("setup.late", outer.t0, 0.25, parent=outer.id, pinned=True, n=1)
    assert tr.events() == [] and not outer.recorded
    evs = {e["name"]: e for e in tr.setup_events()}
    assert list(evs) == ["setup.inner", "setup.outer", "setup.late"]
    assert set(evs["setup.outer"]) == {"name", "tid", "thread", "ts_s", "dur_s",
                                      "id", "parent", "args"}
    assert evs["setup.outer"]["args"] == {"words": 3}
    assert evs["setup.inner"]["args"] == {"passes": 2}
    assert evs["setup.inner"]["parent"] == evs["setup.outer"]["id"]
    assert evs["setup.late"]["parent"] == evs["setup.outer"]["id"]
    assert evs["setup.late"]["dur_s"] == 0.25 and outer.dur > 0
    assert tr.current() is None


def test_pinned_spans_survive_clear_and_stay_ordered_against_the_ring():
    tr = Tracer(enabled=False)
    with tr.span("before", pinned=True):
        time.sleep(0.002)
    tr.clear()          # a fit's run_start: the ring's epoch moves
    tr.configure(enabled=True)
    with tr.span("round"):
        pass
    with tr.span("after", pinned=True):
        pass
    setup = {e["name"]: e for e in tr.setup_events()}
    ring = {e["name"]: e for e in tr.events()}
    assert set(setup) == {"before", "after"}
    # one epoch for both: what began before the clear reads negative, and
    # the three are in the order they happened
    assert setup["before"]["ts_s"] + setup["before"]["dur_s"] < 0
    assert (setup["before"]["ts_s"] < ring["round"]["ts_s"]
            < setup["after"]["ts_s"])
    assert ring["after"]["ts_s"] == pytest.approx(setup["after"]["ts_s"],
                                                  abs=1e-9)


def test_pinned_store_is_bounded_and_the_ring_is_what_it_was():
    tr = Tracer(enabled=True, max_events=8, max_setup=4)
    for i in range(10):
        with tr.span("pinned", pinned=True, i=i):
            with tr.span("plain", i=i):
                pass
    assert [e["args"]["i"] for e in tr.setup_events()] == [6, 7, 8, 9]
    ring = tr.events()
    # events() is the ring alone: the pinned spans once each among the
    # plain ones, oldest dropped, no record of the store's added
    assert len(ring) == 8
    assert [e["name"] for e in ring] == ["plain", "pinned"] * 4
    assert len({e["id"] for e in ring}) == 8
    summary = tr.setup_summary()
    assert summary["spans"]["pinned"]["count"] == 4
    assert summary["compiles"] == {"programs": 0, "cache_hits": 0,
                                   "cache_misses": 0}


def test_unpinned_child_of_a_pinned_parent_and_back_by_id():
    tr = Tracer(enabled=True)
    with tr.span("setup", pinned=True) as setup:
        with tr.span("plain") as plain:
            with tr.span("setup.deep", pinned=True):
                pass
    ring = {e["name"]: e for e in tr.events()}
    store = {e["name"]: e for e in tr.setup_events()}
    assert set(ring) == {"setup", "plain", "setup.deep"}
    assert set(store) == {"setup", "setup.deep"}
    assert ring["plain"]["parent"] == setup.id
    assert store["setup.deep"]["parent"] == plain.id
    assert store["setup"]["id"] == ring["setup"]["id"] == setup.id


def test_pinned_span_under_a_live_trace_is_once_in_each(live_trace, tmp_path):
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    mark = len(tracer.setup_events())
    with tracer.span("t50.plain"):
        pass
    with tracer.span("t50.pinned", pinned=True, size=2):
        pass
    sid = tracer.record("t50.retro", time.monotonic() - 0.1, 0.1, pinned=True)
    host = live_trace()
    ring = [e for e in tracer.events() if e["name"].startswith("t50.")]
    store = [e for e in tracer.setup_events()[mark:]
             if e["name"].startswith("t50.")]
    assert [e["name"] for e in ring] == ["t50.plain", "t50.pinned",
                                         "t50.retro"]
    assert [e["name"] for e in store] == ["t50.pinned", "t50.retro"]
    assert [e["id"] for e in ring[1:]] == [e["id"] for e in store]
    assert store[1]["id"] == sid
    # the ``with`` block is in the profiler's host plane too; a retroactive
    # record reaches the ring only
    names = [n for n, _, _ in host]
    assert names.count("t50.pinned") == 1 and "t50.retro" not in names
    # and the export writes the pinned records first, each once
    p = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(p)
    with open(p) as f:
        xs = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert sum(e["name"] == "t50.pinned" for e in xs) == 1
    assert min(e["ts"] for e in xs) == 0.0
    pinned_ids = {e["id"] for e in tracer.setup_events()}
    kinds = [e["args"]["id"] in pinned_ids for e in xs]
    assert kinds == sorted(kinds, reverse=True) and not kinds[-1]


# -- device scopes are metadata only ---------------------------------------------------


def _hlo_without_metadata(fn, args, monkeypatch, scoped: bool) -> str:
    import contextlib
    import re
    import jax
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    jax.clear_caches()      # the inner jits' traces carry the scopes
    text = jax.jit(fn).lower(*args).compile().as_text()
    monkeypatch.undo()
    assert any(name in text for name in (
        "sgns.scatter_syn0", "cbow.scatter_syn0", "scan.topk",
        "transform.segment_mean")) == scoped
    # metadata is each instruction's ``metadata={...}`` and the module's
    # tables of the files, functions and stack frames those point into
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:.+\n)+", "\n", text)
    return re.sub(r",? ?metadata=\{[^}]*\}", "", text)


def _sgns_shared_step_case():
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.sampler import sample_negatives_hash
    from glint_word2vec_tpu.ops.sgns import (
        EmbeddingPair, sgns_step_shared_core)
    V, D, B, P = 96, 16, 32, 8
    rng = np.random.default_rng(0)

    def step(syn0, syn1, centers, contexts, mask, prob, alias, alpha):
        negs = sample_negatives_hash(prob, alias, np.uint32(7), jnp.int32(3),
                                     (P,))
        return sgns_step_shared_core(
            EmbeddingPair(syn0, syn1), centers, contexts, mask, negs, alpha,
            num_negatives=5, compute_dtype=jnp.bfloat16,
            logits_dtype=jnp.bfloat16)

    args = (jnp.asarray(rng.standard_normal((V, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((V, D)), jnp.float32),
            jnp.asarray(rng.integers(0, V, B), jnp.int32),
            jnp.asarray(rng.integers(0, V, B), jnp.int32),
            jnp.ones(B, jnp.float32), jnp.full(V, 0.5, jnp.float32),
            jnp.asarray(rng.integers(0, V, V), jnp.int32), jnp.float32(0.025))
    return step, args


def _cosine_topk_case():
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.scan import _cosine_topk_batch
    rng = np.random.default_rng(1)
    syn0 = jnp.asarray(rng.standard_normal((96, 16)), jnp.float32)

    def scan(syn0, norms, queries):
        return _cosine_topk_batch(syn0, norms, queries, 5, 90)

    return scan, (syn0, jnp.linalg.norm(syn0, axis=1), syn0[:4])


def _gather_topk_case(mixed=False):
    """The served program: word ids in, top-k out (``scan.gather`` too); the
    mixed form holds a vector query, and so the vector block."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.scan import _gather_topk_batch
    scan, (syn0, norms, queries) = _cosine_topk_case()
    ids = jnp.asarray([3, -1, 95, 3] if mixed else [3, 0, 95, 3], jnp.int32)

    def served(syn0, norms, ids, block):
        return _gather_topk_batch(syn0, norms, ids, block, 5, 90, None)

    return served, (syn0, norms, ids, queries if mixed else None)


def _gather_topk_mixed_case():
    return _gather_topk_case(mixed=True)


def _transform_slide_case():
    """A transform slide's one program (``transform.gather``,
    ``transform.segment_mean``), a further pass of it."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.transform import _segment_means
    _, (syn0, _, _) = _cosine_topk_case()
    ids = jnp.asarray([3, 0, 95, 3, 7, syn0.shape[0]], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 3, 3, 4], jnp.int32)

    def slide(syn0, ids, seg, counts, carried):
        return _segment_means(syn0, ids, seg, counts, carried, 4, syn0.shape[1])

    return slide, (syn0, ids, seg, jnp.asarray([2, 1, 0, 2], jnp.int32),
                   jnp.ones((4, syn0.shape[1]), jnp.float32))


def _sentence_slide_case():
    """A ``sentence_vectors`` slide's one program (those two scopes and
    ``transform.list_gather``, ``transform.compose``), a further pass of it."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.transform import _sentence_means
    _, (syn0, norms, _) = _cosine_topk_case()
    ids = jnp.asarray([3, 0, 95, 3, 7, syn0.shape[0]], jnp.int32)
    seg = jnp.asarray([0, 0, 1, 3, 3, 4], jnp.int32)
    rows = jnp.asarray([5, 9, 9, 2, 40, syn0.shape[0]], jnp.int32)
    token = jnp.asarray([0, 0, 0, 1, 1, 4], jnp.int32)
    token_seg = jnp.asarray([1, 2, 4, 4], jnp.int32)

    def slide(syn0, scale, ids, seg, rows, token, token_seg, counts, sums, kept):
        return _sentence_means(syn0, scale, ids, seg, (syn0, rows, token, token_seg),
                               counts, (sums, kept), 4, syn0.shape[1])

    # the ids' own scales, as the host's encode hands them (PR 60)
    return slide, (syn0, jnp.take(1.0 / norms, ids, mode="clip"), ids, seg,
                   rows, token, token_seg,
                   jnp.asarray([2, 1, 0, 2], jnp.int32),
                   jnp.ones((4, syn0.shape[1]), jnp.float32),
                   jnp.asarray([0, 1, 0, 0], jnp.int32))


def _gather_topk_sharded_case():
    """The same program over a table partitioned by rows on four of the
    virtual devices (``scan.owner_rows``, ``scan.merge`` too)."""
    import jax
    from glint_word2vec_tpu.ops.scan import _gather_topk_batch
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    _, (syn0, norms, _) = _cosine_topk_case()
    plan = make_mesh(1, 4)
    syn0 = jax.device_put(syn0, plan.embedding)
    ids = np.asarray([3, 0, 95, 3], np.int32)

    def served(syn0, norms, ids):
        return _gather_topk_batch(syn0, norms, ids, None, 5, 90, plan.embedding)

    return served, (syn0, norms, ids)


CBOW_SCOPES = ("cbow.gather", "cbow.context_sum", "cbow.pool_matmul",
               "cbow.scatter_syn0", "cbow.scatter_syn1")


def _cbow_step_case(form):
    """One CBOW step of either form over the same block of tokens."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core
    from glint_word2vec_tpu.ops.sgns import cbow_step_shared_core
    V, D, T, P, W = 96, 16, 32, 8, 3
    rng = np.random.default_rng(2)
    kw = dict(compute_dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)

    def scatter(syn0, syn1, tokens, contexts, ctx_mask, negs, alpha):
        return cbow_step_shared_core(
            EmbeddingPair(syn0, syn1), tokens, contexts, ctx_mask,
            jnp.ones(T, jnp.float32), negs, alpha, 5, **kw)

    def banded(syn0, syn1, tokens, left, right, negs, alpha):
        ones = jnp.ones(T, jnp.float32)
        return cbow_step_banded_core(
            EmbeddingPair(syn0, syn1), tokens, left, right, ones, ones, negs,
            alpha, 5, W, **kw)

    tables = (jnp.asarray(rng.standard_normal((V, D)), jnp.float32),
              jnp.asarray(rng.standard_normal((V, D)), jnp.float32),
              jnp.asarray(rng.integers(0, V, T), jnp.int32))
    tail = (jnp.asarray(rng.integers(0, V, P), jnp.int32), jnp.float32(0.025))
    if form == "scatter":
        return scatter, tables + (
            jnp.asarray(rng.integers(0, V, (T, 2 * W)), jnp.int32),
            jnp.asarray(rng.integers(0, 2, (T, 2 * W)), jnp.float32)) + tail
    pos = np.arange(T)
    return banded, tables + (
        jnp.asarray(np.minimum(pos, 2), jnp.int32),
        jnp.asarray(np.minimum(T - 1 - pos, 1), jnp.int32)) + tail


def _cbow_scatter_step_case():
    return _cbow_step_case("scatter")


def _cbow_banded_step_case():
    return _cbow_step_case("banded")


@pytest.mark.parametrize("form", ["scatter", "banded"])
def test_both_cbow_step_forms_carry_the_five_device_scopes(form):
    """The scopes a profile's reader finds on a CBOW step, whichever form ran
    (docs/observability.md §4): in the lowered text's locations."""
    fn, args = _cbow_step_case(form)
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    assert [s for s in CBOW_SCOPES if s not in text] == []


def _toy_cbow_fit(update, **kw):
    """A toy CBOW fit whose producer runs on the calling thread (prefetch off)."""
    trainer, sents = _toy_trainer(n=60, cbow=True, cbow_update=update,
                                  negative_pool=8, **kw)
    beats = []
    trainer.fit(sents, on_heartbeat=beats.append)
    assert beats


@pytest.mark.parametrize("update", ["scatter", "banded"])
def test_cbow_pack_span_is_recorded_with_its_args_only_when_on(update, tmp_path):
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    tracer.clear()
    _toy_cbow_fit(update)
    assert not tracer.enabled and tracer.events() == []
    _toy_cbow_fit(update, telemetry_path=str(tmp_path / "t.jsonl"))
    try:
        events = tracer.events()
        packs = [e for e in events if e["name"] == "producer.cbow_pack"]
        assert packs and all(set(e["args"]) == {"examples", "context_rows"}
                             for e in packs)
        assert sum(e["args"]["examples"] for e in packs) > 0
        assert (sum(e["args"]["context_rows"] for e in packs)
                >= sum(e["args"]["examples"] for e in packs))
        # inside the feed's producer span, on the thread that ran it
        producers = {e["id"] for e in events if e["name"] == "producer"}
        assert all(e["parent"] in producers for e in packs)
    finally:
        tracer.configure(enabled=False)
        tracer.attach_phases(None)
        tracer.clear()


@pytest.mark.parametrize("case", [_sgns_shared_step_case, _cosine_topk_case,
                                  _gather_topk_case, _gather_topk_mixed_case,
                                  _gather_topk_sharded_case, _transform_slide_case,
                                  _sentence_slide_case,
                                  _cbow_scatter_step_case, _cbow_banded_step_case])
def test_named_scopes_change_metadata_only(case, monkeypatch):
    """The compiled step and scan with the scopes are the programs without
    them, up to metadata: what lets a PR that adds scopes say the device
    program did not change."""
    fn, args = case()
    with_scopes = _hlo_without_metadata(fn, args, monkeypatch, scoped=True)
    without = _hlo_without_metadata(fn, args, monkeypatch, scoped=False)
    assert with_scopes == without
