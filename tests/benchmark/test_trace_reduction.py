"""The reduction from a profiler trace to busy time, idle gaps and op sums, on
hand-made intervals and on a small trace recorded here on the CPU backend; and
the two cost functions against bytes and FLOPs worked by hand at the cells' shapes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from costs import cosine_scan, sgns_step  # noqa: E402
from harness import trace as tr  # noqa: E402
from readers import counter, roofline, trace_idle_ms, trace_ops_ms  # noqa: E402

OPS = [("fusion.1", 0.0, 1.0), ("all-reduce.2", 0.5, 1.5), ("fusion.3", 3.0, 4.0),
       ("while.4", 5.0, 8.0), ("fusion.5", 5.0, 6.0), ("all-gather.6", 6.5, 7.0)]


def test_union_and_busy_count_each_instant_once():
    assert tr.union(OPS) == [[0.0, 1.5], [3.0, 4.0], [5.0, 8.0]]
    assert tr.busy_seconds(OPS) == pytest.approx(5.5)
    assert tr.busy_seconds(tr.clip(OPS, 1.0, 5.5)) == pytest.approx(0.5 + 1.0 + 0.5)


def test_idle_share_is_what_the_union_leaves():
    idle = tr.gaps(OPS, 0.0, 10.0)
    assert idle == [(1.5, 3.0), (4.0, 5.0), (8.0, 10.0)]
    assert 1 - tr.busy_seconds(OPS) / 10.0 == pytest.approx(sum(e - s for s, e in idle) / 10)


def test_self_time_gives_a_container_only_what_its_body_leaves():
    t = tr.self_times(OPS)
    assert t["while.4"] == pytest.approx(3.0 - 1.0 - 0.5)
    assert t["fusion.5"] == pytest.approx(1.0) and t["fusion.1"] == pytest.approx(1.0)
    assert sum(t.values()) == pytest.approx(1.0 + 1.0 + 1.0 + 3.0)


def test_pattern_sums_only_matching_ops_and_never_a_container():
    assert tr.pattern_seconds(OPS, "all-reduce|all-gather") == pytest.approx(1.5)
    assert tr.pattern_seconds(OPS, "while") == 0.0
    assert tr.pattern_seconds(OPS, "fusion") == pytest.approx(3.0)


def test_gap_goes_to_the_shortest_host_span_over_its_middle():
    host = [("bench.heartbeat", 1.0, 3.5), ("np.asarray", 2.0, 2.5), ("fit", 0.0, 10.0)]
    by = dict(tr.attribute_gaps([(1.5, 3.0), (4.0, 5.0), (11.0, 12.0)], host))
    assert by == {"np.asarray": pytest.approx(1.5), "fit": pytest.approx(1.0),
                  "no_host_span": pytest.approx(1.0)}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A few jitted matmuls inside a TraceAnnotation, traced on the CPU backend."""
    import time

    import jax
    import jax.numpy as jnp
    import jax.profiler as jp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jp.ProfileOptions()
    opts.python_tracer_level = 0
    jp.start_trace(log_dir, profiler_options=opts)
    with jp.TraceAnnotation("bench.slice"):
        for _ in range(3):
            with jp.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            time.sleep(0.02)
    jp.stop_trace()
    return tr.load(tr.newest_xplane(log_dir), "cpu")


def test_recorded_trace_reduces_to_busy_idle_ops_and_gaps(recorded):
    assert any(n == "bench.slice" for n, _, _ in recorded["host"])
    sl = tr.reduce_slice(recorded, "bench.slice")
    assert 0 < sl["busy_s"] < sl["window_s"]
    assert sl["window_s"] >= 0.06                      # three sleeps of 20 ms
    assert 1 - sl["busy_s"] / sl["window_s"] > 0.3     # and they were idle
    assert any("dot" in name for name, _ in sl["device_ops"])
    assert sum(t for _, t in sl["idle_gaps"]) == pytest.approx(
        sl["window_s"] - sl["busy_s"], rel=1e-6)
    plane = next(iter(sl["planes"].values()))
    assert tr.pattern_seconds(plane, "dot") > 0
    assert tr.pattern_seconds(plane, "all-reduce") == 0.0
    run = {"slice": sl, "counters": {"slice_calls": 3}}
    per_call = trace_ops_ms.read({"per": "call"}, run)
    assert per_call == pytest.approx(1e3 * sl["busy_s"] / 3)
    assert trace_ops_ms.read({"per": "busy", "pattern": "dot"}, run) <= 1.0
    assert trace_ops_ms.read({"per": "call"}, {}) is None


def test_a_slice_with_no_device_op_is_an_error(recorded):
    empty = {"device": {}, "host": recorded["host"]}
    with pytest.raises(RuntimeError, match="no operation ran"):
        tr.reduce_slice(empty, "bench.slice")


def test_sgns_step_cost_at_the_train_cells_shape():
    # B=65,536 pairs, pool 2,048, D padded to 384, float32 tables, one chip:
    # rows touched 2*65,536 + 2,048 = 133,120; gather + read-modify-write = 3 passes
    # of 133,120 * 384 * 4 B = 204,472,320 B -> 613,416,960 B; indices 532,480 B
    c = sgns_step.cost(pairs_per_batch=65536, pool=2048, padded_dim=384,
                       param_dtype="float32", chips=1)
    assert c["bytes"] == 613_416_960 + 532_480
    # three matmuls of 2*B*P*D = 103,079,215,104 FLOPs, plus 10*B*D elementwise
    assert c["flops"] == 3 * 103_079_215_104 + 251_658_240
    four = sgns_step.cost(pairs_per_batch=65536, pool=2048, padded_dim=384,
                          param_dtype="float32", chips=4)
    assert four["bytes"] == c["bytes"] / 4 and four["flops"] == c["flops"] / 4


def test_cosine_scan_cost_at_the_query_cells_shape():
    # 64 queries over 3,000,000 x 300 float32: table 3.6e9 B + norms 1.2e7 B +
    # queries 76,800 B; matmul 2*64*3e6*300 = 1.152e11 FLOPs
    c = cosine_scan.cost(queries=64, vocab=3_000_000, dim=300, table_dtype="float32")
    assert c["bytes"] == 3_600_000_000 + 12_000_000 + 76_800
    assert c["flops"] == 115_200_000_000


def test_roofline_reader_takes_the_larger_bound_and_passes_100_only_if_time_is_short():
    run = {"layer_values": {"scan_device_ms": 10.0},
           "shapes": dict(queries=64, vocab=3_000_000, dim=300, table_dtype="float32"),
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    share = roofline.read({"cost": "cosine_scan", "time_ms": "scan_device_ms"}, run)
    # bytes bound: 3,612,076,800 B / 819e9 B/s = 4.4103 ms of the 10 ms measured
    assert share == pytest.approx(100 * 3_612_076_800 / 819e9 / 10e-3)
    assert roofline.read({"cost": "cosine_scan", "time_ms": "missing"}, run) is None


def test_counter_reader_reads_nothing_where_nothing_was_counted():
    assert counter.read({"num": "a", "den": "b"}, {"counters": {"a": 1.0, "b": 4.0}}) == 0.25
    assert counter.read({"num": "a", "den": "b"}, {"counters": {"a": 1.0}}) is None
    assert counter.read({"num": "a", "den": "b"}, {}) is None


def test_idle_reader_gives_each_call_what_the_device_left_idle():
    run = {"slice": {"window_s": 3.0, "busy_s": 0.5}, "counters": {"slice_calls": 50}}
    assert trace_idle_ms.read({}, run) == pytest.approx(50.0)
    assert trace_idle_ms.read({}, {"slice": run["slice"], "counters": {}}) is None
    assert trace_idle_ms.read({}, {"counters": run["counters"]}) is None
