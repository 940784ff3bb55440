"""The four-chip query cell, rehearsed at its ``tiny`` sizes on four virtual CPU
devices: ``tests/benchmark/test_harness.py`` skips every cell of more than one
chip in its rehearsals and runs the first four-chip cell (the train cell)
untraced, so this cell's own are here, untraced and traced, held to the contract's
line as ``test_tiny_run_ends_in_the_contracts_line`` reads it.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "sgns-nn-10m-300-x4.query-sharded-closed64"


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_on_four_virtual_devices_ends_in_the_contracts_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
         str(2**31 + 45), "--seconds", "2", "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (want | {"breakdown"} if trace else want)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in MANIFEST[group]
                if loader.metric_applies(m, CELL)}
    assert set(line["metrics"]) == set(declared)
    for name, got in line["metrics"].items():
        assert set(got) == {"value", "unit"} and got["unit"] == declared[name]
        assert isinstance(got["value"], float) and got["value"] > 0, name
    device = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    assert device["count"] == 4
    if trace:
        assert 0 < device["busy_s"] <= device["window_s"]
        assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
        # the engagement counters, where the sharded scan runs
        assert line["metrics"]["sharded_query_merge_rows"]["value"] == 44.0
        assert line["metrics"]["scan_topk_rows"]["value"] == 1448.0
        assert line["metrics"]["row_fetch_ops"]["value"] == 1.0
    assert "check " in out.stdout and "(limit " in out.stdout
    assert "check scan_shards_off: 0 " in out.stdout


def test_the_cell_is_the_manifests_second_four_chip_cell():
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four == ["sgns-10m-300-x4.train", CELL]
    cell = loader.resolve(MANIFEST, CELL, ROOT)
    assert cell["kind"] == "query_sharded" and cell["config"]["mesh"] == [1, 4]
    traffic, like = cell["traffic"], json.load(
        open(os.path.join(BENCH, "traffic", "query-closed64.json")))
    for key in ("callers", "num_synonyms", "queries_per_caller", "warmup_batches",
                "trace_slice_s", "check_queries", "check_window_s", "tiny"):
        assert traffic[key] == like[key], key


def test_a_program_without_a_sharded_scan_ends_before_the_tables(monkeypatch):
    """The parent's program says ``{'queries': 8, 'topk_rows': 64}`` of the probe's
    64-row table: a message and exit code 1, at once."""
    from kinds import query_sharded as kind
    monkeypatch.setattr(kind, "scan_engagement",
                        lambda model, k: {"queries": 8, "topk_rows": 64})
    with pytest.raises(SystemExit) as refused:
        kind.require_sharded_scan([1, 4], 10)
    assert "no sharded scan" in str(refused.value) and refused.value.code != 0
    monkeypatch.undo()
    kind.require_sharded_scan([1, 4], 10)       # this program has one


def test_a_warm_up_past_its_deadline_ends_the_process_with_a_message():
    script = (
        "import sys, time; sys.path[:0] = [%r, %r]\n"
        "from kinds.query_sharded import start_warmup_deadline\n"
        "class Stuck:\n"
        "    def stats(self): return {'completed': 0}\n"
        "start_warmup_deadline(Stuck(), 24, 0.5)\n"
        "time.sleep(30)\n" % (ROOT, BENCH))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 3
    assert "did not finish inside its deadline" in out.stderr
