"""The four-chip transform cell, rehearsed at its ``tiny`` sizes on four virtual
CPU devices, untraced and traced, held to the contract's line as
``test_tiny_run_ends_in_the_contracts_line`` reads it; and the two clean ends of
a program that cannot run it.
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

CELL = "sgns-transform-10m-300-x4.transform-sharded-slides10k-closed4"
ONE_CHIP = "sgns-transform-3m-300.transform-slides10k-closed4"
MANIFEST = loader.load_manifest(ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_on_four_virtual_devices_ends_in_the_contracts_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 59), "--seconds", "2", "--trace", str(trace), "--tiny"],
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
        metrics = {k: v["value"] for k, v in line["metrics"].items()}
        # the Zipf draw's hot shard, a quarter where the partition spread evenly
        assert 0.25 < metrics["sharded_transform_hot_shard_share"] <= 1.0
        assert metrics["sharded_transform_gather_roofline"] <= 100.0
        assert 0 < metrics["sharded_query_collective_share"] < 1
        assert 1.0 <= metrics["transform_rows_per_word"] <= 1.6
    for reading in ("slide_shards_off", "owned_rows_sum_off", "owned_max_off",
                    "last_shard_rows_zero", "empty_rows_not_zero"):
        assert f"check {reading}: 0 " in out.stdout, reading
    assert "(limit " in out.stdout


def test_the_cell_is_one_of_the_manifests_four_chip_cells():
    """The cell is one of the manifest's four-chip cells and they fit the slots;
    it resolves to its files; its traffic is the one-chip slide cell's letter for
    letter and one parameter more; it joins the readings of that cell that hold
    here under each reading's own name."""
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= len(MANIFEST["workloads"]) // 4
    cell = loader.resolve(MANIFEST, CELL, ROOT)
    assert cell["kind"] == "transform_sharded" and cell["config"]["mesh"] == [1, 4]
    assert cell["config"]["chips"] == cell["chips"] == 4
    assert cell["config"]["vocab_size"] == 10_000_000 and cell["config"]["vector_size"] == 300
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == cell["config"]["name"]]
    assert cell["config"]["reduced"] == entry["reduced"] == ["vocabulary_words"]
    assert set(cell["config"]["guarantees"]) == {"exact_mean", "oov", "precision", "order",
                                                 "whole_table"}
    traffic, like = cell["traffic"], json.load(
        open(os.path.join(BENCH, "traffic", "transform-slides10k-closed4.json")))
    for key in like:
        if key not in ("kind", "what"):
            assert traffic[key] == like[key], key
    assert set(traffic) - set(like) == {"last_shard_sentences", "last_shard_tokens"}
    assert traffic["last_shard_sentences"] == 1 and traffic["last_shard_tokens"] == 8
    reported = {m["name"] for m in cell["end_to_end"]}
    assert reported == {"query_per_s", "query_p95_ms", "setup_s"}
    assert all(layer["moves"] in reported for layer in cell["layers"])
    mine = {m["name"] for m in MANIFEST["per_layer"] if loader.metric_applies(m, CELL)}
    theirs = {m["name"] for m in MANIFEST["per_layer"]
              if loader.metric_applies(m, ONE_CHIP)}
    # its own roofline's cost counts every live row on one chip; the two walk
    # readings' cells are held to a list by test_transform_walk_layers.py
    assert theirs - mine == {"transform_gather_roofline", "transform_encode_walk_ms",
                             "transform_encode_by_objects"}
    assert mine - theirs == {"sharded_query_collective_share",
                             "sharded_transform_hot_shard_share",
                             "sharded_transform_gather_roofline"}


def test_a_program_without_a_sharded_slide_ends_before_the_tables(monkeypatch):
    """The parent's program says ``{'rows': 3, 'rows_cap': 128, 'passes': 1,
    'inflight': 0}`` of the probe's 64-row table on the mesh: a message and exit
    code 1, at once."""
    from kinds import transform as slides_kind
    from kinds import transform_sharded as kind
    monkeypatch.setattr(
        slides_kind, "slide_engagement",
        lambda model, slide: {"rows": 3, "rows_cap": 128, "passes": 1, "inflight": 0})
    with pytest.raises(SystemExit) as refused:
        kind.require_sharded_slide([1, 4])
    assert "no sharded slide" in str(refused.value) and refused.value.code != 0
    monkeypatch.undo()
    kind.require_sharded_slide([1, 4])       # this program has one


def test_a_warm_up_past_its_deadline_ends_the_process_with_a_message():
    script = (
        "import sys, time; sys.path[:0] = [%r, %r]\n"
        "from kinds import transform_sharded as kind\n"
        "class Stuck:\n"
        "    def completed(self): return [0, 0]\n"
        "kind.slides_kind.start_warmup_deadline(Stuck(), 3, 0.5)\n"
        "time.sleep(30)\n" % (ROOT, BENCH))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 3
    assert "did not finish inside its deadline" in out.stderr
