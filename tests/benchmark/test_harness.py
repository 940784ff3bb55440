"""The manifest, the loader and the one command, on the CPU.

Every cell of BENCHMARK.json resolves to files that exist; every per-layer
metric moves an end-to-end metric that each of its cells reports; names and
units keep to the allowed characters; ``run.py --tiny`` ends in one JSON line
with the contract's keys; a throw-away cell needs new files and one entry only.

A layer file names a READING (a reader and its args), never a kind of traffic:
which cells report it is the manifest's ``workloads`` list alone, and no two
names of one layer stand for the same reading (PR 54).
"""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# the benchmark's contract (the builder's instructions of every `benchmark` PR:
# "`per_layer`: 1 to 128 metrics of single layers"); PR 52 met it at 128 of 128
PER_LAYER_LIMIT = 128
ENTRIES = {m["name"]: m for m in MANIFEST["per_layer"]}
# the one reading that stands under two names: tests/test_serve.py, outside the
# benchmark's paths and so not a `benchmark` PR's to edit, opens this file by
# name; a PR that may edit it retires the name into dispatch_overlap_share
PINNED_FROM_OUTSIDE = {"subword_query_dispatch_overlap_share"}


def _layer_files() -> dict:
    layer_dir = os.path.join(BENCH, "layers")
    specs = {}
    for f in sorted(os.listdir(layer_dir)):
        with open(os.path.join(layer_dir, f)) as fh:
            specs[f[:-len(".json")]] = json.load(fh)
    return specs


LAYER_FILES = _layer_files()


def _reading(name):
    """What a layer file reads: its reader and args, a roofline's pointer at
    the time it divides by aside."""
    spec = LAYER_FILES[name]
    args = {k: v for k, v in spec["args"].items() if k != "time_ms"}
    return spec["reader"], json.dumps(args, sort_keys=True)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_files(cell_name):
    cell = loader.resolve(MANIFEST, cell_name, ROOT)
    assert cell["config"]["chips"] == cell["chips"]
    assert os.path.exists(os.path.join(BENCH, "kinds", cell["kind"] + ".py"))
    assert cell["layers"], "a cell reports at least one per-layer metric"
    for layer in cell["layers"]:
        assert os.path.exists(os.path.join(BENCH, "readers", layer["reader"] + ".py"))
        if layer["reader"] == "roofline":
            assert os.path.exists(
                os.path.join(BENCH, "costs", layer["args"]["cost"] + ".py"))
    reported = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    for layer in cell["layers"]:
        assert layer["moves"] in reported, (layer["name"], layer["moves"])


@pytest.mark.parametrize("cell_name", CELLS)
def test_configuration_file_states_what_it_reduced(cell_name):
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == cell_name)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    assert cfg["reduced"] == entry["reduced"]
    assert all(key in cfg for key in entry["reduced"])
    widths = [k for k in entry["reduced"]
              if k.endswith(("_dim", "_rank", "_size")) or "hidden" in k]
    assert not widths, f"a width may never be reduced: {widths}"
    assert entry["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))


def test_names_units_and_limits_of_the_manifest():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names)), "a name appears twice"
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 4)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    assert len(MANIFEST["per_layer"]) <= PER_LAYER_LIMIT
    cells = 24
    assert (2 + 14 * cells) * (MANIFEST["run_seconds"] + 60) + cells * 180 + 1200 <= 43200
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_every_layer_file_has_an_entry_and_every_entry_a_file():
    assert sorted(LAYER_FILES) == sorted(ENTRIES)


@pytest.mark.parametrize("name", sorted(LAYER_FILES))
def test_a_layer_file_names_a_reading_no_other_name_of_its_layer_has(name):
    spec = LAYER_FILES[name]
    assert set(spec) == {"reader", "args", "what"} and spec["what"], (
        "a layer file names a reading; which cells report it (no `kinds`) is the "
        "manifest's `workloads`")
    if name in PINNED_FROM_OUTSIDE:
        return
    twins = [other for other in LAYER_FILES
             if other != name and other not in PINNED_FROM_OUTSIDE
             and _reading(other) == _reading(name)
             and ENTRIES[other]["layer"] == ENTRIES[name]["layer"]]
    assert not twins, (f"{name} and {twins} are one reading of layer "
                       f"{ENTRIES[name]['layer']!r}: one name, and the cells in its `workloads`")


def test_a_roofline_divides_by_a_time_its_cells_report_before_it():
    order = [m["name"] for m in MANIFEST["per_layer"]]
    rooflines = [n for n in order if LAYER_FILES[n]["reader"] == "roofline"]
    assert rooflines
    for name in rooflines:
        time_ms = LAYER_FILES[name]["args"]["time_ms"]
        assert time_ms in ENTRIES, (name, time_ms)
        # run.py reads the list in order; readers/roofline.py takes layer_values[time_ms]
        assert order.index(time_ms) < order.index(name), (name, time_ms)
        cells = ENTRIES[name].get("workloads", CELLS)
        assert all(loader.metric_applies(ENTRIES[time_ms], c) for c in cells), (name, time_ms)


def _run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # conftest's eight virtual devices: one here
    return subprocess.run([sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell_name", CELLS)
def test_tiny_run_ends_in_the_contracts_line(cell_name, trace):
    cell = loader.resolve(MANIFEST, cell_name, ROOT)
    if cell["chips"] > 1:
        pytest.skip("the four-chip cell is rehearsed on virtual devices in its own test")
    out = _run(["--workload", cell_name, "--seed", str(2**31 + 77), "--seconds", "2",
                "--trace", str(trace), "--tiny"])
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (want | {"breakdown"} if trace else want)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in MANIFEST[group]
                if loader.metric_applies(m, cell_name)}
    assert set(line["metrics"]) == set(declared)
    for name, got in line["metrics"].items():
        assert set(got) == {"value", "unit"} and got["unit"] == declared[name]
        assert isinstance(got["value"], float) and got["value"] > 0
    device = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(device)
    if trace:
        assert 0 < device["busy_s"] <= device["window_s"]
        assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert "check " in out.stdout and "(limit " in out.stdout


def test_four_chip_cell_rehearses_on_virtual_devices():
    four = [c for c in CELLS if loader.resolve(MANIFEST, c, ROOT)["chips"] == 4]
    if not four:
        pytest.skip("no four-chip cell in the manifest")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", four[0], "--seed",
         "5", "--seconds", "2", "--trace", "0", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4


def test_off_the_chip_it_fails_and_prints_no_result():
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())
    assert "does not fall back" in out.stderr


def test_unknown_device_kind_has_no_peaks():
    from harness.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks_for("cpu")


def test_a_new_cell_needs_new_files_and_one_entry_only(tmp_path):
    """A throw-away configuration, mix and per-layer metric, added as files of
    their own beside a copy of the benchmark, resolve with no edit to a file
    that was there; a reading the benchmark already has (``feed_wait_share``)
    the new cell joins by that entry's ``workloads`` list, under no new name."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(os.path.join(root, "benchmark")) for p in fs}
    bench = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(bench, "configs", "sgns-3m-300.json")))
    cfg.update(name="sgns-throwaway", vocab_size=50000)
    json.dump(cfg, open(os.path.join(bench, "configs", "sgns-throwaway.json"), "w"))
    mix = json.load(open(os.path.join(bench, "traffic", "train-zipf-b64k.json")))
    mix.update(pairs_per_batch=1024)
    json.dump(mix, open(os.path.join(bench, "traffic", "train-throwaway.json"), "w"))
    json.dump({"reader": "counter", "args": {"num": "batch_items", "den": "window_s"},
               "what": "test"},
              open(os.path.join(bench, "layers", "throwaway_share.json"), "w"))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "sgns-throwaway", "source": "none",
                                "file": "benchmark/configs/sgns-throwaway.json",
                                "reduced": ["corpus_words"], "why": "test"})
    manifest["workloads"].append({"name": "sgns-throwaway.train", "chips": 1,
                                  "config": "sgns-throwaway",
                                  "traffic": "train-throwaway", "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("train_pairs_per_s", "train_loss_at_budget"):
            m["workloads"].append("sgns-throwaway.train")
    manifest["per_layer"].append({"name": "throwaway_share", "unit": "share",
                                  "better": "lower", "source": "program_counter",
                                  "layer": "fit loop and feed",
                                  "moves": "train_pairs_per_s",
                                  "workloads": ["sgns-throwaway.train"]})
    for m in manifest["per_layer"]:
        if m["name"] == "feed_wait_share":
            m["workloads"].append("sgns-throwaway.train")
    cell = loader.resolve(manifest, "sgns-throwaway.train", root)
    assert cell["config"]["vocab_size"] == 50000
    assert cell["traffic"]["pairs_per_batch"] == 1024 and cell["kind"] == "train"
    assert [l["name"] for l in cell["layers"]] == ["feed_wait_share", "throwaway_share"]
    counters = {"counters": {"host_wait_s": 1.0, "batch_items": 2.0, "window_s": 4.0}}
    assert [importlib.import_module("readers." + l["reader"]).read(l["args"], counters)
            for l in cell["layers"]] == [0.25, 0.5]
    for p, data in before.items():
        hits = [os.path.join(dp, p) for dp, _, fs in os.walk(bench) if p in fs]
        assert open(hits[0], "rb").read() == data
