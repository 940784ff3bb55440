"""BENCHMARK.json, and the files each of its names stands for.

A cell names a configuration and a traffic mix; the loader finds
``configs/<config>.json``, ``traffic/<traffic>.json``, the traffic's kind
(``kinds/<kind>.py``) and, for each per-layer metric that lists the cell (or
lists none), ``layers/<metric>.json`` with its reader. Nothing here names a
cell, a configuration, a mix or a metric: a later PR adds files and entries.
"""

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def metric_applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def sizes(block: dict, tiny: bool) -> dict:
    """A configuration's or a mix's sizes: as written, or for a ``--tiny``
    rehearsal with the file's ``tiny`` block laid over them."""
    out = {k: v for k, v in block.items() if k != "tiny"}
    if tiny:
        out.update(block.get("tiny", {}))
    return out


def resolve(manifest: dict, cell_name: str, root: str = ROOT) -> dict:
    """Everything one run of ``cell_name`` needs, from files found by name under
    ``root``: the configuration's ``file``, and beside the command's own file the
    traffic mix and each per-layer metric that applies to the cell."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise SystemExit(f"benchmark: no workload {cell_name!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[cell_name]
    bench_dir = os.path.join(root, os.path.dirname(manifest["command"][1]))
    configs = {c["name"]: c for c in manifest["configs"]}
    traffic = _read_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    layers = []
    for metric in manifest["per_layer"]:
        if metric_applies(metric, cell_name):
            spec = _read_json(os.path.join(bench_dir, "layers", metric["name"] + ".json"))
            layers.append({**metric, **spec})
    return {
        "name": cell_name,
        "chips": cell["chips"],
        "config": _read_json(os.path.join(root, configs[cell["config"]]["file"])),
        "traffic": traffic,
        "kind": traffic["kind"],
        "end_to_end": [m for m in manifest["end_to_end"]
                       if metric_applies(m, cell_name)],
        "layers": layers,
    }
