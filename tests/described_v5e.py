"""What the compile-for-the-described-v5e tests share: not collected itself.

The TPU's compiler is installed beside the CPU backend, and it compiles for a
chip that is described and not attached (nothing runs): the step families'
files (``tests/test_step_inplace_tpu.py``, ``test_subword_inplace_tpu.py``,
``test_cbow_inplace_tpu.py``, ``test_token_lists_inplace_tpu.py``,
``test_hs_inplace_tpu.py``) and the read programs' (``test_scan_inplace_tpu.py``)
import the two fixtures from here by name, so that the topology is described
inside a fixture of the file that uses it, after a test of that file has
started, and never while a module is imported. One file a family: ``--dist
loadfile`` keeps a file on one worker, and as one file these were 642 s of the
suite's 750 (PR 57).
"""

import os
import re

import pytest
from jax.sharding import SingleDeviceSharding

# sgns-3m-300's size: rows, lanes, pairs a step, pool rows, steps a chunk
V, D, B, P, K = 3_000_000, 384, 65536, 2048, 2
# what the trainer derives at this size (tests/test_coalesce_runs.py,
# tests/test_step_selection.py hold the derivations)
RUNS = dict(center_runs=(10, 24576), context_runs=(6, 20480))
# the same as the shared-pool SGNS row is handed them since PR 58: a ladder of
# caps a scatter, whose first fitting rung the step takes batch by batch
LADDERS = dict(center_runs=(10, (18432, 24576)), context_runs=(6, (18432, 20480)))

# wiki.en's shape (subword-nn-2.5m-300, subword-sentvec-2.5m-300): words,
# bucket rows, trained width, groups of the row table
SUB_V, SUB_K, SUB_D, SUB_GROUPS = 2_519_370, 2_000_000, 300, 11 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _branches(compiled: str):
    """The two branch computations' names of each conditional, in text order."""
    return re.findall(r"conditional\(.*branch_computations=\{%([\w.]+), %([\w.]+)\}",
                      compiled)


def _computation(compiled: str, name: str) -> str:
    """The text of one named computation of a compiled module, with the fused
    computations it calls left out (they are printed before it)."""
    start = compiled.index(f"\n%{name} ")
    return compiled[start:compiled.index("\n}\n", start)]


def _no_table_copied(text: str):
    tables = r"f32\[(?:%d|%d|%d),\d+\]" % (SUB_V, SUB_K, SUB_V + SUB_K)
    assert not re.findall(r"= %s\S* copy\(" % tables, text)
    assert not re.search(r"f32\[%d," % (SUB_V + SUB_K), text)
