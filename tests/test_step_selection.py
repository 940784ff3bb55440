"""The step selection matrix (train/trainer.select_step): which ops-level core
one configuration trains with, the shape of the negatives its chunk draws and
whether syn0's update goes to the scatter by center runs and syn1's by context
runs (the banded CBOW row: both by runs of the block's tokens) — every row the
function can return, both twins, without building a Trainer."""

import collections
import inspect
import os
import re
import sys

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.ops import cbow_banded, sgns_shard
from glint_word2vec_tpu.ops.sgns import Stabilizers
from glint_word2vec_tpu.parallel.mesh import make_mesh
from glint_word2vec_tpu.train import trainer as trainer_mod
from glint_word2vec_tpu.train.trainer import (
    _CONTEXT_MAX_RUN,
    _TOKEN_MAX_RUN,
    _center_run_cap,
    select_step,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

B, P, N, K = 64, 16, 5, 4
BASE = dict(vector_size=16, min_count=1, pairs_per_batch=B, negatives=N,
            steps_per_dispatch=K)
POOL, PER_EXAMPLE, WINDOW_POOLS = (K, P), (K, B, N), (K, 2 * P)
# the shared-pool SGNS row takes its caps as ladders (tight, roomy); the rows
# whose scatter takes one cap (hierarchical softmax, subword) the last rung
RUNS_W5 = (10, _center_run_cap(5, B))
ROOMY_W5 = (10, RUNS_W5[1][-1])
assert RUNS_W5[1] == (18, 24)
# what a trainer derived from its vocabulary (_context_run_cap); () = nothing
CONTEXT_CAP = (20, 24)
BY_CONTEXT = (_CONTEXT_MAX_RUN, CONTEXT_CAP)
# the same for a banded CBOW block's tokens (_token_run_caps: syn0's, syn1's)
TOKEN_CAPS = (44, 36)
BY_TOKEN = (_TOKEN_MAX_RUN, *TOKEN_CAPS)

# where each core is looked up when select_step runs
CORE_HOME = {
    "sgns_step_shared_core": trainer_mod,
    "sgns_step_core": trainer_mod,
    "cbow_step_shared_core": trainer_mod,
    "cbow_step_core": trainer_mod,
    "cbow_step_banded_core": cbow_banded,
    "make_shard_map_sgns_step": sgns_shard,
}

# id: (config beside BASE, mesh, feed_segments)
#     -> (core, negatives, center_runs[, context_runs[, token_runs]]: None
#        where left out)
ROWS = {
    "sgns-shared-gspmd-runs": (
        dict(negative_pool=P, window=5), (1, 1), 1,
        ("sgns_step_shared_core", POOL, RUNS_W5, BY_CONTEXT)),
    "sgns-shared-gspmd-model-axis": (   # rows over 1x4: the batch is still whole,
        dict(negative_pool=P, window=5), (1, 4), 1,   # and its gathers go by runs too
        ("sgns_step_shared_core", POOL, RUNS_W5, BY_CONTEXT)),
    "sgns-shared-gspmd-model-axis-window2": (   # no center runs: gathers by pair
        dict(negative_pool=P, window=2), (1, 4), 1,
        ("sgns_step_shared_core", POOL, None, BY_CONTEXT)),
    "sgns-shared-gspmd-model-axis-two-feed-segments": (   # the same
        dict(negative_pool=P, window=5), (1, 4), 2,
        ("sgns_step_shared_core", POOL, None, BY_CONTEXT)),
    "sgns-shared-gspmd-window2": (   # two pairs a run or fewer: not built
        dict(negative_pool=P, window=2), (1, 1), 1,   # (contexts: the corpus's)
        ("sgns_step_shared_core", POOL, None, BY_CONTEXT)),
    "sgns-shared-gspmd-data-axis": (
        dict(negative_pool=P, window=5), (2, 4), 1,
        ("sgns_step_shared_core", POOL, None)),
    "sgns-shared-gspmd-two-feed-segments": (   # the step sorts by context itself
        dict(negative_pool=P, window=5), (1, 1), 2,
        ("sgns_step_shared_core", POOL, None, BY_CONTEXT)),
    "sgns-shared-gspmd-duplicate-scaling": (
        dict(negative_pool=P, window=5, duplicate_scaling=True), (1, 1), 1,
        ("sgns_step_shared_core", POOL, RUNS_W5, BY_CONTEXT)),
    "sgns-device-pairgen-runs": (
        dict(negative_pool=P, window=5, device_pairgen=True), (1, 1), 1,
        ("sgns_step_shared_core", POOL, RUNS_W5, BY_CONTEXT)),
    "sgns-device-pairgen-data-axis": (
        dict(negative_pool=P, window=5, device_pairgen=True), (2, 4), 1,
        ("sgns_step_shared_core", POOL, None)),
    "sgns-shard-map": (
        dict(negative_pool=P, window=5, step_lowering="shard_map"), (2, 4), 1,
        ("make_shard_map_sgns_step", POOL, None)),
    "sgns-shard-map-sync-every": (
        dict(negative_pool=P, window=5, step_lowering="shard_map",
             sync_every=2), (2, 4), 1,
        ("make_shard_map_sgns_step", WINDOW_POOLS, None)),
    "sgns-per-pair": (
        dict(negative_pool=0, window=5), (1, 1), 1,
        ("sgns_step_core", PER_EXAMPLE, None)),
    "cbow-banded": (   # both token scatters by runs of the block's tokens
        dict(cbow=True, cbow_update="banded", negative_pool=P, window=5),
        (1, 1), 1, ("cbow_step_banded_core", POOL, None, None, BY_TOKEN)),
    "cbow-banded-model-axis": (   # rows over 1x4: the block is still whole
        dict(cbow=True, cbow_update="banded", negative_pool=P, window=5),
        (1, 4), 1, ("cbow_step_banded_core", POOL, None, None, BY_TOKEN)),
    "cbow-banded-data-axis": (   # a block a data shard: no program sorts it whole
        dict(cbow=True, cbow_update="banded", negative_pool=P, window=5),
        (2, 4), 1, ("cbow_step_banded_core", POOL, None)),
    "cbow-scatter-shared": (
        dict(cbow=True, negative_pool=P, window=5), (1, 1), 1,
        ("cbow_step_shared_core", POOL, None)),
    "cbow-per-example": (
        dict(cbow=True, negative_pool=0, window=5), (1, 1), 1,
        ("cbow_step_core", PER_EXAMPLE, None)),
    "cbow-per-example-duplicate-scaling": (   # the AUTO pool resolves to 0
        dict(cbow=True, duplicate_scaling=True, window=5), (1, 1), 1,
        ("cbow_step_core", PER_EXAMPLE, None)),
}


# the rows whose forward gathers go by the scatters' runs (assemble_by_runs): a
# model axis, no data axis, both runs built. Not on one chip, where no
# collective assembles the gathered rows
ASSEMBLES = {"sgns-shared-gspmd-model-axis"}


class _Operand:
    """Stands for any array a step is handed: nothing here computes."""

    def __getattr__(self, name):
        return self

    def reshape(self, *shape):
        return self


class _Batch(dict):
    def __missing__(self, key):
        return _Operand()


@pytest.fixture
def cores(monkeypatch):
    """Every core replaced, where select_step finds it, by a recorder of its
    call bound to the real core's signature."""
    calls, stubs = [], {}
    for name, home in CORE_HOME.items():
        real = inspect.signature(getattr(home, name))

        def stub(*args, _name=name, _real=real, **kwargs):
            calls.append((_name, _real.bind(*args, **kwargs).arguments))
            if _name == "make_shard_map_sgns_step":   # the factory's product
                return lambda *a: calls.append(("shard_map_step", a)) or "out"
            return "out"

        monkeypatch.setattr(home, name, stub)
        stubs[name] = stub
    return calls, stubs


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
@pytest.mark.parametrize("row", list(ROWS))
def test_step_selection(row, with_metrics, cores):
    calls, stubs = cores
    kw, mesh, segments, (core, negatives, runs, *more) = ROWS[row]
    by_context, by_token = (more + [None, None])[:2]
    cfg = Word2VecConfig(**BASE, **kw)
    stab = Stabilizers(update_clip=0.5)

    choice = select_step(cfg, make_mesh(*mesh), segments, CONTEXT_CAP, stab,
                         with_metrics, token_caps=TOKEN_CAPS)

    assert choice.core is stubs[core]
    assert choice.neg_shape(K, B) == negatives
    assert choice.center_runs == runs
    assert choice.context_runs == by_context
    assert choice.token_runs == by_token
    assert choice.assemble_by_runs == (row in ASSEMBLES)
    assert choice.step("params", _Batch(), "negatives", "alpha") == "out"
    # the chosen core ran, once, and no other
    name, bound = calls[0]
    assert name == core and len(calls) == (2 if "shard_map" in core else 1)
    assert bound["stabilizers"] is stab
    # the twin reaches the cores that have one; the others have one twin
    has_twin = core not in ("sgns_step_core", "cbow_step_core")
    assert bound.get("with_metrics", "absent") == (
        with_metrics if has_twin else "absent")
    if core == "sgns_step_shared_core":
        assert bound["center_runs"] == runs
        assert bound["context_runs"] == by_context
        assert bound["assemble_by_runs"] == (row in ASSEMBLES)
        assert bound["duplicate_scaling"] == cfg.duplicate_scaling
    if core == "cbow_step_banded_core":
        assert bound["token_runs"] == by_token
    if core == "make_shard_map_sgns_step":
        assert bound["sync_every"] == cfg.sync_every
        assert calls[1] == ("shard_map_step",
                            ("params", {}, "negatives", "alpha"))


@pytest.mark.parametrize("segments", [1, 2], ids=["whole-batch", "two-feed-segments"])
@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_step_selection_hierarchical_softmax(with_metrics, segments, monkeypatch):
    """loss="hs": the path step, no negatives to draw, syn0's update by center
    runs where one program sees the batch whole; no stabilizers reach it
    (config refuses them beside it)."""
    from glint_word2vec_tpu.ops import hs

    calls = []
    real = inspect.signature(hs.hs_step_core)

    def stub(*args, **kwargs):
        calls.append(real.bind(*args, **kwargs).arguments)
        return "out"

    monkeypatch.setattr(hs, "hs_step_core", stub)
    cfg = Word2VecConfig(**{**BASE, "negatives": 0}, loss="hs", window=5)
    assert cfg.negative_pool == 0
    shape = hs.HsShape(2, 8, 16, 128)
    choice = select_step(cfg, make_mesh(1, 1), segments, (), None, with_metrics,
                         hs_shape=shape)
    runs = ROOMY_W5 if segments == 1 else None      # one cap: the last rung
    assert choice.core is stub and choice.neg_shape is None
    assert choice.center_runs == runs and choice.context_runs is None
    assert choice.step("params", _Batch(path_table="table"), None, "alpha") == "out"
    bound, = calls
    assert bound["table"] == "table" and bound["shape"] is shape
    assert bound["with_metrics"] == with_metrics and bound["center_runs"] == runs
    assert bound["alpha"] == "alpha" and bound["params"] == "params"


def test_context_runs_need_a_cap():
    """A vocabulary whose estimate passes half the batch (_context_run_cap
    gives ()) builds no context coalescing; syn0's stays as it is."""
    cfg = Word2VecConfig(**BASE, negative_pool=P, window=5)
    choice = select_step(cfg, make_mesh(1, 1), 1, (), None, True)
    assert choice.context_runs is None and choice.center_runs == RUNS_W5


def test_token_runs_need_a_cap():
    """A vocabulary whose estimate passes 0.75 of the block (_token_run_caps
    gives (0, 0): the default of ``token_caps``) builds no token coalescing."""
    cfg = Word2VecConfig(**BASE, cbow=True, cbow_update="banded", negative_pool=P,
                         window=5)
    choice = select_step(cfg, make_mesh(1, 1), 1, (), None, True)
    assert choice.token_runs is None and choice.center_runs is None


COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(?:-start)?\(")


@pytest.mark.parametrize("twin", ["_step_fn", "_step_fn_fast"])
def test_x4_step_holds_the_parents_collectives(twin):
    """``sgns-10m-300-x4`` at ``tiny`` on its 1x4 mesh (over the 8 virtual CPU
    devices): the step with both updates coalesced and its forward gathers by
    the same runs compiles to all-reduces alone (the forward assembly of the
    gathered rows over the model axis) — the sorts, the row gathers and the
    conditionals bring no other collective. The gathers' switch holds the
    assembly once in each entry: by runs, both scatters' caps of rows on each
    rung of their ladders (PR 58); by pair, 2B as before PR 49; the pool's P
    rows beside them. A count of the compiled module's ops and their operands'
    rows, not a time."""
    from harness import loader
    from kinds import train as train_kind

    from glint_word2vec_tpu.parallel.distributed import put_global

    cell = loader.resolve(loader.load_manifest(ROOT), "sgns-10m-300-x4.train", ROOT)
    trainer, _, _ = train_kind.build_trainer(cell, 0, tiny=True)
    cfg = trainer.config
    assert (trainer.plan.num_data, trainer.plan.num_model) == (1, 4)
    choice = select_step(cfg, trainer.plan, 1, trainer._context_cap, None, True)
    assert choice.context_runs and choice.center_runs   # both engage here,
    assert choice.assemble_by_runs                      # and so do the gathers

    k, b = cfg.steps_per_dispatch, cfg.pairs_per_batch
    staged = put_global(trainer._chunk_shardings,
                        {"pairs": np.zeros((k, 2, b), trainer._pair_dtype)})
    meta_dev, base_dev = trainer._stage_dispatch_meta(np.zeros((2, k), np.float32), 0)
    compiled = getattr(trainer, twin).lower(
        trainer.params, staged, meta_dev, base_dev,
        trainer._table_prob, trainer._table_alias).compile().as_text()
    assert " sort(" in compiled
    found = collections.Counter(m.group(1) for m in COLLECTIVE.finditer(compiled))
    assert set(found) == {"all-reduce"}
    # the rows each all-reduce carries, by the computation that holds it
    rows_in, computation = collections.defaultdict(int), None
    for line in compiled.splitlines():
        opened = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if opened:
            computation = opened.group(1)
        if re.search(r" all-reduce(?:-start)?\(", line):
            result = line.split(" all-reduce")[0].split("=", 1)[1]
            rows_in[computation] += sum(
                int(r) for r in re.findall(r"\w+\[(\d+),\d+\]", result))
    (caps0, caps1), pool = (choice.center_runs[1], choice.context_runs[1]), cfg.negative_pool
    assert len(caps0) == len(caps1) == 2
    rungs = [cap0 + cap1 for cap0, cap1 in zip(caps0, caps1)]
    assert sorted(rows_in.values()) == sorted(rungs + [2 * b, pool])
    assert rungs[0] < rungs[1] and rungs[1] + pool < 0.4 * (2 * b + pool)
