"""The shared-pool SGNS step updates its tables in place on the TPU.

Compiled at ``sgns-3m-300``'s real size for a v5e chip that is described, not
attached (the TPU's compiler is installed beside the CPU backend; nothing
runs): no instruction of the compiled chunk copies a ``f32[3000000,384]``
table. A ``lax.cond`` writes a table in place only where every read of that
table is ordered before it; syn1's context update does not depend on the pool
rows' gather, and left unordered the compiler copied syn1 into the branch and
back, 14 ms of a 26.5 ms step on the chip (PERF.md §6, PR 30). A count of
instructions, not a time.

The same step at ``sgns-10m-300-x4``'s size on the described 2x2 as a 1x4 mesh
(PR 49): the forward gathers go by the scatters' runs under a conditional of
their own (``assemble_by_runs``), which READS both tables' shards before the
scatters' conditionals write them: no f32[2500000,384] shard is copied or
moved, and what crosses the model axis is bfloat16 rows in all-reduces alone,
45,056 where the batch goes by runs, 131,072 where it does not, 2,048 beside.

The third is no step at all: the health probe every heartbeat runs between two
dispatches (obs/probe.py). Its p99 bucket used to come from a histogram built by
a scatter-add of V indices into s32[128], 26.2 ms a table on the chip and 1.64
ms of every training step (PERF.md §6, PR 32: the ``fusion_s32_128`` pair of the
ledger's breakdowns). The compiled probe holds no scatter, and on the 1x4 mesh
of ``sgns-10m-300-x4`` its reductions stay all-reduces of scalars. Since PR 56
it reads each table ONCE: one fusion fed by each table parameter, with the row
sums and the rows' finite bits as its two results (one variadic reduce; two
reductions written apart compile to two passes a table, and a row holds that).

The other step families' rows and the read programs' are files of their own
beside this one (tests/described_v5e.py lists them): as one file they were
642 s of the suite's 750 on one worker (PR 57).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import B, D, K, LADDERS, P, RUNS, V
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops.sgns import EmbeddingPair, _ladder, sgns_step_shared_core

# one cap a scatter (the helper's program where a rule derives one rung), and
# the ladders the trainer hands this step at the two cells' shapes (PR 58)
CAPS = pytest.mark.parametrize("runs", [RUNS, LADDERS], ids=["one_cap", "ladder"])


def _entries(compiled: str) -> list:
    """Branch computations of each conditional of a compiled module, in text
    order (a ``lax.cond`` has two, a ladder of two caps three)."""
    return [len(names.split(", ")) for names in re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", compiled)]


@CAPS
@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied(one_chip, with_metrics, runs):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, centers, contexts, negatives, alphas):
        def body(p, xs):
            c, x, n, a = xs
            return sgns_step_shared_core(
                p, c, x, jnp.ones(B, jnp.float32), n, a, 5, "exact",
                jnp.bfloat16, logits_dtype=jnp.bfloat16,
                with_metrics=with_metrics, **runs)
        return jax.lax.scan(body, params, (centers, contexts, negatives, alphas))

    table = spec((V, D), jnp.float32)
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table), spec((K, B), jnp.int32),
        spec((K, B), jnp.int32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile()
    text = compiled.as_text()
    assert " sort(" in text
    # ONE flat conditional a table: an entry a rung and the plain scatter
    assert sorted(_entries(text)) == sorted(
        len(_ladder(cap)) + 1 for _, cap in runs.values())
    copies = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= f32\[{V},{D}\]\S* copy\(", line)]
    assert not copies, copies
    # every rung's scatter is handed its own cap's rows, no larger rung's
    for _, cap in runs.values():
        for rung in _ladder(cap):
            assert re.search(rf"f32\[{rung},{D}\]", text), rung
    # both tables in place and the step's working set: the cell's traced runs
    # read 10.2 GB at their peak (ledger, PR 57), the tables 9.2 of them
    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert peak < 10.4e9, peak


@CAPS
@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_shard_is_copied_on_the_model_axis(topo, with_metrics, runs):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    rows, shard = 10_000_000, 2_500_000
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    by_rows = NamedSharding(mesh, PartitionSpec("model", None))

    def spec(shape, dtype, sharding=NamedSharding(mesh, PartitionSpec())):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def chunk(params, centers, contexts, negatives, alphas):
        def body(p, xs):
            c, x, n, a = xs
            new_p, metrics = sgns_step_shared_core(
                p, c, x, jnp.ones(B, jnp.float32), n, a, 5, "exact",
                jnp.bfloat16, logits_dtype=jnp.bfloat16,
                with_metrics=with_metrics, assemble_by_runs=True, **runs)
            return jax.lax.with_sharding_constraint(
                new_p, EmbeddingPair(by_rows, by_rows)), metrics
        return jax.lax.scan(body, params, (centers, contexts, negatives, alphas))

    table = spec((rows, D), jnp.float32, by_rows)
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table), spec((K, B), jnp.int32),
        spec((K, B), jnp.int32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile()
    memory = compiled.memory_analysis()
    compiled = compiled.as_text()
    # the gathers' conditional and one a scatter: an entry a rung and the
    # plain one in each
    caps0, caps1 = (_ladder(cap) for _, cap in runs.values())
    assert sorted(_entries(compiled)) == sorted(
        [len(caps0) + 1, len(caps0) + 1, len(caps1) + 1])
    moved = [line.strip()[:120] for line in compiled.splitlines()
             if re.search(rf"= \(?f32\[{shard},{D}\]\S* (?:copy|all-gather|all-to-all|"
                          r"collective-permute)(?:-start)?\(", line)]
    assert not moved, moved
    # what crosses the mesh: bfloat16 rows, the caps' in one branch of the
    # gathers' conditional, 2B in the other, the pool's beside both
    carried = [re.findall(r"(\w+)\[(\d+),\d+\]", line.split(" all-reduce")[0])
               for line in compiled.splitlines()
               if re.search(r"= \S.* all-reduce(?:-start)?\(", line)
               and re.search(rf"\[\d+,{D}\]", line.split(" all-reduce")[0])]
    assert all(dtype == "bf16" for op in carried for dtype, _ in op), carried
    # ONE combined all-reduce a rung of the gathers' switch (syn1's shorter
    # ladder repeats its last rung)
    rungs = [a + b for a, b in zip(caps0, caps1 + caps1[-1:] * len(caps0))]
    assert sorted(sum(int(r) for _, r in op) for op in carried) == sorted(
        rungs + [2 * B, P]), carried
    # a chip's shards of both tables (7.68 GB) and the step's working set: the
    # cell's traced runs read 8.8 GB a chip at their peak (ledger, PR 57)
    peak = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert peak < 9.0e9, peak


def _sibling_probe(vocab_size: int, threshold: float):
    """The probe with a table's two reductions written apart (ROADMAP A10 (a)'s
    form: the row-wise ``any`` beside the row-wise sum), everything after them
    the probe's own."""
    from glint_word2vec_tpu.obs import probe as P

    def row_sums(m):
        x = m.astype(jnp.float32)
        return jnp.sum(x * x, axis=1), jnp.any(~jnp.isfinite(m), axis=1)

    def probe(params):
        (sums0, bad0), (sums1, bad1) = row_sums(params.syn0), row_sums(params.syn1)
        return P.HealthStats(
            finite=~(bad0.any() | bad1.any()),
            syn0=P._matrix_stats(sums0, vocab_size, threshold),
            syn1=P._matrix_stats(sums1, vocab_size, threshold))

    return jax.jit(probe)


# (rows of syn0, rows of syn1, vocab_size, chips the rows are sharded over)
PROBE_SHAPES = {
    "one_chip": (V, V, V, 1),
    "mesh_1x4": (10_000_000, 10_000_000, 10_000_000, 4),
    # subword-sgns-2.5m-300's carry: syn0 holds the bucket rows too
    "subword": (4_519_376, 2_519_376, 2_519_370, 1),
}


@pytest.fixture(scope="module")
def probe_text(topo, one_chip):
    """The probe compiled for the described v5e, once a (shape, form)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from glint_word2vec_tpu.obs.probe import make_health_probe

    texts = {}

    def compiled(shape: str, form: str = "one_reduce") -> str:
        if (shape, form) not in texts:
            rows0, rows1, vocab, shards = PROBE_SHAPES[shape]
            sharding = one_chip
            if shards > 1:
                mesh = Mesh(np.array(topo.devices).reshape(1, shards), ("data", "model"))
                sharding = NamedSharding(mesh, PartitionSpec("model", None))
            make = make_health_probe if form == "one_reduce" else _sibling_probe
            texts[shape, form] = make(vocab, 100.0).lower(EmbeddingPair(
                jax.ShapeDtypeStruct((rows0, D), jnp.float32, sharding=sharding),
                jax.ShapeDtypeStruct((rows1, D), jnp.float32, sharding=sharding),
            )).compile().as_text()
        return texts[shape, form]

    return compiled


@pytest.mark.parametrize("shape", ["one_chip", "mesh_1x4"])
def test_the_health_probe_holds_no_scatter(probe_text, shape):
    compiled = probe_text(shape)
    assert " reduce(" in compiled
    for op in ("scatter", "sort", "all-gather", "all-to-all", "collective-permute"):
        assert f" {op}(" not in compiled, op
    # what the histogram's scatter produced: one s32[128] a table
    assert not re.search(r"= \(?s32\[128\]", compiled)
    if shape == "mesh_1x4":
        reduced = re.findall(r"= (\S+) all-reduce\(", compiled)
        assert reduced and all(re.fullmatch(r"\(?\w+\[\]\S*", t) for t in reduced), reduced


@pytest.mark.parametrize("form,shape,passes", [
    ("one_reduce", "one_chip", 1), ("one_reduce", "mesh_1x4", 1),
    ("one_reduce", "subword", 1), ("siblings", "one_chip", 2)])
def test_the_health_probe_reads_each_table_once(probe_text, form, shape, passes):
    """PR 56. A table's row sums of squares and its "any element not finite"
    bit come from ONE ``lax.reduce`` over two operands, which the compiler keeps
    as one fusion with two results, ``(f32[rows], pred[rows])``: the entry
    computation holds ONE fusion fed by each table (a shard's rows on the mesh),
    and everything after it reads the vectors. Written apart, the compiler
    folds the row-wise ``any`` and the ``any`` over rows into a whole-table
    ``reduce_or`` of its own and each table is read twice, as it was before
    (24.4 of the probe's 26.3 ms in four passes: PERF.md §6, PR 32): the last
    row pins that, so that nobody simplifies the reduce back."""
    compiled = probe_text(shape, form)
    rows0, rows1, _, shards = PROBE_SHAPES[shape]
    entry = compiled[compiled.index("\nENTRY "):]
    tables = re.findall(rf"%(\S+) = f32\[(\d+),{D}\]\S* parameter\(", entry)
    assert sorted(int(r) for _, r in tables) == sorted([rows0 // shards, rows1 // shards])
    for name, rows in tables:
        readers = [line for line in entry.splitlines()
                   if re.search(rf"[(,] ?%{re.escape(name)}[,)]", line)]
        assert len(readers) == passes and all(" fusion(" in r for r in readers), readers
        results = sorted(t for r in readers
                         for t in re.findall(rf"(\w+)\[{rows}\]", r.split(" fusion(")[0]))
        assert results == (["f32", "pred"] if passes == 1 else ["f32"]), readers
    # no whole table (or shard) is copied or sliced, inside a fusion or out of one
    moved = [line.strip()[:120] for line in compiled.splitlines()
             if re.search(rf"= f32\[\d+,{D}\]\S* (copy|slice|dynamic-slice)\(", line)]
    assert not moved, moved
