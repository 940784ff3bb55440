"""The shared-pool SGNS step updates its tables in place on the TPU.

Compiled at ``sgns-3m-300``'s real size for a v5e chip that is described, not
attached (the TPU's compiler is installed beside the CPU backend; nothing
runs): no instruction of the compiled chunk copies a ``f32[3000000,384]``
table. A ``lax.cond`` writes a table in place only where every read of that
table is ordered before it; syn1's context update does not depend on the pool
rows' gather, and left unordered the compiler copied syn1 into the branch and
back, 14 ms of a 26.5 ms step on the chip (PERF.md §6, PR 30). A count of
instructions, not a time.

The third program is the subword step at ``subword-sgns-2.5m-300``'s size (PR
31; PR 34: one list per distinct center word): syn0 is read by one conditional
(the centers' listed rows: per word, per run or plain) and written by another,
and neither may copy f32[4519376,384]; each branch scatters into syn0 once; the
temporaries are no larger than with the per-run form alone. Since PR 47 the
writing conditional has a fourth entry, the per-word form with its sorted slots
cut to the trainer's slot capacity (278,528 of 491,520). An entry of the switch
and not a conditional or a loop of passes inside the per-word entry: either of
those made the compiler copy the table inside the PLAIN entry's scan, once
before and once after each chunk's scatter, 6.46 GB of temporaries on a 16 GB
chip (the compile is refused: PERF.md §6, PR 47).

The fourth is no step at all: the health probe every heartbeat runs between two
dispatches (obs/probe.py). Its p99 bucket used to come from a histogram built by
a scatter-add of V indices into s32[128], 26.2 ms a table on the chip and 1.64
ms of every training step (PERF.md §6, PR 32: the ``fusion_s32_128`` pair of the
ledger's breakdowns). The compiled probe holds no scatter, and on the 1x4 mesh
of ``sgns-10m-300-x4`` its reductions stay all-reduces of scalars. Since PR 56
it reads each table ONCE: one fusion fed by each table parameter, with the row
sums and the rows' finite bits as its two results (one variadic reduce; two
reductions written apart compile to two passes a table, and a row holds that).

The fifth (PR 33) is the banded CBOW step with the token row source and the
position weights at ``cbow-subword-2m-300``'s size: syn0 (f32[4000000,384]) is
read by the tokens' list gather and written by their list scatter, once a step;
syn1 by the centers' scatter and the pool's; the third leaf rides the carry.
Since PR 36 the list scatter sits in one conditional (the slots sorted by row
inside the step and cut to the trainer's slot capacity, or a block over it
whole): a scatter into syn0 in each branch, the step's own sort in the cut
branch alone, the gather ordered before the conditional by its data (no copy).
Since PR 43 the list gather is in two parts where the trainer derives a tail
capacity: every token's first group (f32[524368,384]) and, in a loop of dynamic
trip count, the capacity's later groups a pass (f32[32768,384]; no conditional,
no whole form beside it). The loop READS syn0 before the scatter's conditional
writes it, its result orders the two, and neither copies the table; the tail
tokens' row ids are read by a gather and not by a loop of slices, an iteration
a token.
That the subword skip-gram step is the program it was is held where it is
cheap, on its lowered text (``tests/test_cbow_subword.py``).

The same step at ``sgns-10m-300-x4``'s size on the described 2x2 as a 1x4 mesh
(PR 49): the forward gathers go by the scatters' runs under a conditional of
their own (``assemble_by_runs``), which READS both tables' shards before the
scatters' conditionals write them: no f32[2500000,384] shard is copied or
moved, and what crosses the model axis is bfloat16 rows in all-reduces alone,
45,056 where the batch goes by runs, 131,072 where it does not, 2,048 beside.

The plain banded step at ``cbow-3m-300``'s size (PR 46) has a row of its own:
each token scatter goes through a conditional of its own on the block's tokens
sorted inside the step (``token_runs``: syn0's by every slot's token, syn1's
by the tokens of the slots that train an example; the sorts lie outside the
conditionals, the heads' compaction inside each coalesced branch). syn0 is
read by the tokens' gather and syn1 by the tokens' and the pool rows' gathers
before them, and neither table is copied: one scatter into its table in each
branch, and the pool rows' after syn1's. With the token row source syn1's
conditional stands beside the lists'.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core

V, D, B, P, K = 3_000_000, 384, 65536, 2048, 2
# what the trainer derives at this size (tests/test_coalesce_runs.py,
# tests/test_step_selection.py hold the derivations)
RUNS = dict(center_runs=(10, 24576), context_runs=(6, 20480))


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


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied(one_chip, with_metrics):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, centers, contexts, negatives, alphas):
        def body(p, xs):
            c, x, n, a = xs
            return sgns_step_shared_core(
                p, c, x, jnp.ones(B, jnp.float32), n, a, 5, "exact",
                jnp.bfloat16, logits_dtype=jnp.bfloat16,
                with_metrics=with_metrics, **RUNS)
        return jax.lax.scan(body, params, (centers, contexts, negatives, alphas))

    table = spec((V, D), jnp.float32)
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table), spec((K, B), jnp.int32),
        spec((K, B), jnp.int32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile().as_text()
    assert " sort(" in compiled and " conditional(" in compiled
    copies = [line.strip()[:120] for line in compiled.splitlines()
              if re.search(rf"= f32\[{V},{D}\]\S* copy\(", line)]
    assert not copies, copies


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_shard_is_copied_on_the_model_axis(topo, with_metrics):
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
                with_metrics=with_metrics, assemble_by_runs=True, **RUNS)
            return jax.lax.with_sharding_constraint(
                new_p, EmbeddingPair(by_rows, by_rows)), metrics
        return jax.lax.scan(body, params, (centers, contexts, negatives, alphas))

    table = spec((rows, D), jnp.float32, by_rows)
    compiled = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table), spec((K, B), jnp.int32),
        spec((K, B), jnp.int32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile().as_text()
    # the gathers' conditional and one a scatter
    assert compiled.count(" conditional(") == 3
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
    caps = RUNS["center_runs"][1] + RUNS["context_runs"][1]
    assert sorted(sum(int(r) for _, r in op) for op in carried) == sorted(
        [caps, 2 * B, P]), carried


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied_with_the_subword_row_source(one_chip, with_metrics):
    from glint_word2vec_tpu.ops.subword import SubwordShape, SubwordTable

    words, rows0, groups = 2_519_376, 4_519_376, 11 << 20
    # what the trainer derives at this size (PERF.md §6, PR 31, PR 34 and PR
    # 47; tests/test_coalesce_runs.py holds the word cap's derivation,
    # tests/test_subword.py the slot capacity's)
    shape = SubwordShape(max_groups=5, max_run=10, head_cap=24576,
                         word_run=8, word_cap=12288, slot_cap=278528)
    # temp_size_in_bytes of the same compile with word_cap=0, the parent's
    # form (my compile for the described v5e, PR 34): the per-run branch's
    # [24576, 40, 384] float32 block is the largest of either program
    parent_temporaries = {True: 1_574_144_512, False: 1_574_402_048}

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, table, centers, contexts, negatives, alphas):
        def body(p, xs):
            c, x, n, a = xs
            return sgns_step_shared_core(
                p, c, x, jnp.ones(B, jnp.float32), n, a, 5, "exact",
                jnp.bfloat16, logits_dtype=jnp.bfloat16,
                with_metrics=with_metrics, context_runs=RUNS["context_runs"],
                subword=(table, shape))
        return jax.lax.scan(body, params, (centers, contexts, negatives, alphas))

    program = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(spec((rows0, D), jnp.float32), spec((words, D), jnp.float32)),
        SubwordTable(spec((words + 2,), jnp.int32), spec((groups, 8), jnp.int32),
                     spec((words + 1,), jnp.int32)),
        spec((K, B), jnp.int32), spec((K, B), jnp.int32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile()
    compiled = program.as_text()
    # the row ids, the gather, the scatter, syn1's
    assert compiled.count(" conditional(") >= 4
    copies = [line.strip()[:120] for line in compiled.splitlines()
              if re.search(rf"= f32\[({rows0}|{words}),{D}\]\S* copy\(", line)]
    assert not copies, copies
    # one scatter into syn0 in each of the four branches (plain, per run, per
    # word whole and cut to the slot capacity), and nowhere else
    assert len(re.findall(rf"= f32\[{rows0},{D}\]\S* scatter\(", compiled)) == 4
    # the cut entry's scatter takes the capacity's slots; no loop but the
    # chunk's scan and the plain form's two
    assert re.search(r"= f32\[278528,384\]\S* ", compiled)
    assert compiled.count(" while(") == 3
    # the per-word form reads its heads' row ids alone: [12288 · 5, 8], inside
    # a branch, beside the per-run form's [24576 · 5, 8]
    assert re.search(r"= s32\[61440,8\]\S* fusion\(", compiled)
    assert (program.memory_analysis().temp_size_in_bytes
            <= parent_temporaries[with_metrics])


# what the trainer derives for a block of 65,546 kept tokens at V = 3M: run
# length, syn0's cap, syn1's (tests/test_coalesce_runs.py holds the derivation;
# at V = 2M syn0's would be a unit less, and the token row source takes its place)
TOKEN_RUNS = (6, 40960, 32768)


def _branches(compiled: str):
    """The two branch computations' names of each conditional, in text order."""
    return re.findall(r"conditional\(.*branch_computations=\{%([\w.]+), %([\w.]+)\}",
                      compiled)


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied_in_the_plain_banded_step(one_chip, with_metrics):
    from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core

    tokens, window = 65546, 5

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, toks, left, right, center, negatives, alphas):
        def body(p, xs):
            tk, l, r, c, n, a = xs
            return cbow_step_banded_core(
                p, tk, l, r, c, jnp.ones(tokens, jnp.float32), n, a, 5, window,
                "exact", jnp.bfloat16, jnp.bfloat16, with_metrics,
                token_runs=TOKEN_RUNS)
        return jax.lax.scan(body, params, (toks, left, right, center, negatives, alphas))

    block, table = spec((K, tokens), jnp.int32), spec((V, D), jnp.float32)
    program = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table), block, block, block,
        spec((K, tokens), jnp.float32), spec((K, P), jnp.int32),
        spec((K,), jnp.float32)).compile()
    compiled = program.as_text()
    copies = [line.strip()[:120] for line in compiled.splitlines()
              if re.search(rf"= f32\[{V},{D}\]\S* copy\(", line)]
    assert not copies, copies
    # a conditional a table
    conditionals = _branches(compiled)
    assert len(conditionals) == 2
    caps = []
    for branches in conditionals:
        texts = [_computation(compiled, name) for name in branches]
        # one scatter into the table in each branch (inside a branch it is a
        # fusion that gives the table back)
        assert [len(re.findall(rf"= f32\[{V},{D}\]\S* fusion\(", t)) for t in texts] == [1, 1]
        # the tokens are sorted before the conditional; the coalesced branch
        # compacts its heads by a sort of its own and hands the scatter its
        # cap's rows, the other the block's
        sorts = [t.count(" sort(") for t in texts]
        assert sorted(sorts) == [0, 1], sorts
        coalesced = texts[sorts.index(1)]
        caps += [cap for cap in TOKEN_RUNS[1:] if f"f32[{cap},{D}]" in coalesced]
        assert f"f32[{tokens},{D}]" in texts[sorts.index(0)]
    assert sorted(caps) == sorted(TOKEN_RUNS[1:]), caps
    # the two stable sorts that carry the positions (by token, and by token
    # with the slots that train nothing sent last) and the two compactions
    assert compiled.count(" sort(") == 4
    # syn0's two, syn1's two and the pool rows'
    assert len(re.findall(rf"= f32\[{V},{D}\]\S* scatter\(", compiled)) == 5
    # what the conditionals add to the parent's temporaries (444.5 / 427.2 MB
    # with token_runs=None: my compile for the described v5e, PR 46): the
    # sorts' s32[65546] arrays, and nothing [T, D] wide that outlives a branch
    assert program.memory_analysis().temp_size_in_bytes < 460_000_000


@pytest.mark.parametrize("tail_cap", [0, 4096], ids=["slots_cut", "both_capacities"])
@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied_with_token_lists_and_position_weights(one_chip, with_metrics,
                                                                  tail_cap):
    from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core
    from glint_word2vec_tpu.ops.subword import SubwordShape, SubwordTable

    words, rows0, groups, tokens, window = 2_000_000, 4_000_000, 3 << 20, 65546, 5
    # what the trainer derives at this size: every token slot its own list, the
    # lists' scatter under the slot capacity and (PR 43) their gather under the
    # tail capacity (tests/test_coalesce_runs.py holds the derivations);
    # without the second the program is PR 36's
    shape = SubwordShape(max_groups=2, max_run=1, head_cap=tokens, slot_cap=393216,
                         tail_cap=tail_cap)
    # temp_size_in_bytes of the same compile with slot_cap=0, the parent's form
    # (my compile for the described v5e, PR 36), and what the conditional adds
    # whatever the capacity (1,048,736 and 360,448 read the same): 21.7 MB of
    # 1.83 GB, five s32[1048736] arrays; syn1's conditional (PR 46) adds its
    # sorts' s32[65546] arrays
    parent_temporaries = {True: 1_833_126_400, False: 1_833_384_448}
    conditional_adds = 24 << 20

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, table, toks, left, right, center, negatives, alphas):
        def body(p, xs):
            tk, l, r, c, n, a = xs
            return cbow_step_banded_core(
                p, tk, l, r, c, jnp.ones(tokens, jnp.float32), n, a, 10, window,
                "exact", jnp.bfloat16, jnp.bfloat16, with_metrics,
                subword=(table, shape), token_runs=(6, 38912, 32768))
        return jax.lax.scan(body, params, (toks, left, right, center, negatives, alphas))

    block = spec((K, tokens), jnp.int32)
    program = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(spec((rows0, D), jnp.float32), spec((words, D), jnp.float32),
                      spec((2 * window, D), jnp.float32)),
        SubwordTable(spec((words + 2,), jnp.int32), spec((groups, 8), jnp.int32),
                     spec((words + 1,), jnp.int32)),
        block, block, block, spec((K, tokens), jnp.float32), spec((K, 4096), jnp.int32),
        spec((K,), jnp.float32)).compile()
    compiled = program.as_text()
    # the lists' scatter and (PR 46) syn1's token scatter, and nothing else:
    # the head capacity is known while tracing, and the tail capacity builds a
    # loop, not a branch
    conditionals = _branches(compiled)
    assert len(conditionals) == 2
    copies = [line.strip()[:120] for line in compiled.splitlines()
              if re.search(rf"= f32\[({rows0}|{words}),{D}\]\S* copy\(", line)]
    assert not copies, copies

    def scatters(rows):
        return len(re.findall(rf"= f32\[{rows},{D}\]\S* scatter\(", compiled))

    # the lists reach syn0's scatter once a step, in either branch; syn1 takes
    # the centers' rows (in either branch of its own conditional) and the pool's
    assert (scatters(rows0), scatters(words)) == (2, 3)
    lists, = [b for b in conditionals
              if f"f32[{rows0},{D}]" in _computation(compiled, b[0])]
    syn1s, = [b for b in conditionals if b != lists]
    # the step's own sort of the (row, slot) keys is the cut branch's: a branch
    # is a computation of its own, and one of the two holds no stable sort (the
    # whole form leaves its indices to XLA, which sorts them its own way)
    own = [bool(re.search(r" sort\([^\n]*is_stable=true", _computation(compiled, name)))
           for name in lists]
    assert sorted(own) == [False, True], own
    # and hands the scatter the capacity's slots (their update rows are read
    # in sorted order inside the scatter's own fusion: no [393216, D] block)
    assert "s32[393216]" in _computation(compiled, lists[own.index(True)])
    assert "s32[393216]" not in _computation(compiled, lists[own.index(False)])
    # syn1's: the cap's rows in the coalesced branch, the block's in the other
    texts = [_computation(compiled, name) for name in syn1s]
    assert sorted(f"f32[32768,{D}]" in t for t in texts) == [False, True]
    assert (program.memory_analysis().temp_size_in_bytes
            <= parent_temporaries[with_metrics] + conditional_adds)
    # the scan, and under a tail capacity the loop over the passes of the tail
    # tokens' later groups: every token's first group is gathered outside it,
    # the capacity's later groups inside, and the block is never gathered whole
    assert compiled.count(" while(") == (2 if tail_cap else 1)
    def gathers(heads, slots):
        return len(re.findall(rf"= f32\[{heads},{slots},{D}\]\S* gather\(", compiled))

    assert (gathers(tokens, 16), gathers(tokens, 8), gathers(tail_cap, 8)) == (
        (0, 1, 1) if tail_cap else (1, 0, 0))


def _computation(compiled: str, name: str) -> str:
    """The text of one named computation of a compiled module, with the fused
    computations it calls left out (they are printed before it)."""
    start = compiled.index(f"\n%{name} ")
    return compiled[start:compiled.index("\n}\n", start)]


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


@pytest.mark.parametrize("with_metrics", [True, False], ids=["full", "fast"])
def test_no_table_is_copied_under_hierarchical_softmax(one_chip, with_metrics):
    from glint_word2vec_tpu.ops.hs import HsShape, hs_step_core
    from glint_word2vec_tpu.ops.subword import SubwordTable

    groups = 11 << 20
    # what the trainer derives at this size (train/trainer.py _hs_caps over the
    # benchmark's Zipf counts and the AUTO subsample; PERF.md §6, PR 37)
    shape = HsShape(max_groups=4, max_run=8, word_cap=18432, slot_cap=311296)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(params, table, centers, contexts, alphas):
        def body(p, xs):
            c, x, a = xs
            return hs_step_core(p, c, x, jnp.ones(B, jnp.float32), a, table, shape,
                                "exact", jnp.bfloat16, with_metrics,
                                center_runs=RUNS["center_runs"])
        return jax.lax.scan(body, params, (centers, contexts, alphas))

    table = spec((V, D), jnp.float32)
    program = jax.jit(chunk, donate_argnums=(0,)).lower(
        EmbeddingPair(table, table),
        SubwordTable(spec((V + 2,), jnp.int32), spec((groups, 8), jnp.int32),
                     spec((V + 1,), jnp.int32)),
        spec((K, B), jnp.int32), spec((K, B), jnp.int32),
        spec((K,), jnp.float32)).compile()
    compiled = program.as_text()
    # syn1's reads, syn1's writes, syn0's scatter by center runs
    assert compiled.count(" conditional(") == 3
    copies = [line.strip()[:120] for line in compiled.splitlines()
              if re.search(rf"= f32\[{V},{D}\]\S* copy\(", line)]
    assert not copies, copies
    # syn1: the sorted slots' scatter and the per-pair loop's; syn0: by runs and plain
    assert len(re.findall(rf"= f32\[{V},{D}\]\S* scatter\(", compiled)) == 4
    # the pieces' paths are one [18432 · 32, 384] bfloat16 gather, and no
    # [65536 · 32, 384] block exists in either form
    assert re.search(r"bf16\[589824,384\]", compiled)
    assert not re.search(r"\[2097152,384\]|\[65536,32,384\]", compiled)
    assert program.memory_analysis().temp_size_in_bytes < 2_600_000_000


SUB_V, SUB_K, SUB_D, SUB_GROUPS = 2_519_370, 2_000_000, 300, 11 << 20


def _no_table_copied(text: str):
    tables = r"f32\[(?:%d|%d|%d),\d+\]" % (SUB_V, SUB_K, SUB_V + SUB_K)
    assert not re.findall(r"= %s\S* copy\(" % tables, text)
    assert not re.search(r"f32\[%d," % (SUB_V + SUB_K), text)


@pytest.mark.parametrize("width", [SUB_D, 384], ids=["as_trained", "whole_lanes"])
def test_the_composed_tables_block_copies_no_table(one_chip, width):
    """``width`` 384: the table a ``resident="rows"`` model composes straight
    at whole lanes (PR 52), written in place as the [V, 300] one is."""
    from glint_word2vec_tpu.ops import subword as sw

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table = sw.SubwordTable(spec((SUB_V + 2,), jnp.int32),
                            spec((SUB_GROUPS, 8), jnp.int32),
                            spec((SUB_V + 1,), jnp.int32))
    compiled = sw._compose_block.lower(
        spec((SUB_V, width), jnp.float32), spec((SUB_V, SUB_D), jnp.float32),
        spec((SUB_K, 384), jnp.float32), table, spec((), jnp.int32),
        max_groups=5, block=1 << 13).compile()
    _no_table_copied(compiled.as_text())
    memory = compiled.memory_analysis()
    # the result is the donated operand, and a block's gather is what is made
    assert memory.alias_size_in_bytes >= 4 * SUB_V * width
    assert memory.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("lists", [True, False], ids=["with_lists", "words_alone"])
def test_the_subword_scan_copies_no_table(one_chip, lists, monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # _grouped_scores asks for the backend while it is traced: the TPU's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    extra = ((spec((SUB_K, 384), jnp.float32), spec((32, 48), jnp.int32))
             if lists else ())
    compiled = w2v._gather_topk_batch.lower(
        spec((SUB_V, SUB_D), jnp.float32), spec((SUB_V,), jnp.float32),
        spec((32,), jnp.int32), None, 11, SUB_V, False, *extra).compile()
    _no_table_copied(compiled.as_text())
    # two score blocks of [32, 2,519,552] float32 and no table beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("queries", [32, 64])
def test_the_sharded_scan_moves_no_table_and_nothing_v_wide(topo, queries, monkeypatch):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from glint_word2vec_tpu.models import word2vec as w2v

    rows, dim, k = 10_000_000, 300, 11
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    shards = NamedSharding(mesh, PartitionSpec("model", None))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*axes)))

    # _grouped_scores asks for the backend while it is traced: the TPU's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = w2v._gather_topk_batch.lower(
        spec((rows, dim), jnp.float32, "model", None),
        spec((rows,), jnp.float32, "model"), spec((queries,), jnp.int32),
        None, k, rows, shards).compile()
    text = compiled.as_text()
    assert not re.findall(r"= f32\[2500\d{3},300\]\S* (?:copy|gather|all-gather)\(", text)
    assert " all-to-all(" not in text and " collective-permute(" not in text
    reduced = re.findall(r"= (\S+?)\{\S* all-reduce(?:-start)?\(", text)
    assert reduced == [f"f32[{queries},{dim}]"], reduced
    gathered = re.findall(r"= \(?(\w+)\[([\d,]+)\]\S* all-gather(?:-start)?\(", text)
    assert len(gathered) == 2, gathered
    for _, dims in gathered:
        assert int(np.prod([int(d) for d in dims.split(",")])) == 4 * k * queries
    memory = compiled.memory_analysis()
    # a chip's shard and its norms are the arguments; one score block beside them
    assert memory.argument_size_in_bytes < 3.1e9
    assert 4 * queries * 2_500_000 < memory.temp_size_in_bytes < 4 * queries * 2_500_000 * 1.2


@pytest.mark.parametrize("carried", [False, True], ids=["one_pass", "a_further_pass"])
def test_the_transform_slide_copies_no_table_and_writes_no_gathered_block(one_chip, carried):
    """``transform_sentences``' one program a slide (PR 48) at
    ``sgns-transform-3m-300``'s size: 327,680 ids gathered from the whole-lane
    form of syn0 into 10,000 sentences. No copy of the table, and the sorted
    scatter-add takes the gather as a producer: nothing ``[rows, 384]`` is
    written (what is made is the ``[10000, 384]`` sums)."""
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, sentences = 327_680, 10_000
    compiled = w2v._segment_means.lower(
        spec((V, D), jnp.float32), spec((rows,), jnp.int32), spec((rows,), jnp.int32),
        spec((sentences,), jnp.int32),
        spec((sentences, D), jnp.float32) if carried else None,
        segments=sentences, dim=300).compile()
    text = compiled.as_text()
    assert not re.findall(r"= f32\[%d,\d+\]\S* copy\(" % V, text)
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("carried", [False, True], ids=["one_pass", "a_further_pass"])
def test_the_sentence_vector_slide_copies_no_table_and_writes_no_block(one_chip, carried):
    """``sentence_vectors``' one program a slide (PR 52) at
    ``subword-sentvec-2.5m-300``'s size: 327,680 word rows gathered from the
    composed table at whole lanes and scaled by their inverse norms, 294,912
    list rows gathered from the bucket rows into 32,768 tokens, normalised,
    both summed into 10,000 sentences. No copy of either table, no sort, and
    every gather is its sorted scatter-add's producer: neither gathered block
    nor the token block is written (what is made is the ``[10000, 384]``
    sums)."""
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, listed, tokens, sentences = 327_680, 294_912, 32_768, 10_000
    lists = (spec((SUB_K, 384), jnp.float32), spec((listed,), jnp.int32),
             spec((listed,), jnp.int32), spec((tokens,), jnp.int32))
    before = ((spec((sentences, 384), jnp.float32), spec((sentences,), jnp.int32))
              if carried else None)
    compiled = w2v._sentence_means.lower(
        spec((SUB_V, 384), jnp.float32), spec((SUB_V,), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.int32), lists,
        spec((sentences,), jnp.int32), before, segments=sentences, dim=300).compile()
    text = compiled.as_text()
    _no_table_copied(text)
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_same_gather_from_the_300_wide_table_copies_all_of_it(one_chip):
    """Why the model keeps a whole-lane form for its row reads: handed syn0 as
    the scan reads it, the same program first copies the whole table row-major
    (the parent's ``self.syn0[idx]``: 3.6 GB and ~13 ms a call before one row
    is read)."""
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = w2v._segment_means.lower(
        spec((V, 300), jnp.float32), spec((327_680,), jnp.int32),
        spec((327_680,), jnp.int32), spec((10_000,), jnp.int32), None,
        segments=10_000, dim=300).compile()
    assert re.findall(r"= f32\[%d,300\]\S* copy\(" % V, compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes > 3 << 30


@pytest.mark.parametrize("cap,k", [(512, 1), (2048, 1), (2048, 10)])
def test_the_analogy_scan_copies_no_table_and_holds_no_block_wider_than_a_tile(one_chip, cap, k):
    """``Word2VecModel.analogies``' one program a (capacity, k) (PR 55) at
    ``sgns-analogy-3m-300``'s size: the question rows read a lane tile at a
    time in place from the float32 table, the bfloat16 form of it that the
    model keeps (``_scan_table``) scored 65,536 rows a block. No copy or
    conversion of a [3,000,000, 300] table (a one-row slice under a ``while``
    made a row-major copy; a float32 table handed to the matmul is converted
    whole, once a program, 1.8 GB of temporaries), no score block wider than a
    tile, and at k = 1 not even that: the matmul, the masks and the variadic
    reduce are ONE output fusion, so the block never leaves the chip's fast
    memory. For k > 1 one [capacity, 65,536] float32 block is held."""
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, dim, block = 3_000_000, 300, 1 << 16
    compiled = w2v._analogy_topk.lower(
        spec((rows, dim), jnp.float32), spec((rows, dim), jnp.bfloat16),
        spec((rows,), jnp.float32), spec((3 * cap,), jnp.int32), spec((), jnp.int32),
        spec((cap, 3), jnp.int32), k=k, candidates=rows, block_rows=block).compile()
    text = compiled.as_text()
    assert not re.findall(
        r"= \w+\[%d,%d\]\S* (?:copy|transpose|gather|convert)\(" % (rows, dim), text)
    widths = [int(w) for w in re.findall(r"= \w+\[%d,(\d+)\]" % cap, text)]
    assert max(widths) <= block, max(widths)
    if k == 1:
        fused = re.findall(r"-> \(f32\[%d\], s32\[%d\]\) \{" % (cap, cap), text)
        assert fused, "the block's maximum is no longer the matmul's own output fusion"
    memory = compiled.memory_analysis()
    held = 4 * cap * block if k > 1 else 0
    assert memory.temp_size_in_bytes < held * 1.05 + (64 << 20), memory.temp_size_in_bytes


def test_a_float32_table_at_the_default_precision_is_multiplied_as_bfloat16(one_chip):
    """Why ``_scan_table`` keeps a bfloat16 form: handed the float32 table, the
    compiler converts all of it to bfloat16 itself, outside the blocks' loop,
    once a program, and the matmul's operands are bfloat16 either way."""
    from glint_word2vec_tpu.models import word2vec as w2v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, dim, cap = 3_000_000, 300, 512
    compiled = w2v._analogy_topk.lower(
        spec((rows, dim), jnp.float32), spec((rows, dim), jnp.float32),
        spec((rows,), jnp.float32), spec((3 * cap,), jnp.int32), spec((), jnp.int32),
        spec((cap, 3), jnp.int32), k=1, candidates=rows, block_rows=1 << 16).compile()
    text = compiled.as_text()
    assert re.search(r"= bf16\[%d,%d\]\S* convert\(" % (rows, dim), text)
    assert not re.search(r"convolution\(\S*f32\[", text)
    assert compiled.memory_analysis().temp_size_in_bytes > 2 * rows * dim
