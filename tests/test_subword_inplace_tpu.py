"""The subword skip-gram step and the composed table's block write in place on the TPU.

Compiled for a v5e chip that is described, not attached (tests/described_v5e.py;
nothing runs). The subword step at ``subword-sgns-2.5m-300``'s size (PR
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
That the step is the program it was is held where it is cheap, on its lowered
text (``tests/test_cbow_subword.py``).

``ops/subword._compose_block`` (PR 40, PR 52) at wiki.en's shape: the composed
table is the donated operand, written in place, and a block's gather is all
that is made.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import B, D, K, P, RUNS, SUB_D, SUB_GROUPS, SUB_K, SUB_V, _no_table_copied
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops.sgns import EmbeddingPair, sgns_step_shared_core


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
