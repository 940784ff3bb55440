"""The banded CBOW step with token lists and position weights updates its tables in place.

Compiled for a v5e chip that is described, not attached (tests/described_v5e.py;
nothing runs). The banded CBOW step (PR 33) with the token row source and the
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
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import D, K, _branches, _computation
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops.sgns import EmbeddingPair


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
