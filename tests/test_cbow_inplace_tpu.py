"""The plain banded CBOW step updates its tables in place on the TPU.

Compiled at ``cbow-3m-300``'s size for a v5e chip that is described, not
attached (tests/described_v5e.py; nothing runs; PR 46):
each token scatter goes through a conditional of its own on the block's tokens
sorted inside the step (``token_runs``: syn0's by every slot's token, syn1's
by the tokens of the slots that train an example; the sorts lie outside the
conditionals, the heads' compaction inside each coalesced branch). syn0 is
read by the tokens' gather and syn1 by the tokens' and the pool rows' gathers
before them, and neither table is copied: one scatter into its table in each
branch, and the pool rows' after syn1's. With the token row source syn1's
conditional stands beside the lists'
(tests/test_token_lists_inplace_tpu.py).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import D, K, P, V, _branches, _computation
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops.sgns import EmbeddingPair


# what the trainer derives for a block of 65,546 kept tokens at V = 3M: run
# length, syn0's cap, syn1's (tests/test_coalesce_runs.py holds the derivation;
# at V = 2M syn0's would be a unit less, and the token row source takes its place)
TOKEN_RUNS = (6, 40960, 32768)


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
