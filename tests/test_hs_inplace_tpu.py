"""The hierarchical-softmax step updates its tables in place on the TPU.

Compiled at ``skipgram-hs-3m-300``'s size for a v5e chip that is described, not
attached (tests/described_v5e.py; nothing runs; PR 37): syn1's rows are read by one
conditional and written by another, syn0 by its scatter by center runs, and
no f32[3000000,384] table is copied; the pieces' paths are one bfloat16 gather.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import B, D, K, RUNS, V
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops.sgns import EmbeddingPair


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
