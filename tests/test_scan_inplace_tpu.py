"""The read side's programs read their tables in place on the TPU.

``ops/scan.py`` (the neighbour scan on one chip and under ``shard_map`` on the
described 2x2 as a 1x4 mesh, the analogy scan) and ``ops/transform.py`` (the
sentence slides, on one chip and under ``shard_map`` on the 1x4 mesh), compiled at their cells' published shapes for a v5e chip that
is described, not attached (tests/described_v5e.py; nothing runs): no copy, gather
or conversion of a table, nothing V wide across the mesh, no gathered block
written. A count of instructions and of bytes, not a time.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_v5e import D, SUB_D, SUB_K, SUB_V, V, _no_table_copied
from described_v5e import one_chip, topo  # noqa: F401  (fixtures)

from glint_word2vec_tpu.ops import scan, transform


@pytest.mark.parametrize("lists", [True, False], ids=["with_lists", "words_alone"])
def test_the_subword_scan_copies_no_table(one_chip, lists, monkeypatch):

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # _grouped_scores asks for the backend while it is traced: the TPU's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    extra = ((spec((SUB_K, 384), jnp.float32), spec((32, 48), jnp.int32))
             if lists else ())
    compiled = scan._gather_topk_batch.lower(
        spec((SUB_V, SUB_D), jnp.float32), spec((SUB_V,), jnp.float32),
        spec((32,), jnp.int32), None, 11, SUB_V, False, *extra).compile()
    _no_table_copied(compiled.as_text())
    # two score blocks of [32, 2,519,552] float32 and no table beside them
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("queries", [32, 64])
def test_the_sharded_scan_moves_no_table_and_nothing_v_wide(topo, queries, monkeypatch):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec


    rows, dim, k = 10_000_000, 300, 11
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    shards = NamedSharding(mesh, PartitionSpec("model", None))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*axes)))

    # _grouped_scores asks for the backend while it is traced: the TPU's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = scan._gather_topk_batch.lower(
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

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, sentences = 327_680, 10_000
    compiled = transform._segment_means.lower(
        spec((V, D), jnp.float32), spec((rows,), jnp.int32), spec((rows,), jnp.int32),
        spec((sentences,), jnp.int32),
        spec((sentences, D), jnp.float32) if carried else None,
        segments=sentences, dim=300).compile()
    text = compiled.as_text()
    assert not re.findall(r"= f32\[%d,\d+\]\S* copy\(" % V, text)
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _entry(compiled: str) -> str:
    """The entry computation of a compiled module: what is scheduled and
    written, without the fused computations' own instructions."""
    return compiled[compiled.index("\nENTRY "):]


def _lane_of_the_row(table, ids, seg, segments):
    """ROADMAP A14 (a)'s form of the word side, NOT the program's: a row's
    inverse norm kept in a spare lane of the row itself (lane 300 of 384), to
    "ride the row gather for nothing". Kept here as the record of why it was
    not taken (PR 60): reading one lane of the gathered rows makes the
    compiler WRITE the gathered block, twice."""
    rows = table.at[ids].get(mode="fill", fill_value=0)
    unit = rows * rows[:, 300][:, None]
    return jax.ops.segment_sum(unit, seg, num_segments=segments,
                               indices_are_sorted=True)


@pytest.mark.parametrize("form", ["one_pass", "a_further_pass", "lane_of_the_row"])
def test_the_sentence_vector_slide_copies_no_table_and_writes_no_block(one_chip, form):
    """``sentence_vectors``' one program a slide (PR 52) at
    ``subword-sentvec-2.5m-300``'s size: 327,680 word rows gathered from the
    composed table at whole lanes and scaled by the inverse norms the host's
    encode took for them (a dense ``[327680]`` operand, PR 60), 294,912
    list rows gathered from the bucket rows into 32,768 tokens, normalised,
    both summed into 10,000 sentences. No copy of either table, no sort, and
    every gather is its sorted scatter-add's producer: neither gathered block
    nor the token block is written (what is made is the ``[10000, 384]``
    sums), and the program gathers ROWS alone: no one-element gather of a
    scale an id is left (2.28 ms of the slide's 15.5 before PR 60). The case
    ``lane_of_the_row`` compiles the form that was refuted instead
    (:func:`_lane_of_the_row`): over 900 MB of temporaries a slide."""

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, listed, tokens, sentences = 327_680, 294_912, 32_768, 10_000
    if form == "lane_of_the_row":
        compiled = jax.jit(_lane_of_the_row, static_argnames="segments").lower(
            spec((SUB_V, 384), jnp.float32), spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), segments=sentences).compile()
        assert re.findall(r"= f32\[%d,384\]" % rows, _entry(compiled.as_text()))
        assert compiled.memory_analysis().temp_size_in_bytes > 900e6
        return
    lists = (spec((SUB_K, 384), jnp.float32), spec((listed,), jnp.int32),
             spec((listed,), jnp.int32), spec((tokens,), jnp.int32))
    before = ((spec((sentences, 384), jnp.float32), spec((sentences,), jnp.int32))
              if form == "a_further_pass" else None)
    compiled = transform._sentence_means.lower(
        spec((SUB_V, 384), jnp.float32), spec((rows,), jnp.float32),
        spec((rows,), jnp.int32), spec((rows,), jnp.int32), lists,
        spec((sentences,), jnp.int32), before, segments=sentences, dim=300).compile()
    text = compiled.as_text()
    _no_table_copied(text)
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    # the scale arrives dense: no gather of one float32 an id ...
    assert not [line.strip()[:100] for line in _entry(text).splitlines()
                if re.search(r"= f32\[%d\]\S* fusion\(" % rows, line)
                and "kind=kCustom" in line]
    # ... and the gathered rows are their scatter-add's producer, never written
    assert not re.findall(r"= f32\[%d,384\]" % rows, _entry(text))


def _mesh_1x4(topo):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, PartitionSpec(*axes)))

    return NamedSharding(mesh, PartitionSpec("model", None)), spec


_COLLECTIVE = (r"= (\S+?)\{\S* (all-reduce|all-gather|all-to-all|reduce-scatter|"
               r"collective-permute)(?:-start)?\(")


@pytest.mark.parametrize("carried", [False, True], ids=["one_pass", "a_further_pass"])
def test_the_sharded_slide_moves_no_table_and_one_block_of_partial_sums(topo, carried):
    """``transform_sentences``' one program a slide over a table partitioned by
    rows (PR 59) at ``sgns-transform-10m-300-x4``'s size, on the described 2x2
    as a 1x4 mesh: 327,680 replicated ids, every chip gathering the ones it
    owns from its own ``[2,500,000, 384]`` block of the whole-lane form. Exactly
    one collective, the all-reduce of the ``[10000, 384]`` float32 partial sums;
    the only thing 2,500,000 (or 10,000,000) rows wide in the whole module is
    the block, as a parameter: no copy, gather output, slice or all-gather of
    it; no sort; and the gather is the sorted scatter-add's producer, so nothing
    ``[rows, 384]`` is written either."""
    shards, spec = _mesh_1x4(topo)
    rows, sentences, vocab = 327_680, 10_000, 10_000_000
    compiled = transform._segment_means.lower(
        spec((vocab, D), jnp.float32, "model", None), spec((rows,), jnp.int32),
        spec((rows,), jnp.int32), spec((sentences,), jnp.int32),
        spec((sentences, D), jnp.float32) if carried else None,
        segments=sentences, dim=300, shards=shards).compile()
    text = compiled.as_text()
    assert re.findall(_COLLECTIVE, text) == [(f"f32[{sentences},{D}]", "all-reduce")]
    wide = [line.strip()[:80] for line in text.splitlines()
            if re.search(r"= \(?\w+\[(?:2500000|10000000)[,\]]", line)]
    # the entry's parameter, and the fusions' that read it in place
    assert wide and all(" parameter(" in line for line in wide), wide
    assert " sort(" not in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 3.9e9        # a chip's block, the ids
    assert memory.temp_size_in_bytes < 64 << 20


def test_the_whole_lane_form_is_made_under_the_sharding_with_no_collective(topo):
    """``_row_table`` on a mesh: ``lane_padded``'s jitted pad, the table's
    sharding out, compiles, for each chip, to the one-chip program over
    its quarter: no collective, and nothing wider than a quarter of the
    300-wide table in and a quarter of the 384-wide form out."""
    from glint_word2vec_tpu.ops.subword import lane_padded

    _, spec = _mesh_1x4(topo)
    made = {}
    real_jit = jax.jit

    def keep(fun, **kw):
        made["jit"] = real_jit(fun, **kw)
        return lambda rows: made.setdefault("lowered", made["jit"].lower(rows))

    import unittest.mock
    with unittest.mock.patch.object(jax, "jit", keep):
        lane_padded(spec((10_000_000, 300), jnp.float32, "model", None))
    compiled = made["lowered"].compile()
    assert not re.findall(_COLLECTIVE, compiled.as_text())
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 3.1e9 and memory.output_size_in_bytes < 3.9e9
    assert "f32[10000000," not in compiled.as_text().split("ENTRY")[1]


def test_the_sharded_row_read_moves_the_rows_asked_for_and_no_block(topo):
    """``pull`` / ``transform_words`` over a partitioned table
    (``_sharded_rows``): one all-reduce of the ``[Q, 384]`` rows, no copy or
    gather output a block wide."""
    shards, spec = _mesh_1x4(topo)
    compiled = transform._sharded_rows.lower(
        spec((10_000_000, D), jnp.float32, "model", None), spec((10_000,), jnp.int32),
        dim=300, shards=shards).compile()
    text = compiled.as_text()
    assert re.findall(_COLLECTIVE, text) == [(f"f32[10000,{D}]", "all-reduce")]
    assert not re.findall(r"= f32\[2500000,\d+\]\S* (?:copy|gather|all-gather)\(", text)


def test_the_same_gather_from_the_300_wide_table_copies_all_of_it(one_chip):
    """Why the model keeps a whole-lane form for its row reads: handed syn0 as
    the scan reads it, the same program first copies the whole table row-major
    (the parent's ``self.syn0[idx]``: 3.6 GB and ~13 ms a call before one row
    is read)."""

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = transform._segment_means.lower(
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

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, dim, block = 3_000_000, 300, 1 << 16
    compiled = scan._analogy_topk.lower(
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

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, dim, cap = 3_000_000, 300, 512
    compiled = scan._analogy_topk.lower(
        spec((rows, dim), jnp.float32), spec((rows, dim), jnp.float32),
        spec((rows,), jnp.float32), spec((3 * cap,), jnp.int32), spec((), jnp.int32),
        spec((cap, 3), jnp.int32), k=1, candidates=rows, block_rows=1 << 16).compile()
    text = compiled.as_text()
    assert re.search(r"= bf16\[%d,%d\]\S* convert\(" % (rows, dim), text)
    assert not re.search(r"convolution\(\S*f32\[", text)
    assert compiled.memory_analysis().temp_size_in_bytes > 2 * rows * dim
