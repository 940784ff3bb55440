"""Tests for model ops (C8/C12 analogs): transform, sentence averaging, synonyms,
analogy, norms, multiply, exports, stop."""

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.models.word2vec import Word2VecModel

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon"]


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(0)
    vocab = Vocabulary.from_words_and_counts(WORDS, [50, 40, 30, 20, 10])
    syn0 = rng.normal(size=(5, 8)).astype(np.float32)
    # make beta nearly parallel to alpha so synonyms are predictable
    syn0[1] = syn0[0] * 2.0 + rng.normal(size=8).astype(np.float32) * 1e-3
    return Word2VecModel(vocab, syn0, syn1=np.zeros_like(syn0),
                         config=Word2VecConfig(vector_size=8)), syn0


def test_transform_word(model):
    m, syn0 = model
    np.testing.assert_allclose(m.transform("alpha"), syn0[0], rtol=1e-6)
    with pytest.raises(KeyError, match="not in vocabulary"):
        m.transform("zzz")


def test_transform_words_batched(model):
    m, syn0 = model
    out = list(m.transform_words(["gamma", "alpha", "gamma"], batch_size=2))
    np.testing.assert_allclose(out[0], syn0[2], rtol=1e-6)
    np.testing.assert_allclose(out[1], syn0[0], rtol=1e-6)
    np.testing.assert_allclose(out[2], syn0[2], rtol=1e-6)
    with pytest.raises(KeyError):
        list(m.transform_words(["alpha", "zzz"]))


def test_transform_sentences_average_and_oov(model):
    m, syn0 = model
    out = m.transform_sentences([
        ["alpha", "beta"],          # mean of two vectors
        ["alpha", "zzz", "alpha"],  # OOV dropped, duplicates count (ml:451-452)
        ["zzz"],                    # no in-vocab words → zero vector
        [],
    ])
    np.testing.assert_allclose(out[0], (syn0[0] + syn0[1]) / 2, rtol=1e-5)
    np.testing.assert_allclose(out[1], syn0[0], rtol=1e-5)
    np.testing.assert_array_equal(out[2], np.zeros(8))
    np.testing.assert_array_equal(out[3], np.zeros(8))


def test_transform_sentences_batch_boundary(model):
    m, syn0 = model
    sents = [["alpha"]] * 7
    out = m.transform_sentences(sents, batch_size=3)  # 3+3+1 flushes
    for row in out:
        np.testing.assert_allclose(row, syn0[0], rtol=1e-5)


def test_pull_and_multiply(model):
    m, syn0 = model
    np.testing.assert_allclose(m.pull([2, 0]), syn0[[2, 0]], rtol=1e-6)
    v = np.arange(8, dtype=np.float32)
    np.testing.assert_allclose(m.multiply(v), syn0 @ v, rtol=1e-4)


def test_norms(model):
    m, syn0 = model
    np.testing.assert_allclose(
        np.asarray(m.norms), np.linalg.norm(syn0, axis=1), rtol=1e-5)


def test_find_synonyms_word_query_excludes_self(model):
    m, _ = model
    res = m.find_synonyms("alpha", 2)
    words = [w for w, _ in res]
    assert "alpha" not in words
    assert words[0] == "beta"          # nearly parallel by construction
    assert res[0][1] > 0.999
    # scores sorted descending
    assert res[0][1] >= res[1][1]


def test_find_synonyms_vector_query(model):
    m, syn0 = model
    res = m.find_synonyms(syn0[0], 1)
    assert res[0][0] in ("alpha", "beta")  # self allowed for vector queries (mllib:621)


def test_find_synonyms_num_larger_than_vocab(model):
    m, _ = model
    res = m.find_synonyms("alpha", 50)
    assert len(res) == 4  # vocab minus query word


def test_find_synonyms_batch_matches_per_query(model):
    """find_synonyms_batch = find_synonyms per row, in one device dispatch per
    chunk: word and vector queries mix, word queries exclude themselves, and a
    chunk smaller than the query list exercises the chunking path."""
    m, syn0 = model
    queries = ["alpha", syn0[0], "gamma", "beta", syn0[3]]
    batched = m.find_synonyms_batch(queries, 2, chunk=2)
    assert len(batched) == len(queries)
    for q, got in zip(queries, batched):
        want = m.find_synonyms(q, 2)
        assert [w for w, _ in got] == [w for w, _ in want]
        # scores agree to matmul-association tolerance ([Q,V] vs [V] paths)
        np.testing.assert_allclose([s for _, s in got], [s for _, s in want],
                                   atol=1e-5)
    with pytest.raises(KeyError, match="not in vocabulary"):
        m.find_synonyms_batch(["alpha", "zzz"], 2)


def test_analogy_excludes_queries(model):
    m, _ = model
    res = m.analogy("alpha", "beta", "gamma", num=2)
    for w, _ in res:
        assert w not in ("alpha", "beta", "gamma")


def test_get_vectors_and_iter(model):
    m, syn0 = model
    vecs = m.get_vectors()
    assert set(vecs) == set(WORDS)
    np.testing.assert_allclose(vecs["delta"], syn0[3], rtol=1e-6)
    streamed = dict(m.iter_vectors(batch_size=2))
    for w in WORDS:
        np.testing.assert_allclose(streamed[w], vecs[w], rtol=1e-6)


def test_to_local(model):
    m, syn0 = model
    words, mat = m.to_local()
    assert words == WORDS
    np.testing.assert_allclose(mat, syn0, rtol=1e-6)


def _read_word2vec_format(path, binary):
    """Reference reader for the classic word2vec format — parses exactly the way
    gensim's KeyedVectors.load_word2vec_format / word2vec.c's distance tool do:
    header "<vocab> <dim>", then per word either space-joined decimals + newline
    (text) or <dim> little-endian float32s + newline (binary, word ends at ' ')."""
    with open(path, "rb") as f:
        header = f.readline().split()
        v, d = int(header[0]), int(header[1])
        words, vecs = [], np.empty((v, d), np.float32)
        for i in range(v):
            if binary:
                w = bytearray()
                while True:
                    ch = f.read(1)
                    if ch == b" ":
                        break
                    w.extend(ch)
                words.append(w.decode())
                vecs[i] = np.frombuffer(f.read(4 * d), dtype="<f4")
                assert f.read(1) == b"\n"
            else:
                parts = f.readline().split()
                words.append(parts[0].decode())
                vecs[i] = [float(x) for x in parts[1:]]
    return words, vecs


@pytest.mark.parametrize("binary", [False, True])
def test_export_word2vec_round_trip(model, tmp_path, binary):
    """export_word2vec writes the exact classic format (the reference's toLocal
    ecosystem hand-off, mllib:651-662): a gensim-style parser reads back identical
    words and float32-identical vectors."""
    m, syn0 = model
    path = str(tmp_path / ("vecs.bin" if binary else "vecs.txt"))
    m.export_word2vec(path, binary=binary, batch_size=2)  # exercise block seams
    words, vecs = _read_word2vec_format(path, binary)
    assert words == WORDS
    np.testing.assert_array_equal(vecs, syn0.astype(np.float32))


def test_vocab_size_mismatch_raises():
    vocab = Vocabulary.from_words_and_counts(["a"], [1])
    with pytest.raises(ValueError, match="rows"):
        Word2VecModel(vocab, np.zeros((2, 4), np.float32))


def test_stop_releases():
    vocab = Vocabulary.from_words_and_counts(["a", "b"], [2, 1])
    m = Word2VecModel(vocab, np.zeros((2, 4), np.float32))
    m.stop()
    m.stop()  # idempotent
    with pytest.raises(RuntimeError, match="stopped"):
        m.transform("a")


# -- one device program per query batch: word ids in, the top-k out ----------------


def _scan_model(table: str):
    """203 words × 16 dims with a zero-norm row: float32, bfloat16, or
    row-sharded over the eight fake devices (208 padded rows)."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(3)
    syn0 = rng.standard_normal((203, 16)).astype(np.float32)
    syn0[11] = 0.0
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(203)], np.ones(203, np.int64))
    if table == "sharded":
        return Word2VecModel(vocab, syn0, plan=make_mesh(1, 8)), syn0
    return Word2VecModel(vocab, jnp.asarray(syn0, jnp.dtype(table))), syn0


def _scan_batches(syn0):
    rng = np.random.default_rng(4)
    vec = [rng.standard_normal(16).astype(np.float32) for _ in range(3)]
    return {
        # (queries, num, chunk)
        "words_repeated": (["w5", "w9", "w5", "w202", "w11"], 4, 128),
        "vectors": ([syn0[4] * 3.0, vec[0], np.zeros(16, np.float32)], 4, 128),
        "mixed": (["w5", vec[1], "w5", syn0[9], "w0"], 4, 128),
        "one_word": (["w7"], 3, 128),
        "one_vector": ([vec[2]], 3, 128),
        "longer_than_chunk": (["w1", vec[0], "w2", "w3", "w1", vec[1], "w200"],
                              5, 3),
        "num_over_vocabulary": (["w5", vec[2], "w202"], 300, 128),
    }


def _row_by_row(model, queries, num, chunk, oracle="matrix_scan"):
    """Today's replies from yesterday's host side: the block read one row
    at a time, stacked, and scanned by the matrix-query form
    (``matrix_scan``), or scored whole by ``_cosine_batch`` and ranked on
    the host by the tests' reference (``host_rank``: tests/topk_reference.py,
    ties to the lower row)."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.scan import _cosine_batch, _cosine_topk_batch
    from topk_reference import host_topk
    model.norms
    k, out = min(num + 1, model.num_words), []
    for lo in range(0, len(queries), chunk):
        part = queries[lo:lo + chunk]
        block = jnp.stack([
            model._full0[model.vocab.get(q)] if isinstance(q, str)
            else jnp.asarray(q, jnp.float32) for q in part])
        if oracle == "host_rank":
            scores, idxs = host_topk(np.asarray(_cosine_batch(
                model._full0, model._norms, block, model.num_words)), k)
        else:
            scores, idxs = _cosine_topk_batch(
                model._full0, model._norms, block, k, model.num_words)
        out.extend(model._replies(
            [q if isinstance(q, str) else None for q in part],
            np.asarray(scores), np.asarray(idxs), num))
    return out


@pytest.mark.parametrize("oracle", ["matrix_scan", "host_rank"])
@pytest.mark.parametrize("table", ["float32", "bfloat16", "sharded"])
@pytest.mark.parametrize("batch", [
    "words_repeated", "vectors", "mixed", "one_word", "one_vector",
    "longer_than_chunk", "num_over_vocabulary"])
def test_gathered_batch_equals_row_by_row(batch, table, oracle):
    model, syn0 = _scan_model(table)
    queries, num, chunk = _scan_batches(syn0)[batch]
    got = model.find_synonyms_batch(queries, num, chunk=chunk)
    want = _row_by_row(model, queries, num, chunk, oracle)
    assert len(got) == len(queries)
    for q, g, w in zip(queries, got, want):
        assert [x for x, _ in g] == [x for x, _ in w]
        np.testing.assert_allclose([s for _, s in g], [s for _, s in w],
                                   rtol=0, atol=1e-6)
        assert len(g) == min(num, 203 - isinstance(q, str))
        if isinstance(q, str):
            assert q not in [x for x, _ in g]      # self-exclusion intact
    model.stop()


# -- the top-k in two exact stages: lax.top_k's scores and ids, ties included ------


def _topk_case(case: str, rows: int):
    """(syn0, norms, queries, k, valid_rows, two_stage) of one selection
    case. k = 11 takes groups of 128 columns at these sizes: 1,408 members,
    so 1,409 rows are the fewest the two stages take."""
    import jax.numpy as jnp
    rng = np.random.default_rng(len(case) * 100 + rows)
    num_rows = {"multiple_of_group": 2048, "with_tail": 1700,
                "just_over_hand_back": 1409, "just_under_hand_back": 1408,
                }.get(case, 2100)
    k, valid_rows, dim = 11, num_rows, 16
    syn0 = rng.standard_normal((num_rows, dim)).astype(np.float32)
    queries = syn0[rng.integers(0, num_rows, rows)] + 0.1
    if case.startswith("zero_ties"):
        # every row but a few points away from every query, so a zero-norm
        # row's 0.0 outranks them and the k-th place falls inside the tie
        way = rng.standard_normal(dim).astype(np.float32)
        syn0 = (-way + 0.05 * rng.standard_normal((num_rows, dim))
                ).astype(np.float32)
        queries = (way + 0.05 * rng.standard_normal((rows, dim))
                   ).astype(np.float32)
        if case == "zero_ties_across_boundaries":
            above = [3, 130, 700, 701, 1300, 1900, 2050, 2099]   # 3 places left
            zero = [126, 127, 128, 129, 255, 256, 2047, 2048]
        else:   # 14 groups whose maximum is the k-th score, and the tail
            above = [5, 640, 1999, 2098, 2099]
            zero = [128 * g + 127 - 9 * g for g in range(14)] + [2060]
        syn0[above] = way
        syn0[zero] = 0.0
    elif case == "valid_rows_in_last_group":
        num_rows, valid_rows = 1700, 1650       # -inf from the 13th group on
        syn0, syn0[1640:1660] = syn0[:1700], queries[0]
    elif case == "fewer_valid_rows_than_k":
        valid_rows = 7
    elif case == "zero_query":
        queries[::2] = 0.0      # every score 0.0: the lowest ids win
    syn0 = jnp.asarray(syn0)
    return (syn0, jnp.linalg.norm(syn0, axis=1), jnp.asarray(queries), k,
            valid_rows, case != "just_under_hand_back")


@pytest.mark.parametrize("rows", [1, 34, 64])
@pytest.mark.parametrize("case", [
    "multiple_of_group", "with_tail", "just_over_hand_back",
    "just_under_hand_back", "zero_ties_across_boundaries",
    "zero_ties_in_more_than_k_groups", "valid_rows_in_last_group",
    "fewer_valid_rows_than_k", "zero_query"])
def test_two_stage_topk_is_lax_top_k(case, rows):
    """Scores and ids bit-equal to ``lax.top_k`` of the same score block."""
    import jax
    from glint_word2vec_tpu.ops import scan
    syn0, norms, queries, k, valid_rows, two_stage = _topk_case(case, rows)
    assert bool(scan._topk_group(syn0.shape[0], k)) is two_stage
    want_s, want_i = jax.lax.top_k(
        scan._cosine_batch(syn0, norms, queries, valid_rows), k)
    if case.startswith("zero_ties"):
        assert (np.asarray(want_s)[:, -1] == 0.0).all()    # k-th inside the tie
    got_s, got_i = scan._cosine_topk_batch(syn0, norms, queries, k, valid_rows)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


@pytest.mark.parametrize("rows", [1, 5, 34])
def test_what_only_a_tpu_takes(rows, monkeypatch):
    """The table's rows padded to whole groups under the matmul
    (``_grouped_scores``) and the query rows to whole tiles of 8 on the host
    (``_topk_dispatch``; one query stays one), the padding rows dropped after
    the fetch. Run here by naming the backend, over a vocabulary of its own
    so that no other test meets these traces."""
    import jax
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops import scan
    rng = np.random.default_rng(rows)
    syn0 = rng.standard_normal((1733, 16)).astype(np.float32)
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(1733)], np.ones(1733, np.int64))
    model = Word2VecModel(vocab, jnp.asarray(syn0))
    queries = [f"w{i}" for i in rng.integers(0, 1733, rows - 1)] + [syn0[9]]
    want = model.find_synonyms_batch(queries, 10)
    whole = np.asarray(scan._cosine_batch(
        model._full0, model._norms, jnp.asarray(syn0[:rows]), 1700))
    seen = []
    real = scan._gather_topk_batch
    monkeypatch.setattr(scan, "_gather_topk_batch",
                        lambda *a: seen.append(a) or real(*a))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    block = np.asarray(scan._grouped_scores(
        model._full0, model._norms, jnp.asarray(syn0[:rows]), 1700, 128))
    assert block.shape == (rows, 1792)
    np.testing.assert_allclose(block[:, :1733], whole, rtol=0, atol=1e-6)
    assert np.isneginf(block[:, 1700:]).all()
    got = model.find_synonyms_batch(queries, 10)
    (_, _, ids, vectors, k, _, _), = seen
    padded = 1 if rows == 1 else -(-rows // 8) * 8
    assert ids.shape == (padded,) and vectors.shape == (padded, 16)
    assert (ids[rows - 1:] == -1).all() and not vectors[rows:].any()
    assert len(got) == rows
    for g, w in zip(got, want):
        assert [word for word, _ in g] == [word for word, _ in w]
        np.testing.assert_allclose([s for _, s in g], [s for _, s in w],
                                   rtol=0, atol=1e-6)
    model.stop()
    # the inner jits' traces took the TPU's branch and are cached by shape:
    # they must not outlive the patch (another file lowers 1,733 rows too)
    jax.clear_caches()


@pytest.mark.parametrize("batch", ["words_exclude_themselves", "vectors"])
def test_two_stage_replies_are_the_single_top_k_s(batch, monkeypatch):
    """Through ``find_synonyms_batch`` over a vocabulary the two stages
    take: a word query asks for num + 1 and leaves itself out, a vector
    query keeps every neighbour; both as the single ``lax.top_k`` replies."""
    import jax
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops import scan
    rng = np.random.default_rng(8)
    syn0 = rng.standard_normal((1700, 16)).astype(np.float32)
    syn0[[40, 900, 1699]] = 0.0
    vocab = Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(1700)], np.ones(1700, np.int64))
    model = Word2VecModel(vocab, jnp.asarray(syn0))
    queries = (["w3", "w1699", "w3", "w128"] if batch.startswith("words") else
               [syn0[7] * 2.0, np.zeros(16, np.float32), syn0[1698] + 0.5])
    assert scan._topk_group(1700, 11) == 128
    got = model.find_synonyms_batch(queries, 10)

    def single(syn0, norms, ids, block, k, valid_rows, partitioned):
        return jax.lax.top_k(scan._cosine_batch(
            syn0, norms, scan._query_block(syn0, ids, block), valid_rows), k)

    monkeypatch.setattr(scan, "_gather_topk_batch", single)
    assert got == model.find_synonyms_batch(queries, 10)
    for q, reply in zip(queries, got):
        assert len(reply) == 10
        if isinstance(q, str):
            assert q not in [word for word, _ in reply]
    model.stop()


def test_unknown_word_dispatches_nothing(monkeypatch):
    from glint_word2vec_tpu.models import word2vec as w2v
    model, _ = _scan_model("float32")
    calls = []
    # the name the model calls: its own module's
    monkeypatch.setattr(w2v, "_topk_dispatch", lambda *a: calls.append(a))
    # the unknown word sits in the second chunk: the first is not sent either
    with pytest.raises(KeyError, match="zzz not in vocabulary"):
        model.find_synonyms_batch(["w1", "w2", "w3", "zzz"], 2, chunk=2)
    assert calls == []
    model.stop()


def test_block_dtype_is_what_stacking_gave():
    """An all-word batch scans in the table's dtype (the benchmark's
    bfloat16 control rides on it); a vector query promotes the block."""
    import jax
    import jax.numpy as jnp
    from glint_word2vec_tpu.ops.scan import _query_block
    table = jnp.ones((8, 4), jnp.bfloat16)
    ids = jnp.asarray([2, -1], jnp.int32)
    words = jax.eval_shape(lambda t, i: _query_block(t, i, None), table, ids)
    mixed = jax.eval_shape(lambda t, i, b: _query_block(t, i, b), table, ids,
                           jnp.zeros((2, 4), jnp.float32))
    assert words.dtype == jnp.bfloat16 and mixed.dtype == jnp.float32
    assert words.shape == mixed.shape == (2, 4)


@pytest.mark.parametrize("table,partitioned", [("float32", False),
                                               ("sharded", True)])
def test_row_sharded_table_takes_the_gather(table, partitioned, monkeypatch):
    """What the program reads rows with follows the table it is handed: Q
    slices of a table on one device; of a row-partitioned one, each shard's
    slices of its own rows and one [Q, D] all-reduce. No program gathers
    from the table, and the partitioned one all-gathers neither the table
    nor anything V (or V / n) wide: the candidates, [Q, n * k]."""
    import re
    from glint_word2vec_tpu.ops import scan
    model, _ = _mesh_model((1, 4)) if partitioned else _scan_model(table)
    seen = []
    real = scan._gather_topk_batch
    monkeypatch.setattr(
        scan, "_gather_topk_batch",
        lambda *a: seen.append(a) or real(*a))
    model.find_synonyms_batch(["w1", "w2"], 3)
    (syn0, norms, ids, block, k, valid_rows, shards), = seen
    assert (shards is not None) is partitioned
    assert block is None and ids.dtype == np.int32
    hlo = real.lower(syn0, norms, ids, block, k, valid_rows, shards
                     ).compile().as_text()
    ops = [line for line in hlo.splitlines()
           if re.search(r" (all-gather|gather|all-to-all)(-start)?\(", line)]
    rows = syn0.shape[0] // (4 if partitioned else 1)
    wide = [line for line in ops for dims in re.findall(
                r"[fs]\d+\[([\d,]+)\]", line.split(" metadata=")[0])
            if any(int(d) >= rows // 2 for d in dims.split(","))]
    assert wide == []
    assert (" all-reduce(" in hlo or " all-reduce-start(" in hlo) is partitioned
    if partitioned:
        gathered = [line for line in ops if "all-gather" in line]
        assert gathered and all(f"[2,{4 * k}]" in line or f"[4,2,{k}]" in line
                                or f"[4,{k},2]" in line for line in gathered)
    model.stop()


# -- a table partitioned by rows: one program under shard_map ------------------------

_MESH_ROWS = 12003      # 12,008 with the mesh's padding rows; 3,002 a shard of 4
_MESHES = [(1, 2), (1, 4), (1, 8), (2, 2)]
_MESH_TABLE = {}


def _mesh_table():
    """12,003 words x 16 dims: a zero-norm row, and one row planted on three
    shards of every mesh (rows 100, 7000 and 11990: ties across shards)."""
    if not _MESH_TABLE:
        rng = np.random.default_rng(12)
        syn0 = rng.standard_normal((_MESH_ROWS, 16)).astype(np.float32)
        syn0[11] = 0.0
        syn0[7000] = syn0[11990] = syn0[100]
        _MESH_TABLE["syn0"] = syn0
        _MESH_TABLE["vocab"] = Vocabulary.from_words_and_counts(
            [f"w{i}" for i in range(_MESH_ROWS)], np.ones(_MESH_ROWS, np.int64))
    return _MESH_TABLE["vocab"], _MESH_TABLE["syn0"]


def _mesh_model(mesh):
    """The table on ``mesh`` (data, model), or on one device for None."""
    import jax.numpy as jnp
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    vocab, syn0 = _mesh_table()
    if mesh is None:
        return Word2VecModel(vocab, jnp.asarray(syn0)), syn0
    return Word2VecModel(vocab, syn0, plan=make_mesh(*mesh)), syn0


def _mesh_batches(syn0):
    rng = np.random.default_rng(13)
    vec = [rng.standard_normal(16).astype(np.float32) for _ in range(2)]
    return {
        # (queries, num)
        "words": (["w5", "w9000", "w5", "w3001"], 10),
        "vectors": ([syn0[4] * 3.0, vec[0], np.zeros(16, np.float32)], 10),
        "mixed": (["w5", vec[1], "w11999", syn0[9]], 10),
        # the planted row's copies score 1.0 on three shards: lower row first
        "ties_across_shards": (["w100", "w7000", syn0[100] * 2.0], 10),
        # every score 0: the lowest rows of the whole table, all on shard 0
        "zero_norm_word": (["w11", "w12"], 10),
        "last_shard_last_row": ([f"w{_MESH_ROWS - 1}", "w11990"], 10),
        "one_word": (["w6001"], 3),
    }


def _assert_same_replies(got, want):
    """Ids equal, ties included; scores equal to 1e-6 (the CPU's matmul
    gives a row other last bits in a block of another width)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert [x for x, _ in g] == [x for x, _ in w]
        np.testing.assert_allclose([s for _, s in g], [s for _, s in w],
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("oracle", ["one_device", "host_rank"])
@pytest.mark.parametrize("mesh", _MESHES, ids=lambda m: f"{m[0]}x{m[1]}")
@pytest.mark.parametrize("batch", [
    "words", "vectors", "mixed", "ties_across_shards", "zero_norm_word",
    "last_shard_last_row", "one_word"])
def test_sharded_scan_is_the_one_device_programs(batch, mesh, oracle):
    """``Word2VecModel(plan=make_mesh(d, n))`` answers with the one-device
    program's replies, ties included: the table never gathered, each shard
    ranked by its own two stages (1,501 to 6,004 rows a shard, in runs of
    128), the mesh's padding rows never returned. ``host_rank``: the same
    replies from the one-device table's whole score block ranked on the host
    (``_row_by_row``), which shares no selection with either program."""
    from glint_word2vec_tpu.ops import scan
    one, syn0 = _mesh_model(None)
    queries, num = _mesh_batches(syn0)[batch]
    want = (one.find_synonyms_batch(queries, num) if oracle == "one_device"
            else _row_by_row(one, queries, num, 128, "host_rank"))
    model, _ = _mesh_model(mesh)
    assert model._full0.shape[0] == 12008
    counts = scan._scan_counts(model._full0, num + 1)
    rows = 12008 // mesh[1]
    assert counts["shards"] == mesh[1]
    assert counts["merge_rows"] == mesh[1] * (num + 1)
    assert counts["topk_rows"] == scan._topk_rows(rows, num + 1) <= rows
    assert scan._topk_group(rows, num + 1) == 128
    got = model.find_synonyms_batch(queries, num)
    _assert_same_replies(got, want)
    if batch == "ties_across_shards":
        assert [w for w, _ in got[0][:2]] == ["w7000", "w11990"]
        assert [w for w, _ in got[2][:3]] == ["w100", "w7000", "w11990"]
    if batch == "zero_norm_word":
        assert [w for w, _ in got[0]] == [f"w{i}" for i in range(10)]
    for reply in got:
        assert all(int(w[1:]) < _MESH_ROWS for w, _ in reply)
    one.stop()
    model.stop()


@pytest.mark.parametrize("mesh", [(1, 8), (2, 2)], ids=lambda m: f"{m[0]}x{m[1]}")
def test_sharded_scan_with_k_over_a_shards_rows(mesh):
    """More neighbours asked for than a shard has rows (26 and 104 of 208),
    which one ``lax.top_k`` ranks: every shard hands over all of its rows
    and the merge ranks them."""
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    one, syn0 = _scan_model("float32")
    vocab = one.vocab
    model = Word2VecModel(vocab, syn0, plan=make_mesh(*mesh))
    for queries, num in (["w5", "w202", syn0[9]], 150), (["w7"], 300):
        _assert_same_replies(model.find_synonyms_batch(queries, num),
                             one.find_synonyms_batch(queries, num))
    one.stop()
    model.stop()


@pytest.mark.parametrize("mesh", [(1, 4), (2, 2)], ids=lambda m: f"{m[0]}x{m[1]}")
def test_sharded_scan_keeps_two_batches_in_flight(mesh):
    """Both halves over a mesh, as the serve batcher runs them: two batches
    begun before either is finished, finished on other threads, each the
    batch call's replies to the last bit."""
    import threading
    model, syn0 = _mesh_model(mesh)
    batches = _mesh_batches(syn0)
    first_q, num = batches["mixed"]
    second_q, _ = batches["ties_across_shards"]
    want = [model.find_synonyms_batch(q, num) for q in (first_q, second_q)]
    begun = [model.find_synonyms_begin(q, num) for q in (first_q, second_q)]
    out = [None, None]

    def finish(i):
        out[i] = model.find_synonyms_finish(begun[i])

    threads = [threading.Thread(target=finish, args=(i,)) for i in (1, 0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert out == want
    model.stop()


def test_multiply_on_a_mesh_is_one_sharded_matvec():
    """Every shard multiplies the rows it holds, over a vocabulary that does
    not divide too: the table is never gathered (the ``syn0`` view's slice
    along the partitioned rows would), the padding rows' zeros are dropped."""
    import jax
    model, syn0 = _mesh_model((1, 4))
    v = syn0[3] * 0.5
    got = model.multiply(v)
    assert got.shape == (_MESH_ROWS,)
    np.testing.assert_allclose(got, syn0 @ v, rtol=0, atol=1e-5)
    hlo = jax.jit(lambda t, x: t @ x).lower(model._full0, v).compile().as_text()
    assert " all-gather(" not in hlo and f"f32[{12008 // 4}]" in hlo
    model.stop()


# -- the batch call's two halves (find_synonyms_begin / find_synonyms_finish) --------


@pytest.mark.parametrize("finished", ["as_begun", "last_begun_first"])
@pytest.mark.parametrize("table", ["float32", "sharded"])
@pytest.mark.parametrize("batch", ["words_repeated", "vectors", "mixed",
                                   "one_word", "longer_than_chunk"])
def test_the_two_halves_are_the_batch_call_bit_for_bit(batch, table, finished):
    """``find_synonyms_batch(q, k)`` is ``finish(begin(q, k))``: the same
    replies to the last bit with the second half on another thread (the
    serve batcher's completer) and a second call begun between the two.
    ``last_begun_first``: the second call is finished WHOLE before the first
    is touched (``begun=`` permits it; the serve completer relies on results
    being bound to their ``pending``, not to the order of the begins), a call
    of several chunks enqueueing its later parts while the other's wait."""
    import threading
    model, syn0 = _scan_model(table)
    queries, num, chunk = _scan_batches(syn0)[batch]
    want = model.find_synonyms_batch(queries, num, chunk=chunk)
    first = model.find_synonyms_begin(queries, num, chunk=chunk)
    second = model.find_synonyms_begin(queries[::-1], num, chunk=chunk)
    out = {}

    def finish(name, pending):
        out[name] = model.find_synonyms_finish(pending)

    order = (("first", first), ("second", second))
    threads = [threading.Thread(target=finish, args=(n, p))
               for n, p in (order if finished == "as_begun" else order[::-1])]
    for t in threads:
        t.start()
        if finished == "last_begun_first":
            t.join(timeout=60)
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert out["first"] == want
    assert [[w for w, _ in r] for r in out["second"]] == [
        [w for w, _ in r] for r in want[::-1]]
    model.stop()


def test_a_call_of_many_chunks_keeps_two_parts_in_flight():
    """Begin enqueues two parts; finish enqueues the next as it fetches one:
    never a third score block, whatever the number of chunks."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    model, _ = _scan_model("float32")
    queries = [f"w{i}" for i in range(10)]
    want = [model.find_synonyms(q, 3) for q in queries]
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        pending = model.find_synonyms_begin(queries, 3, chunk=2)
        assert len(pending.parts) == 5 and len(pending.results) == 2
        # the halves' own spans: a program compiled on the way is a pinned
        # span (xla.compile) in the ring beside them
        begun = [e["name"] for e in tracer.events()
                 if e["name"].startswith("serve.")]
        got = model.find_synonyms_finish(pending)
        names = [e["name"] for e in tracer.events()
                 if e["name"].startswith("serve.")]
    finally:
        tracer.configure(enabled=False)
        tracer.clear()
    assert begun == ["serve.row_fetch", "serve.scan_enqueue", "serve.scan_enqueue"]
    fetch_enqueue_reply = ["serve.result_fetch", "serve.scan_enqueue", "serve.reply_build"]
    assert names[3:] == fetch_enqueue_reply * 3 + [
        "serve.result_fetch", "serve.reply_build"] * 2
    assert [[w for w, _ in r] for r in got] == [[w for w, _ in r] for r in want]
    model.stop()


def test_the_halves_spans_name_the_callers_span_on_any_thread():
    """``finish`` on another thread records its spans under the span that
    enclosed ``begin``: by id, not by that thread's stack."""
    import threading
    from glint_word2vec_tpu.obs.spans import default_tracer
    tracer = default_tracer()
    model, _ = _scan_model("float32")
    tracer.configure(enabled=True)
    tracer.clear()
    try:
        with tracer.span("caller") as outer:
            pending = model.find_synonyms_begin(["w1", "w2"], 3)
        t = threading.Thread(target=model.find_synonyms_finish, args=(pending,))
        t.start()
        t.join(timeout=60)
        evs = tracer.events()
    finally:
        tracer.configure(enabled=False)
        tracer.clear()
    assert not t.is_alive()
    parents = {e["name"]: e["parent"] for e in evs
               if e["name"].startswith("serve.")}
    assert parents == {name: outer.id for name in (
        "serve.row_fetch", "serve.scan_enqueue", "serve.result_fetch",
        "serve.reply_build")}
    # the model's norms, made at its first scan, are a pinned span under the
    # caller's too (and in the ring while something records)
    assert {e["name"]: e["parent"] for e in evs}["model.norms"] == outer.id
    model.stop()
