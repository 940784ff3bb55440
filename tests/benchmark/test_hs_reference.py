"""The plain hierarchical-softmax reference: its own tree on a hand-worked
vocabulary of six words, its loss on a pair worked by hand, the rule for a
node many pairs share; a rehearsal of kind ``train_hs``; the lower-precision
control failing every norm limit of ``correct`` on every leaf; and runs whose
tree, paths or step are broken underneath coming out not correct."""

import importlib
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loader  # noqa: E402
from kinds import train_hs as kind  # noqa: E402
from reference import hs_ref  # noqa: E402

MANIFEST = loader.load_manifest(ROOT)
CELL = "skipgram-hs-3m-300.train"
SIX = [9, 7, 5, 5, 2, 1]


def _cell() -> dict:
    return loader.resolve(MANIFEST, CELL)


def test_reference_imports_nothing_of_the_program():
    source = open(os.path.join(BENCH, "reference", "hs_ref.py")).read()
    assert "glint_word2vec_tpu" not in source.split('"""', 2)[2]
    assert "jax.value_and_grad" in source and '"highest"' in source
    # its tree is its own loop, not the package's
    assert "def create_binary_tree" in source and "huffman" not in source


def test_reference_tree_of_six_words_by_hand():
    """word2vec.c's two cursors on 9 7 5 5 2 1: (1, 2) -> node 6 = 3; (3, 5) ->
    node 7 = 8 (the node of 3 is less than the word of 5, so it is taken
    first and the word is the ``1`` child); (5, 7) -> node 8 = 12; (8, 9) ->
    node 9 = 17; (12, 17) -> the root."""
    parent, binary = hs_ref.create_binary_tree(SIX)
    assert parent[:10] == [9, 8, 8, 7, 6, 6, 7, 9, 10, 10]
    assert binary[:10] == [1, 1, 0, 1, 1, 0, 0, 0, 0, 1]
    paths = [hs_ref.word_path((parent, binary), w) for w in range(6)]
    assert paths == [([4, 3], [1, 1]), ([4, 2], [0, 1]), ([4, 2], [0, 0]),
                     ([4, 3, 1], [1, 0, 1]), ([4, 3, 1, 0], [1, 0, 0, 1]),
                     ([4, 3, 1, 0], [1, 0, 0, 0])]
    assert sum(c * len(p) for c, (p, _) in zip(SIX, paths)) == 69      # a heap's cost


def _six_word_batch():
    tree = hs_ref.create_binary_tree(SIX)
    rng = np.random.default_rng(0)
    syn0 = rng.normal(size=(6, 4)).astype(np.float32)
    syn1 = rng.normal(size=(5, 4)).astype(np.float32)
    centers = np.array([0, 3, 3, 5, 1], np.int32)
    contexts = [4, 0, 1, 2, 5]
    points = np.zeros((5, 4), np.int32)
    codes = np.zeros((5, 4), np.int32)
    lengths = np.zeros(5, np.int32)
    for i, x in enumerate(contexts):
        p, c = hs_ref.word_path(tree, x)
        points[i, :len(p)], codes[i, :len(c)], lengths[i] = p, c, len(p)
    return syn0, syn1, centers, points, codes, lengths


def test_reference_loss_and_update_of_pairs_by_hand():
    syn0, syn1, centers, points, codes, lengths = _six_word_batch()

    def sigmoid(x):
        return 1.0 / (1.0 + np.exp(-x))

    loss = 0.0
    want0, want1 = syn0.astype(np.float64), syn1.astype(np.float64)
    for i, c in enumerate(centers):
        for d in range(lengths[i]):
            node, bit = points[i, d], codes[i, d]
            f = float(syn0[c].astype(np.float64) @ syn1[node].astype(np.float64))
            loss -= np.log(sigmoid((1 - 2 * bit) * f))
            g = 1 - bit - sigmoid(f)                        # word2vec.c's g, less alpha
            want0[c] += 0.1 * g * syn1[node]
            want1[node] += 0.1 * g * syn0[c]
    got0, got1, mean = hs_ref.hs_step(syn0, syn1, centers, points, codes, lengths, 0.1)
    np.testing.assert_allclose(got0, want0, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got1, want1, rtol=2e-6, atol=2e-6)
    assert abs(mean - loss / 5) < 1e-6 * loss
    # a slot past a path's end is never read
    points[0, lengths[0]:], codes[0, lengths[0]:] = 3, 1
    again0, again1, _ = hs_ref.hs_step(syn0, syn1, centers, points, codes, lengths, 0.1)
    np.testing.assert_array_equal(again0, got0)
    np.testing.assert_array_equal(again1, got1)


def test_reference_rule_for_a_node_many_pairs_share():
    syn0, syn1, centers, points, codes, lengths = _six_word_batch()
    m = hs_ref.node_pairs(5, points, lengths)
    assert m.tolist() == [2, 2, 2, 3, 5]                     # the root is in every path
    plain0, plain1, _ = hs_ref.hs_step(syn0, syn1, centers, points, codes, lengths, 0.1)
    got0, got1, _ = hs_ref.hs_step(syn0, syn1, centers, points, codes, lengths, 0.1,
                                   max_node_pairs=2)
    np.testing.assert_array_equal(got0, plain0)              # syn0's side: never
    scale = np.minimum(1.0, 2 / m)[:, None]
    np.testing.assert_allclose(np.asarray(got1) - syn1,
                               (np.asarray(plain1) - syn1) * scale, rtol=1e-5, atol=1e-7)


def test_tiny_rehearsal_of_the_kind_is_correct(capsys):
    out = kind.run(_cell(), seed=2**31 + 49, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    printed = capsys.readouterr().out
    assert "path table" in printed and "check path_table_mismatches: 0" in printed
    assert "check hs_nodes_mismatches: 0" in printed
    assert out["counters"]["hs_tree_s"] > 0
    assert set(out["end_to_end"]) == {"train_pairs_per_s", "train_loss_at_budget",
                                      "setup_s"}
    # the check's batches are the feed's: a piece's path once, not a pair's
    shapes = out["shapes"]
    assert 1.0 < shapes["syn1_rows_per_pair"] < 0.5 * shapes["path_nodes_per_pair"]
    assert 8.0 < shapes["path_nodes_per_pair"] < 15.0
    cost = importlib.import_module("costs.hs_step").cost(**shapes)
    assert cost["bytes"] > 0 and cost["flops"] > 0


def test_the_configuration_states_the_programs_rule():
    from glint_word2vec_tpu.ops import hs
    cfg = _cell()["config"]
    assert cfg["hs_max_node_pairs"] == hs.MAX_NODE_PAIRS
    assert str(hs.MAX_NODE_PAIRS) in cfg["assumed"]["shared_node_rule"]


@pytest.mark.parametrize("control", [False, True], ids=["program", "bfloat16_tables"])
def test_limits_pass_the_program_and_fail_bfloat16_tables(control):
    cell = _cell()
    limits = loader.sizes(cell["config"], True)["check"]["train"]
    got, = kind.check_readings(cell, [41], control=control, tiny=True)

    def limit(name):
        kind_of, _, leaf = name.removeprefix("fast_").partition(".")
        return kind.leaf_limit(limits, kind_of, leaf) if kind_of in limits else 0.0

    over = {n for n in got if got[n] > limit(n)}
    if not control:
        assert not over, (got, limits)
    else:
        # every norm limit fails, each leaf's: the top nodes' and the others'
        norms = {n for n in got if "norm_gap" in n}
        assert len(norms) == 12 and norms <= over, (sorted(norms - over), got)


def _one_code_bit_flipped(monkeypatch):
    """One bit of the tree: a child of the root becomes the other child."""
    from glint_word2vec_tpu.data import huffman
    real = huffman.huffman_parents

    def flipped(counts):
        parent, binary = real(counts)
        child = int(np.flatnonzero(parent[:-1] == parent.shape[0] - 1)[0])
        binary = binary.copy()
        binary[child] ^= 1
        return parent, binary

    monkeypatch.setattr(huffman, "huffman_parents", flipped)


def _paths_cut_short(monkeypatch):
    """ISSUE 37's "paths cut at 16", at the tiny vocabulary's scale: its
    longest path is 15 nodes, so the cut is at 9."""
    from glint_word2vec_tpu.data import huffman
    from glint_word2vec_tpu.data.subword import GROUP, NO_ROW
    real = huffman.build_path_table

    def cut(counts, at=9):
        table = real(counts)
        rows = table.rows.copy().reshape(-1)
        for w in np.flatnonzero(table.counts[:-1] > at):
            lo = int(table.offsets[w]) * GROUP
            rows[lo + at:lo + int(table.counts[w])] = NO_ROW
        return table._replace(rows=rows.reshape(table.rows.shape),
                              counts=np.minimum(table.counts, at))

    monkeypatch.setattr(huffman, "build_path_table", cut)


def _the_roots_update_dropped(monkeypatch):
    from glint_word2vec_tpu.ops import hs
    real = hs.hs_step_core

    def dropped(params, *args, **kw):
        moved, metrics = real(params, *args, **kw)
        root = args[4].counts.shape[0] - 3                   # node V − 2
        return moved._replace(
            syn1=moved.syn1.at[root].set(params.syn1[root])), metrics

    monkeypatch.setattr(hs, "hs_step_core", dropped)


def _the_sum_replaced_by_a_mean(monkeypatch):
    from glint_word2vec_tpu.ops import hs
    monkeypatch.setattr(hs, "MAX_NODE_PAIRS", 1)             # min(1, 1 / m_j)


@pytest.mark.parametrize("fault, caught_by", [
    (_one_code_bit_flipped, "path_table_mismatches"),
    (_paths_cut_short, "hs_nodes_mismatches"),
    (_the_roots_update_dropped, "first_gradient_norm_gap.top_nodes"),
    (_the_sum_replaced_by_a_mean, "first_gradient_norm_gap.top_nodes"),
], ids=lambda f: getattr(f, "__name__", f))
def test_a_run_broken_underneath_is_not_correct(fault, caught_by, monkeypatch, capsys):
    """The rest of a run, past the harness's look for a chip, with the tree,
    the paths or the timed step broken underneath."""
    fault(monkeypatch)
    out = kind.run(_cell(), seed=45, seconds=1.0, trace=False, tiny=True,
                   t_start=time.perf_counter())
    assert out["correct"] is False and out["attempted"] > 0
    failed = [l.split()[1].rstrip(":") for l in capsys.readouterr().out.splitlines()
              if l.startswith("check ") and l.endswith("FAILED")]
    assert caught_by in failed, failed
    if fault is _one_code_bit_flipped:
        # half the vocabulary's first bit is wrong: the losses say so too
        assert "loss_rel_gap" in failed
    if fault is _paths_cut_short:
        assert "path_table_mismatches" in failed
    if fault is _the_roots_update_dropped:
        # the other leaves' first step is right
        assert not [n for n in failed if "first_gradient" in n
                    and "top_nodes" not in n], failed
