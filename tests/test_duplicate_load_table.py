"""The duplicate load read from a table made once (ISSUE 53).

``Trainer._duplicate_load`` used to make a full float64 pass over the counts
for every ratio the AUTO subsample search asked about, 62 of them a
constructor. It reads two running sums at a ``searchsorted`` split now
(``data/pipeline.KeptCountTable``). The pass survives here as the plain
reference: the loads agree to rounding on every shape of vocabulary, the
search resolves the ratio it resolved, and the span says what was counted.
"""

import functools

import numpy as np
import pytest

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import KeptCountTable, keep_probabilities
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.obs.spans import default_tracer
from glint_word2vec_tpu.train.trainer import Trainer, _pairs_per_kept_token

WINDOW = 5
# 1e-12 (the search's lower probe) to 1e-2, and subsampling off
RATIOS = (0.0, 1e-12, 1e-11, 1e-10, 1e-9, 1e-8, 1e-7, 1e-6, 1e-5, 1e-4,
          6.17e-4, 1e-3, 3e-3, 1e-2)


def _zipf(v, scale=1e9, power=1.07):
    """The benchmark's ``zipf_counts`` shape, as the whole counts a
    vocabulary holds."""
    return np.maximum(scale / (np.arange(v) + 10.0) ** power, 5.0).astype(np.int64)


def _shuffled(counts):
    return counts[np.random.default_rng(7).permutation(counts.size)]


# name -> (counts, pairs_per_batch)
VOCABULARIES = {
    "zipf_200k": (_zipf(200_000), 65536),
    # a real vocabulary's tail: the last ~30,000 of 50,000 words all at 5,
    # and runs of equal whole counts all the way down to them
    "tail_of_ties": (np.maximum(1e5 / (np.arange(50_000) + 1.0), 5.0)
                     .astype(np.int64), 65536),
    "one_word": (np.array([1000], np.int64), 256),
    "two_equal_words": (np.array([700, 700], np.int64), 256),
    "uniform": (np.full(1000, 50, np.int64), 1024),
    "unsorted": (_shuffled(_zipf(5000, scale=1e7)), 8192),
    # one epoch supplies fewer pairs than a batch holds: real_pairs binds
    "corpus_under_a_batch": ((2000.0 / (np.arange(300) + 1.0)).astype(np.int64)
                             + 5, 65536),
}


def _direct_load(counts, train_words_count, ratio, pairs_per_batch, window):
    """The pass the table stands for, as ``_duplicate_load`` made it."""
    keep = keep_probabilities(counts, train_words_count, ratio)
    eff = np.asarray(counts, np.float64) * keep
    s = float(eff.sum())
    if s <= 0.0:
        return 0.0
    real_pairs = min(float(pairs_per_batch), s * _pairs_per_kept_token(window))
    return float(eff.max()) / s * real_pairs


def _vocab(counts):
    return Vocabulary.from_words_and_counts(
        [f"w{i}" for i in range(counts.size)], counts)


@functools.lru_cache(maxsize=None)
def _trainer(name):
    counts, pairs_per_batch = VOCABULARIES[name]
    cfg = Word2VecConfig(vector_size=8, window=WINDOW, negatives=2, min_count=1,
                         pairs_per_batch=pairs_per_batch, subsample_ratio=1e-4,
                         allow_unstable=True, seed=1)
    return Trainer(cfg, _vocab(counts))


@pytest.mark.parametrize("ratio", RATIOS, ids=lambda r: f"{r:g}")
@pytest.mark.parametrize("name", sorted(VOCABULARIES))
def test_the_tables_load_is_the_direct_passes(name, ratio):
    counts, pairs_per_batch = VOCABULARIES[name]
    want = _direct_load(counts, int(counts.sum()), ratio, pairs_per_batch, WINDOW)
    got = _trainer(name)._duplicate_load(ratio)
    assert want > 0
    assert got == pytest.approx(want, rel=1e-10, abs=0)
    if name == "unsorted":  # the order handed over does not matter
        table = KeptCountTable(np.sort(counts)[::-1], int(counts.sum()))
        assert _trainer(name)._load_table.kept(ratio) == table.kept(ratio)
    if name == "corpus_under_a_batch":
        kept = KeptCountTable(counts, int(counts.sum())).kept(ratio)[0]
        assert kept * _pairs_per_kept_token(WINDOW) < pairs_per_batch


@pytest.mark.parametrize("ratio,subsampled", [(1e-2, "none"), (1e-12, "every")])
def test_the_split_at_both_ends_of_the_vocabulary(ratio, subsampled):
    counts, pairs_per_batch = VOCABULARIES["zipf_200k"]
    total = int(counts.sum())
    under_one = int((keep_probabilities(counts, total, ratio) < 1.0).sum())
    assert under_one == {"none": 0, "every": counts.size}[subsampled]
    kept_sum, kept_max = KeptCountTable(counts, total).kept(ratio)
    eff = counts * keep_probabilities(counts, total, ratio)
    assert kept_sum == pytest.approx(float(eff.sum()), rel=1e-10)
    assert kept_max == float(eff.max())
    if subsampled == "none":  # nothing dropped: the counts themselves, exactly
        assert (kept_sum, kept_max) == (float(total), float(counts[0]))


def _search_with(load, configured=1e-3, target=Trainer._DUP_LOAD_TARGET):
    """``_bound_duplicate_channel``'s search over another load function: the
    two probes' bracket and the 60 geometric halvings."""
    lo, hi = 1e-12, configured
    assert load(hi) > Trainer._DUP_LOAD_REFUSE and load(lo) <= target
    for _ in range(60):
        mid = (lo * hi) ** 0.5
        if load(mid) > target:
            hi = mid
        else:
            lo = mid
    return lo


@pytest.mark.parametrize("words,pairs_per_batch",
                         [(20_000, 65536), (200_000, 65536), (20_000, 131072)])
def test_the_auto_ratio_is_the_one_the_direct_pass_resolves(words, pairs_per_batch):
    counts = _zipf(words, scale=1e8, power=1.05)
    total = int(counts.sum())
    cfg = Word2VecConfig(vector_size=8, window=WINDOW, min_count=5,
                         pairs_per_batch=pairs_per_batch, seed=1)  # ratio AUTO
    trainer = Trainer(cfg, _vocab(counts))
    want = _search_with(lambda r: _direct_load(
        counts, total, r, pairs_per_batch, WINDOW))
    assert want < 1e-3
    assert trainer.config.subsample_ratio == pytest.approx(want, rel=1e-9, abs=0)
    assert f"{trainer.config.subsample_ratio:.3g}" == f"{want:.3g}"


# a tiny vocabulary on a LARGE corpus: no ratio bounds the top word's share
UNBOUNDABLE = np.array([10**9, 9 * 10**8, 8 * 10**8], np.int64)


@pytest.mark.parametrize("counts,ratio", [
    (_zipf(20_000, scale=1e8, power=1.05), 1e-3),  # the first probe's exit
    (UNBOUNDABLE, None),                           # the second probe's exit
], ids=["explicit_ratio_over_the_boundary", "auto_ratio_nothing_bounds"])
def test_allow_unstable_leaves_the_ratio_at_both_exits(counts, ratio):
    kw = {} if ratio is None else {"subsample_ratio": ratio}
    cfg = Word2VecConfig(vector_size=8, pairs_per_batch=65536, min_count=5,
                         seed=1, **kw)
    with pytest.raises(ValueError, match="duplicate_scaling"):
        Trainer(cfg, _vocab(counts))
    trainer = Trainer(cfg.replace(allow_unstable=True), _vocab(counts))
    assert trainer.config.subsample_ratio == 1e-3
    assert trainer._duplicate_load(1e-3) > Trainer._DUP_LOAD_REFUSE


@pytest.mark.parametrize("form,pairs_per_batch,extra,want", [
    # (passes, evaluations) of every trainer.resolve_auto a constructor leaves:
    # the pool rule's, then one a _resolve_duplicate_channel
    ("lowered", 65536, {}, [(0, 0), (1, 62)]),
    ("held", 256, {}, [(0, 0), (1, 1)]),
    # a token feed resolves before it derives its keep table and the
    # constructor again after: the second finds the table and the ratio made
    ("lowered_token_feed", 65536, {"device_pairgen": True},
     [(0, 0), (1, 62), (0, 1)]),
    ("duplicate_scaling", 65536, {"duplicate_scaling": True}, [(0, 0), (0, 0)]),
])
def test_resolve_auto_counts_passes_and_evaluations(form, pairs_per_batch, extra,
                                                    want):
    tracer = default_tracer()
    tracer.configure(enabled=False)
    after = max((e["id"] for e in tracer.setup_events()), default=0)
    cfg = Word2VecConfig(vector_size=8, window=WINDOW, min_count=5, seed=1,
                         pairs_per_batch=pairs_per_batch, **extra)
    trainer = Trainer(cfg, _vocab(_zipf(20_000, scale=1e8, power=1.05)))
    spans = [e["args"] for e in tracer.setup_events()
             if e["id"] > after and e["name"] == "trainer.resolve_auto"]
    assert [(a["passes"], a["evaluations"]) for a in spans] == want
    assert (trainer.config.subsample_ratio < 1e-3) == form.startswith("lowered")
    # _stability_warnings read it last and dropped it: one table a constructor
    # (none under duplicate_scaling, where nobody asks for the load)
    assert trainer._load_table is None
    assert trainer._auto_passes == (form != "duplicate_scaling")
