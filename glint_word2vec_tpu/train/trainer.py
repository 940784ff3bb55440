"""Synchronous training executor — the TPU-native replacement for the reference's
per-partition async loop (C7, mllib:392-433).

What the reference does with Spark partitions racing Hogwild-style against parameter
servers (2 RPC round-trips per 50-pair minibatch, 1-deep future pipelining, mllib:417-429),
this trainer does as one jitted, donated, sharded step over large fixed-shape batches:

- lr decay keeps the exact reference schedule: ``alpha = lr·(1 − words/total)`` floored at
  ``lr·1e-4``, recomputed from the subsampled-word clock (mllib:405-413), where
  ``total = num_iterations · train_words_count + 1`` (mllib:363).
- the training heartbeat mirrors the reference's every-10k-words log line
  (wordCount/alpha/fPlus, mllib:411-412) and adds loss + throughput.
- mid-training checkpointing (the reference has none — a numIterations run is
  all-or-nothing, SURVEY §5) via ``checkpoint_every_steps``.
- determinism: per-step keys are ``fold_in(root_key, global_step)`` — replacing the
  reference's XORShift-seeded async chaos, which made its results untestable numerically
  (SURVEY §4).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, replace as dc_replace
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.pipeline import epoch_batches, epoch_batches_cbow
from glint_word2vec_tpu.data.vocab import Vocabulary
from glint_word2vec_tpu.obs.spans import (
    TraceAnnotation, default_tracer, now as span_clock, pinned_call)
from glint_word2vec_tpu.ops.sampler import build_alias_table, sample_negatives_hash
from glint_word2vec_tpu.ops.sgns import (
    EmbeddingPair,
    Stabilizers,
    StepMetrics,
    cbow_step_core,
    cbow_step_shared_core,
    init_embeddings,
    sgns_step_core,
    sgns_step_shared_core,
)
from glint_word2vec_tpu.parallel.distributed import put_global
from glint_word2vec_tpu.parallel.mesh import (
    MeshPlan, make_mesh, pad_dim_to_lanes, pad_vocab_for_sharding)
from glint_word2vec_tpu.train import faults, feeds
from glint_word2vec_tpu.train.checkpoint import TrainState, save_model
from glint_word2vec_tpu.train.faults import NonFiniteParamsError

logger = logging.getLogger("glint_word2vec_tpu")


def _pairs_per_kept_token(window: int) -> float:
    """Analytic E[pairs emitted per kept token] under the reference's legacy
    asymmetric window (mllib:381-390): span b = nextInt(window) to the left and
    max(b − 1, 0) to the right. Ignores sentence-boundary clipping, so it
    OVERESTIMATES slightly — every caller (tokens-per-step sizing, heartbeat
    pair estimates, the duplicate-load stability bound) wants the conservative
    direction. Floored at 1e-3 so window=1 (zero expected pairs) never divides
    by zero."""
    b = np.arange(window, dtype=np.float64)
    return max(float(b.mean() + np.clip(b - 1, 0, None).mean()), 1e-3)


def _cbow_examples_per_kept_token(window: int) -> float:
    """Analytic P[a kept token trains a CBOW example] under the legacy
    asymmetric window: the b = nextInt(window) = 0 draw yields zero context
    (and so no example), hence (window−1)/window. Sentence-boundary clipping
    is ignored (slight overestimate — heartbeat display only; the banded feed
    settles exact totals from the scanned metrics at end of run). Floored like
    :func:`_pairs_per_kept_token`."""
    return max((window - 1) / window, 1e-3)


def _center_run_cap(window: int, batch: int) -> Tuple[int, ...]:
    """Static row caps of the step's coalesced syn0 scatter
    (ops/sgns.scatter_add_by_runs), an ascending ladder the step takes the
    first fitting rung of, batch by batch; () = do not build it. The pair feed
    emits a kept token's pairs consecutively, so a batch holds one center run
    per token that emits any pair: (window−1)/window of the kept tokens over
    :func:`_pairs_per_kept_token` pairs each, 0.25 runs a pair at window 5
    (0.262 measured: sentence ends clip windows). The LAST rung is that share
    with 40% of room, in eighths of the batch (24,576 of 65,536 at window 5):
    the estimate knows nothing of the corpus, and one of short sentences
    (queries, titles) makes more runs a pair. The scatter is priced by the
    rows it is handed, padding too, so a tight rung stands before it
    (:func:`_run_ladder`: 18,432). Where a run holds two pairs or fewer on
    average (window ≤ 2) nothing is built."""
    runs_per_pair = (_cbow_examples_per_kept_token(window)
                     / _pairs_per_kept_token(window))
    if runs_per_pair > 0.5 or batch < 8:
        return ()
    eighth = batch // 8
    return _run_ladder(runs_per_pair * batch, batch,
                       -(-int(1.4 * runs_per_pair * batch) // eighth) * eighth)


def _last_rung(caps: Tuple[int, ...]) -> int:
    """The roomy cap of a ladder alone, 0 = none built: what the rows whose
    scatter takes one cap are handed (hierarchical softmax, subword)."""
    return caps[-1] if caps else 0


def _run_ladder(heads: float, batch: int, roomy: int) -> Tuple[int, ...]:
    """``(tight, roomy)``: before a coalesced scatter's ``roomy`` cap a rung
    for the ``heads`` expected of a batch with 15% of room, to the NEAREST
    unit (:func:`_nearest_units`, and for its reason: every seed compiles one
    program): 2,100 feed batches of five seeds hold 16,901-17,405 center
    heads and 16,425-16,806 (V = 3M) / 16,635-17,141 (10M) context heads
    under 18,432 of 65,536 (my CPU count, PERF.md §6, PR 58). A batch over it
    pays the roomy rung's price, as every batch did before. One rung where the
    tight one would not be under the roomy one, or the batch has no units."""
    tight = _nearest_units(heads, batch, 1.15)[0] if batch >= 32 else 0
    return (tight, roomy) if 0 < tight < roomy else (roomy,)


# pairs in a piece of a context run (ops/sgns.run_sums makes one shifted add
# for each beyond the first): named, with the cap's room, by the chip (PERF.md
# §6, PR 30)
_CONTEXT_MAX_RUN = 6


def _kept_token_distribution(counts: np.ndarray, train_words_count: int,
                             subsample_ratio: float) -> Optional[np.ndarray]:
    """p_w of a token the subsampling kept being word w (float64 [V]); None
    where it keeps no token."""
    from glint_word2vec_tpu.data.pipeline import keep_probabilities
    kept = np.asarray(counts, np.float64) * keep_probabilities(
        counts, train_words_count, subsample_ratio)
    total = kept.sum()
    return kept / total if total > 0 else None


def _expected_heads(counts: np.ndarray, train_words_count: int,
                    subsample_ratio: float, draws: float, entries: float,
                    max_run: int) -> Optional[float]:
    """Heads expected where ``entries`` entries, each carrying the word of one
    of ``draws`` kept tokens, are sorted by word and cut every ``max_run``:
    the tokens are drawn from the kept-token distribution p, so
    Σ 1 − (1 − p_w)^draws distinct words, and a word expected in more entries
    than a run holds (``entries`` · p_w) adds a piece per run's length of
    them. None where subsampling keeps no token."""
    p = _kept_token_distribution(counts, train_words_count, subsample_ratio)
    return None if p is None else float(
        _heads_by_word(p, draws, entries, max_run).sum())


def _heads_by_word(p: np.ndarray, draws: float, entries: float,
                   max_run: int) -> np.ndarray:
    """:func:`_expected_heads` word by word (float64 [V]): the chance the
    word is drawn at all, and a piece per run's length of its entries where
    it is expected in more than a run holds."""
    per_word = entries * p
    return (-np.expm1(draws * np.log1p(-np.minimum(p, 1 - 1e-12)))
            + np.where(per_word > max_run, per_word / max_run, 0.0))


def _context_run_cap(counts: np.ndarray, train_words_count: int,
                     subsample_ratio: float, window: int,
                     batch: int) -> Tuple[int, ...]:
    """Static row caps of the step's coalesced syn1 scatter
    (ops/sgns.scatter_add_by_runs on the batch sorted by context), a ladder as
    :func:`_center_run_cap`'s; () = do not build it. Unlike center runs,
    context runs are a property of the corpus: sorted by context a batch
    holds one run per distinct context word, cut every
    :data:`_CONTEXT_MAX_RUN` pairs (:func:`_expected_heads`: its contexts are
    its kept tokens, ``batch`` / :func:`_pairs_per_kept_token` of them, over
    ``batch`` pairs). At V = 3M / 10M that reads 16,218 / 16,472 where feed
    batches hold 16,440-17,050 (sentence ends clip windows, so a batch holds
    ~5% more tokens). The LAST rung has 20% of room, in sixteenths of the
    batch (20,480 of 65,536), the tight one before it 15% to the nearest unit
    (:func:`_run_ladder`: 18,432). An estimate over half the batch builds
    nothing."""
    heads = None if batch < 16 else _expected_heads(
        counts, train_words_count, subsample_ratio,
        batch / _pairs_per_kept_token(window), batch, _CONTEXT_MAX_RUN)
    if heads is None:
        return ()
    sixteenth = batch // 16
    cap = -(-int(1.2 * heads) // sixteenth) * sixteenth
    return _run_ladder(heads, batch, cap) if cap <= batch // 2 else ()


# tokens in a piece of a word's run in a banded CBOW block sorted by word:
# named, with the caps' room and the break-even, by the chip (PERF.md §6, PR 46)
_TOKEN_MAX_RUN = 6


def _token_run_caps(counts: np.ndarray, train_words_count: int,
                    subsample_ratio: float, tokens: int,
                    window: int) -> Tuple[int, int]:
    """Static row caps ``(syn0's, syn1's)`` of the banded CBOW step's two
    coalesced token scatters (ops/cbow_banded.py ``token_runs``: the block's
    ``tokens`` kept tokens sorted by word inside the step, one row per piece
    of a word's run), (0, 0) = do not build them. Every slot of a block is a
    kept token, so sorted by word it holds one run per distinct word, cut
    every :data:`_TOKEN_MAX_RUN` (:func:`_expected_heads` with the block's
    tokens as draws and as entries): syn0's rows. syn1's are the runs of the
    slots that train an example, :func:`_cbow_examples_per_kept_token` of
    them. At V = 3M that reads 35,338 and 29,053 where 550 feed blocks of
    five seeds hold 34,555-35,246 and 28,187-28,851 pieces: 15% of room to
    the NEAREST 32nd of the block (as :func:`_nearest_units` rounds and for its
    reason: every seed compiles one program; a block's count moves ±1%),
    40,960 and 32,768 of 65,546. About half the rows, not a quarter as SGNS
    contexts: a coalesced scatter still wins far above
    :func:`_context_run_cap`'s half-batch (sort, row gather, run sums and
    compaction cost what ~16,000 rows do at this run length), so the rule
    builds nothing only where syn0's cap passes 0.75 of the block (a flat
    vocabulary, every token another word)."""
    if tokens < 32:
        return 0, 0
    live = tokens * _cbow_examples_per_kept_token(window)
    heads = [_expected_heads(counts, train_words_count, subsample_ratio,
                             n, n, _TOKEN_MAX_RUN) for n in (tokens, live)]
    if heads[0] is None:
        return 0, 0
    unit = tokens // 32
    cap0, cap1 = (int(1.15 * h / unit + 0.5) * unit for h in heads)
    return (cap0, cap1) if cap0 <= 0.75 * tokens else (0, 0)


# run heads in a piece of a word (the subword row source's second level,
# ops/subword.py): named, with the cap's unit, by the chip (PERF.md §6, PR 34)
_WORD_MAX_RUN = 8


def _word_pieces_by_word(counts: np.ndarray, train_words_count: int,
                          subsample_ratio: float, window: int,
                          batch: int) -> Optional[np.ndarray]:
    """Word pieces a batch's center runs are expected to make, word by word
    (float64 [V]; None where subsampling keeps no token), sorted by word and
    cut every :data:`_WORD_MAX_RUN` heads (:func:`_heads_by_word`): a batch
    holds one center run per kept token that emits a pair, ``batch`` · runs a
    pair of them (:func:`_center_run_cap` has the share), and a run has one
    head."""
    p = _kept_token_distribution(counts, train_words_count, subsample_ratio)
    runs = batch * (_cbow_examples_per_kept_token(window)
                    / _pairs_per_kept_token(window))
    return None if p is None else _heads_by_word(p, runs, runs, _WORD_MAX_RUN)


def _word_pieces(counts: np.ndarray, train_words_count: int,
                 subsample_ratio: float, window: int, batch: int) -> float:
    """:func:`_word_pieces_by_word` summed; every pair its own piece where
    subsampling keeps no token."""
    pieces = _word_pieces_by_word(counts, train_words_count, subsample_ratio,
                                  window, batch)
    return float(batch) if pieces is None else float(pieces.sum())


def _word_cap(counts: np.ndarray, train_words_count: int,
              subsample_ratio: float, window: int, batch: int,
              run_cap: int) -> int:
    """Static capacity of the subword row source's per-word form
    (ops/subword.py: one list per distinct center word of the batch, not one
    per center run), 0 = do not build it. :func:`_word_pieces` with 20% of
    room, in 32nds of the batch: at wiki.en's shape (V = 2,519,370, Zipf
    counts, the AUTO subsample) it reads 10,100 where 2,000 feed batches of
    five seeds hold 10,200-10,637 pieces (sentence ends clip windows, so a
    batch holds ~4% more runs; their lists 235,178-247,787 live slots:
    :func:`_word_slot_cap`), 12,288 of 65,536. Over 0.8 of ``run_cap`` (a flat
    distribution: every center another word) the second level saves nothing
    and is not built."""
    if not run_cap or batch < 32:
        return 0
    unit = batch // 32
    cap = -(-int(1.2 * _word_pieces(counts, train_words_count, subsample_ratio,
                                    window, batch)) // unit) * unit
    return cap if cap <= 0.8 * run_cap else 0


def _nearest_units(live: float, slots: int,
                   room: float = 1.2) -> Tuple[int, int]:
    """``live`` expected live slots of a block of ``slots`` (32 or more) with
    20% of room (``room``: 1.2), to the NEAREST unit, and the unit: the power
    of two at or under a 32nd of the block's slots, so 15-25% of room. Nearest
    and not up: the estimate moves a fraction of a percent with the seed's
    strings, and rounding up gives some seeds a unit more, another program
    (PERF.md §6, PR 36)."""
    unit = 1 << ((slots // 32).bit_length() - 1)
    return int(room * live / unit + 0.5) * unit, unit


def _live_slot_cap(live: float, slots: int) -> int:
    """Static capacity of a list scatter that sorts its block's ``slots`` by
    row inside the step and takes the live prefix (ops/subword.py), 0 = do not
    build it: :func:`_nearest_units` of the ``live`` ones expected; where the
    cut saves under a fifth of the slots (a vocabulary whose lists are full)
    it is not built."""
    if slots < 32:
        return 0
    cap, _ = _nearest_units(live, slots)
    return cap if cap <= 0.8 * slots else 0


def _slot_cap(counts: np.ndarray, train_words_count: int,
              subsample_ratio: float, list_rows: np.ndarray, tokens: int,
              slots: int) -> int:
    """Static capacity of a CBOW token block's list scatter (ops/subword.py:
    the block's ``slots`` = tokens · max_groups · 8 slots sorted by row inside
    the step and cut to the live ones), 0 = do not build it. A block's live
    slots are its tokens' list lengths (``list_rows`` [V], the row table's
    own counts): ``tokens`` · Σ p_w · list_rows[w] over the kept-token
    distribution, through :func:`_live_slot_cap`. At cc.en.300's shape
    326,700-331,300 are expected over the benchmark's seeds where feed blocks
    hold 329,900-332,600: 393,216 = 12 units of 1,048,736 slots for every
    seed, where rounding UP gives 12 units to some seeds and 13 to others, two
    programs 0.64 ms a step apart (PERF.md §6, PR 36)."""
    p = _kept_token_distribution(counts, train_words_count, subsample_ratio)
    if p is None:
        return 0
    live = tokens * float(p @ np.asarray(list_rows, np.float64)[:p.shape[0]])
    return _live_slot_cap(live, slots)


def _word_slot_cap(counts: np.ndarray, train_words_count: int,
                   subsample_ratio: float, window: int, batch: int,
                   list_rows: np.ndarray, slots: int) -> int:
    """Static capacity of the per-word form's list scatter (ops/subword.py:
    the ``slots`` = word_cap · max_groups · 8 slots of the word heads' block
    sorted by row inside the step and cut to the live ones), 0 = do not
    build it. A piece lists its word's rows once, so the block's live slots
    are Σ pieces_w · list_rows[w] (:func:`_word_pieces_by_word`, the sum
    :func:`_word_cap` takes; ``list_rows`` [V], the row table's own counts),
    through :func:`_live_slot_cap`. At wiki.en's shape 231,800-233,000 are
    expected over the benchmark's seeds, 278,528 = 34 units of 8,192 of the
    block's 491,520 slots for every seed, where 2,000 feed batches of five
    seeds hold 235,178-247,787 (medians 240,800-242,000: sentence ends clip
    windows, so a batch holds ~4% more runs than the estimate, as
    :func:`_word_cap` found of the pieces; my CPU count, PR 47): the largest
    is 11% under the capacity, and a batch over it takes the whole form."""
    pieces = _word_pieces_by_word(counts, train_words_count, subsample_ratio,
                                  window, batch)
    if pieces is None:
        return 0
    live = float(pieces @ np.asarray(list_rows, np.float64)[:pieces.shape[0]])
    return _live_slot_cap(live, slots)


def _tail_cap(counts: np.ndarray, train_words_count: int,
              subsample_ratio: float, list_rows: np.ndarray, tokens: int) -> int:
    """Static capacity of a CBOW token block's tail gather (ops/subword.py:
    every token's first group of rows gathered densely, the later groups of
    the tokens that have them as a second block of this many tokens a pass;
    a block with more takes another pass), 0 = do not build it. A block's tails are its tokens whose word lists more rows
    than a group holds (``list_rows`` [V], the row table's own counts):
    ``tokens`` · Σ p_w · [list_rows[w] > GROUP] over the kept-token
    distribution, with 20% of room, to the NEAREST unit (a 32nd of the block's
    tokens, and at least one), as :func:`_nearest_units` rounds and for its reason:
    every seed of the benchmark compiles one program. At cc.en.300's shape
    (n-grams of 5: a word of 10 letters or more) 3,306-3,317 are expected over
    the benchmark's seeds where feed blocks hold 3,107-3,505: 4,096 = two
    units of 2,048 of the block's 65,546 tokens (PERF.md §4, PR 43). Lists of
    one group have no tails, and where more than a quarter of the tokens have
    one the second block saves too little: not built, and the gather reads
    every slot of the block."""
    from glint_word2vec_tpu.data.subword import GROUP
    p = _kept_token_distribution(counts, train_words_count, subsample_ratio)
    if p is None or tokens < 32:
        return 0
    tails = tokens * float(
        p @ (np.asarray(list_rows)[:p.shape[0]] > GROUP).astype(np.float64))
    if not 0 < tails <= tokens / 4:
        return 0
    unit = tokens // 32
    return max(int(1.2 * tails / unit + 0.5), 1) * unit


# pairs in a piece of a context word (the hierarchical-softmax step's per-word
# form, ops/hs.py: a piece's path is gathered once and contracted with its
# pairs)
_HS_MAX_RUN = 8


def _hs_caps(counts: np.ndarray, train_words_count: int,
             subsample_ratio: float, window: int, batch: int,
             lengths: np.ndarray, slots_per_word: int) -> Tuple[int, int]:
    """Static capacities ``(word_cap, slot_cap)`` of the hierarchical-softmax
    step's per-word form (ops/hs.py), (0, 0) = do not build it.

    Sorted by context a batch holds one piece per distinct context word and,
    for a word expected in more pairs than a piece holds, a piece per
    :data:`_HS_MAX_RUN` of them (:func:`_heads_by_word`);
    a piece lists its word's path once, ``lengths[w]`` live slots (the path
    table's own counts). ``word_cap`` is the expected pieces with 20% of room
    in 32nds of the batch; ``slot_cap`` the expected live slots with 20% of
    room, to the nearest unit (:func:`_nearest_units`: the power of two at
    or under a 32nd of the block's slots). A piece count over half the batch
    (contexts that hardly repeat) saves too little to build, and a batch
    expected to hold no whole piece (a window wider than the batch) none."""
    p = _kept_token_distribution(counts, train_words_count, subsample_ratio)
    if p is None or batch < 32:
        return 0, 0
    pieces = _heads_by_word(p, batch / _pairs_per_kept_token(window), batch,
                            _HS_MAX_RUN)
    unit = batch // 32
    word_cap = -(-int(1.2 * pieces.sum()) // unit) * unit
    slots = word_cap * slots_per_word
    if word_cap > batch // 2 or slots < 32:
        return 0, 0
    live = float(pieces @ np.asarray(lengths, np.float64)[:p.shape[0]])
    slot_cap, slot_unit = _nearest_units(live, slots)
    return word_cap, max(min(slot_cap, slots), slot_unit)


class StepChoice(NamedTuple):
    """One row of the step selection matrix (:func:`select_step`)."""

    core: Callable   # the ops-level step the row runs — what names the row
    # (params, batch, negatives, alpha) -> (params, StepMetrics); ``batch`` is
    # the dict the row's chunk body builds
    step: Callable
    # (K, B) -> one chunk's negatives; None: the step samples nothing
    neg_shape: Optional[Callable[[int, int], Tuple[int, ...]]]
    # (max_run, cap) where syn0's update goes to the scatter by center runs:
    # the cap an ascending ladder on the shared-pool SGNS row, whose step
    # takes a rung per batch; one int on the subword and path rows
    center_runs: Optional[Tuple[int, Union[int, Tuple[int, ...]]]]
    # the same for syn1's context update, by runs of the batch sorted by context
    context_runs: Optional[Tuple[int, Union[int, Tuple[int, ...]]]] = None
    # (max_run, syn0's cap, syn1's cap) where the banded CBOW step's two token
    # scatters go by runs of the block's tokens sorted by word
    token_runs: Optional[Tuple[int, int, int]] = None
    # the forward gathers go by the same runs (one row a piece over the model
    # axis, expanded on each chip): the last row on a mesh with a model axis
    assemble_by_runs: bool = False


def select_step(cfg: Word2VecConfig, plan: MeshPlan, feed_segments: int,
                context_cap: int, stabilizers: Optional[Stabilizers],
                with_metrics: bool, subword_shape=None,
                hs_shape=None,
                token_caps: Tuple[int, int] = (0, 0)) -> StepChoice:
    """The step selection matrix: which update one configuration trains with.
    Every legal combination is one row, read top to bottom; what is on no row
    config.__post_init__ refuses at construction, never silently downgrades
    (P = negative_pool, n = negatives, nd = the mesh's data degree):

      cbow   cbow_update  loss  P    duplicate_scaling  step_lowering  → core                      negatives
      -----  -----------  ----  ---  -----------------  -------------  --------------------------  ----------
      True   "banded"     ns    > 0  False              gspmd          cbow_step_banded_core       [K, P]
      True   "scatter"    ns    > 0  False              gspmd          cbow_step_shared_core       [K, P]
      True   "scatter"    ns    = 0  any                gspmd          cbow_step_core              [K, B, n]
      False  —            hs    = 0  False              gspmd          hs_step_core                none
      False  —            ns    = 0  any                gspmd          sgns_step_core              [K, B, n]
      False  —            ns    > 0  False              "shard_map"    make_shard_map_sgns_step    [K, P]
                                                        sync_every>1   (the same, windowed)        [K, nd·P]
      False  —            ns    > 0  any                gspmd          sgns_step_shared_core       [K, P]
                                                                       (+ center_runs,
                                                                       context_runs and
                                                                       assemble_by_runs, below)

    Three options ride a row or are one, each on one device and each with its
    table as jit arguments of the chunk (``_step_extra``):

      option                 its row                                 refused beside (config)
      ---------------------  --------------------------------------  -------------------------------------
      subword                the last (the CENTER's row source,      cbow "scatter", P = 0, "shard_map", a
                             host pair feed) or the first (a token   mesh over 1x1, device_pairgen,
                             BLOCK's, token feed):                   duplicate_scaling, sharded_checkpoint,
                             ``subword=(table, shape)``,             max_row_norm, row_l2,
                             ops/subword.py                          norm_watch="recover"
      cbow_position_weights  the first, with or without subword: a   every other row; sharded_checkpoint
                             third leaf ``EmbeddingPair.pos``
                             [2·window, D], the window summed by
                             taps in place of two prefix sums
      loss="hs"              its own (the fourth): the context's     cbow, subword, device_pairgen,
                             PATH as the output side, ops/hs.py;     "shard_map", a mesh over 1x1,
                             no sampler, no alias tables, no         duplicate_scaling, sharded_checkpoint,
                             negatives array (``neg_shape`` None)    fused_logits, bf16_chain, every
                                                                     stabilizer, n != 0, P > 0

    ``context_cap`` is :func:`_context_run_cap` of the trainer's vocabulary
    (a ladder; the last row takes it whole, the subword row its last rung),
    ``stabilizers`` is the trainer's state (None = all off), ``with_metrics``
    the twin; the rows without a ``with_metrics`` form have one twin.
    ``subword_shape`` is the trainer's :class:`..ops.subword.SubwordShape`
    and ``hs_shape`` its :class:`..ops.hs.HsShape` (None where the model is
    not subword, not hierarchical softmax). ``token_caps`` is
    :func:`_token_run_caps` of the trainer's vocabulary and block: the first
    row's ``token_runs`` where one program sees the block whole (no data
    axis), as ``context_cap`` is the last row's ``context_runs``."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    logits_dtype = jnp.dtype(cfg.logits_dtype)
    n, pool = cfg.negatives, cfg.negative_pool

    def shared_pool(K, B):
        return (K, pool)

    def per_example(K, B):
        return (K, B, n)

    if cfg.cbow and cfg.cbow_update == "banded":
        from glint_word2vec_tpu.ops.cbow_banded import cbow_step_banded_core
        token_runs = None
        if plan.num_data == 1 and all(token_caps):
            token_runs = (_TOKEN_MAX_RUN, *token_caps)

        def step(params, batch, negatives, alpha):
            band = batch["band"]
            return cbow_step_banded_core(
                params, batch["tokens"],
                band.left.reshape(-1), band.right.reshape(-1),
                band.center.reshape(-1), band.token.reshape(-1),
                negatives, alpha, n, cfg.window, cfg.sigmoid_mode,
                compute_dtype, logits_dtype, with_metrics,
                stabilizers=stabilizers,
                subword=(None if subword_shape is None else
                         (batch["subword_table"], subword_shape)),
                token_runs=token_runs)

        return StepChoice(cbow_step_banded_core, step, shared_pool, None,
                          token_runs=token_runs)

    if cfg.cbow and pool > 0 and not cfg.duplicate_scaling:
        def step(params, batch, negatives, alpha):
            return cbow_step_shared_core(
                params, batch["centers"], batch["contexts"], batch["ctx_mask"],
                batch["mask"], negatives, alpha, n,
                cfg.sigmoid_mode, compute_dtype, logits_dtype, with_metrics,
                stabilizers=stabilizers)

        return StepChoice(cbow_step_shared_core, step, shared_pool, None)

    if cfg.cbow:
        # per-example CBOW (pool resolved to 0: small batches, or
        # duplicate_scaling — config refuses an explicit pool beside it)
        def step(params, batch, negatives, alpha):
            return cbow_step_core(
                params, batch["centers"], batch["contexts"], batch["ctx_mask"],
                batch["mask"], negatives, alpha,
                cfg.sigmoid_mode, compute_dtype, cfg.duplicate_scaling,
                stabilizers=stabilizers)

        return StepChoice(cbow_step_core, step, per_example, None)

    # syn0's update, one scatter row per center run (the step chooses per
    # batch; ops/sgns.scatter_add_by_runs), where one program sees the batch
    # whole: a batch split over a data axis or fed in per-process segments
    # cuts runs at every seam. The shared-pool row's (the ladder of caps) and
    # the path row's (its last rung: a switch around that step's in-place
    # scatter is A12's to compile, ops/hs.py)
    caps = ()
    if plan.num_data == 1 and feed_segments == 1:
        caps = _center_run_cap(cfg.window, cfg.pairs_per_batch)

    if cfg.loss == "hs":
        from glint_word2vec_tpu.ops.hs import hs_step_core
        runs = (2 * cfg.window, _last_rung(caps)) if caps else None

        def step(params, batch, negatives, alpha):
            return hs_step_core(
                params, batch["centers"], batch["contexts"], batch["mask"],
                alpha, batch["path_table"], hs_shape, cfg.sigmoid_mode,
                compute_dtype, with_metrics, center_runs=runs)

        return StepChoice(hs_step_core, step, None, runs)

    fused, chain = cfg.fused_logits, cfg.bf16_chain
    if pool == 0:
        def step(params, batch, negatives, alpha):
            return sgns_step_core(
                params, batch["centers"], batch["contexts"], batch["mask"],
                negatives, alpha, cfg.sigmoid_mode, compute_dtype,
                cfg.duplicate_scaling, stabilizers=stabilizers,
                fused=fused, bf16_chain=chain)

        return StepChoice(sgns_step_core, step, per_example, None)

    if cfg.step_lowering == "shard_map":
        # the explicit schedule (ops/sgns_shard.py, docs/sharding.md):
        # owner-local gathers + ONE model-axis psum forward, owner-local
        # scatters + ONE data-axis payload all_gather backward — zero update
        # bytes over the model axis (HLO-audited, tools/collectives.py)
        from glint_word2vec_tpu.ops.sgns_shard import make_shard_map_sgns_step
        step = make_shard_map_sgns_step(
            plan.mesh, n, cfg.sigmoid_mode, compute_dtype, logits_dtype,
            with_metrics, stabilizers=stabilizers, fused=fused,
            bf16_chain=chain, sync_every=cfg.sync_every)

        # local-SGD window (docs/sharding.md §Local-SGD): the step consumes
        # [k, B]-stacked batches and [k, nd·P] negatives — each data shard a
        # DISJOINT [k, P] lattice slice, so the merged run is deterministic
        # per (seed, mesh, k)
        def window_pools(K, B):
            return (K, plan.num_data * pool)

        return StepChoice(make_shard_map_sgns_step, step,
                          shared_pool if cfg.sync_every == 1 else window_pools,
                          None)

    # syn1's, one row per run of the pairs sorted by context: the step sorts,
    # so the feed's order and its segments do not matter, a data axis does
    context_runs = None
    if plan.num_data == 1 and context_cap:
        context_runs = (_CONTEXT_MAX_RUN, context_cap)

    if subword_shape is not None:
        # one cap a scatter: a switch beside that step's own stands at the
        # edge of the chip's memory (A11's to compile)
        context_runs = context_runs and (_CONTEXT_MAX_RUN,
                                         _last_rung(context_cap))

        def step(params, batch, negatives, alpha):
            return sgns_step_shared_core(
                params, batch["centers"], batch["contexts"], batch["mask"],
                negatives, alpha, n, cfg.sigmoid_mode, compute_dtype,
                False, logits_dtype, with_metrics,
                stabilizers=stabilizers, fused=fused, bf16_chain=chain,
                context_runs=context_runs,
                subword=(batch["subword_table"], subword_shape))

        return StepChoice(
            sgns_step_shared_core, step, shared_pool,
            (subword_shape.max_run, subword_shape.head_cap), context_runs)

    # the forward gathers by the same runs, where the tables' rows lie over a
    # model axis: the gathered rows are then assembled by an all-reduce, which
    # carries one row a piece in place of one a pair (ops/sgns.gather_by_runs;
    # 35% of the bytes at window 5). On one chip nothing is assembled and the
    # expansion costs what the smaller gather saves (PERF.md §6, PR 49): the
    # step there is the one it was
    runs = (2 * cfg.window, caps) if caps else None
    assemble = bool(plan.num_model > 1 and runs and context_runs)

    def step(params, batch, negatives, alpha):
        return sgns_step_shared_core(
            params, batch["centers"], batch["contexts"], batch["mask"],
            negatives, alpha, n, cfg.sigmoid_mode, compute_dtype,
            cfg.duplicate_scaling, logits_dtype, with_metrics,
            stabilizers=stabilizers, fused=fused, bf16_chain=chain,
            center_runs=runs, context_runs=context_runs,
            assemble_by_runs=assemble)

    return StepChoice(sgns_step_shared_core, step, shared_pool, runs,
                      context_runs, assemble_by_runs=assemble)


@dataclass
class HeartbeatRecord:
    words: int
    alpha: float
    loss: float
    mean_f_pos: float
    pairs_per_sec: float
    # --- extended telemetry (round 11, docs/observability.md). Defaults keep
    # pre-round-11 constructors valid; every field lands in the JSONL sink ---
    global_step: int = -1
    host_wait_s: float = 0.0       # host-side wait since the previous heartbeat
    dispatch_s: float = 0.0        # dispatch time since the previous heartbeat
    norms: Optional[dict] = None   # fused health-probe channels (obs/probe.py)
                                   # when the probe ran this round: per-matrix
                                   # max/mean/p99 row norm + frac_over, plus
                                   # update_mag (delta of mean_norm between
                                   # consecutive probes — a cheap update-
                                   # magnitude proxy needing no extra pass)
    # --- mid-run recovery state (round 13): before this, only run_start/
    # run_end carried them — telemetry_tail and the blackbox had to replay
    # the whole sink file to know whether a live run had already recovered
    recoveries: int = 0            # recoveries performed so far this fit
    lr_scale: float = 1.0          # effective lr multiplier this heartbeat's
                                   # chunk actually DISPATCHED under
    phases: Optional[dict] = None  # per-phase log2 duration histograms over
                                   # this heartbeat window (obs/phases.py)
                                   # when time attribution is armed
    # --- local-SGD window metadata (config.sync_every, docs/sharding.md
    # §Local-SGD): which merge cadence this run dispatched under and how many
    # delta-merge rounds have completed — a consumer replaying telemetry can
    # tell a merged carry from a mid-window one would-be state (there is
    # none: dispatch boundaries ARE merge boundaries, which is exactly what
    # these fields let it verify)
    sync_every: int = 1            # merge cadence (1 = fully synchronous)
    merge_round: int = -1          # completed delta-merge rounds at this
                                   # heartbeat (global_step // sync_every);
                                   # -1 when sync_every == 1 (no windows)


class Trainer:
    """Owns the sharded embedding pair and runs the synchronous SGNS/CBOW loop."""

    @pinned_call("trainer.init", lambda self: dict(
        words=self.vocab.size,
        mesh=f"{self.plan.num_data}x{self.plan.num_model}"))
    def __init__(
        self,
        config: Word2VecConfig,
        vocab: Vocabulary,
        plan: Optional[MeshPlan] = None,
        params: Optional[EmbeddingPair] = None,
        train_state: Optional[TrainState] = None,
    ):
        """The whole of it is the pinned span ``trainer.init`` (obs/spans.py,
        docs/observability.md §4), and each of its phases a pinned child:
        ``trainer.resolve_auto``, ``sampler.alias_table``, ``params.place``,
        ``vocab.subword_table`` / ``vocab.huffman_tree``,
        ``trainer.capacities``, ``trainer.build_step``."""
        self._tracer = default_tracer()
        self.config = config
        self.vocab = vocab
        # the duplicate load's table (_duplicate_load makes it, the
        # constructor's last reader, _stability_warnings, drops it), the passes
        # over the counts made for it and the ratios evaluated from it: the
        # ``passes`` and ``evaluations`` of ``trainer.resolve_auto``
        self._load_table = None
        self._auto_passes = 0
        self._auto_evaluations = 0
        # vocab-scaled AUTO pool (EVAL.md round-5): config resolved the pool
        # without seeing the vocabulary; at > 500k words the measured safe
        # load band tightens 600 -> 160, so a still-AUTO pool re-resolves
        # upward here. Must run before anything reads config.negative_pool.
        with self._tracer.span("trainer.resolve_auto", pinned=True, passes=0,
                               evaluations=0):
            self._resolve_vocab_scaled_pool()
        config = self.config
        if plan is None:
            shape = config.mesh_shape or (config.num_data_shards, config.num_model_shards)
            n_avail = len(jax.devices())
            if shape[0] * shape[1] > n_avail:
                # never a silent 1x1 fallback: a run asked to shard that trains
                # unsharded reports numbers for a different system
                raise ValueError(
                    f"a {shape[0]}x{shape[1]} (data x model) mesh needs "
                    f"{shape[0] * shape[1]} devices but only {n_avail} are "
                    "available; lower num_data_shards/num_model_shards/"
                    "mesh_shape or pass an explicit plan")
            plan = make_mesh(*shape)
        self.plan = plan
        # design verdict, not a TODO (PERF.md §7): rows is the production
        # layout — it divides the per-update-row scatter bound by the mesh
        # size and owns whole rows for shard checkpoints; cols stays an
        # experimental single-host option for the per-pair-sampling regime.
        # Two guards: the pure-config half (whose construction twin lives in
        # config.__post_init__ — refusal parity, graftlint R8/graftcheck)
        # and the runtime half (process count, which config cannot see).
        if config.embedding_partition == "cols" and config.sharded_checkpoint:
            raise ValueError(
                "embedding_partition='cols' is experimental and single-host only: "
                "row-shards checkpoints need each process to own whole rows "
                "(design rationale: PERF.md §7); use 'rows'")
        if config.embedding_partition == "cols" and jax.process_count() > 1:
            raise ValueError(
                "embedding_partition='cols' is experimental and single-host only: "
                "multi-process runs need each process to own whole rows "
                "(design rationale: PERF.md §7); use 'rows'")
        if (config.step_lowering == "shard_map"
                and config.pairs_per_batch % plan.num_data):
            raise ValueError(
                f"step_lowering='shard_map' splits the batch over the data "
                f"axis with static shapes: pairs_per_batch="
                f"{config.pairs_per_batch} must be divisible by num_data="
                f"{plan.num_data}")
        self.padded_vocab = pad_vocab_for_sharding(vocab.size, plan.num_model)
        # rows of syn0: the vocabulary's, and where the model is subword the
        # bucket rows after them (ops/subword.py); syn1 has the vocabulary's
        self._syn0_rows = self.padded_vocab
        if config.subword:
            # runtime twin of config's mesh refusal: a plan handed in
            if plan.mesh.devices.size > 1:
                raise ValueError(
                    f"subword=True trains on one device, and the plan holds "
                    f"{plan.mesh.devices.size}: a word's listed rows would "
                    "live on other chips (no sharded row source yet)")
            self._syn0_rows = pad_vocab_for_sharding(
                vocab.size + config.subword_buckets, plan.num_model)
        # Pad the minor dim to the TPU lane width: D=300 rows are misaligned and row
        # gathers/scatters measurably slower than at 384. Padded columns are zero-init and
        # receive zero gradient (all products with the zero columns vanish), so they stay
        # zero and are sliced off on export.
        self.padded_dim = pad_dim_to_lanes(
            config.vector_size, config.pad_vector_to_lanes)
        self._emb_sharding = (plan.embedding_cols
                              if config.embedding_partition == "cols"
                              else plan.embedding)
        if config.embedding_partition == "cols" and self.padded_dim % plan.num_model:
            raise ValueError(
                f"embedding_partition='cols' needs the padded vector dim "
                f"{self.padded_dim} divisible by num_model={plan.num_model}")
        if config.loss == "hs":
            # runtime twin of config's mesh refusal: a plan handed in
            if plan.mesh.devices.size > 1:
                raise ValueError(
                    f"loss='hs' trains on one device, and the plan holds "
                    f"{plan.mesh.devices.size}: a path's nodes would live on "
                    "other chips (no sharded path side yet)")
            # no negatives are drawn: no alias table, and the chunk takes none
            self.table = self._table_prob = self._table_alias = None
            self._sampler_args: tuple = ()
        else:
            with self._tracer.span("sampler.alias_table", pinned=True,
                                   words=vocab.size):
                self.table = build_alias_table(
                    vocab.counts, config.sample_power,
                    workers=config.io_workers)
                # replicated device copies, passed into the jitted chunk as
                # ARGUMENTS every dispatch — closure-captured constants take a
                # catastrophically slow gather path on TPU (see ops/prng.py)
                tabs = put_global(plan.replicated,
                                  {"prob": np.asarray(self.table.prob),
                                   "alias": np.asarray(self.table.alias)})
            self._table_prob = tabs["prob"]
            self._table_alias = tabs["alias"]
            self._sampler_args = (self._table_prob, self._table_alias)
        # ``placed``: 0 where the tables came in padded and placed
        with self._tracer.span("params.place", pinned=True,
                               placed=1) as place:
            self._root_key = jax.random.key(config.seed)
            if params is None:
                params = init_embeddings(
                    self._syn0_rows, config.vector_size,
                    jax.random.fold_in(self._root_key, 0),
                    dtype=jnp.dtype(config.param_dtype))
                if config.subword:      # syn1 has the vocabulary's rows alone
                    params = EmbeddingPair(params.syn0,
                                           params.syn1[:self.padded_vocab])
            if config.cbow_position_weights and params.pos is None:
                # the model starts as plain CBOW: every position weighs one
                params = params._replace(pos=jnp.ones(
                    (2 * config.window, config.vector_size),
                    jnp.dtype(config.param_dtype)))
            if (params.pos is not None) != config.cbow_position_weights or (
                    params.pos is not None
                    and params.pos.shape[0] != 2 * config.window):
                raise ValueError(
                    f"cbow_position_weights={config.cbow_position_weights} "
                    f"with window={config.window} needs position weights of "
                    f"{2 * config.window} rows, and the params hold "
                    f"{None if params.pos is None else params.pos.shape}")
            # the carry's shardings: the tables by rows, the position weights
            # (a few rows every example reads) on every device
            self._params_sharding = EmbeddingPair(
                self._emb_sharding, self._emb_sharding,
                None if params.pos is None else plan.replicated)
            pos = params.pos
            if (isinstance(params.syn0, jax.Array)
                    and params.syn0.shape == (self._syn0_rows, self.padded_dim)
                    and params.syn0.dtype == jnp.dtype(config.param_dtype)
                    and params.syn0.sharding.is_equivalent_to(
                        self._emb_sharding, 2)):
                # already padded and placed (e.g. streamed in by
                # load_params_into_plan)
                self.params = params
                place.set(placed=0)
            else:
                params = self._pad_params(params)
                placed = put_global(
                    self._emb_sharding,
                    # every process computes the same deterministic init
                    # (same key), so the callback assembly is consistent
                    # across hosts
                    {"syn0": np.asarray(params.syn0),
                     "syn1": np.asarray(params.syn1)})
                self.params = EmbeddingPair(placed["syn0"], placed["syn1"])
            if pos is not None and not (
                    isinstance(pos, jax.Array)
                    and pos.shape == (2 * config.window, self.padded_dim)
                    and pos.dtype == jnp.dtype(config.param_dtype)
                    and pos.sharding.is_equivalent_to(plan.replicated, 2)):
                # lane-padded as the tables are, the padding exactly 0
                padded = np.zeros((2 * config.window, self.padded_dim),
                                  jnp.dtype(config.param_dtype))
                padded[:, :pos.shape[1]] = np.asarray(pos)
                pos = put_global(plan.replicated, {"pos": padded})["pos"]
            self.params = self.params._replace(pos=pos)
        self.state = train_state or TrainState()
        # additive checkpoint-metadata keys (train/checkpoint.py
        # extra_metadata) merged into EVERY save this trainer performs —
        # periodic and final alike. Owned by drivers above the trainer (the
        # continual loop records its vocab_lineage chain here); empty = the
        # pre-continual metadata, byte-identical.
        self.extra_checkpoint_meta: dict = {}
        # Chunk transfer layout (feeds.HostPairs): pairs ride in ONE packed
        # array per dispatch — through a narrow host→device link the per-transfer
        # overhead dominates, so fewer/larger puts win. Indices ship as uint16 when the
        # vocab allows (halves feed bytes; upcast on device is free).
        self._pair_dtype = np.uint16 if self.padded_vocab <= 65536 else np.int32
        if config.cbow:
            self._chunk_shardings = {"centers": plan.batch_stacked,
                                     "contexts": plan.ctx_stacked,
                                     "nctx": plan.batch_stacked}
        else:
            self._chunk_shardings = {"pairs": plan.pairs_stacked}
        # Sharded input feed (the repartition analog, mllib:345): each process
        # generates only its 1/N of the sentence stream; the global batch is assembled
        # from per-process segments by a per-round allgather (feeds.GatheredPairs). The
        # batch's B axis is composed of N per-process segments, each prefix-masked.
        self._feed_segments = 1
        if config.shard_input and jax.process_count() > 1:
            n = jax.process_count()
            if config.pairs_per_batch % n:
                raise ValueError(
                    f"shard_input=True needs pairs_per_batch divisible by the "
                    f"process count ({config.pairs_per_batch} % {n} != 0)")
            self._feed_segments = n
        # the subword row source (config.subword): every word's list of input
        # rows, built once and placed beside the tables at the end of
        # construction (_place_subword_table); the chunk takes it as
        # arguments (_step_extra)
        self._subword_shape = None
        self._step_extra: tuple = ()
        self.subword_table_time = 0.0
        # the hierarchical-softmax path table (config.loss="hs"): every word's
        # path through the vocabulary's Huffman tree, in the row table's
        # format and placed the same way (_place_path_table)
        self._hs_shape = None
        self.hs_tree_time = 0.0
        if config.loss == "hs" and self._feed_segments > 1:
            raise ValueError(
                "loss='hs' needs the batch whole in one program: a feed in "
                "per-process segments (shard_input on several processes) is "
                "not wired to the path side")
        if config.subword:
            if self._feed_segments > 1:
                raise ValueError(
                    "subword=True needs the batch whole in one program: a "
                    "feed in per-process segments (shard_input on several "
                    "processes) cuts center runs at every seam")
        # On-device pair generation (ops/pairgen.py): host ships raw token blocks,
        # the jitted step subsamples + windows them itself — same hash lattice, so
        # the pair stream is bit-identical to the host pipeline's.
        if config.device_pairgen:
            if config.cbow:
                raise ValueError("device_pairgen is skip-gram only (CBOW batches "
                                 "are grouped windows the device generator does "
                                 "not produce)")
            if config.window == 1:
                raise ValueError(
                    "device_pairgen with window=1 emits no pairs at all under the "
                    "reference's legacy asymmetric window (b = nextInt(1) = 0 "
                    "always, and the right bound is exclusive) — use window >= 2")
            self._init_token_block_feed(
                "device_pairgen",
                config.tokens_per_step or self._auto_tokens_per_step(),
                _pairs_per_kept_token(config.window))
            # ops/pairgen._cumsum_i32 is exact only while prefix sums stay below
            # 2^24 (f32 mantissa); the largest sum is T * (2*window - 1) pair counts
            if self._tokens_per_step * (2 * config.window - 1) >= 1 << 24:
                raise ValueError(
                    f"tokens_per_step={self._tokens_per_step} with window="
                    f"{config.window} overflows the device generator's exact-f32 "
                    f"prefix-sum bound (T * (2*window - 1) must stay below 2^24); "
                    "lower tokens_per_step or split the batch")
        # Banded CBOW (config.cbow_update="banded", ops/cbow_banded.py): rides
        # the same token-block feed plumbing as device_pairgen — the host packs
        # kept-token blocks, the jitted step derives window draws from the hash
        # lattice — but with a ±window halo overlap at block cuts
        # (pipeline.pack_halo_token_blocks) so chunk-edge windows are exact.
        # The config-level selection matrix already refused unsupported
        # combinations (duplicate_scaling/pool=0/window=1).
        self._banded_cbow = bool(config.cbow and config.cbow_update == "banded")
        self._block_halo = 0
        if self._banded_cbow:
            self._block_halo = config.window
            # core slots per segment block = examples per segment per step
            self._init_token_block_feed(
                "cbow_update='banded'",
                config.pairs_per_batch // self.plan.num_data
                + 2 * self._block_halo,
                _cbow_examples_per_kept_token(config.window))
        # bound the duplicate-overload divergence channel (EVAL.md measured
        # boundary): auto-lower an AUTO subsample_ratio or refuse an explicit
        # unstable one. Idempotent — the device-feed path already resolved it
        # before deriving its keep probabilities above.
        self._resolve_duplicate_channel()
        # resume continues the (seed, counter) PRNG lattice where the checkpoint left
        # off — restarting at 0 would redraw the run's opening negative-sample stream
        self.global_step = self.state.global_step
        self.pairs_trained = 0.0  # real (unmasked) pairs dispatched over this run
        from collections import deque
        # bounded ring (config.heartbeat_ring): pre-round-11 this was an
        # unbounded list — weeks-long runs leaked one record per heartbeat.
        # The full history persists in the telemetry sink file instead.
        self.heartbeats: "deque" = deque(maxlen=config.heartbeat_ring)
        # non-finite guardrail state (config.nonfinite_policy): a ring of the
        # last K good device-resident param snapshots plus small jitted probes,
        # all built lazily — a policy="none" run pays nothing
        self._snapshot_ring: "deque" = deque(maxlen=config.rollback_history)
        self.rollbacks_performed = 0
        # stabilization + auto-recovery state (docs/robustness.md escalation
        # ladder). _stabilizers starts from the config knobs but is TRAINER
        # state: a norm_watch="recover" firing may engage max_row_norm
        # mid-run (the step functions are rebuilt then). _lr_scale multiplies
        # the dispatched alphas (see _stage_dispatch_meta) — recovery backs
        # it off by config.recover_lr_backoff per firing; it persists across
        # fit() calls on this trainer (a recovered run's mitigation should
        # outlive the fit that needed it), while the recovery BUDGET resets
        # per fit like max_rollbacks.
        self._stabilizers = Stabilizers(
            max_row_norm=config.max_row_norm,
            update_clip=config.update_clip,
            row_l2=config.row_l2)
        self._lr_scale = 1.0
        self.recoveries_performed = 0
        self._health_fn: Optional[Callable] = None  # fused probe (obs/probe.py)
        self._copy_params_fn: Optional[Callable] = None
        self._poison_fn: Optional[Callable] = None  # scripted NaN injection
        self._scale_fn: Optional[Callable] = None   # scripted finite blowup
        # run-telemetry layer (docs/observability.md) — all lazy/no-op when
        # config.telemetry_path is empty and norm_watch is "off"
        from glint_word2vec_tpu.obs.watch import NormWatchdog
        # the open spans of a heartbeat round, outermost first (heartbeat,
        # then heartbeat.refill): begun in _finish_round, ended in
        # _after_dispatch; empty whenever nothing records
        self._round: List = []
        # a fit up to its first heartbeat (fit(), _record_first_heartbeat)
        self._first_beat: Optional[List[Optional[float]]] = None
        self._first_beat_ann = None
        self._telemetry = None
        if config.telemetry_path:
            from glint_word2vec_tpu.obs.sink import TelemetrySink
            self._telemetry = TelemetrySink(
                config.telemetry_path,
                rotate_bytes=config.telemetry_rotate_bytes)
        # flight recorder (obs/blackbox.py): exists only with telemetry on —
        # the dump path derives from telemetry_path. Feeding it is a deque
        # append per dispatch round; the dump itself only runs on fit death.
        self._blackbox = None
        if self._telemetry is not None:
            from glint_word2vec_tpu.obs.blackbox import FlightRecorder
            self._blackbox = FlightRecorder(
                config.telemetry_path + ".blackbox.json",
                config.blackbox_ring)
        # per-phase host time attribution (obs/phases.py): armed whenever
        # anything consumes it — the sink (heartbeat/run_end rollups) or the
        # live status endpoint. Disabled adds cost one attribute check.
        from glint_word2vec_tpu.obs.phases import PhaseAccumulator
        observing = self._telemetry is not None or config.status_port > 0
        self._phases = PhaseAccumulator(enabled=observing)
        self._statusd = None                 # obs/statusd.py, fit-scoped
        self._prev_sigterm = None            # saved handler while fit runs
        self._sigterm_installed = False      # see _install_run_signals
        # arm (or DISARM) the process-wide tracer's TELEMETRY recording for
        # this trainer — at construction, not only at fit start: the fit
        # paths build their feed iterators before _start_run_bookkeeping
        # runs, and the producer spans must observe the right state from the
        # start. Disarming matters as much as arming: a telemetry-off trainer
        # after a telemetry-on one in the same process (the overhead A/B's
        # off arm) must not keep recording spans into the shared ring. The
        # phase accumulator attaches under the same rule (spans tee durations
        # into it — obs/spans.py _PHASE_OF). A live jax.profiler trace arms
        # the spans whatever this says (obs/spans.py): nothing here can
        # switch that off.
        self._tracer.configure(enabled=observing)
        self._tracer.attach_phases(self._phases if observing else None)
        self.norm_watchdog = NormWatchdog(
            config.norm_watch, config.norm_watch_threshold,
            config.norm_watch_max, config.norm_watch_frac)
        self._last_probe_channels: Optional[dict] = None
        # At most ONE collective-bearing program may be in flight on a
        # multi-device CPU mesh: XLA:CPU collectives rendezvous across
        # per-device threads of a bounded shared pool, so when a SECOND
        # program reaches its collectives while the first is still at a
        # rendezvous, the two runs' blocked participants can starve each
        # other and everything stops at 0% CPU. Observed live on the forced
        # 8-device mesh (either step lowering, ~200-dispatch fits): the
        # racers were the producer-thread feed-touch program
        # (feeds._stage_to_device — its cross-shard reduction lowers to
        # collectives; now skipped on this backend) and the finiteness probe
        # (now dispatched only after draining the carry). This flag guards
        # both and gates _after_dispatch, which drains the carry after every
        # chunk so the invariant holds for the dispatch pipeline itself.
        # TPU/GPU execute programs in launch order on the device stream — no
        # gate, pipelining untouched.
        self._sync_collectives = (
            jax.default_backend() == "cpu" and plan.mesh.devices.size > 1)
        if config.subword:
            self._place_subword_table()
        if config.loss == "hs":
            self._place_path_table()
        with self._tracer.span("trainer.build_step", pinned=True):
            self._build_step_twins()

    # -- setup -------------------------------------------------------------------------

    def _init_token_block_feed(self, feature: str, tokens_per_step: int,
                               est_pairs_per_token: float) -> None:
        """Shared feed setup of the two token-block feeds (device_pairgen and
        banded CBOW): multi-process segment-ownership checks, duplicate-channel
        resolution BEFORE keep-probability derivation (an AUTO subsample may be
        lowered there; feature-specific shape errors fire before this runs),
        the replicated keep table, T, the chunk shardings, and the analytic
        pairs (or CBOW examples) a kept token makes: a round's estimate for
        the heartbeat's display, settled against the device's exact totals at
        the end of the fit. One owner so the two feeds cannot drift on these
        invariants."""
        config = self.config
        plan = self.plan
        if jax.process_count() > 1:
            if not config.shard_input:
                raise ValueError(
                    f"{feature} with multiple processes requires "
                    "shard_input=True (each process packs token blocks for "
                    "its own data segments; a replicated token feed would "
                    "have every process regenerate everything)")
            if plan.num_data % jax.process_count():
                raise ValueError(
                    f"{feature} across {jax.process_count()} processes "
                    f"needs the mesh data degree ({plan.num_data}) "
                    "divisible by the process count — each process produces "
                    "num_data/process_count token segments")
        Sd = plan.num_data
        if config.pairs_per_batch % Sd:
            raise ValueError(
                f"{feature} needs pairs_per_batch divisible by the data-"
                f"parallel degree ({config.pairs_per_batch} % {Sd} != 0)")
        self._resolve_duplicate_channel()
        from glint_word2vec_tpu.data.pipeline import keep_probabilities
        keep = keep_probabilities(
            self.vocab.counts, self.vocab.train_words_count,
            self.config.subsample_ratio).astype(np.float32)
        self._keep_host = keep
        kp = np.zeros(self.padded_vocab, np.float32)
        kp[:self.vocab.size] = keep
        self._keep_prob_dev = put_global(plan.replicated, {"k": kp})["k"]
        self._tokens_per_step = tokens_per_step
        self._est_pairs_per_token = est_pairs_per_token
        self._chunk_shardings = {"tokens": plan.tokens_stacked,
                                 "starts": plan.tokens_stacked,
                                 "obase": plan.tokens_stacked}

    def _auto_tokens_per_step(self) -> int:
        """Token slots per step for the device pair generator: targets ~93% pair-slot
        fill from the analytic per-kept-token pair rate E[window span] (boundary
        clipping at sentence edges is ignored, which *overestimates* the rate, so the
        realized fill lands safely below target instead of overflowing). A step's
        actual pair count concentrates tightly (std ≈ √T window-draw noise, <1% of B),
        so overflow drops stay rare; the trainer counts and reports them."""
        cfg = self.config
        # the packer subsamples host-side, so shipped tokens are KEPT tokens
        rate = _pairs_per_kept_token(cfg.window)
        T = int(np.ceil(0.93 * cfg.pairs_per_batch / self.plan.num_data / rate))
        return max(T, 64)

    def _put_row_table(self, table) -> tuple:
        """A row table (data/subword.SubwordRows: the subword lists, or the
        hierarchical-softmax paths) on the device, waited for: ``(offsets,
        rows, counts)``, the chunk's table arguments, the groups in whole
        units (data/subword.groups_in_whole_units has the reason)."""
        from glint_word2vec_tpu.data.subword import groups_in_whole_units
        groups = groups_in_whole_units(table.rows)
        placed = put_global(self.plan.replicated, {
            "offsets": table.offsets, "rows": groups, "counts": table.counts})
        jax.block_until_ready(placed)
        return placed["offsets"], placed["rows"], placed["counts"]

    def _place_subword_table(self) -> None:
        """Build the vocabulary's row table (data/subword.py) and put it on
        the device: the pinned span ``vocab.subword_table``, whose ``dur`` is
        ``subword_table_time``; the step's shape (ops/subword.py) takes the
        center-run capacity the plain step has and, under it, the word
        capacity :func:`_word_cap` derives from the counts and the slot
        capacity :func:`_word_slot_cap` derives from them and the lists'
        lengths; a CBOW token block's takes the slot capacity
        :func:`_slot_cap` and the tail capacity :func:`_tail_cap` derive."""
        from glint_word2vec_tpu.data.subword import build_subword_table
        cfg = self.config
        with self._tracer.span("vocab.subword_table", pinned=True,
                               words=self.vocab.size) as span:
            rows = build_subword_table(
                self.vocab.words, cfg.subword_min_n, cfg.subword_max_n,
                cfg.subword_buckets)
            self._step_extra = self._put_row_table(rows)
            span.set(slots=rows.slots)
        self.subword_table_time = span.dur
        with self._tracer.span("trainer.capacities", pinned=True):
            self._derive_subword_shape(rows)
        logger.info("subword table: %d words, %d slots, %s in %.2fs",
                    self.vocab.size, rows.slots, self._subword_shape,
                    self.subword_table_time)

    def _derive_subword_shape(self, rows) -> None:
        """The subword step's shape from the row table and the counts: the
        capacity derivations of :meth:`_place_subword_table`'s docstring."""
        from glint_word2vec_tpu.data.subword import GROUP
        from glint_word2vec_tpu.ops import subword as sw
        cfg = self.config
        if self._banded_cbow:
            # the row source of a token block (ops/cbow_banded.py): every
            # token slot of the block reads its own word's list; the lists'
            # scatter takes the live slots alone, and their gather the first
            # group of each and the few later ones, where the counts promise
            # few enough of them
            t = self._tokens_per_step
            kept = (self.vocab.counts, self.vocab.train_words_count,
                    cfg.subsample_ratio, rows.counts, t)
            self._subword_shape = sw.SubwordShape(
                rows.max_groups, 1, t,
                slot_cap=_slot_cap(*kept, t * rows.max_groups * GROUP),
                tail_cap=_tail_cap(*kept))
        else:
            # center runs as the plain step's (one head per run of a center's
            # pairs); where none are built every pair is its own head
            cap = (_last_rung(_center_run_cap(cfg.window, cfg.pairs_per_batch))
                   if self.plan.num_data == 1 else 0)
            # and under them one head per distinct word of the batch's
            # centers, where the vocabulary's counts promise fewer
            kept = (self.vocab.counts, self.vocab.train_words_count,
                    cfg.subsample_ratio, cfg.window, cfg.pairs_per_batch)
            word_cap = _word_cap(*kept, cap)
            # and of that block's slots the live ones alone to the scatter
            self._subword_shape = sw.SubwordShape(
                rows.max_groups, *((2 * cfg.window, cap) if cap
                                   else (1, cfg.pairs_per_batch)),
                _WORD_MAX_RUN, word_cap, slot_cap=_word_slot_cap(
                    *kept, rows.counts, word_cap * rows.max_groups * GROUP))

    def _place_path_table(self) -> None:
        """Build the vocabulary's Huffman tree and every word's path
        (data/huffman.py) and put the path table on the device: the pinned
        span ``vocab.huffman_tree``, whose ``dur`` is ``hs_tree_time``; the
        step's shape (ops/hs.py) takes the capacities :func:`_hs_caps`
        derives from the counts and the paths' lengths."""
        from glint_word2vec_tpu.data.huffman import build_path_table
        from glint_word2vec_tpu.data.subword import GROUP
        from glint_word2vec_tpu.ops.hs import HsShape
        cfg = self.config
        with self._tracer.span("vocab.huffman_tree", pinned=True,
                               words=self.vocab.size) as span:
            paths = build_path_table(self.vocab.counts)
            self._step_extra = self._put_row_table(paths)
            span.set(nodes=self.vocab.size - 1,
                     max_code_len=int(paths.counts.max()), slots=paths.slots)
        self.hs_tree_time = span.dur
        with self._tracer.span("trainer.capacities", pinned=True):
            word_cap, slot_cap = (0, 0) if self.plan.num_data > 1 else _hs_caps(
                self.vocab.counts, self.vocab.train_words_count,
                cfg.subsample_ratio, cfg.window, cfg.pairs_per_batch,
                paths.counts, paths.max_groups * GROUP)
        self._hs_shape = HsShape(paths.max_groups, _HS_MAX_RUN, word_cap,
                                 slot_cap)
        logger.info("huffman tree: %d words, code lengths up to %d, %d slots, "
                    "%s in %.2fs", self.vocab.size, int(paths.counts.max()),
                    paths.slots, self._hs_shape, self.hs_tree_time)

    def _pad_params(self, params: EmbeddingPair) -> EmbeddingPair:
        def pad(a, rows):
            a = jnp.asarray(a)
            row_pad = rows - a.shape[0]
            col_pad = self.padded_dim - a.shape[1]
            if row_pad or col_pad:
                a = jnp.pad(a, ((0, row_pad), (0, col_pad)))
            return a

        return EmbeddingPair(syn0=pad(params.syn0, self._syn0_rows),
                             syn1=pad(params.syn1, self.padded_vocab))

    def _stability_warnings(self, check_pool: bool = True) -> None:
        """Large synchronous batches can diverge through two per-step row-overload
        channels the reference's tiny async minibatches never hit (measured, EVAL.md):

        - POOL load ``B·n/P``: every pool row absorbs the negative gradient of all B
          pairs scaled by n/P. B=64k/P=64 (load 5120) trains to NaN at lr 0.025; the
          same run at P=256 (load 1280) is stable with the best quality of the sweep.
          The config default auto-scales the pool to load ≤ 600, so the generic
          warning fires only on explicit pool choices — but the round-5
          LARGE-VOCAB advisory (load > 300 at vocab > 500k, a measured finite-
          blowup region) also covers the auto-scaled default: at large
          vocabularies the default IS inside the measured danger zone.
        - DUPLICATE load ``B·max_word_share``: a frequent word's context occurrences
          scatter-add summed updates. With no subsampling the top Zipf word is ~1% of
          pairs (~650 summed updates at B=64k) and training explodes even at small
          pool loads; frequency subsampling (≈1e-4) or duplicate_scaling bounds it.
          This channel also hits the per-pair (negative_pool=0) paths — they get
          ``check_pool=False``.
        """
        cfg = self.config
        if cfg.duplicate_scaling:
            return  # mean-update semantics bound both channels by construction
        pool_load = (cfg.pairs_per_batch * cfg.negatives / cfg.negative_pool
                     if check_pool else 0.0)
        if pool_load > 300 and self.vocab.size > 500_000:
            # large-vocab advisory (EVAL.md round-5 ladder) — takes precedence
            # over the generic >2000 warning, whose "keep the load ~1300"
            # advice sits deep inside the measured large-vocab blowup region.
            # Mechanism: at 1.6M vocab a word serves in the pool only ~2x per
            # run, so each service's load-sized summed update is never
            # re-corrected — measured FINITE norm blowup (purity 0.99 -> 0.14,
            # no NaN) at load 640 over 120M words; load 160 (pool 2048) fixed
            # that collapse at the same lr and tames norm growth ~8x at 240M
            # (it delays the channel rather than eliminating it — EVAL.md).
            # The load <= 600 auto-rule is calibrated at 90k vocab; grow the
            # pool for large-vocabulary long runs.
            logger.warning(
                "negative-pool load %.0f with a %d-word vocabulary: large-vocab "
                "long runs measured a finite norm blowup in this region "
                "(EVAL.md round-5 ladder — purity collapse without NaN at load "
                "640; load 160 fixed that collapse and tames norm growth on "
                "longer runs); consider negative_pool >= %d (an AUTO pool "
                "scales itself to load <= 160 past 500k vocab — this one was "
                "set explicitly), or the stabilizer/watchdog knobs "
                "(max_row_norm, norm_watch='recover' — docs/robustness.md)",
                pool_load, self.vocab.size,
                128 * (-(-cfg.pairs_per_batch * cfg.negatives // (160 * 128))))
        elif pool_load > 2000:
            logger.warning(
                "pairs_per_batch*negatives/negative_pool = %.0f > 2000: pool-row "
                "updates this large can diverge at default learning rates — scale "
                "negative_pool with the batch (e.g. %d) to keep the load ~1300 "
                "(EVAL.md)", pool_load,
                max(64, int(cfg.pairs_per_batch * cfg.negatives / 1300)))
        dup_load = self._duplicate_load(cfg.subsample_ratio)
        self._load_table = None  # its last reader: three arrays of V doubles go
        if dup_load > 300:
            logger.warning(
                "expected duplicates of the most frequent word per %d-pair batch "
                "= %.0f > 300: summed scatter updates this dense can diverge — "
                "set subsample_ratio (~1e-4, recommended) or "
                "duplicate_scaling=True, or shrink pairs_per_batch (EVAL.md)",
                cfg.pairs_per_batch, dup_load)
        elif pool_load > 1000 and dup_load > 150:
            # the channels COMPOUND on frequent syn1 rows over long runs: B=64k/P=256
            # (pool 1280, dups ~260 — neither alone past its threshold) was stable on
            # a 17M-word corpus but NaN'd at 60M; either channel halved holds (EVAL.md)
            logger.warning(
                "pool load %.0f and top-word duplicate load %.0f are each below "
                "their individual divergence thresholds but compound on frequent "
                "rows over long runs (measured NaN at 60M words, EVAL.md) — for "
                "long runs grow negative_pool (load <= ~600) or shrink "
                "pairs_per_batch", pool_load, dup_load)

    def _duplicate_load(self, subsample_ratio: float) -> float:
        """Expected in-batch duplicates of the most frequent word under the given
        subsample ratio — the divergence channel's driving quantity (EVAL.md).

        The top word's share of the kept counts, ``max(c*keep) / sum(c*keep)``,
        times the batch's real pairs. Both come from a
        :class:`~glint_word2vec_tpu.data.pipeline.KeptCountTable` in O(log V):
        with ``y = ratio*T/c`` a keep probability is ``min(sqrt(y) + y, 1)``,
        so the words split at ONE count and the sum is two running sums read
        at the split (the closed form is the table's docstring). The table is
        made at the first call (the one pass over the counts) and dropped by
        ``_stability_warnings``, its last reader in the constructor: the AUTO
        search's 62 evaluations cost no pass. A later caller makes it again
        and it stays until the next ``_stability_warnings``."""
        from glint_word2vec_tpu.data.pipeline import KeptCountTable
        cfg = self.config
        if self._load_table is None:
            self._load_table = KeptCountTable(
                self.vocab.counts, self.vocab.train_words_count)
            self._auto_passes += 1
        self._auto_evaluations += 1
        s, top = self._load_table.kept(subsample_ratio)
        if s <= 0.0:
            return 0.0
        # a batch cannot hold more REAL pairs than one epoch supplies — on
        # corpora smaller than pairs_per_batch the batch is mostly mask padding
        real_pairs = min(float(cfg.pairs_per_batch),
                         s * _pairs_per_kept_token(cfg.window))
        # NB: a max(s, 1.0) floor on the denominator would deflate the SHARE
        # whenever strong subsampling drives the total effective count below 1
        # (the share is scale-free; only s == 0 needs guarding)
        return top / s * real_pairs

    # the measured NaN boundary is ~300 expected top-word duplicates per batch
    # (EVAL.md round-4 addendum: 336 trains to NaN at 60M words); auto-lowering
    # targets 250 for margin under the run-to-run corpus variation
    _DUP_LOAD_REFUSE = 300.0
    _DUP_LOAD_TARGET = 250.0

    def _resolve_duplicate_channel(self) -> None:
        """Bound the duplicate-overload channel at construction, like the pool
        channel's auto-sizing (config.py): an AUTO subsample_ratio is lowered
        until the expected top-word duplicates per batch fall under the measured
        divergence boundary; an explicit ratio past the boundary is REFUSED
        (config.allow_unstable overrides to the old warn-only behavior). The
        reference never faces this channel — its async 50-pair minibatches
        interleave a frequent word's updates instead of summing them
        (mllib:417-429). The pinned span ``trainer.resolve_auto``, with the
        passes over the counts it made as ``passes`` (one, the making of
        ``_duplicate_load``'s table, or none where an earlier resolution left
        it) and the ratios it evaluated from the table as ``evaluations``
        (62 where the search ran: two probes and 60 halvings; 1 where the
        configured ratio already holds)."""
        passes, evaluations = self._auto_passes, self._auto_evaluations
        with self._tracer.span("trainer.resolve_auto", pinned=True) as span:
            self._bound_duplicate_channel()
            span.set(passes=self._auto_passes - passes,
                     evaluations=self._auto_evaluations - evaluations)

    def _bound_duplicate_channel(self) -> None:
        cfg = self.config
        if cfg.duplicate_scaling:
            return  # mean-update semantics bound the channel by construction
        load = self._duplicate_load(cfg.subsample_ratio)
        if load <= self._DUP_LOAD_REFUSE:
            return
        if not getattr(cfg, "_auto_subsample", False):
            if cfg.allow_unstable:
                return  # _stability_warnings still names the danger at fit time
            raise ValueError(
                f"expected duplicates of the most frequent word per "
                f"{cfg.pairs_per_batch}-pair batch = {load:.0f} exceed the "
                f"measured divergence boundary (~{self._DUP_LOAD_REFUSE:.0f}: "
                f"summed scatter updates this dense trained to NaN at 60M words, "
                f"EVAL.md) with subsample_ratio={cfg.subsample_ratio}. Lower "
                f"subsample_ratio (~1e-4), set duplicate_scaling=True, shrink "
                f"pairs_per_batch, or set allow_unstable=True to proceed anyway")
        # AUTO ratio: binary-search the largest ratio meeting the target load
        # (smaller ratio = stronger subsampling = fewer top-word duplicates)
        lo, hi = 1e-12, cfg.subsample_ratio
        if self._duplicate_load(lo) > self._DUP_LOAD_TARGET:
            if cfg.allow_unstable:
                return  # _stability_warnings still names the danger at fit time
            raise ValueError(
                f"the duplicate-overload channel cannot be bounded by subsampling "
                f"alone on this corpus (top-word duplicates per "
                f"{cfg.pairs_per_batch}-pair batch stay > "
                f"{self._DUP_LOAD_TARGET:.0f} at any ratio — tiny vocabulary?); "
                f"set duplicate_scaling=True, shrink pairs_per_batch, or set "
                f"allow_unstable=True for a short toy run")
        for _ in range(60):
            mid = (lo * hi) ** 0.5  # geometric: the scale spans many decades
            if self._duplicate_load(mid) > self._DUP_LOAD_TARGET:
                hi = mid
            else:
                lo = mid
        logger.warning(
            "auto subsample_ratio lowered 1e-3 -> %.3g: at pairs_per_batch=%d "
            "this corpus's most frequent word would otherwise see ~%.0f summed "
            "duplicate updates per batch, past the measured divergence boundary "
            "(~%.0f, EVAL.md); pass subsample_ratio explicitly to pin a value",
            lo, cfg.pairs_per_batch, load, self._DUP_LOAD_REFUSE)
        self.config = cfg.replace(subsample_ratio=lo)
        # replace() re-derives a still-AUTO pool with the CONFIG-level load
        # rule (<= 600 — config cannot see the vocabulary), which would
        # silently revert a vocab-scaled enlargement already applied at
        # __init__; re-apply the large-vocab rule so the auto-lowered-
        # subsample config keeps the safe pool (graftcheck-review finding)
        self._resolve_vocab_scaled_pool()

    # Vocab-scaled AUTO pool rule, provenance EVAL.md round-5 ladder: the
    # config-time load <= 600 auto-rule was calibrated at 90k vocab, where
    # every pool row re-serves (and is re-corrected) thousands of times per
    # run. At 1.6M vocab a word serves in the pool only ~2x per run, so each
    # service's load-sized summed update is never re-corrected — measured
    # FINITE norm blowup (purity 0.99 -> 0.14, NO NaN) at load 640 over 120M
    # words; load 160 (pool 2048) fixed that collapse at the same lr and
    # tamed norm growth ~8x at 240M words. The boundary between the regimes
    # is taken at 500k (the construction-time advisory's threshold since
    # round 5); between 90k and 500k no collapse was ever measured at load
    # <= 600.
    _LARGE_VOCAB_BOUNDARY = 500_000
    _LARGE_VOCAB_SAFE_LOAD = 160.0

    def _resolve_vocab_scaled_pool(self) -> None:
        """Re-resolve a still-AUTO shared pool for the vocabulary the config
        never saw: once vocab.size > 500k, grow the pool until the load
        B·n/P sits inside the measured large-vocab safe band (<= 160,
        provenance above), rounded up to the 128-lane MXU tile. Explicit
        pools are NEVER changed — `_stability_warnings` names the danger
        instead — and auto-ness is preserved on the replaced config, so
        ``replace()``/``from_dict`` re-resolution semantics are intact (a
        later geometry change re-derives the pool from -1 as before)."""
        cfg = self.config
        if not getattr(cfg, "_auto_pool", False) or cfg.negative_pool <= 0:
            return
        if self.vocab.size <= self._LARGE_VOCAB_BOUNDARY:
            return
        load = cfg.pairs_per_batch * cfg.negatives / cfg.negative_pool
        if load <= self._LARGE_VOCAB_SAFE_LOAD:
            return
        p_min = -(-cfg.pairs_per_batch * cfg.negatives
                  // int(self._LARGE_VOCAB_SAFE_LOAD))
        pool = max(128, 128 * (-(-p_min // 128)))
        logger.warning(
            "auto negative_pool %d -> %d: a %d-word vocabulary puts the "
            "resolved pool load %.0f inside the measured large-vocab finite-"
            "blowup region (EVAL.md round-5: collapse at load 640, fixed at "
            "160); pass negative_pool explicitly to pin a value",
            cfg.negative_pool, pool, self.vocab.size, load)
        new_cfg = cfg.replace(negative_pool=pool)
        new_cfg._auto_pool = True  # still AUTO — geometry changes re-derive
        self.config = new_cfg

    @property
    def _shared_pool(self) -> bool:
        """Whether the step runs on a batch-shared negative pool: the paths
        with a [B, pool] logit chain (``logits_dtype`` applies) and a
        metrics-elided fast twin."""
        cfg = self.config
        return cfg.negative_pool > 0 and not (
            cfg.cbow and cfg.duplicate_scaling)

    def _build_step_twins(self) -> None:
        """(Re)build both step twins from the trainer's current state (at
        construction, and again when a recovery engages ``max_row_norm``)."""
        cfg = self.config
        # select_step's SGNS shared-pool row reads it; CBOW has no such row
        self._context_cap = () if cfg.cbow or cfg.loss == "hs" else _context_run_cap(
            self.vocab.counts, self.vocab.train_words_count,
            cfg.subsample_ratio, cfg.window, cfg.pairs_per_batch)
        # select_step's banded CBOW row reads it; no other row has a token block
        self._token_caps = _token_run_caps(
            self.vocab.counts, self.vocab.train_words_count,
            cfg.subsample_ratio, self._tokens_per_step,
            cfg.window) if self._banded_cbow else (0, 0)
        self._step_fn = self._build_step()
        # fast twin (metrics elided) for the shared-pool paths (skip-gram and
        # CBOW): the paths whose loss side-channel is an extra full [B, pool]
        # pass (PERF.md §4); the CBOW+duplicate_scaling and per-pair paths
        # keep full metrics (their loss chains are not the measured slice)
        self._step_fn_fast = (
            self._build_step(with_metrics=False)
            if self._shared_pool or cfg.loss == "hs" else self._step_fn)

    def _build_step(self, with_metrics: bool = True) -> Callable:
        """Build the jitted chunk function around the step :func:`select_step`
        chooses. ``with_metrics=False`` builds the fast twin of the
        shared-pool paths (skip-gram and CBOW): loss/mean_f_pos elided (one
        fewer full [B, P] pass, ~0.3 ms at the headline shape — PERF.md §4),
        pairs kept exact. The trainer dispatches the fast twin for chunks no
        heartbeat will sample (see _dispatch_step_fn); both twins share the
        same update math, so the trained parameters are bit-identical."""
        cfg = self.config
        # dispatch-side twins of the config selection matrix (construction
        # already refused these — graftlint R8 refusal parity; kept here so a
        # hand-mutated config can never reach an unsupported lowering)
        if (cfg.fused_logits or cfg.bf16_chain) and cfg.cbow:
            raise ValueError(
                "fused_logits/bf16_chain support the SGNS chains only (not "
                "cbow) — config construction refuses these combinations")
        if cfg.sync_every > 1 and cfg.step_lowering != "shard_map":
            raise ValueError(
                "sync_every > 1 (local-SGD) requires the shard_map lowering "
                "— the owner-local k-step window has no GSPMD form; config "
                "construction refuses this combination (docs/sharding.md "
                "§Local-SGD)")
        if with_metrics:  # the fast twin's build would only repeat them
            if cfg.logits_dtype != "float32" and not self._shared_pool:
                logger.warning(
                    "logits_dtype=%s only applies to the shared-pool paths "
                    "(negative_pool > 0, no CBOW+duplicate_scaling); this "
                    "configuration keeps the float32 logit chain",
                    cfg.logits_dtype)
            # the per-pair paths have no pool to overload, but the duplicate
            # channel still applies to them (EVAL.md)
            self._stability_warnings(check_pool=cfg.negative_pool > 0)
        # in-step stabilizers: trainer state, not raw config — a
        # norm_watch="recover" firing may have engaged max_row_norm since
        # construction (the rebuild path through _perform_recovery). None
        # when all off, so the default step compiles bit-identical to the
        # pre-stabilizer step.
        stab = self._stabilizers if self._stabilizers.enabled else None
        choice = select_step(cfg, self.plan, self._feed_segments,
                             self._context_cap, stab, with_metrics,
                             subword_shape=self._subword_shape,
                             hs_shape=self._hs_shape,
                             token_caps=self._token_caps)
        inner, neg_shape = choice.step, choice.neg_shape
        # np.uint32 (not a Python int): any negative or 64-bit seed masked to 32 bits
        # lands in [2^31, 2^32), which jnp.asarray rejects under int32 canonicalization
        seed = np.uint32(cfg.seed & 0xFFFFFFFF)
        if self._banded_cbow:
            return self._build_banded_cbow_chunk(inner, neg_shape, seed)
        if cfg.loss == "hs":
            return self._build_hs_chunk(inner)

        is_cbow = cfg.cbow
        S = self._feed_segments
        # > 1 only on the shard_map SGNS path (config refuses every other
        # combination) — the chunk below scans windows instead of steps
        sync_k = cfg.sync_every

        if cfg.device_pairgen:
            from glint_word2vec_tpu.ops.pairgen import device_block_pairs
            W = cfg.window
            Sd = self.plan.num_data
            Bl = cfg.pairs_per_batch // Sd

            gen = jax.vmap(
                lambda tk, st, nv, lo, hi, kp, sb, wb: device_block_pairs(
                    tk, st, nv, lo, hi, kp, sb, wb,
                    window=W, num_pairs=Bl, presubsampled=True),
                in_axes=(0, 0, 0, 0, 0, None, 0, 0))

            def device_chunk(params, arrays, meta, base_step, prob, alias,
                             keep_prob, sub_bases, win_bases):
                # meta rows: [0] per-step alphas; [1:1+Sd] per-segment valid-token
                # counts. Pair counts are unknown to the host here — the device
                # derives them; exact totals ride back in the scanned metrics.
                alphas, nvalid = meta[0], meta[1:].T          # [K], [K, Sd]
                K = alphas.shape[0]
                negatives = sample_negatives_hash(
                    prob, alias, seed, base_step, neg_shape(K, Sd * Bl))
                # tie feed + negatives to the params carry (see chunk below)
                params, arrays, negatives = jax.lax.optimization_barrier(
                    (params, arrays, negatives))

                def build_batch(xs, nv):
                    ob = jax.lax.bitcast_convert_type(xs["obase"], jnp.uint32)
                    with jax.named_scope("pairgen"):
                        dp = gen(xs["tokens"].astype(jnp.int32), xs["starts"],
                                 nv.astype(jnp.int32), ob[:, 0], ob[:, 1],
                                 keep_prob, sub_bases, win_bases)
                    return {"centers": dp.centers.reshape(-1),
                            "contexts": dp.contexts.reshape(-1),
                            "mask": dp.mask.reshape(-1)}, dp.dropped_pairs.sum()

                def body(p, inp):
                    xs, alpha, nv, negs = inp
                    batch, dropped = build_batch(xs, nv)
                    new_p, metrics = inner(p, batch, negs, alpha)
                    new_p = jax.lax.with_sharding_constraint(
                        new_p, self._params_sharding)
                    return new_p, (metrics, dropped)

                return jax.lax.scan(
                    body, params, (arrays, alphas, nvalid, negatives))

            return jax.jit(device_chunk, donate_argnums=(0,))

        def chunk(params, arrays, meta, base_step, prob, alias, *subword_table):
            # ``subword_table``: nothing, or the row table's three arrays
            # (config.subword; _step_extra) — arguments, like the alias tables
            # scan over steps_per_dispatch stacked batches in one device dispatch:
            # per-step dispatch/transfer latency would otherwise dominate the ~ms
            # step. Two hard-won TPU constraints
            # (measured 3.4M → 200M+ pairs/s on v5e, see ops/prng.py):
            #  - no jax.random (threefry) ops anywhere in this program — negatives
            #    come from the counter-based hash PRNG, drawn for the whole chunk
            #    before the scan;
            #  - the alias tables enter as jit arguments (prob, alias), never as
            #    closure constants.
            # Feed-bandwidth constraints (fewer, larger, narrower transfers):
            #  - pairs arrive as ONE packed [K, 2, B] array (possibly uint16);
            #  - the per-pair mask never ships: batches are prefix-masked by
            #    construction, so mask_k = (iota < real_k), rebuilt on device from
            #    the [2, K] meta array (row 0 alphas, row 1 real counts).
            # meta rows: [0] per-batch alphas; [1:1+S] per-segment real counts. With the
            # sharded feed (S > 1) the B axis is S contiguous per-process segments, each
            # prefix-masked on its own, so the mask is rebuilt per segment.
            alphas, reals = meta[0], meta[1:].T   # [K], [K, S] (scan runs over K)
            K = alphas.shape[0]
            if is_cbow:
                B = arrays["centers"].shape[1]
            else:
                B = arrays["pairs"].shape[2]
            negatives = sample_negatives_hash(
                prob, alias, seed, base_step, neg_shape(K, B))
            # SERIALIZATION PROPERTY: every collective in the chunk should
            # data-depend on the params carry, so a chunk dispatched behind
            # another program can never start its collectives early. The feed
            # arrays and the pre-scan sampler output are otherwise carry-
            # independent (GSPMD is free to reshard them with small
            # all-gathers), which would let chunk N+1's collectives race
            # chunk N's on XLA:CPU's shared rendezvous pool — the starvation
            # deadlock documented at _sync_collectives (whose gate is the
            # enforced fix; this barrier removes the structural exposure at
            # zero cost — params are program inputs, so within-program
            # TPU/GPU scheduling is untouched).
            params, arrays, negatives = jax.lax.optimization_barrier(
                (params, arrays, negatives))
            pos = jnp.arange(B // S, dtype=jnp.float32)

            def build_batch(xs, real):
                mask = (pos[None, :] < real[:, None]).astype(jnp.float32).reshape(-1)
                if is_cbow:
                    ctx = xs["contexts"].astype(jnp.int32)
                    # contexts are left-packed; the mask ships as a count (~40x
                    # fewer feed bytes than a [B, C] float mask)
                    nctx = xs["nctx"].astype(jnp.int32)
                    ctx_mask = (jnp.arange(ctx.shape[-1])[None, :]
                                < nctx[:, None]).astype(jnp.float32)
                    return {"centers": xs["centers"].astype(jnp.int32),
                            "contexts": ctx, "ctx_mask": ctx_mask, "mask": mask}
                prs = xs["pairs"].astype(jnp.int32)
                batch = {"centers": prs[0], "contexts": prs[1], "mask": mask}
                if subword_table:
                    from glint_word2vec_tpu.ops.subword import SubwordTable
                    batch["subword_table"] = SubwordTable(*subword_table)
                return batch

            def body(p, inp):
                xs, alpha, real, negs = inp
                new_p, metrics = inner(p, build_batch(xs, real), negs, alpha)
                new_p = jax.lax.with_sharding_constraint(
                    new_p, self._params_sharding)
                return new_p, metrics

            xs_all = (arrays, alphas, reals, negatives)
            if sync_k > 1:
                # local-SGD windowed dispatch (config.sync_every, docs/
                # sharding.md §Local-SGD): the chunk scans over K/k WINDOWS,
                # each a single shard_map program running k owner-local steps
                # per data shard + the one delta-merge collective. Config
                # guarantees k | steps_per_dispatch, so every dispatch
                # boundary is a merge boundary: the params carry this scan
                # hands back is always fully merged — snapshot-ring/rollback
                # and the preemption save (all of which run between
                # dispatches) can never resurrect an unmerged shard. Metrics
                # come back [W, k] and reshape to the [K] layout
                # _finish_round expects.
                W = K // sync_k

                def build_window(xs, real):          # real: [k, S]
                    mask = (pos[None, None, :] < real[:, :, None]).astype(
                        jnp.float32).reshape(sync_k, -1)
                    prs = xs["pairs"].astype(jnp.int32)   # [k, 2, B]
                    return {"centers": prs[:, 0], "contexts": prs[:, 1],
                            "mask": mask}

                def body_window(p, inp):
                    xs, alpha, real, negs = inp
                    new_p, metrics = inner(
                        p, build_window(xs, real), negs, alpha)
                    new_p = jax.lax.with_sharding_constraint(
                        new_p, self._params_sharding)
                    return new_p, metrics

                xs_win = jax.tree.map(
                    lambda x: x.reshape((W, sync_k) + x.shape[1:]), xs_all)
                final_p, m = jax.lax.scan(body_window, params, xs_win)
                m = jax.tree.map(
                    lambda x: x.reshape((K,) + x.shape[2:]), m)
                return final_p, m
            return jax.lax.scan(body, params, xs_all)

        return jax.jit(chunk, donate_argnums=(0,))

    def _build_hs_chunk(self, inner: Callable) -> Callable:
        """Jitted chunk for loss="hs": the pair feed's packed [K, 2, B] pairs
        and [2, K] meta as the skip-gram chunk takes them, no sampler and no
        alias tables, and the path table's three arrays as arguments
        (``_step_extra``), as the subword chunks take the row table's."""
        from glint_word2vec_tpu.ops.subword import SubwordTable

        def hs_chunk(params, arrays, meta, base_step, *path_table):
            alphas, reals = meta[0], meta[1]
            # tie the feed to the params carry (the skip-gram chunk has the why)
            params, arrays = jax.lax.optimization_barrier((params, arrays))
            pos = jnp.arange(arrays["pairs"].shape[2], dtype=jnp.float32)
            table = SubwordTable(*path_table)

            def body(p, inp):
                xs, alpha, real = inp
                prs = xs["pairs"].astype(jnp.int32)
                new_p, metrics = inner(
                    p, {"centers": prs[0], "contexts": prs[1],
                        "mask": (pos < real).astype(jnp.float32),
                        "path_table": table}, None, alpha)
                new_p = jax.lax.with_sharding_constraint(
                    new_p, self._params_sharding)
                return new_p, metrics

            return jax.lax.scan(body, params, (arrays, alphas, reals))

        return jax.jit(hs_chunk, donate_argnums=(0,))

    def _build_banded_cbow_chunk(self, inner: Callable, neg_shape: Callable,
                                 seed: np.uint32) -> Callable:
        """Jitted chunk for cbow_update='banded': same feed/chunk signature as
        the device_pairgen chunk (token blocks + hash-lattice draws on device;
        keep_prob/sub_bases ride along unused — the packer presubsampled), but
        each scan step derives per-slot CBOW window intervals
        (ops/pairgen.device_cbow_windows) and applies the banded update
        (``inner`` — :func:`select_step`'s cbow_step_banded_core row). Segments
        are flattened [Sd, T] → [Sd·T] for ONE prefix-sum pass: window
        intervals are in-block by construction, so prefix differences never
        leak across segments. The second return slot keeps the device-feed
        (metrics, dropped) shape; banded blocks have fixed example slots, so
        dropped is identically 0."""
        cfg = self.config
        from glint_word2vec_tpu.ops.pairgen import device_cbow_windows
        W = cfg.window
        H = self._block_halo

        win = jax.vmap(
            lambda tk, st, nv, lo, hi, wb: device_cbow_windows(
                tk, st, nv, lo, hi, wb, window=W, halo=H),
            in_axes=(0, 0, 0, 0, 0, 0))

        def banded_chunk(params, arrays, meta, base_step, prob, alias,
                         keep_prob, sub_bases, win_bases, *subword_table):
            # ``subword_table``: nothing, or the row table's three arrays
            # (config.subword; _step_extra), as the pair feed's chunk takes it
            del keep_prob, sub_bases  # host packer already subsampled
            alphas, nvalid = meta[0], meta[1:].T          # [K], [K, Sd]
            K = alphas.shape[0]
            negatives = sample_negatives_hash(
                prob, alias, seed, base_step,
                neg_shape(K, cfg.pairs_per_batch))
            # tie feed + negatives to the params carry (see _build_step's
            # chunk for the live-deadlock rationale)
            params, arrays, negatives = jax.lax.optimization_barrier(
                (params, arrays, negatives))

            def body(p, inp):
                xs, alpha, nv, negs = inp
                ob = jax.lax.bitcast_convert_type(xs["obase"], jnp.uint32)
                tok = xs["tokens"].astype(jnp.int32)
                with jax.named_scope("pairgen"):
                    band = win(tok, xs["starts"], nv.astype(jnp.int32),
                               ob[:, 0], ob[:, 1], win_bases)
                batch = {"tokens": tok.reshape(-1), "band": band}
                if subword_table:
                    from glint_word2vec_tpu.ops.subword import SubwordTable
                    batch["subword_table"] = SubwordTable(*subword_table)
                new_p, metrics = inner(p, batch, negs, alpha)
                new_p = jax.lax.with_sharding_constraint(
                    new_p, self._params_sharding)
                return new_p, (metrics, jnp.int32(0))

            return jax.lax.scan(body, params, (arrays, alphas, nvalid, negatives))

        if self.params.pos is None:
            return jax.jit(banded_chunk, donate_argnums=(0,))
        # the position weights come back placed as they went in: left to
        # itself jit hands a donated carry's leaf the placement of the first
        # argument of its rank (syn0's, by rows), and the next dispatch, whose
        # argument is then placed otherwise, compiles the chunk a second time
        return jax.jit(banded_chunk, donate_argnums=(0,),
                       out_shardings=(self._params_sharding, None))

    def _stage_dispatch_meta(self, meta: np.ndarray, base_step, *bases):
        """Explicitly stage the small per-dispatch host arrays (the meta rows,
        the PRNG base step, and any hash-lattice base vectors) as replicated
        device arrays. The compiled-step transfer contract (tools/stepaudit.py,
        docs/static-analysis.md; enforced by a scripted fit under
        ``jax.transfer_guard("disallow")``) requires every jitted-chunk
        argument to arrive on device: an implicit numpy→device transfer at
        dispatch time is exactly the silent host-transfer regression the
        auditor exists to catch. Cost: a few hundred replicated bytes per
        dispatch through the same put_global discipline as the feed arrays.

        This is also the single owner of the recovery lr backoff: every
        feed's alphas ride meta row 0 through here, so one multiplicative
        ``_lr_scale`` (1.0 until a norm_watch="recover" firing backs it off)
        covers the host feed, both device feeds, and the sharded paths
        without touching any producer. Identical on every process — the
        scale only changes on probe rounds, which are allgather-consistent."""
        with self._tracer.span("dispatch.meta"):
            meta = np.asarray(meta, np.float32)
            if self._lr_scale != 1.0:
                meta = meta.copy()  # never mutate the producer's array in place
                meta[0] *= np.float32(self._lr_scale)
            host = {"meta": meta,
                    "base": np.int32(base_step)}
            for i, b in enumerate(bases):
                host[f"b{i}"] = b
            placed = put_global(self.plan.replicated, host)
            return (placed["meta"], placed["base"],
                    *[placed[f"b{i}"] for i in range(len(bases))])

    def _after_dispatch(self) -> None:
        """Collective-program serialization gate (see __init__): on the
        multi-device CPU backend, wait for the dispatched chunk's carry
        before anything else may launch a program. No-op elsewhere, so the
        host/device pipelining this trainer is built around is unchanged on
        real accelerators; on the CPU mesh the dispatch_time split becomes
        device-inclusive, which that backend never reported honestly
        anyway.

        First, where the round before this dispatch was a heartbeat round
        that something recorded, its ``heartbeat.refill`` and ``heartbeat``
        spans end here: the device has work again."""
        if self._round:
            self._close_round(dispatched=True)
        if self._sync_collectives:
            with self._tracer.span("device_block"):
                jax.block_until_ready(self.params)

    def _dispatch_step_fn(self, max_steps: int) -> Callable:
        """The step function for the NEXT dispatch: the fast (metrics-elided)
        twin unless a heartbeat may sample this chunk's metrics. ``max_steps``
        is an upper bound on the real steps the chunk advances, so the
        prediction can only err toward the full-metrics twin (a heartbeat never
        lands on an elided chunk)."""
        if (self._step_fn_fast is self._step_fn
                or self.global_step + max_steps - self._last_log_step
                >= self.config.heartbeat_every_steps):
            return self._step_fn
        return self._step_fn_fast

    # -- training ----------------------------------------------------------------------

    def fit(
        self,
        sentences: Sequence[np.ndarray],
        checkpoint_path: Optional[str] = None,
        checkpoint_every_steps: Optional[int] = None,
        on_heartbeat: Optional[Callable[[HeartbeatRecord], None]] = None,
        corpus_words: Optional[int] = None,
    ) -> EmbeddingPair:
        """Run the remaining iterations of training over encoded sentences.

        ``sentences``: int32 index arrays (already OOV-filtered and chunked — C4 output).
        Resumes from ``self.state`` if a prior checkpoint set it.

        ``corpus_words``: raw token count of ``sentences``, when it differs
        from what the vocabulary's counts imply — the continual case
        (docs/continual.md), where an incremental fit feeds only the corpus
        TAIL while ``vocab.counts`` carries the full merged history. The
        lr-decay clock then anneals over the fed corpus (scaled by the same
        expected-subsample-keep ratio), not over a history-sized total it
        would never reach. Default None = the corpus is the vocabulary's
        source (every non-continual fit), behavior unchanged.
        """
        cfg = self.config
        # the fit up to its first heartbeat, once a fit: the pinned spans
        # ``fit.first_heartbeat`` and its child ``fit.first_dispatch``, both
        # recorded by the first heartbeat round (_record_first_heartbeat)
        # from these two readings of the recorder's clock: this one, and the
        # first ``dispatch.enqueue``'s return; None once they are recorded
        self._first_beat = [span_clock(), None]
        # where this fit publishes checkpoints — the SIGTERM preemption hook
        # (config.checkpoint_on_preempt) drains its emergency save here, so
        # the handler needs it before the run's bookkeeping starts
        self._active_checkpoint_path = checkpoint_path
        from glint_word2vec_tpu.data.pipeline import expected_kept_words
        train_words = expected_kept_words(
            self.vocab.counts, self.vocab.train_words_count, cfg.subsample_ratio)
        if corpus_words is not None:
            # per-iteration expected KEPT words of the fed corpus: the
            # vocab-wide keep ratio applied to the fed token count
            train_words = (train_words
                           / max(float(self.vocab.train_words_count), 1.0)
                           * float(corpus_words))
        total_words = float(cfg.num_iterations * train_words + 1)
        K = max(1, cfg.steps_per_dispatch)
        # ONE loop over ONE feed (train/feeds.py): how a round's arrays are
        # made is the feed's; everything from here on is the same for all.
        # Banded CBOW rides the token-block feeds (same chunk plumbing as
        # device_pairgen; its blocks overlap by ±window — see __init__).
        gathered = self._feed_segments > 1
        if cfg.device_pairgen or self._banded_cbow:
            make = (feeds.GatheredTokenBlocks if gathered
                    else feeds.TokenBlocks)
        else:
            make = feeds.GatheredPairs if gathered else feeds.HostPairs
        feed = make(self, sentences, float(train_words), total_words, K)

        self._start_run_bookkeeping()
        # under a live trace (config.profile_dir starts one just above)
        # ``fit.first_heartbeat`` is in the trace too, from here: on the
        # device's clock beside the first step's operations
        self._first_beat_ann = None
        if TraceAnnotation.is_enabled():
            self._first_beat_ann = TraceAnnotation("fit.first_heartbeat")
            self._first_beat_ann.__enter__()
        beacons = (self._start_peer_beacons(checkpoint_path) if gathered
                   else None)
        rounds = feed.rounds(beacons)
        # a feed that counts on the device: [K] per round, summed at the end
        pairs_arrays: List[jax.Array] = []
        dropped_arrays: List[jax.Array] = []
        est_total = 0.0
        try:
            while True:
                t0 = time.perf_counter()
                rnd = next(rounds, None)
                if not feed.books_own_time:
                    wait = time.perf_counter() - t0
                    self.host_wait_time += wait
                    self._phases.add("producer_wait", wait)
                if rnd is None:
                    break
                t0 = time.perf_counter()
                if (cfg.feed_consistency_check and not feed.placed
                        and jax.process_count() > 1):
                    # host arrays every process made (the replicated feed, where
                    # divergence CAN happen: each regenerated the stream itself)
                    # or assembled; a feed that places its own checks them first
                    self._assert_feed_consistent(rnd.arrays, rnd.meta)
                with self._tracer.span("dispatch", steps=rnd.real):
                    stacked = rnd.arrays
                    if not feed.placed:  # else a round ahead, under stage_put
                        with self._tracer.span("dispatch.put"):
                            stacked = put_global(self._chunk_shardings, stacked)
                    meta_dev, base_dev, *bases_dev = self._stage_dispatch_meta(
                        rnd.meta, self.global_step + 1, *rnd.bases)
                    with self._tracer.span("dispatch.enqueue"):
                        self.params, out = self._dispatch_step_fn(rnd.real)(
                            self.params, stacked, meta_dev, base_dev,
                            *feed.step_args, *bases_dev, *self._step_extra)
                    if (self._first_beat is not None
                            and self._first_beat[1] is None):
                        self._first_beat[1] = span_clock()
                self.dispatch_time += time.perf_counter() - t0
                self._after_dispatch()
                metrics = out
                if feed.counts_on_device:
                    metrics, dropped = out
                    pairs_arrays.append(metrics.pairs)
                    dropped_arrays.append(dropped)
                    est_total += rnd.pairs
                self._finish_round(
                    rnd.real, rnd.pairs, rnd.meta[0], metrics, rnd.state,
                    checkpoint_path, checkpoint_every_steps, on_heartbeat)
                feed.ack()
        except BaseException:
            self._abort_run()  # its docstring has the why-not-sys.exc_info
            raise
        finally:
            self._stop_profiler()
            if beacons is not None:
                beacons.stop()
            feed.close()

        if feed.counts_on_device:
            self._settle_device_pairgen_books(
                pairs_arrays, dropped_arrays, est_total)
        self.state = TrainState(
            iteration=cfg.num_iterations,
            words_processed=feed.final_words,
            finished=True, global_step=self.global_step)
        if checkpoint_path:
            self.save_checkpoint(checkpoint_path)
        self._end_run("ok")
        return self.params

    def _device_seg_blocks(self, sentences: Sequence[np.ndarray], k: int, s: int,
                           workers: Optional[int] = None):
        """[T]-token blocks of data segment s, iteration k, for the device pair
        generator — SUBSAMPLED on the host (same hashrng draws on raw ordinals as
        data/pipeline, vectorized over ~1M-raw-token slabs; a per-sentence Python
        loop measurably starved the feed), so the wire carries only kept tokens and
        the lr clock is exact. The kept stream is cut at T boundaries — a sentence
        straddling a cut loses its cross-cut window context, the same class of
        boundary as the reference's maxSentenceLength chunking (mllib:341); at
        production T (tens of thousands) that is ~0.02% of windows. Yields
        (tokens[T], start_bits, n_valid, kept_ordinal_base, kept_count).

        Deterministic per (seed, k, s) and independent of which process runs it —
        the property the sharded multi-process feed relies on (a 2-process run's
        segment s is bit-identical to a single-process run's). ``workers``
        (default ``config.producer_workers``) fans the per-slab subsample work
        across a thread pool (pipeline.ordered_pool_map): the draws are keyed
        by raw-token ordinals, so each slab is a pure function of its (slab,
        ordinal base) job and the merged stream is bit-identical at any worker
        count — only the T-boundary packing below stays serial.

        Banded-CBOW mode (self._block_halo > 0): the same kept stream is cut
        with a ±halo OVERLAP instead (pipeline.pack_halo_token_blocks) — blocks
        advance by T − 2·halo core slots, so chunk-edge windows are exact (no
        cross-cut context loss at all) and the 5th tuple element counts only
        the NEW core tokens (the lr clock must not double-count overlap)."""
        from glint_word2vec_tpu.data.hashrng import (
            STREAM_SUBSAMPLE, hash_u01_at, stream_base)
        from glint_word2vec_tpu.data.pipeline import (
            iter_sentence_slabs, ordered_pool_map, pack_halo_token_blocks,
            stream_rng)
        cfg = self.config
        if workers is None:
            workers = cfg.producer_workers
        Sd = self.plan.num_data
        T = self._tokens_per_step
        tok_dt = self._pair_dtype
        keep = self._keep_host
        rng = stream_rng(cfg.seed, k, s)
        order = np.arange(s, len(sentences), Sd)
        if cfg.shuffle:
            rng.shuffle(order)
        sub_base = stream_base(cfg.seed, STREAM_SUBSAMPLE, k, s)

        def slab_jobs():
            raw_ord = 0
            for slab in iter_sentence_slabs(sentences, order):
                yield slab, raw_ord
                raw_ord += sum(int(x.shape[0]) for x in slab)

        def run_slab(job):
            """(kept_tokens, sentence_start_flags) of one ~1M-raw-token slab —
            pure in (slab, raw ordinal base); None for an all-dropped slab."""
            slab, raw_ord = job
            tokens = np.concatenate(slab) if len(slab) > 1 else slab[0]
            lens = np.fromiter(
                (x.shape[0] for x in slab), np.int64, len(slab))
            n = tokens.shape[0]
            sids = np.repeat(np.arange(len(slab)), lens)
            if cfg.subsample_ratio > 0:
                u = hash_u01_at(sub_base, np.arange(
                    raw_ord, raw_ord + n, dtype=np.uint64))
                m = u <= keep[tokens]
                ktoks, ksids = tokens[m], sids[m]
            else:
                ktoks, ksids = tokens, sids
            if ktoks.shape[0] == 0:
                return None
            kstart = np.empty(ktoks.shape[0], bool)
            kstart[0] = True
            kstart[1:] = ksids[1:] != ksids[:-1]
            return ktoks.astype(tok_dt), kstart

        def kept_slabs():
            for res in ordered_pool_map(run_slab, slab_jobs(), workers):
                if res is not None:
                    yield res

        if self._block_halo:
            yield from pack_halo_token_blocks(
                kept_slabs(), T, self._block_halo, tok_dt)
            return

        base = 0
        rest_tok = np.empty(0, tok_dt)
        rest_start = np.empty(0, bool)

        def emit(toks, starts):
            n = toks.shape[0]
            buf = np.zeros(T, tok_dt)
            buf[:n] = toks
            bits = np.packbits(np.pad(starts, (0, T - n)), bitorder="little")
            return (buf, bits, n, base, float(n))

        for ktoks, kstart in kept_slabs():
            rest_tok = np.concatenate([rest_tok, ktoks])
            rest_start = np.concatenate([rest_start, kstart])
            while rest_tok.shape[0] >= T:
                yield emit(rest_tok[:T], rest_start[:T])
                base += T
                rest_tok = rest_tok[T:]
                rest_start = rest_start[T:].copy()
                if rest_start.shape[0]:
                    # the cut tail acts as a new sentence (device treats the
                    # leading run of a block as one regardless)
                    rest_start[0] = True
        if rest_tok.shape[0]:
            yield emit(rest_tok, rest_start)

    def _settle_device_pairgen_books(
        self,
        pairs_arrays: List[jax.Array],
        dropped_arrays: List[jax.Array],
        est_total: float,
    ) -> None:
        """End-of-run accounting shared by both device-feed paths: heartbeats ran
        on the analytic pair estimate; settle the books against the exact trained
        and overflow-dropped totals the device reports."""
        if not pairs_arrays:
            return
        exact = float(jnp.concatenate(pairs_arrays).sum())
        dropped_total = float(jnp.stack(dropped_arrays).sum())
        self.pairs_trained += exact - est_total
        self._pairs_since_log = max(
            self._pairs_since_log + exact - est_total, 0.0)
        if dropped_total > 0.02 * max(exact, 1.0):
            logger.warning(
                "device pairgen dropped %.0f pairs (%.1f%% of %.0f trained) to "
                "overflow — raise tokens_per_step (or lower pairs_per_batch "
                "fill pressure)", dropped_total,
                100.0 * dropped_total / exact, exact)
        elif dropped_total:
            logger.info("device pairgen: %.0f overflow pairs dropped "
                        "(%.3f%%)", dropped_total,
                        100.0 * dropped_total / max(exact, 1.0))

    def _assert_feed_consistent(self, arrays: dict, meta: np.ndarray) -> None:
        """Debug-mode SPMD divergence detector (config.feed_consistency_check):
        every process fingerprints its ASSEMBLED global feed + meta and one
        allgather compares them. Identical step inputs on every process are the
        contract that makes the jitted update SPMD-consistent; a mismatch here
        (nondeterministic host pipeline, clock drift, corrupted transport)
        would otherwise surface only as silent training divergence. Aux-
        subsystem analog of race detection: the reference accepted races by
        design (Hogwild, SURVEY §5) — a synchronous design can verify its
        no-divergence contract instead."""
        import zlib

        from jax.experimental import multihost_utils
        h = 0
        for name in sorted(arrays):
            h = zlib.crc32(np.ascontiguousarray(arrays[name]).tobytes(), h)
        h = zlib.crc32(np.ascontiguousarray(meta).tobytes(), h)
        fps = multihost_utils.process_allgather(
            {"fp": np.asarray([h], np.int64)})["fp"][:, 0]
        if not (fps == fps[0]).all():
            raise RuntimeError(
                "SPMD feed divergence: per-process fingerprints of the "
                f"assembled global batch differ ({[int(f) for f in fps]}) — "
                "host pipelines produced different feeds (nondeterministic "
                "input ordering or clock drift); training would silently "
                "diverge from here")

    def _touch(self, stacked):
        """Dispatch a tiny consuming op over staged feed arrays so their
        host→device upload is enqueued NOW (on the calling thread) instead of
        lazily at step-dispatch time — the transfer-forcing half of
        ``feeds._stage_to_device``, shared with the gathered round stager."""
        if not hasattr(self, "_touch_fn"):
            import operator

            def touch(arrays):
                return jax.tree.reduce(
                    operator.add,
                    jax.tree.map(
                        lambda x: x.reshape(-1)[:1].astype(jnp.float32).sum(),
                        arrays))

            self._touch_fn = jax.jit(touch)
        return self._touch_fn(stacked)

    @property
    def _needs_snapshot_ring(self) -> bool:
        """Single derived predicate for arming the snapshot ring: ANY
        consumer — nonfinite rollback or the watchdog recovery ladder —
        arms it. Pre-round-12 only nonfinite_policy=='rollback' seeded the
        ring, so every other consumer found it empty on first firing (the
        previously-dead norm_watch='recover' + nonfinite_policy='halt'
        combination; regression-tested in tests/test_stabilizers.py)."""
        return (self.config.nonfinite_policy == "rollback"
                or self.config.norm_watch == "recover")

    def _start_run_bookkeeping(self) -> None:
        self.rollbacks_performed = 0  # max_rollbacks is a per-fit() budget
        self.recoveries_performed = 0  # max_recoveries likewise
        if self._needs_snapshot_ring and not self._snapshot_ring:
            # seed the ring with the starting params so even a blowup inside
            # the first heartbeat window has a restore point
            self._snapshot_ring.append(
                (self._copy_params(self.params), self.global_step))
        self.host_wait_time = 0.0      # fit() blocked on batch production (incl. the
                                       # producer's device staging when prefetching)
        self.dispatch_time = 0.0       # fit() inside (async) step dispatch; also the
                                       # feed transfer when prefetch_chunks=0 (no
                                       # producer thread to stage on)
        self._last_log_time = time.perf_counter()
        self._last_log_step = self.global_step
        self._pairs_since_log = 0.0
        self._last_hb_host_wait = 0.0
        self._last_hb_dispatch = 0.0
        self._profiling = False
        self._profile_start_step = self.global_step
        if self.config.profile_dir:
            import jax.profiler
            jax.profiler.start_trace(self.config.profile_dir)
            self._profiling = True
            logger.info("jax.profiler trace -> %s", self.config.profile_dir)
        # run telemetry (docs/observability.md): stamp the run, arm the span
        # tracer. The tracer is process-wide (checkpoint save/load record
        # spans without a Trainer handle), cleared per run so a trace file
        # describes exactly one fit.
        import os
        self._run_ended = False
        # preemption-deadline state (config.checkpoint_on_preempt): the
        # SIGTERM handler only ARMS the deadline; _finish_round's tail
        # drains it. Reset per fit so a resumed run re-arms cleanly.
        self._preempt_deadline = None
        self._preempt_signum = 0
        # last step a checkpoint actually published at — the preempt record's
        # progress-lost-since-last-save denominator
        self._last_save_step = int(self.global_step)
        self._run_id = f"{os.getpid()}-{int(time.time())}-{self.global_step}"
        observing = self._telemetry is not None or self.config.status_port > 0
        self._tracer.configure(enabled=observing)
        self._phases.clear()
        self._tracer.attach_phases(self._phases if observing else None)
        self._last_hb_phases = self._phases.raw_snapshot()
        # per-round marks for the flight recorder's dispatch ring
        self._bb_wait_mark = 0.0
        self._bb_disp_mark = 0.0
        if self._blackbox is not None:
            self._blackbox.begin_run(self._run_id)
        self._install_run_signals()
        if self.config.status_port and self._statusd is None:
            from glint_word2vec_tpu.obs.statusd import StatusServer
            self._statusd = StatusServer(
                self.config.status_port, self.status_snapshot).start()
        if self._telemetry is not None:
            from glint_word2vec_tpu.obs.trace import clock_anchor
            self._tracer.clear()
            cfg = self.config
            self._emit(
                "run_start", run_id=self._run_id, vocab_size=self.vocab.size,
                # the clock anchor (obs/trace.py): one simultaneous
                # wall/monotonic reading so tools/obs_collect.py can place
                # this process's spans on the fleet timeline
                **clock_anchor(),
                # what the process did before this fit, by pinned span
                # (obs/spans.py): Trainer() and its phases, the compiles
                setup=self._tracer.setup_summary(),
                mesh=[self.plan.num_data, self.plan.num_model],
                config={k: getattr(cfg, k) for k in (
                    "vector_size", "learning_rate", "pairs_per_batch",
                    "negatives", "negative_pool", "subsample_ratio",
                    "param_dtype", "compute_dtype", "logits_dtype", "cbow",
                    "step_lowering", "device_pairgen", "nonfinite_policy",
                    "norm_watch", "norm_watch_threshold", "norm_watch_max",
                    "norm_watch_frac", "heartbeat_every_steps",
                    "max_row_norm", "update_clip", "row_l2",
                    "recover_lr_backoff", "max_recoveries")})

    def _record_first_heartbeat(self) -> None:
        """``fit.first_heartbeat``: ``fit()``'s entry to the return of the
        first heartbeat's ``device_block`` (the feed's construction, the feed
        thread's first chunk, the first dispatch's compile or load and its
        run, the probe's). Its child ``fit.first_dispatch`` ends where the
        first ``dispatch.enqueue`` returned. Both pinned and retroactive
        (obs/spans.py): the first round's ``dispatch`` and ``heartbeat`` keep
        the parents every round's have. One pair a fit."""
        t_fit, t_enqueued = self._first_beat
        self._first_beat = None
        self._end_first_beat_annotation()
        beat = self._tracer.record(
            "fit.first_heartbeat", t_fit, span_clock() - t_fit, pinned=True,
            step=self.global_step,
            steps=self.global_step - self._last_log_step)
        self._tracer.record("fit.first_dispatch", t_fit, t_enqueued - t_fit,
                            parent=beat, pinned=True)

    def _end_first_beat_annotation(self) -> None:
        ann, self._first_beat_ann = self._first_beat_ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def _open_round_span(self, name: str, **args) -> None:
        span = self._tracer.open(name, **args)
        if span is not None:
            self._round.append(span)

    def _close_round(self, dispatched: bool) -> None:
        """End the heartbeat round's open spans, innermost first. Where no
        dispatch followed (the fit's last heartbeat, or one that raised) the
        refill was none and is not kept, and the ``heartbeat`` ends where
        the refill would have begun."""
        end = None
        while self._round:
            span = self._round.pop()
            if not dispatched and span.name == "heartbeat.refill":
                span.close(keep=False)
                end = span.t0
            else:
                span.close(end=end)

    def _stop_profiler(self) -> None:
        # the fit loop leaves through here (its ``finally``), before the
        # fit's last save: a heartbeat round no dispatch followed ends too
        if self._round:
            self._close_round(dispatched=False)
        self._end_first_beat_annotation()   # a fit that never reached one
        if getattr(self, "_profiling", False):
            import jax.profiler
            jax.profiler.stop_trace()
            self._profiling = False

    # rollback re-seed: the negative-sample stream is a pure function of
    # (seed, global_step) — ops/prng.py — so jumping the counter far past any
    # step the run will legitimately reach gives the retried stretch a fresh
    # negative-sample path WITHOUT rebuilding the jitted step (the seed itself
    # is a compile-time constant). 2^22 steps is ~275B pairs at B=64k, far
    # beyond any single fit; repeated rollbacks jump again, so paths never
    # overlap.
    _ROLLBACK_STEP_JUMP = 1 << 22

    def _health_stats(self) -> dict:
        """Run the fused on-device health probe (obs/probe.py) and return its
        channel dict: the old finiteness bit PLUS per-matrix row-norm
        channels (max/mean/p99, frac over the watchdog threshold), and the
        host-side update-magnitude proxy (delta of mean_norm between
        consecutive probes). Each table is read ONCE: one variadic reduce a
        table gives the bit per padded row beside the row's sum of squares,
        and the norm channels come from the sums' first vocab.size entries
        (obs/probe.py ``_row_sums``; two reductions written apart compile to
        two passes a table).

        Drains in-flight chunk dispatches BEFORE launching the probe: on a
        multi-device mesh the probe's cross-shard reductions are themselves a
        collective-bearing program; dispatching it while a chunk is still at
        its collective rendezvous puts two independent collective programs in
        flight — the XLA:CPU rendezvous-starvation deadlock documented at
        _sync_collectives in __init__. Waiting on the carry is the sync the
        heartbeat fetch was already paying, so steady-state cost is
        unchanged. The result is fetched EXPLICITLY (jax.device_get) so the
        probe stays clean under the stepaudit transfer contract
        (tools/stepaudit.py runs scripted fits under jax.transfer_guard)."""
        if self._health_fn is None:
            from glint_word2vec_tpu.obs.probe import make_health_probe
            self._health_fn = make_health_probe(
                self.vocab.size, self.config.norm_watch_threshold)
        from glint_word2vec_tpu.obs.probe import stats_to_channels
        with self._tracer.span("heartbeat.drain"):
            jax.block_until_ready(self.params)
        with self._tracer.span("health_probe"):
            channels = stats_to_channels(
                jax.device_get(self._health_fn(self.params)))
        prev = self._last_probe_channels
        if prev is not None:
            channels["update_mag"] = round(
                abs(channels["syn0"]["mean_norm"] - prev["syn0"]["mean_norm"])
                + abs(channels["syn1"]["mean_norm"]
                      - prev["syn1"]["mean_norm"]), 9)
        self._last_probe_channels = channels
        return channels

    def _params_finite(self) -> bool:
        return bool(self._health_stats()["finite"])

    def _copy_params(self, params: EmbeddingPair) -> EmbeddingPair:
        if self._copy_params_fn is None:
            self._copy_params_fn = jax.jit(
                lambda p: jax.tree.map(jnp.copy, p))
        return self._copy_params_fn(params)

    def _nonfinite_diagnostic(self) -> str:
        bad0 = int(jnp.sum(~jnp.isfinite(self.params.syn0)))
        bad1 = int(jnp.sum(~jnp.isfinite(self.params.syn1)))
        return (
            f"non-finite parameters at global step {self.global_step}: "
            f"{bad0} entries in syn0, {bad1} in syn1 (of "
            f"{self.padded_vocab}x{self.padded_dim} each). Likely causes, in "
            f"measured order (EVAL.md): pool-row overload "
            f"(grow negative_pool), duplicate-overload (lower subsample_ratio "
            f"~1e-4 or set duplicate_scaling=True), or learning rate too high "
            f"for {self.config.param_dtype}. Set nonfinite_policy='rollback' "
            f"to auto-recover from the last good snapshot instead of halting")

    def _nonfinite_guard(self, channels: Optional[dict] = None) -> None:
        """Heartbeat-cadence finiteness guardrail (config.nonfinite_policy).
        The probe is a separate jitted reduction over the params carry (the
        fused health probe, obs/probe.py — finiteness plus the norm channels
        in one pass), fetched alongside the heartbeat's metrics fetch (which
        already forces a device sync) — the training step functions are
        untouched, so the fast metrics-elided twin stays elided. ``channels``
        lets a caller that already probed this round (the watchdog/heartbeat
        path in _finish_round) share the fetch. On a finite probe under
        ``rollback``, the current params are snapshotted into the ring; on a
        non-finite probe the policy decides: ``halt`` raises with a
        diagnostic, ``rollback`` pops and restores the newest good snapshot
        and jumps the negative-sample counter lattice so the retried stretch
        draws different negatives (the host data stream keeps advancing — the
        updates between the snapshot and the blowup are sacrificed, the same
        accounting loss as resuming a checkpoint). Repeated blowups before the
        next finite probe step back through the older ring entries; an
        emptied ring raises."""
        cfg = self.config
        if channels is None:
            channels = self._health_stats()
        if channels["finite"]:
            self._maybe_snapshot(channels)
            return
        if cfg.nonfinite_policy == "halt":
            raise NonFiniteParamsError(self._nonfinite_diagnostic())
        if not self._snapshot_ring:
            if self.rollbacks_performed:
                raise NonFiniteParamsError(
                    f"rollback ring exhausted after "
                    f"{self.rollbacks_performed} rollback(s) — repeated "
                    f"divergence consumed every good snapshot; this needs a "
                    f"config change, not retries. "
                    + self._nonfinite_diagnostic())
            raise NonFiniteParamsError(
                self._nonfinite_diagnostic()
                + " (rollback requested but no good snapshot was taken yet "
                  "— blowup before the first probe)")
        if self.rollbacks_performed >= cfg.max_rollbacks:
            raise NonFiniteParamsError(
                f"giving up after {self.rollbacks_performed} rollbacks — the "
                f"run keeps diverging; this needs a config change, not "
                f"retries. " + self._nonfinite_diagnostic())
        snap_step, old_step = self._restore_snapshot()
        self.rollbacks_performed += 1
        logger.warning(
            "non-finite params at step %d: rolled back to the snapshot from "
            "step %d and re-seeded the negative-sample lattice (counter -> %d; "
            "rollback %d/%d)", old_step, snap_step, self.global_step,
            self.rollbacks_performed, self.config.max_rollbacks)

    def _restore_snapshot(self) -> Tuple[int, int]:
        """POP the newest snapshot-ring entry and restore it directly (no
        copy — the entry leaves the ring, so the next dispatch is free to
        donate its buffers), then jump the negative-sample counter lattice
        far past any step the run will legitimately reach so the retried
        stretch draws a fresh sample path without rebuilding the jitted step
        (the seed is a compile-time constant). Popping is what makes the
        deeper ring entries reachable: a retry that blows up again before
        the next good probe steps back to the NEXT-older snapshot instead of
        thrashing on the same one, and an emptied ring escalates to the
        caller's halt diagnostic. ONE owner for both consumers (non-finite
        rollback and watchdog recovery) so the reseed invariant cannot
        drift. Returns (snapshot_step, pre-restore global_step)."""
        params, snap_step = self._snapshot_ring.pop()
        self.params = params
        old_step = self.global_step
        self.global_step = max(self.global_step, snap_step) + \
            self._ROLLBACK_STEP_JUMP
        self.state = dc_replace(self.state, global_step=self.global_step)
        return int(snap_step), old_step

    def _maybe_snapshot(self, channels: dict) -> None:
        """Append the current params to the snapshot ring when any consumer
        needs it (the `_needs_snapshot_ring` predicate) AND the probed state
        is worth restoring: finite, and — when the watchdog is armed — not a
        state it would flag (a carry mid-blowup must never become the 'good'
        restore point the recovery then thrashes back to)."""
        if not self._needs_snapshot_ring or not channels["finite"]:
            return
        if (self.norm_watchdog.policy != "off"
                and self.norm_watchdog.would_fire(channels)):
            return
        self._snapshot_ring.append(
            (self._copy_params(self.params), self.global_step))

    def _watchdog_check(self, channels: dict) -> bool:
        """Feed one probe result to the finite-blowup watchdog and persist any
        firing to the telemetry sink — for ``halt`` the record is emitted
        BEFORE the raise, so the run log carries the evidence the exception
        message summarizes. Under ``norm_watch="recover"`` a firing runs the
        mitigate-and-recover half of the ladder (:meth:`_perform_recovery`);
        returns True when that consumed this round (the caller must not
        snapshot the pre-restore params)."""
        from glint_word2vec_tpu.train.faults import NormBlowupError
        try:
            reason = self.norm_watchdog.check(channels, self.global_step)
        except NormBlowupError:
            if self._telemetry is not None:
                self._emit(
                    "watchdog", step=self.global_step, policy="halt",
                    reason=self.norm_watchdog.last_reason or "",
                    channels=channels)
            raise
        if reason and self._telemetry is not None:
            self._emit(
                "watchdog", step=self.global_step,
                policy=self.config.norm_watch, reason=reason,
                channels=channels)
        if reason and self.config.norm_watch == "recover":
            self._perform_recovery(reason, channels)
            return True
        return False

    def _perform_recovery(self, reason: str, channels: dict) -> None:
        """The mitigate→recover half of the detect→mitigate→recover ladder
        (docs/robustness.md), run once per firing probe under
        ``norm_watch="recover"``:

        1. emit the telemetry ``recovery`` record FIRST — before any state
           mutates, so even a crash mid-recovery leaves the evidence;
        2. roll back to the newest snapshot-ring entry (popped, like the
           nonfinite path — repeated firings step back through older
           entries) and jump the negative-sample counter lattice so the
           retried stretch draws a fresh sample path;
        3. auto-engage mitigation for the resumed run: multiply the
           effective lr by ``config.recover_lr_backoff`` (compounding), and
           engage ``max_row_norm`` at ``config.norm_watch_threshold`` if no
           clamp was configured (the step functions are rebuilt — one
           recompile per engagement, logged);
        4. budget: after ``config.max_recoveries`` recoveries in one fit —
           or with no snapshot left — degrade to the ``halt`` contract
           (NormBlowupError with the full diagnostic, record emitted before
           the raise), exactly like the non-finite guardrail's exhaustion
           path."""
        from glint_word2vec_tpu.train.faults import NormBlowupError
        cfg = self.config

        def emit(action: str, snap_step: int, lr_scale: float,
                 clamp: float) -> None:
            if self._telemetry is not None:
                self._emit(
                    "recovery", step=self.global_step, action=action,
                    reason=reason, snapshot_step=snap_step,
                    recoveries_performed=self.recoveries_performed
                    + (1 if action == "rollback" else 0),
                    max_recoveries=cfg.max_recoveries,
                    lr_scale=round(lr_scale, 9), max_row_norm=clamp,
                    channels=channels)

        if self.recoveries_performed >= cfg.max_recoveries:
            emit("halt", -1, self._lr_scale, self._stabilizers.max_row_norm)
            raise NormBlowupError(
                f"recovery budget exhausted after {self.recoveries_performed}"
                f" recoveries (max_recoveries={cfg.max_recoveries}) — the "
                f"run keeps re-entering the blowup region under lr_scale="
                f"{self._lr_scale:g} and max_row_norm="
                f"{self._stabilizers.max_row_norm:g}; this needs a config "
                f"change (negative_pool/subsample_ratio/learning_rate — "
                f"EVAL.md), not more retries. Last firing: {reason}")
        if not self._snapshot_ring:
            emit("halt", -1, self._lr_scale, self._stabilizers.max_row_norm)
            raise NormBlowupError(
                f"norm_watch='recover' fired with no good snapshot left "
                f"({self.recoveries_performed} recovery(ies) already "
                f"consumed the ring) — repeated blowups before any finite "
                f"healthy probe; this needs a config change, not retries. "
                f"Last firing: {reason}")

        new_scale = self._lr_scale * cfg.recover_lr_backoff
        engage_clamp = not self._stabilizers.max_row_norm
        clamp_after = (cfg.norm_watch_threshold if engage_clamp
                       else self._stabilizers.max_row_norm)
        emit("rollback", int(self._snapshot_ring[-1][1]), new_scale,
             clamp_after)

        snap_step, old_step = self._restore_snapshot()
        self.recoveries_performed += 1
        self._lr_scale = new_scale
        if engage_clamp:
            # engage the clamp at the watchdog threshold: the boundary the
            # firing measured health by — rows at/below it are by definition
            # outside the firing signature (provenance: healthy EVAL rows
            # sit at norm 1-15, the threshold at 100)
            self._stabilizers = self._stabilizers._replace(
                max_row_norm=float(cfg.norm_watch_threshold))
            self._build_step_twins()
        logger.warning(
            "norm watchdog recovery %d/%d at step %d: rolled back to the "
            "snapshot from step %d, re-seeded the sample lattice (counter -> "
            "%d), lr backed off to x%g%s — firing: %s",
            self.recoveries_performed, cfg.max_recoveries, old_step,
            snap_step, self.global_step, self._lr_scale,
            (f", engaged max_row_norm={self._stabilizers.max_row_norm:g}"
             if engage_clamp else ""), reason)

    def _emit(self, kind: str, **fields) -> None:
        """One telemetry record to the sink AND the flight recorder's ring
        (obs/blackbox.py) — single owner of record assembly, so the dump's
        ring entries are byte-for-byte the records the JSONL carries."""
        if self._telemetry is not None:
            self._telemetry.emit(kind, **fields)
        if self._blackbox is not None:
            self._blackbox.observe(kind, fields)

    def _install_run_signals(self) -> None:
        """Arm the flight recorder's SIGTERM hook for the duration of fit():
        SIGTERM is the first thing a preemption/k8s eviction sends and, unlike
        SIGINT (delivered as KeyboardInterrupt, which the fit loop's abort
        handler already turns into a dump), it would otherwise kill the
        process with no artifact. Main-thread only (the signal module's
        rule); restored by _teardown_run_inspection.

        Also armed — blackbox or not — when config.checkpoint_on_preempt
        asks the fit to answer a preemption with an emergency checkpoint
        instead of just dying (docs/robustness.md)."""
        if self._blackbox is None and not self.config.checkpoint_on_preempt:
            return
        import signal
        try:
            # signal.signal returns the PRIOR handler — which is legally
            # None when a non-Python (C-level) handler was installed, so a
            # separate installed flag distinguishes "nothing to restore"
            # from "prior handler unknown" (restored as SIG_DFL, best
            # effort — leaving OUR handler installed would loop forever on
            # the re-raise below)
            self._prev_sigterm = signal.signal(
                signal.SIGTERM, self._on_sigterm)
            self._sigterm_installed = True
        except ValueError:
            self._sigterm_installed = False  # non-main-thread fit: no hook

    def _on_sigterm(self, signum, frame) -> None:
        import os
        from glint_word2vec_tpu.obs.blackbox import FlightRecorder
        if self._blackbox is not None:
            self._blackbox.dump(FlightRecorder.signal_cause(signum),
                                extra=self._dump_context())
        # preemption-deadline checkpointing (config.checkpoint_on_preempt):
        # a handler can interrupt arbitrary host code — mid-dispatch, inside
        # a collective, halfway through a save — where launching the
        # emergency save HERE could deadlock or tear. So the handler only
        # ARMS a deadline and returns; the in-flight dispatch finishes
        # naturally and _finish_round's tail (the first point where no
        # collective is in flight) drains the carry through the normal
        # digest-verified save path via _preempt_exit. First signal wins:
        # a repeat TERM while armed just returns (the deadline is already
        # running); one arriving after the run ended falls through to the
        # die-now path below.
        if (self.config.checkpoint_on_preempt
                and not getattr(self, "_run_ended", True)
                and getattr(self, "_active_checkpoint_path", None)):
            if getattr(self, "_preempt_deadline", None) is None:
                self._preempt_deadline = (
                    time.monotonic() + self.config.preempt_deadline_s)
                self._preempt_signum = int(signum)
                logger.warning(
                    "SIGTERM at step %d: preemption deadline armed "
                    "(%.1fs) — finishing in-flight dispatch, then "
                    "emergency checkpoint", self.global_step,
                    self.config.preempt_deadline_s)
            return
        # _end_run's teardown RESTORES the pre-fit disposition (it must run
        # before the re-raise, not after — nothing after os.kill runs under
        # the default disposition), so the re-raised signal is delivered
        # with the exit semantics the sender expects: SIG_DFL dies with
        # rc = -SIGTERM, a framework's SIG_IGN/custom handler applies as if
        # the fit had never hooked the signal
        self._end_run("error")
        os.kill(os.getpid(), signum)

    def _teardown_run_inspection(self) -> None:
        """Stop the fit-scoped status endpoint and restore the SIGTERM
        disposition — idempotent, runs at every run end (ok or error,
        including from inside the SIGTERM handler itself)."""
        if self._statusd is not None:
            self._statusd.stop()
            self._statusd = None
        if getattr(self, "_sigterm_installed", False):
            import signal
            self._sigterm_installed = False
            signal.signal(
                signal.SIGTERM,
                self._prev_sigterm if self._prev_sigterm is not None
                else signal.SIG_DFL)
            self._prev_sigterm = None

    def _dump_context(self) -> dict:
        """The at-death snapshots the flight-recorder dump carries beside the
        rings: where the time went, what the spans saw, the live gauges."""
        return {"phases": self._phases.summary(),
                "spans": self._tracer.span_summary(),
                "status": self.status_snapshot()}

    def status_snapshot(self) -> dict:
        """The live-inspection gauge snapshot (obs/statusd.py serves this as
        /status.json and renders /metrics from it). Reads only plain host
        attributes and bounded rings — never device state — so a scrape can
        never interleave a collective into the dispatch pipeline."""
        hb = self.heartbeats[-1] if self.heartbeats else None
        return {
            "run_id": getattr(self, "_run_id", ""),
            "status": ("idle" if getattr(self, "_run_ended", True)
                       else "running"),
            "global_step": int(self.global_step),
            "words": int(self.state.words_processed),
            "pairs_trained": float(self.pairs_trained),
            "pairs_per_sec": float(hb.pairs_per_sec) if hb else None,
            "alpha": float(hb.alpha) if hb else None,
            "lr_scale": float(self._lr_scale),
            "recoveries": int(self.recoveries_performed),
            "rollbacks": int(self.rollbacks_performed),
            "watchdog_fires": int(self.norm_watchdog.fires),
            "heartbeats": len(self.heartbeats),
            "host_wait_s_total": round(
                getattr(self, "host_wait_time", 0.0), 3),
            "dispatch_s_total": round(
                getattr(self, "dispatch_time", 0.0), 3),
            "norms": self._last_probe_channels,
            "phases": self._phases.summary(),
            "setup": self._tracer.setup_summary(),
        }

    @property
    def last_run_stats(self) -> dict:
        """Runtime outcome of the last fit: the robustness end state the
        EVAL harness emits into its rows, plus — when time attribution is
        armed — the per-phase rollup, so "where did the time go" rides the
        same surface as "did it recover"."""
        stats = {
            "watchdog_fires": int(self.norm_watchdog.fires),
            "rollbacks_performed": int(self.rollbacks_performed),
            "recoveries_performed": int(self.recoveries_performed),
            "lr_scale_final": float(self._lr_scale),
            "engaged_max_row_norm": float(self._stabilizers.max_row_norm),
            "engaged_update_clip": float(self._stabilizers.update_clip),
            "engaged_row_l2": float(self._stabilizers.row_l2),
        }
        phases = self._phases.summary()
        if phases:
            stats["phases"] = phases
        return stats

    def _end_run(self, status: str) -> None:
        """Emit the run_end record + export the Chrome trace (idempotent per
        _start_run_bookkeeping). The success path calls this AFTER the final
        checkpoint save so that save's span lands in the exported trace; the
        error path reaches it through _finish_run_telemetry in the fit
        ``finally`` blocks."""
        self._teardown_run_inspection()
        if getattr(self, "_run_ended", True):
            return
        self._run_ended = True
        if self._telemetry is not None:
            self._emit(
                "run_end", run_id=self._run_id, status=status,
                steps=int(self.global_step),
                pairs_trained=float(self.pairs_trained),
                host_wait_s_total=round(self.host_wait_time, 3),
                dispatch_s_total=round(self.dispatch_time, 3),
                watchdog_fires=int(self.norm_watchdog.fires),
                rollbacks=int(self.rollbacks_performed),
                recoveries=int(self.recoveries_performed),
                lr_scale=round(float(self._lr_scale), 9),
                phases=self._phases.summary(),
                spans=self._tracer.span_summary())
            try:
                self.export_trace(self.config.telemetry_path + ".trace.json")
            except OSError as e:
                # best-effort like the sink — and _end_run runs inside the
                # abort path's except clause, where a raise here would MASK
                # the original training exception
                logger.warning("trace export failed: %s", e)

    def export_trace(self, path: str) -> int:
        """Export the collected host trace spans as a Chrome-trace JSON file
        (Perfetto / chrome://tracing loadable), the pinned set-up spans
        first; returns the event count. Runs automatically at run end when
        telemetry is on; callable any time for an on-demand snapshot of a
        live run."""
        return self._tracer.export_chrome_trace(path)

    def _abort_run(self) -> None:
        """Sits in the fit loop's ``except BaseException: ...; raise``:
        run_end with status="error" before the raise unwinds (guardrail
        halt, watchdog halt, feed error). An ``except`` clause — NOT
        ``sys.exc_info()`` in the ``finally`` — because exc_info also
        reports an OUTER handled exception (fit() called inside an except
        block, e.g. the crash-recovery resume pattern) and would mislabel a
        successful recovery fit as an error. (Reading exc_info HERE is safe:
        this method only runs inside the except clause, where it is by
        construction the in-flight exception.) The success path emits after
        the final checkpoint save instead (see _end_run). Dumps the flight
        recorder LAST, after run_end — so the dump's event ring carries the
        terminal run_end record too."""
        import sys
        exc = sys.exc_info()[1]
        self._end_run("error")
        if self._blackbox is not None:
            from glint_word2vec_tpu.obs.blackbox import FlightRecorder
            self._blackbox.dump(
                FlightRecorder.exception_cause(exc) if exc is not None
                else None,
                extra=self._dump_context())

    def _finish_round(
        self,
        real: int,
        real_pairs: float,
        alphas: np.ndarray,            # [K] per-batch alphas of this round
        metrics: StepMetrics,
        state: TrainState,             # global_step is filled in here
        checkpoint_path: Optional[str],
        checkpoint_every_steps: Optional[int],
        on_heartbeat: Optional[Callable[[HeartbeatRecord], None]],
    ) -> None:
        """Post-dispatch bookkeeping of the fit loop, whatever the feed: progress counters,
        heartbeat cadence (the reference's every-10k-words line, mllib:404-413 —
        fetching device metrics forces a sync, so it runs on a chunked cadence to keep
        the async dispatch pipeline full), the non-finite guardrail + scripted fault
        hooks (train/faults.py), and periodic checkpointing."""
        cfg = self.config
        self.global_step += real
        self._pairs_since_log += real_pairs
        self.pairs_trained += real_pairs
        self.state = dc_replace(state, global_step=self.global_step)
        # the lr scale THIS round's chunk actually dispatched under — a
        # recovery below backs _lr_scale off for the NEXT dispatch, and the
        # heartbeat must not retroactively report the new scale for a chunk
        # trained at the old one
        lr_scale_at_dispatch = self._lr_scale
        if self._blackbox is not None:
            # one tiny record per round: the finest-grained trace of what the
            # run was doing right before a death (heartbeats are 1-in-N)
            self._blackbox.note_dispatch(
                self.global_step, real,
                self.dispatch_time - self._bb_disp_mark,
                self.host_wait_time - self._bb_wait_mark)
            self._bb_disp_mark = self.dispatch_time
            self._bb_wait_mark = self.host_wait_time

        if faults.take_nan_injection(self.global_step):
            if self._poison_fn is None:
                self._poison_fn = jax.jit(lambda p: p._replace(
                    syn0=p.syn0.at[0, 0].set(
                        jnp.asarray(jnp.nan, p.syn0.dtype))))
            self.params = self._poison_fn(self.params)
        scale = faults.take_scale_injection(self.global_step)
        if scale:
            if self._scale_fn is None:
                self._scale_fn = jax.jit(lambda p, f: jax.tree.map(
                    lambda x: x * f.astype(x.dtype), p))
            self.params = self._scale_fn(self.params, jnp.float32(scale))
        faults.crash_at_step(self.global_step)
        faults.maybe_stall(self.global_step)

        # jax.profiler window (config.profile_steps): stop the trace once the
        # configured number of steps completed after fit start
        if (self._profiling and cfg.profile_steps
                and self.global_step - self._profile_start_step
                >= cfg.profile_steps):
            self._stop_profiler()
            logger.info("jax.profiler window closed after %d steps",
                        self.global_step - self._profile_start_step)

        ckpt_due = bool(checkpoint_path and checkpoint_every_steps
                        and self.global_step % checkpoint_every_steps < real)
        hb_due = (self.global_step - self._last_log_step
                  >= cfg.heartbeat_every_steps)
        if hb_due:
            # the heartbeat round as one span tree on this thread
            # (docs/observability.md §4): heartbeat.drain, health_probe,
            # device_block, heartbeat.callback, then heartbeat.refill, which
            # _after_dispatch ends with the round once the next chunk is
            # enqueued
            self._open_round_span(
                "heartbeat", step=self.global_step,
                steps=self.global_step - self._last_log_step)
        # ONE fused probe per probing round (obs/probe.py): finiteness for the
        # guardrail + the norm channels for the watchdog and the heartbeat
        channels: Optional[dict] = None
        if hb_due and (cfg.nonfinite_policy != "none"
                       or cfg.norm_watch != "off"
                       or self._telemetry is not None):
            channels = self._health_stats()
        if cfg.nonfinite_policy != "none" and hb_due and not ckpt_due:
            # heartbeat-cadence probe; checkpoint rounds are covered by the
            # guard inside save_checkpoint itself (every save — periodic AND
            # the end-of-fit finished save — is probed exactly once, so a
            # blown-up state never overwrites the on-disk good checkpoint)
            self._nonfinite_guard(channels)
        elif (channels is not None and channels["finite"]
              and cfg.nonfinite_policy == "none"):
            # the guard isn't in play (policy "none"), but ring consumers
            # (norm_watch="recover") still need heartbeat-cadence snapshots;
            # with a policy set, checkpoint rounds snapshot through the
            # save-side guard sharing this probe
            self._maybe_snapshot(channels)
        if channels is not None and channels["finite"]:
            # the finite-blowup watchdog (config.norm_watch, obs/watch.py):
            # only meaningful on a finite carry — a non-finite one is the
            # guardrail's jurisdiction above (inf rows would trivially trip
            # every norm channel on the way down a rollback)
            self._watchdog_check(channels)

        if hb_due:
            now = time.perf_counter()
            pps = self._pairs_since_log / max(now - self._last_log_time, 1e-9)
            self._pairs_since_log = 0.0
            # EXPLICIT fetch of the [K]-sized metric vectors, then host-side
            # indexing: device-side `metrics.loss[real - 1]` dispatches a
            # gather whose index operand rides an IMPLICIT int32 host→device
            # transfer — the regression class the stepaudit transfer guard
            # disallows, reachable here only on heartbeat rounds (which the
            # audit's scripted fits are too short to hit; tests/test_obs.py
            # runs a probing fit under the guard to keep this path honest)
            with self._tracer.span("device_block") as blocked:
                (loss_k, fpos_k, pairs_k, rows0_k, rows1_k, rows_sw_k,
                 slots_sw_k, gather_sw_k, nodes_hs_k, assembly_k,
                 slots0_k, slots1_k, pos) = jax.device_get(
                    (metrics.loss, metrics.mean_f_pos, metrics.pairs,
                     metrics.syn0_rows, metrics.syn1_rows,
                     metrics.subword_rows, metrics.subword_slots,
                     metrics.subword_gather_slots, metrics.hs_nodes,
                     metrics.assembly_rows, metrics.syn0_slots,
                     metrics.syn1_slots, self.params.pos))
                if pairs_k[real - 1] > 0:
                    # how far the step coalesced each table's update: 1.0
                    # plain, heads over pairs where runs were summed first
                    # (syn0's by center, syn1's by context; the banded CBOW
                    # step's both by token over its live examples: ~0.67 and
                    # ~0.55 coalesced, ~1.26 a block over a cap, and syn1's
                    # alone beside the token row source)
                    # and the forward assembly over a model axis, where the
                    # step gathers by the same runs: both caps and the pool
                    # over the pairs (~0.59 on the tight rung, ~0.72 on the
                    # roomy one), 2B + P over them plain (2.03);
                    # and the static rows each scatter was handed, padding
                    # and all (the shared-pool SGNS step: the cap of the rung
                    # the batch took, ~0.28 tight, 0.375 / 0.3125 roomy)
                    for name, rows_k in (("syn0_rows_per_pair", rows0_k),
                                         ("syn1_rows_per_pair", rows1_k),
                                         ("assembly_rows_per_pair", assembly_k),
                                         ("syn0_slots_per_pair", slots0_k),
                                         ("syn1_slots_per_pair", slots1_k)):
                        if rows_k is not None:
                            blocked.set(**{name: float(
                                rows_k[real - 1] / pairs_k[real - 1])})
                if rows_sw_k is not None and pairs_k[real - 1] > 0:
                    # rows of the subword lists (the centers', or a CBOW
                    # block's tokens') that reached syn0's scatter live
                    # (config.subword), over the step's pairs or examples
                    blocked.set(subword_rows_per_pair=float(
                        rows_sw_k[real - 1] / pairs_k[real - 1]))
                if slots_sw_k is not None and pairs_k[real - 1] > 0:
                    # slots the lists' scatter was handed, live or padding
                    # (a token block's, the word heads' of a skip-gram batch):
                    # the slot capacity's engagement counter
                    blocked.set(subword_slots_per_pair=float(
                        slots_sw_k[real - 1] / pairs_k[real - 1]))
                if gather_sw_k is not None and pairs_k[real - 1] > 0:
                    # and its list gather: the tail capacity's
                    blocked.set(subword_gather_slots_per_pair=float(
                        gather_sw_k[real - 1] / pairs_k[real - 1]))
                if nodes_hs_k is not None and pairs_k[real - 1] > 0:
                    # live (pair, node) terms of a hierarchical-softmax step
                    # (config.loss="hs") over its pairs: the mean path length
                    # of the batch's contexts; a path cut short reads lower
                    blocked.set(hs_path_nodes_per_pair=float(
                        nodes_hs_k[real - 1] / pairs_k[real - 1]))
                if pos is not None:
                    # how far the position weights have moved from the ones
                    # they start at, |pos − 1| / |1|: 0 = the leaf is not
                    # training, non-finite = it diverged
                    away = np.asarray(
                        pos, np.float64)[:, :self.config.vector_size] - 1.0
                    blocked.set(position_drift=float(
                        np.sqrt((away * away).sum() / away.size)))
            if self._first_beat is not None:
                self._record_first_heartbeat()
            # per-phase attribution over THIS heartbeat window (obs/
            # phases.py): delta of the accumulator the spans + wait sites
            # have been feeding since the previous heartbeat
            phases_window = None
            if self._phases.enabled:
                phases_window = self._phases.delta(
                    self._last_hb_phases) or None
                self._last_hb_phases = self._phases.raw_snapshot()
            rec = HeartbeatRecord(
                words=self.state.words_processed,
                # the EFFECTIVE lr: recovery backoff multiplies the
                # dispatched alphas at _stage_dispatch_meta
                alpha=float(alphas[real - 1]) * lr_scale_at_dispatch,
                loss=float(loss_k[real - 1]),
                mean_f_pos=float(fpos_k[real - 1]),
                pairs_per_sec=pps,
                global_step=self.global_step,
                host_wait_s=self.host_wait_time - self._last_hb_host_wait,
                dispatch_s=self.dispatch_time - self._last_hb_dispatch,
                norms=channels,
                recoveries=self.recoveries_performed,
                lr_scale=lr_scale_at_dispatch,
                phases=phases_window,
                sync_every=int(cfg.sync_every),
                merge_round=(self.global_step // cfg.sync_every
                             if cfg.sync_every > 1 else -1))
            self._last_hb_host_wait = self.host_wait_time
            self._last_hb_dispatch = self.dispatch_time
            self.heartbeats.append(rec)
            logger.info(
                "wordCount = %d, alpha = %.6f, loss = %.4f, fPlus = %.4f, "
                "pairs/s = %.0f", rec.words, rec.alpha, rec.loss,
                rec.mean_f_pos, rec.pairs_per_sec)
            if self._telemetry is not None:
                self._emit(
                    "heartbeat", step=rec.global_step, words=rec.words,
                    alpha=rec.alpha, loss=rec.loss,
                    mean_f_pos=rec.mean_f_pos,
                    pairs_per_sec=round(rec.pairs_per_sec, 3),
                    host_wait_s=round(rec.host_wait_s, 6),
                    dispatch_s=round(rec.dispatch_s, 6),
                    recoveries=int(rec.recoveries),
                    lr_scale=round(float(rec.lr_scale), 9),
                    # local-SGD runs only: the synchronous default keeps the
                    # pre-knob record shape byte-identical
                    **({"sync_every": rec.sync_every,
                        "merge_round": rec.merge_round}
                       if rec.sync_every > 1 else {}),
                    **({"norms": channels} if channels is not None else {}),
                    **({"phases": phases_window} if phases_window else {}))
            if on_heartbeat is not None:
                # the caller's time, not the program's: kept apart from the
                # fit's own spans in a profile
                with self._tracer.span("heartbeat.callback"):
                    on_heartbeat(rec)
            self._last_log_time, self._last_log_step = now, self.global_step

        if ckpt_due:
            # share this round's probe fetch with the save-side guard — the
            # params are unchanged since _health_stats above, and a second
            # full [V, D] reduction + sync per coincident round is the probe
            # cost this method's single-probe rule exists to avoid
            self.save_checkpoint(checkpoint_path, _channels=channels)

        # preemption drain (config.checkpoint_on_preempt): the SIGTERM
        # handler only ARMED _preempt_deadline — this is the first point
        # after it where the in-flight dispatch has completed and no
        # collective is mid-flight, so the emergency save can run the
        # normal atomic path. Never returns.
        if getattr(self, "_preempt_deadline", None) is not None:
            self._preempt_exit(checkpoint_path, channels)

        if hb_due:
            # the drain left the device with nothing queued: it waits from
            # here until the next chunk reaches it, inside this span
            self._open_round_span("heartbeat.refill")

    def _preempt_exit(self, checkpoint_path: Optional[str],
                      channels: Optional[dict]) -> None:
        """The deferred half of the SIGTERM preemption path (_on_sigterm
        armed it; _finish_round's tail calls it): within the remaining
        deadline budget, drain the carry through the normal digest-verified
        atomic save (save_checkpoint's np.asarray blocks on the async
        dispatch, and its nonfinite/norm guard still vetoes a blown-up
        carry — never a torn or unverified emergency save; the atomic
        protocol leaves the previous verified checkpoint in place on any
        failure). Then the ``preempt`` telemetry record, run_end with
        status="preempted", a final flight-recorder dump whose event ring
        carries both terminal records, and the re-raised signal under the
        restored disposition so the sender sees the exit code it expects
        (rc = -SIGTERM). Never returns."""
        import os
        signum = self._preempt_signum or 15
        remaining = self._preempt_deadline - time.monotonic()
        steps_since_save = int(self.global_step) - int(self._last_save_step)
        saved = False
        if checkpoint_path and steps_since_save == 0:
            # a ckpt_due save already published at this very step (the
            # coincident round) — zero progress to lose, nothing to rewrite
            saved = True
        elif checkpoint_path and remaining > 0:
            try:
                self.save_checkpoint(checkpoint_path, _channels=channels)
                saved = True
            except BaseException as e:  # noqa: BLE001 — the guard raising
                # on a non-finite carry, or I/O dying under eviction
                # pressure: fall back to the blackbox-only exit
                logger.warning(
                    "emergency checkpoint failed (%s); falling back to "
                    "blackbox-only exit", e)
        else:
            logger.warning(
                "preempt deadline missed by %.1fs — blackbox-only exit",
                max(-remaining, 0.0))
        self._emit("preempt", step=int(self.global_step), saved=saved,
                   checkpoint=checkpoint_path or "",
                   deadline_s=float(self.config.preempt_deadline_s),
                   steps_since_save=0 if saved else steps_since_save)
        self._end_run("preempted")
        if self._blackbox is not None:
            from glint_word2vec_tpu.obs.blackbox import FlightRecorder
            self._blackbox.dump(FlightRecorder.signal_cause(signum),
                                extra=self._dump_context())
        os.kill(os.getpid(), signum)

    def _start_peer_beacons(self, checkpoint_path: Optional[str]):
        """Arm the per-process liveness beacons of a multi-process fit
        (train/supervisor.py BeaconBoard; docs/robustness.md): each process
        heartbeats a tiny file beside the checkpoint path, and the
        main-thread ``check_or_raise`` before every allgather turns a dead
        peer into a clean PeerDeathError abort instead of an eternal
        collective hang (the board's watcher thread hard-exits the process
        if it IS already wedged inside the collective). Returns None when
        off (``peer_beacon_s=0``), when single-process, or when there is no
        checkpoint path to anchor the beacon directory to."""
        import os
        if self.config.peer_beacon_s <= 0 or not checkpoint_path:
            return None
        import jax
        if jax.process_count() <= 1:
            return None
        from glint_word2vec_tpu.train.supervisor import BeaconBoard
        board = BeaconBoard(
            os.path.join(os.path.dirname(os.path.abspath(checkpoint_path)),
                         "beacons"),
            process_index=jax.process_index(),
            num_processes=jax.process_count(),
            interval_s=self.config.peer_beacon_s)
        board.start()
        return board

    def _batch_stream(self, sentences: Sequence[np.ndarray], iteration: int,
                      shard: int = 0, num_shards: int = 1):
        """One iteration's batches: the whole stream, or one process's shard of
        it (feeds.GatheredPairs), a ``num_shards``-th of the batch each."""
        cfg = self.config
        common = dict(
            pairs_per_batch=cfg.pairs_per_batch // num_shards, window=cfg.window,
            subsample_ratio=cfg.subsample_ratio, seed=cfg.seed, iteration=iteration,
            shard=shard, num_shards=num_shards,
            shuffle=cfg.shuffle, producer_workers=cfg.producer_workers)
        # batches are prefix-masked by construction (PairBatcher pads only the tail),
        # so only the real count ships — the device rebuilds mask = (iota < real)
        if cfg.cbow:
            for b in epoch_batches_cbow(sentences, self.vocab, **common):
                yield {"centers": b.centers, "contexts": b.contexts,
                       "nctx": b.n_ctx, "real": b.num_real,
                       "words_seen": b.words_seen}
        else:
            for b in epoch_batches(sentences, self.vocab, **common):
                yield {"centers": b.centers, "contexts": b.contexts,
                       "real": b.num_real_pairs, "words_seen": b.words_seen}

    # -- export / persistence ----------------------------------------------------------

    def unpadded_params(self) -> EmbeddingPair:
        V, D = self.vocab.size, self.config.vector_size
        return EmbeddingPair(syn0=self.params.syn0[:V, :D],
                             syn1=self.params.syn1[:V, :D])

    def subword_buckets(self) -> Optional[jax.Array]:
        """syn0's bucket rows [subword_buckets, D] (they follow the
        vocabulary's rows), None where the model is not subword."""
        if not self.config.subword:
            return None
        V = self.vocab.size
        return self.params.syn0[V:V + self.config.subword_buckets,
                                :self.config.vector_size]

    def subword_rows(self):
        """The vocabulary's row table as the step reads it (an
        ops/subword.SubwordTable on the device) and its longest list's
        groups, for the model built after the fit: it composes its query
        table from them and need not build them again. None where the model
        is not subword."""
        if not self.config.subword:
            return None
        from glint_word2vec_tpu.ops.subword import SubwordTable
        return SubwordTable(*self._step_extra), self._subword_shape.max_groups

    def position_weights(self) -> Optional[jax.Array]:
        """The position weights [2·window, D] (config.cbow_position_weights),
        None on every other model. Part of the trained state, saved and
        restored, and no part of a served vector."""
        if self.params.pos is None:
            return None
        return self.params.pos[:, :self.config.vector_size]

    def save_checkpoint(self, path: str,
                        _channels: Optional[dict] = None) -> None:
        if self.config.nonfinite_policy != "none":
            # every save — periodic and the finished end-of-fit one — runs the
            # guardrail first: 'halt' refuses to replace the last good on-disk
            # checkpoint with NaNs, 'rollback' saves the restored snapshot.
            # _channels: a probe result fetched THIS round with no dispatch
            # since (the coincident heartbeat+checkpoint round) — reused so
            # the round pays one probe, not two
            self._nonfinite_guard(_channels)
        from glint_word2vec_tpu.parallel.distributed import is_multiprocess
        # additive metadata every save carries (periodic saves included, so a
        # SIGTERM mid-increment leaves the provenance in place): the continual
        # driver parks the vocab_lineage chain here (continual/loop.py)
        extra = self.extra_checkpoint_meta or None
        if self.config.sharded_checkpoint or is_multiprocess():
            # row-shards layout: each process writes its own rows, no host gather
            from glint_word2vec_tpu.train.checkpoint import save_model_sharded
            save_model_sharded(
                path, self.vocab.words, self.vocab.counts,
                self.params.syn0, self.params.syn1, self.config, self.state,
                vocab_size=self.vocab.size, vector_size=self.config.vector_size,
                extra_metadata=extra)
        else:
            p = self.unpadded_params()
            buckets, pos = self.subword_buckets(), self.position_weights()
            save_model(
                path, self.vocab.words, self.vocab.counts,
                np.asarray(p.syn0), np.asarray(p.syn1),
                self.config, self.state, extra_metadata=extra,
                subword_buckets=(None if buckets is None
                                 else np.asarray(buckets)),
                position_weights=None if pos is None else np.asarray(pos))
        logger.info("checkpoint saved to %s at step %d", path, self.global_step)
        # the preempt record's progress-lost denominator (docs/robustness.md)
        self._last_save_step = int(self.global_step)
        if self._telemetry is not None or self._blackbox is not None:
            # the publish-side correlation record (obs/trace.py): carries
            # the freshly-written checkpoint's publish_sig — the SAME
            # string the serving watcher and fleet router compare — so the
            # collector joins save → watcher detect → per-replica reload
            # into one causal chain. Through _emit, not the sink directly,
            # so the flight recorder's event ring mirrors it.
            from glint_word2vec_tpu.serve.reload import (
                publish_signature, publish_signature_str)
            sig = publish_signature_str(publish_signature(path))
            if sig is not None:
                self._emit("publish", publish_sig=sig, checkpoint=path,
                           step=int(self.global_step), publisher="trainer")
