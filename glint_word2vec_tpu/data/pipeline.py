"""Host-side data pipeline: index → subsample → dynamic window → fixed-shape pair batches.

Replaces the reference's three per-iteration RDD stages (components C4/C5/C6):

- sentence indexing + chunking to maxSentenceLength (mllib:335-343),
- frequency subsampling (mllib:371-379),
- dynamic context-window generation (mllib:381-390),

with vectorized NumPy producing **fixed-shape padded (center, context, mask) batches** — the
shape discipline jit/pjit needs, replacing the reference's ragged Scala arrays.

Behavioral notes vs. the reference (intentional divergences, each covered by a unit test):

- Subsampling: the reference computes ``percentageCn = vocabCns(word) / trainWordsCount`` in
  *integer* division (mllib:374-376, Int/Long → Long), which truncates to 0 and makes the
  keep-probability +Inf — i.e. subsampling in the reference is a silent no-op. We implement
  the evidently intended float formula ``keep = (sqrt(pct/ratio) + 1) * (ratio/pct)`` with
  ``pct = count/train_words_count`` (the classic word2vec rule the code was transcribing).
- Window: the reference draws ``b = nextInt(window)`` (uniform 0..window-1) and takes context
  positions ``[max(0, i-b), min(i+b, len))`` excluding ``i`` (mllib:384-388) — note the upper
  bound is *exclusive*, so the right context is one short (b-1 words). We reproduce this
  exactly by default for parity (``legacy_asymmetric_window=True``); the symmetric variant is
  available for quality.
- RNG: the reference's per-partition XORShift seeding (``seed ^ ((idx+1)<<16) ^ ((-k-1)<<8)``,
  mllib:372) is reproduced in spirit: each (iteration, shard) gets an independent
  ``numpy.random.Generator`` derived from (seed, iteration, shard) so runs are deterministic.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Iterator, List, Sequence, Tuple

import numpy as np

from glint_word2vec_tpu.data.vocab import Vocabulary


def ordered_pool_map(fn, jobs: Iterable, workers: int, ahead: int = 2):
    """Map ``fn`` over ``jobs`` on a thread pool, yielding results in job order.

    The host feed's parallelism primitive (PERF.md §10): every job is a pure
    function of its inputs (the streams are position-keyed — hashrng — not
    sequential-RNG), so running them concurrently and consuming in submission
    order yields the bit-identical stream at ANY worker count. ``workers <= 1``
    degrades to a plain serial loop (no pool, no thread — exactly the
    pre-round-8 producer). At most ``workers + ahead`` jobs are in flight, so
    a slow consumer bounds memory.
    """
    if workers <= 1:
        for job in jobs:
            yield fn(job)
        return
    import collections
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=workers,
                              thread_name_prefix="glint-feed-worker")
    pending: "collections.deque" = collections.deque()
    try:
        cap = workers + ahead
        for job in jobs:
            pending.append(pool.submit(fn, job))
            if len(pending) >= cap:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()
    finally:
        while pending:
            pending.pop().cancel()
        pool.shutdown(wait=False, cancel_futures=True)


def stream_rng(seed: int, iteration: int, shard: int) -> np.random.Generator:
    """The batch stream's RNG: deterministic per (seed, iteration, shard) — the analog
    of the reference's XORShift reseed ``seed ^ ((idx+1)<<16) ^ ((-k-1)<<8)``
    (mllib:372,382). The uint64 mask is the single place the host pipeline normalizes
    user seeds (compat setSeed accepts the reference's full Long surface, and
    SeedSequence rejects negative entropy); the device-side negative sampler applies
    its own uint32 mask in the trainer — the two streams are independent by design."""
    return np.random.default_rng(
        np.random.SeedSequence(entropy=seed & 0xFFFFFFFFFFFFFFFF,
                               spawn_key=(iteration, shard)))


def encode_sentences(
    sentences: Iterable[Sequence[str]],
    vocab: Vocabulary,
    max_sentence_length: int = 1000,
) -> List[np.ndarray]:
    """Words → vocab indices, OOV dropped, chunked to max_sentence_length (mllib:335-343)."""
    index = vocab.index
    out: List[np.ndarray] = []
    for sentence in sentences:
        ids = [index[w] for w in sentence if w in index]
        if not ids:
            continue
        arr = np.asarray(ids, dtype=np.int32)
        for start in range(0, len(arr), max_sentence_length):
            chunk = arr[start:start + max_sentence_length]
            if chunk.size:
                out.append(chunk)
    return out


def keep_probabilities(
    counts: np.ndarray, train_words_count: int, subsample_ratio: float
) -> np.ndarray:
    """Per-word keep probability ``(sqrt(pct/ratio)+1)*(ratio/pct)`` (intended semantics of
    mllib:374-377; see module docstring for the reference's integer-division bug)."""
    if subsample_ratio <= 0:
        return np.ones(counts.shape[0], dtype=np.float64)  # disabled (the reference's
        # observed behavior at any setting, due to its integer-division bug)
    pct = counts.astype(np.float64) / float(train_words_count)
    ratio = float(subsample_ratio)
    keep = (np.sqrt(pct / ratio) + 1.0) * (ratio / pct)
    return np.minimum(keep, 1.0)


# keep_probabilities is sqrt(y) + y with y = ratio * T / count: under 1
# exactly where sqrt(y) < (sqrt(5) - 1) / 2, i.e. where count > ratio * T / this
_KEEP_SPLIT = ((5.0 ** 0.5 - 1.0) / 2.0) ** 2


class KeptCountTable:
    """``(counts * keep_probabilities(counts, T, ratio))``'s sum and max at any
    ratio, from two running sums over the counts made once.

    A word's kept count is ``sqrt(ratio*T*c) + ratio*T`` where its keep
    probability is under 1 (``c > ratio*T / _KEEP_SPLIT``: the HEAD of the
    counts in descending order) and ``c`` where it is 1 (the tail), so with
    ``j`` the head's length, one ``searchsorted``,

        sum = sqrt(ratio*T) * sum_{i<j} sqrt(c_i) + ratio*T * j + sum_{i>=j} c_i

    and the max is the most frequent word's: the kept count grows with the
    count in the head, and meets the tail's ``c`` at the split. Float64
    throughout, like the pass it stands for; three arrays of V doubles."""

    def __init__(self, counts: np.ndarray, train_words_count: int):
        c = np.asarray(counts, np.float64)
        # a Vocabulary's counts lie in descending order; a hand-made one's may not
        if not np.all(c[:-1] >= c[1:]):
            c = np.ascontiguousarray(np.sort(c)[::-1])
        self._desc = c
        self._train_words_count = train_words_count
        self._sqrt_before = np.zeros(c.size + 1)
        np.sqrt(c, out=self._sqrt_before[1:])
        np.cumsum(self._sqrt_before[1:], out=self._sqrt_before[1:])
        # whole counts add exactly in float64 (below 2**53 words), so the
        # tail's sum, total - before, loses nothing
        self._count_before = np.zeros(c.size + 1)
        np.cumsum(c, out=self._count_before[1:])

    def kept(self, subsample_ratio: float) -> Tuple[float, float]:
        """``(sum, max)`` of the kept counts under ``subsample_ratio``;
        subsampling off (``<= 0``) is all tail."""
        c = self._desc
        if subsample_ratio <= 0:
            head, scale = 0, 0.0
        else:
            scale = float(subsample_ratio) * float(self._train_words_count)
            # the ascending view of the descending counts: how many are <= the split
            head = c.size - int(np.searchsorted(
                c[::-1], scale / _KEEP_SPLIT, side="right"))
        total = (scale ** 0.5 * self._sqrt_before[head] + scale * head
                 + (self._count_before[-1] - self._count_before[head]))
        # the first word's, as the pass computed it (0 over no words at all)
        top = (c[:1] * keep_probabilities(
            c[:1], self._train_words_count, subsample_ratio)).max(initial=0.0)
        return float(total), float(top)


def expected_kept_words(
    counts: np.ndarray, train_words_count: int, subsample_ratio: float
) -> int:
    """Expected number of words surviving subsampling per iteration — the lr-decay clock
    total. The reference uses the raw trainWordsCount (mllib:363) because its subsampling
    keeps everything (no-op bug); with real subsampling the clock must count what the
    stream actually yields or alpha never reaches its floor."""
    keep = keep_probabilities(counts, train_words_count, subsample_ratio)
    return int(np.round((counts * keep).sum()))


def subsample_sentence(
    sentence: np.ndarray, keep_prob: np.ndarray, rng: np.random.Generator
) -> np.ndarray:
    """Drop frequent words: keep word w with probability keep_prob[w] (mllib:371-379)."""
    draws = rng.random(sentence.shape[0])
    return sentence[draws <= keep_prob[sentence]]


def dynamic_window_pairs(
    sentence: np.ndarray,
    window: int,
    rng: np.random.Generator,
    legacy_asymmetric_window: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """(center, context) index pairs with per-position random window shrink.

    Reference behavior (mllib:384-388): ``b = nextInt(window)`` ∈ [0, window), context
    positions ``p ∈ [max(0, i-b), min(i+b, len))``, ``p != i`` — i.e. b words of left
    context, b-1 of right. With ``legacy_asymmetric_window=False`` the right bound becomes
    inclusive (b both sides), the classic word2vec shape.

    Vectorized: per-position left/right context lengths → ragged arange, no Python loop.
    Returns (centers, contexts), both int32 [num_pairs].
    """
    L = sentence.shape[0]
    if L == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32))
    positions = np.arange(L, dtype=np.int64)
    b = rng.integers(0, window, size=L)  # nextInt(window): 0..window-1
    left = np.minimum(b, positions)
    right_extent = b if not legacy_asymmetric_window else b - 1
    right = np.clip(np.minimum(right_extent, L - 1 - positions), 0, None)
    total = left + right
    num_pairs = int(total.sum())
    if num_pairs == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32))
    centers_pos = np.repeat(positions, total)
    # Ragged per-group offset 0..total_i-1
    group_starts = np.cumsum(total) - total
    offsets = np.arange(num_pairs, dtype=np.int64) - np.repeat(group_starts, total)
    left_rep = np.repeat(left, total)
    # offsets < left → left context (i-left+k); offsets >= left → right context, skip center
    ctx_pos = centers_pos - left_rep + offsets + (offsets >= left_rep)
    return (sentence[centers_pos].astype(np.int32), sentence[ctx_pos].astype(np.int32))


@dataclass
class PairBatch:
    """One fixed-shape device batch of training pairs.

    mask is 1.0 for real pairs, 0.0 for padding; padded center/context indices are 0 but
    contribute zero gradient because the step multiplies through by mask.
    ``words_seen`` is the cumulative count of (subsampled) training words up to and including
    this batch within the current shard — the reference's ``wordCount`` lr-decay clock
    (mllib:405-413).
    """

    centers: np.ndarray    # int32 [B]
    contexts: np.ndarray   # int32 [B]
    mask: np.ndarray       # float32 [B]
    words_seen: int
    num_real_pairs: int


class PairBatcher:
    """Accumulates N parallel ragged streams into fixed-size batches along axis 0.

    Used with 2 streams (centers, contexts) for skip-gram and 3 (centers, contexts [B,C],
    ctx_mask [B,C]) for CBOW — one implementation of the accumulate / slice-full-batches /
    carry-remainder / pad-last invariants.
    """

    def __init__(self, pairs_per_batch: int, num_streams: int = 2):
        self.B = int(pairs_per_batch)
        self.num_streams = num_streams
        self._bufs: List[List[np.ndarray]] = [[] for _ in range(num_streams)]
        self._buffered = 0

    def add(self, *arrays: np.ndarray) -> None:
        assert len(arrays) == self.num_streams
        if arrays[0].shape[0] == 0:
            return
        for buf, arr in zip(self._bufs, arrays):
            buf.append(arr)
        self._buffered += arrays[0].shape[0]

    def _pop_full(self) -> Iterator[Tuple]:
        if self._buffered < self.B:
            return
        cats = [np.concatenate(buf) for buf in self._bufs]
        n_full = cats[0].shape[0] // self.B
        for i in range(n_full):
            sl = slice(i * self.B, (i + 1) * self.B)
            yield (*(c[sl] for c in cats), self.B)
        rest = [c[n_full * self.B:] for c in cats]
        self._buffered = rest[0].shape[0]
        self._bufs = [[r] if self._buffered else [] for r in rest]

    def drain(self, flush: bool = False) -> Iterator[Tuple]:
        """Yields ``(*stream_slices, num_real)`` tuples of exactly B rows each. With
        ``flush``, the remainder is zero-padded to B and ``num_real < B`` marks it."""
        yield from self._pop_full()
        if flush and self._buffered:
            cats = [np.concatenate(buf) for buf in self._bufs]
            n = cats[0].shape[0]
            pad = self.B - n
            padded = [
                np.concatenate([c, np.zeros((pad, *c.shape[1:]), c.dtype)])
                for c in cats
            ]
            self._bufs = [[] for _ in range(self.num_streams)]
            self._buffered = 0
            yield (*padded, n)


def _block_pairs(
    tokens: np.ndarray,          # int32 [N] concatenated sentence tokens
    lengths: np.ndarray,         # int64 [S] sentence lengths (sum == N)
    keep: np.ndarray,            # float32 [V] per-word keep probability
    window: int,
    seed: int,
    iteration: int,
    shard: int,
    token_base: int,             # raw-token ordinal of this block's first token
    legacy_asymmetric_window: bool,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Subsample + dynamic-window pair generation for a whole block of sentences in a
    handful of vectorized ops (no per-sentence Python loop — the hot host path; a
    per-sentence equivalent exists as :func:`subsample_sentence` +
    :func:`dynamic_window_pairs` for unit-testing the formulas). All randomness is
    position-keyed (:mod:`.hashrng`), so the native C++ generator
    (``native/pairgen.cpp``) produces this exact stream in parallel.

    Returns (centers, contexts, center_word_index, words_kept) where
    ``center_word_index[p]`` is the kept-word ordinal (within this block) of pair p's
    center — the per-pair lr-decay clock, so downstream batches can credit exactly the
    words consumed *up to each batch* rather than the whole block at once."""
    prologue = _subsample_and_window(
        tokens, lengths, keep, window, seed, iteration, shard, token_base,
        legacy_asymmetric_window)
    if prologue is None:
        return (np.empty(0, np.int32), np.empty(0, np.int32),
                np.empty(0, np.int64), 0)
    toks, left, total, Nk = prologue
    num_pairs = int(total.sum())
    if num_pairs == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32),
                np.empty(0, np.int64), int(Nk))
    center_flat = np.repeat(np.arange(Nk, dtype=np.int64), total)
    group_starts = np.cumsum(total) - total
    offsets = np.arange(num_pairs, dtype=np.int64) - np.repeat(group_starts, total)
    left_rep = np.repeat(left, total)
    ctx_flat = center_flat - left_rep + offsets + (offsets >= left_rep)
    return (toks[center_flat].astype(np.int32), toks[ctx_flat].astype(np.int32),
            center_flat + 1, int(Nk))


def _subsample_and_window(
    tokens: np.ndarray,
    lengths: np.ndarray,
    keep: np.ndarray,
    window: int,
    seed: int,
    iteration: int,
    shard: int,
    token_base: int,
    legacy_asymmetric_window: bool,
):
    """Shared prologue of :func:`_block_pairs` and :func:`_block_cbow` — one place
    owns the subsample/window stream contract (mirrored bit-identically by
    native/pairgen.cpp and ops/pairgen.py).

    Returns (kept_tokens, left, total, Nk) where ``left[i]``/``total[i]`` are pair
    counts to the left / in total of kept position i under the per-position window
    draw, or None for an empty block."""
    from glint_word2vec_tpu.data.hashrng import (
        STREAM_SUBSAMPLE, STREAM_WINDOW, hash_mod_at, hash_u01_at, stream_base)

    N = tokens.shape[0]
    if N == 0:
        return None
    ordinals = np.arange(token_base, token_base + N, dtype=np.uint64)
    sent_ids = np.repeat(np.arange(lengths.shape[0]), lengths)
    # subsample the whole block at once (mllib:371-379 semantics)
    sub_base = stream_base(seed, STREAM_SUBSAMPLE, iteration, shard)
    kept_mask = hash_u01_at(sub_base, ordinals) <= keep.astype(np.float32)[tokens]
    toks = tokens[kept_mask]
    sids = sent_ids[kept_mask]
    Nk = toks.shape[0]
    if Nk == 0:
        return None
    # per-sentence positions after subsampling
    new_lengths = np.bincount(sids, minlength=lengths.shape[0])
    new_starts = np.concatenate([[0], np.cumsum(new_lengths)])[:-1]
    pos = np.arange(Nk, dtype=np.int64) - new_starts[sids]
    slen = new_lengths[sids]
    # dynamic window draw (mllib:384-388), keyed by the RAW token ordinal so draws
    # are independent of the subsample outcome of other positions
    win_base = stream_base(seed, STREAM_WINDOW, iteration, shard)
    b = hash_mod_at(win_base, ordinals[kept_mask], window)
    left = np.minimum(b, pos)
    right_extent = b if not legacy_asymmetric_window else b - 1
    right = np.clip(np.minimum(right_extent, slen - 1 - pos), 0, None)
    total = (left + right).astype(np.int64)
    return toks, left, total, int(Nk)


def epoch_batches(
    sentences: Sequence[np.ndarray],
    vocab: Vocabulary,
    *,
    pairs_per_batch: int,
    window: int,
    subsample_ratio: float = 0.0,
    seed: int = 0,
    iteration: int = 1,
    shard: int = 0,
    num_shards: int = 1,
    shuffle: bool = True,
    legacy_asymmetric_window: bool = True,
    flush_last: bool = True,
    block_words: int = 1_000_000,
    backend: str = "auto",   # "auto" | "numpy" | "native" (C++ generator if built)
    producer_workers: int = 1,
) -> Iterator[PairBatch]:
    """One iteration's stream of fixed-shape pair batches for one data shard.

    Mirrors the reference's per-iteration pipeline (mllib:367-390): fresh subsample + fresh
    window draw each iteration, deterministic per (seed, iteration, shard) — the analog of
    the XORShift reseed ``seed ^ ((idx+1)<<16) ^ ((-k-1)<<8)`` at mllib:372,382.

    Sentences are round-robin assigned to shards (the analog of repartition, mllib:345)
    and processed in ~``block_words``-word blocks, each block fully vectorized
    (:func:`_block_pairs`) or handed to the multithreaded native generator
    (``native/pairgen.cpp``, bit-identical stream) — the host must outrun a TPU
    consuming millions of pairs/s.

    ``producer_workers > 1`` fans the per-slab generation across a thread pool
    (:func:`ordered_pool_map`): every slab's output is a pure function of
    (tokens, lengths, token_base) under the position-keyed hashrng draws, so
    the merged stream is bit-identical to the serial one at any worker count —
    only the batching/clock accumulation below stays serial. The NATIVE
    backend already fans each call over ``default_threads()`` C++ threads, so
    the pooled path DIVIDES that budget across the concurrent calls
    (``n_threads = default_threads() // workers``) — pools compose instead of
    multiplying into N×M oversubscription; the native stream is deterministic
    at any thread count.
    """
    if backend == "auto":
        from glint_word2vec_tpu.data.native import native_available
        use_native = native_available()
    else:
        use_native = backend == "native"
    if use_native:
        from glint_word2vec_tpu.data.native import block_pairs_native
    rng = stream_rng(seed, iteration, shard)
    keep = keep_probabilities(
        vocab.counts, vocab.train_words_count, subsample_ratio).astype(np.float32)
    order = np.arange(shard, len(sentences), num_shards)
    if shuffle:
        rng.shuffle(order)
    batcher = PairBatcher(pairs_per_batch, num_streams=3)
    words_base = 0   # kept words fully consumed in prior blocks
    words_seen = 0
    native_threads = 0
    if use_native and producer_workers > 1:
        from glint_word2vec_tpu.data.native import default_threads
        native_threads = max(1, default_threads() // producer_workers)

    def slab_jobs():
        token_base = 0  # raw tokens consumed in prior blocks (position-key base)
        for block in iter_sentence_slabs(sentences, order, block_words):
            yield block, token_base
            token_base += sum(int(s.shape[0]) for s in block)

    def run_slab(job):
        block, token_base = job
        tokens = np.concatenate(block) if len(block) > 1 else block[0]
        lengths = np.fromiter((s.shape[0] for s in block), np.int64, len(block))
        if use_native:
            return block_pairs_native(
                tokens, lengths, keep, window, seed, iteration, shard,
                token_base, legacy_asymmetric_window,
                n_threads=native_threads)
        return _block_pairs(tokens, lengths, keep, window, seed, iteration,
                            shard, token_base, legacy_asymmetric_window)

    for c, x, clock, kept in ordered_pool_map(
            run_slab, slab_jobs(), producer_workers):
        # The reference counts *subsampled* words into its decay clock (mllib:414); the
        # per-pair clock credits words as their pairs are actually emitted, so alpha
        # advances per batch, not per block.
        batcher.add(c, x, words_base + clock)
        words_base += kept
        for bc, bx, bclock, n in batcher.drain():
            mask = np.ones(pairs_per_batch, np.float32)
            words_seen = int(bclock[n - 1])
            yield PairBatch(bc, bx, mask, words_seen, n)
    for bc, bx, bclock, n in batcher.drain(flush=flush_last):
        mask = (np.arange(pairs_per_batch) < n).astype(np.float32)
        words_seen = int(bclock[n - 1]) if n else words_seen
        yield PairBatch(bc, bx, mask, words_seen, n)
    # trailing subsampled words with no emitted pairs still count toward the clock for
    # the *next* iteration's prev_words baseline — callers use iteration boundaries, so
    # nothing further to emit here


def iter_sentence_slabs(
    sentences: Sequence[np.ndarray],
    order: np.ndarray,
    block_words: int = 1_000_000,
) -> Iterator[List[np.ndarray]]:
    """Whole-sentence slabs of ~``block_words`` raw tokens in the given order — the
    vectorization granule shared by the host pair pipeline (:func:`epoch_batches`)
    and the device-feed packer (Trainer._device_seg_blocks), so their stream
    contracts stay aligned on one slab rule."""
    slab: List[np.ndarray] = []
    nwords = 0
    for si in order:
        s = sentences[si]
        slab.append(s)
        nwords += s.shape[0]
        if nwords >= block_words:
            yield slab
            slab, nwords = [], 0
    if slab:
        yield slab


def count_train_words(sentences: Sequence[np.ndarray]) -> int:
    return int(sum(int(s.shape[0]) for s in sentences))


# ---------------------------------------------------------------------------------------
# CBOW variant (BASELINE config 5): grouped context windows instead of flat pairs.
# ---------------------------------------------------------------------------------------


def dynamic_window_cbow(
    sentence: np.ndarray,
    window: int,
    rng: np.random.Generator,
    legacy_asymmetric_window: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-position padded context windows for CBOW.

    Same window draw as :func:`dynamic_window_pairs` (so skip-gram and CBOW see identical
    context structure), but grouped per center: returns (centers [L], contexts [L, C],
    ctx_mask [L, C]) with C = 2·window. Positions with zero context are dropped.
    """
    L = sentence.shape[0]
    C = 2 * window
    if L == 0:
        return (np.empty(0, np.int32), np.empty((0, C), np.int32),
                np.empty((0, C), np.float32))
    positions = np.arange(L, dtype=np.int64)
    b = rng.integers(0, window, size=L)
    left = np.minimum(b, positions)
    right_extent = b if not legacy_asymmetric_window else b - 1
    right = np.clip(np.minimum(right_extent, L - 1 - positions), 0, None)
    total = left + right
    num_pairs = int(total.sum())
    contexts = np.zeros((L, C), dtype=np.int32)
    ctx_mask = np.zeros((L, C), dtype=np.float32)
    if num_pairs:
        group_starts = np.cumsum(total) - total
        offsets = np.arange(num_pairs, dtype=np.int64) - np.repeat(group_starts, total)
        rows = np.repeat(positions, total)
        left_rep = np.repeat(left, total)
        ctx_pos = rows - left_rep + offsets + (offsets >= left_rep)
        contexts[rows, offsets] = sentence[ctx_pos]
        ctx_mask[rows, offsets] = 1.0
    keep = total > 0
    return (sentence[keep].astype(np.int32), contexts[keep], ctx_mask[keep])


def _block_cbow(
    tokens: np.ndarray,          # int32 [N] concatenated sentence tokens
    lengths: np.ndarray,         # int64 [S] sentence lengths (sum == N)
    keep: np.ndarray,            # float32 [V] per-word keep probability
    window: int,
    seed: int,
    iteration: int,
    shard: int,
    token_base: int,
    legacy_asymmetric_window: bool,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """CBOW analog of :func:`_block_pairs`: whole-slab vectorized subsample + grouped
    context windows — no per-sentence Python loop (which starved a >5M-example/s
    device consumer ~5x), and the same position-keyed hashrng draws, so the stream
    is deterministic per (seed, iteration, shard) and block-size independent.

    Returns (centers [Nk], contexts [Nk, 2*window] left-packed, n_ctx [Nk],
    center_word_index [Nk], words_kept). Positions with zero context are dropped
    (the per-sentence generator does the same)."""
    C = 2 * window
    empty = (np.empty(0, np.int32), np.empty((0, C), np.int32),
             np.empty(0, np.int32), np.empty(0, np.int64), 0)
    prologue = _subsample_and_window(
        tokens, lengths, keep, window, seed, iteration, shard, token_base,
        legacy_asymmetric_window)
    if prologue is None:
        return empty
    toks, left, total, Nk = prologue
    # the CBOW-specific host work of the scatter form's feed (the subsample
    # and window draws above are the skip-gram feed's too)
    with _cbow_pack_span() as sp:
        j = np.arange(C, dtype=np.int64)[None, :]
        ctx_pos = np.where(j < left[:, None],
                           np.arange(Nk, dtype=np.int64)[:, None] - left[:, None] + j,
                           np.arange(Nk, dtype=np.int64)[:, None] + j - left[:, None] + 1)
        valid = j < total[:, None]
        contexts = np.where(valid, toks[np.clip(ctx_pos, 0, Nk - 1)], 0).astype(np.int32)
        has_ctx = total > 0
        out = (toks[has_ctx].astype(np.int32), contexts[has_ctx],
               total[has_ctx].astype(np.int32),
               np.flatnonzero(has_ctx) + 1, int(Nk))
        sp.set(examples=int(out[0].shape[0]), context_rows=int(out[2].sum()))
    return out


def _cbow_pack_span():
    """Span ``producer.cbow_pack`` (docs/observability.md §4): the host work a
    CBOW feed adds to a chunk, on whichever thread does it. Imported here so
    that this module stays free of JAX until a CBOW feed runs."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    return default_tracer().span("producer.cbow_pack")


def pack_halo_token_blocks(
    slabs: Iterable[Tuple[np.ndarray, np.ndarray]],
    T: int,
    halo: int,
    tok_dtype=np.int32,
) -> Iterator[Tuple[np.ndarray, np.ndarray, int, int, int]]:
    """Sentence-contiguous [T]-slot token blocks with a ±``halo`` overlap — the
    feed granule of the banded CBOW step (ops/cbow_banded.py).

    ``slabs`` yields (kept_tokens, start_flags) chunks of the kept-token stream
    (already subsampled; ``start_flags[i]`` True iff a sentence begins at that
    token — the stream's first token must carry a flag). Blocks advance by the
    CORE width ``Tc = T − 2·halo``: block k holds kept positions
    ``[k·Tc − halo, k·Tc − halo + T)``, so every kept token is a **core** slot
    (``[halo, T−halo)``) of exactly one block and a halo slot of its neighbors.
    With ``halo ≥ window`` the overlap makes chunk-edge windows EXACT — both the
    forward context mean and the backward context gradient of a center near a
    cut see/reach their cross-cut neighbors (contrast the non-overlapping
    skip-gram device feed, which loses ~0.02% of windows at the cuts).

    Pre-stream slots of block 0 (positions < 0) are zero tokens with no start
    bits; they are never centers (core slots begin at slot ``halo`` = stream
    position 0) and never contexts (the stream-start start bit clamps every
    real window at position 0), so they ride as inert padding inside the valid
    prefix.

    Yields ``(tokens[T], start_bits, n_valid, ordinal_base, n_core)`` per
    block: ``n_valid`` counts the valid slot prefix, ``ordinal_base`` is the
    kept-token ordinal of slot 0 (wrapped to uint64 — block 0's is −halo), and
    ``n_core`` the NEW core tokens this block trains (the lr-clock increment;
    overlap slots are not re-counted).
    """
    if halo <= 0:
        raise ValueError(f"halo must be positive, got {halo}")
    Tc = T - 2 * halo
    if Tc <= 0:
        raise ValueError(f"T={T} leaves no core slots at halo={halo}")
    buf_tok = np.zeros(halo, tok_dtype)   # virtual pre-stream slots of block 0
    buf_start = np.zeros(halo, bool)
    bpos = -halo                          # stream position of buf[0]

    def emit(n_core: int):
        n = min(buf_tok.shape[0], T)
        tokens = np.zeros(T, tok_dtype)
        tokens[:n] = buf_tok[:n]
        bits = np.packbits(np.pad(buf_start[:n], (0, T - n)),
                           bitorder="little")
        return (tokens, bits, n, bpos & 0xFFFFFFFFFFFFFFFF, n_core)

    def advance():
        nonlocal buf_tok, buf_start, bpos
        buf_tok = buf_tok[Tc:]
        buf_start = buf_start[Tc:].copy()
        bpos += Tc

    for ktoks, kstart in slabs:
        if ktoks.shape[0] == 0:
            continue
        # the banded form's CBOW-specific host work: a span over taking a slab
        # into the buffer, then one a block (closed before the yield: open
        # across it, it would time the consumer)
        with _cbow_pack_span() as sp:
            buf_tok = np.concatenate([buf_tok, ktoks.astype(tok_dtype)])
            buf_start = np.concatenate([buf_start, kstart])
            sp.set(examples=0, context_rows=0)
        while buf_tok.shape[0] >= T:
            with _cbow_pack_span() as sp:
                block = emit(Tc)
                advance()
                sp.set(examples=Tc, context_rows=T)
            yield block
    # flush: emit while un-centered core positions remain (len > halo ⟺ some
    # stream token at position ≥ bpos + halo has not been a core slot yet)
    while buf_tok.shape[0] > halo:
        yield emit(min(buf_tok.shape[0] - halo, Tc))
        advance()


@dataclass
class CbowBatch:
    centers: np.ndarray    # int32 [B]
    contexts: np.ndarray   # int32 [B, C] — LEFT-PACKED: real slots first
    n_ctx: np.ndarray      # int32 [B] — real context count; ctx_mask = iota < n_ctx
                           # (shipping the count instead of a [B, C] float mask cuts
                           # the CBOW feed bytes ~40x; the device rebuilds the mask)
    mask: np.ndarray       # float32 [B]
    words_seen: int
    num_real: int

    @property
    def ctx_mask(self) -> np.ndarray:
        C = self.contexts.shape[1]
        return (np.arange(C)[None, :] < self.n_ctx[:, None]).astype(np.float32)


def epoch_batches_cbow(
    sentences: Sequence[np.ndarray],
    vocab: Vocabulary,
    *,
    pairs_per_batch: int,
    window: int,
    subsample_ratio: float = 0.0,
    seed: int = 0,
    iteration: int = 1,
    shard: int = 0,
    num_shards: int = 1,
    shuffle: bool = True,
    legacy_asymmetric_window: bool = True,
    block_words: int = 1_000_000,
    producer_workers: int = 1,
) -> Iterator[CbowBatch]:
    """CBOW analog of :func:`epoch_batches`: fixed-shape [B, 2·window] context
    batches, block-vectorized (:func:`_block_cbow`) with the same position-keyed
    hashrng stream — deterministic per (seed, iteration, shard), no per-sentence
    Python loop, and sharded exactly like the skip-gram feed (the multi-process
    allgather protocol consumes either). ``producer_workers``: same per-slab
    thread-pool fan-out (and bit-identity contract) as :func:`epoch_batches`."""
    B = int(pairs_per_batch)
    rng = stream_rng(seed, iteration, shard)
    keep = keep_probabilities(
        vocab.counts, vocab.train_words_count, subsample_ratio).astype(np.float32)
    order = np.arange(shard, len(sentences), num_shards)
    if shuffle:
        rng.shuffle(order)
    batcher = PairBatcher(B, num_streams=4)
    words_base = 0
    words_seen = 0

    def slab_jobs():
        token_base = 0
        for block in iter_sentence_slabs(sentences, order, block_words):
            yield block, token_base
            token_base += sum(int(s.shape[0]) for s in block)

    def run_slab(job):
        block, token_base = job
        tokens = np.concatenate(block) if len(block) > 1 else block[0]
        lengths = np.fromiter((s.shape[0] for s in block), np.int64, len(block))
        return _block_cbow(
            tokens, lengths, keep, window, seed, iteration, shard, token_base,
            legacy_asymmetric_window)

    for c, x, nc, clock, kept in ordered_pool_map(
            run_slab, slab_jobs(), producer_workers):
        batcher.add(c, x, nc, words_base + clock)
        words_base += kept
        for bc, bx, bn, bclock, n in batcher.drain():
            words_seen = int(bclock[n - 1])
            yield CbowBatch(bc, bx, bn, np.ones(B, np.float32), words_seen, n)
    for bc, bx, bn, bclock, n in batcher.drain(flush=True):
        words_seen = int(bclock[n - 1]) if n else words_seen
        yield CbowBatch(bc, bx, bn, (np.arange(B) < n).astype(np.float32),
                        words_seen, n)
