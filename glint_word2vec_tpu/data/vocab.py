"""Vocabulary builder (reference component C1).

Reimplements ``learnVocab`` (mllib/feature/ServerSideGlintWord2Vec.scala:258-279): count
words, drop those with count < min_count, sort by descending count, assign indices in that
order, and record the total count of retained training words (``trainWordsCount``).

The reference does this as a Spark word-count job with a driver-side collect; here it is a
single-pass host-side counter. Multi-host corpora shard by file and merge counters
(:func:`merge_counts`).
"""

from __future__ import annotations

import collections
import itertools
import os
import weakref
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Sequence

import numpy as np


@dataclass
class Vocabulary:
    """Immutable vocabulary: words sorted by descending corpus frequency.

    ``words[i]`` has count ``counts[i]``; ``index[word] == i``. Matches the reference's
    contract that word index order == matrix row order == descending frequency
    (mllib:261-279, save sidecar order mllib:495-496).
    """

    words: List[str]
    counts: np.ndarray  # int64 [vocab_size]
    index: Dict[str, int] = field(repr=False)
    train_words_count: int = 0
    # the words' table in native/lookup.cpp, built at the first large lookup
    _native: "_NativeTable" = field(default=None, init=False, repr=False,
                                    compare=False)

    @property
    def size(self) -> int:
        return len(self.words)

    def __len__(self) -> int:
        return len(self.words)

    def __contains__(self, word: str) -> bool:
        return word in self.index

    def get(self, word: str, default: int = -1) -> int:
        return self.index.get(word, default)

    def lookup(self, tokens: Sequence[str]) -> np.ndarray:
        """``int32[len(tokens)]``: every token's index, -1 for a token the
        vocabulary lacks. The batch form of :meth:`get`, with no Python
        statement a token, for callers that resolve a slide of sentences at
        a time (``Word2VecModel.transform_sentences``: ~330,000 tokens a
        call). A batch of :data:`NATIVE_LOOKUP_TOKENS` or more goes to the
        native table (``native/lookup.cpp``, built from the words at the
        first such batch): the tokens joined and encoded once, then looked
        up OUTSIDE the interpreter lock, so callers on several threads
        resolve their slides side by side (``dict.get`` mapped over the
        list holds the lock for ~220 ns a token over 3M words: 73 ms a
        slide, every caller in turn; PERF.md §6, PR 48). Smaller batches,
        tokens that hold the separator or do not encode, and a host
        without the toolchain take the mapped ``dict.get``: the same ids."""
        if len(tokens) >= NATIVE_LOOKUP_TOKENS:
            ids = self._native_lookup(tokens)
            if ids is not None:
                return ids
        return np.fromiter(
            map(self.index.get, tokens, itertools.repeat(-1)), np.int32,
            count=len(tokens))

    def _native_lookup(self, tokens: Sequence[str]):
        """:meth:`lookup` through ``native/lookup.cpp``, or None where that
        cannot answer for the dict."""
        from glint_word2vec_tpu.data.native import default_threads
        lib = _load_native()
        if lib is None:
            return None
        try:
            blob = _SEP.join(tokens).encode("utf-8")
        except (TypeError, UnicodeEncodeError):
            return None     # not all str, or a lone surrogate
        table = self._native
        if table is None:
            # once a vocabulary: a pinned span (obs/spans.py)
            with _tracer().span("vocab.native_table", pinned=True,
                                words=len(self.words)):
                table = self._native = _NativeTable(lib, self.words)
        if table.handle is None:
            return None
        out = np.empty(len(tokens), np.int32)
        done = lib.glint_lookup_tokens(
            table.handle, blob, len(blob), _SEP.encode(), len(tokens),
            out.ctypes.data, min(default_threads(), _LOOKUP_THREADS))
        return out if done == len(tokens) else None   # a token holds the separator

    @classmethod
    def from_words_and_counts(cls, words: Sequence[str], counts: Sequence[int]) -> "Vocabulary":
        with _tracer().span("vocab.build", pinned=True, words=len(words)):
            counts = np.asarray(counts, dtype=np.int64)
            index = {w: i for i, w in enumerate(words)}
            return cls(words=list(words), counts=counts, index=index,
                       train_words_count=int(counts.sum()))

    @classmethod
    def from_counter(cls, counter: "collections.Counter[str]", min_count: int) -> "Vocabulary":
        items = [(w, c) for w, c in counter.items() if c >= min_count]
        if not items:
            raise ValueError(
                "The vocabulary size should be > 0. You may need to check the setting of "
                "min_count, which could be large enough to remove all your words in sentences.")
        # Descending count; stable on first-seen order for ties (the reference's sortWith is
        # likewise stable, mllib:266).
        items.sort(key=lambda wc: -wc[1])
        words = [w for w, _ in items]
        counts = np.fromiter((c for _, c in items), dtype=np.int64, count=len(items))
        index = {w: i for i, w in enumerate(words)}
        return cls(words=words, counts=counts, index=index,
                   train_words_count=int(counts.sum()))


# tokens in a Vocabulary.lookup batch from which the native table answers:
# under it the join and the call cost more than the mapped dict.get saves,
# and a model that only looks up a few words never builds the table
NATIVE_LOOKUP_TOKENS = 4096

# threads one native lookup splits its tokens over, at most: four callers'
# slides side by side read 540,000 sentences/s at 1, 622,000 at 2, 633,000 at
# 4 and 618,000 at 8 on the chip's 13-core host (PERF.md §6, PR 48)
_LOOKUP_THREADS = 4

# what joins a batch's tokens for the native lookup; a token that holds it
# sends the batch to the dict
_SEP = "\n"

_lib = None
_lib_failed = False


def _tracer():
    """The one span recorder (obs/spans.py), imported where a span is taken:
    it brings ``jax.profiler`` with it, and this module has no other use
    for jax."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    return default_tracer()


def _load_native():
    """``native/lookup.cpp`` under data/native.py's build-on-first-use
    contract, or None (``dict.get`` then answers every batch)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    import ctypes

    from glint_word2vec_tpu.data.native import build_or_reload
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "native", "lookup.cpp")
    lib = None
    if not os.environ.get("GLINT_DISABLE_NATIVE"):
        lib = build_or_reload(src, os.path.join(os.path.dirname(src), "liblookup"),
                              "glint_lookup_abi_version", 1, "c++17", "lookup")
    if lib is None:
        _lib_failed = True
        return None
    lib.glint_lookup_build.restype = ctypes.c_void_p
    lib.glint_lookup_build.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64]   # bytes, end, words
    lib.glint_lookup_free.restype = None
    lib.glint_lookup_free.argtypes = [ctypes.c_void_p]
    lib.glint_lookup_tokens.restype = ctypes.c_int64
    lib.glint_lookup_tokens.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,   # table, buf, len
        ctypes.c_char, ctypes.c_int64, ctypes.c_void_p,     # sep, tokens, out
        ctypes.c_int32]                                     # threads
    _lib = lib
    return _lib


class _NativeTable:
    """One vocabulary's table in ``native/lookup.cpp``, word i at id i (of a
    word the list holds twice the last position, as ``Vocabulary.index``
    keeps it): ``handle`` (None where a word does not encode), freed when the
    last reference to this object goes."""

    def __init__(self, lib, words: List[str]):
        self.handle = None
        try:
            encoded = [w.encode("utf-8") for w in words]
        except UnicodeEncodeError:
            return
        end = np.cumsum(np.fromiter(map(len, encoded), np.int64, count=len(encoded)))
        self.handle = lib.glint_lookup_build(b"".join(encoded), end.ctypes.data, len(encoded))
        weakref.finalize(self, lib.glint_lookup_free, self.handle)


def count_words(sentences: Iterable[Sequence[str]]) -> "collections.Counter[str]":
    counter: "collections.Counter[str]" = collections.Counter()
    for sentence in sentences:
        counter.update(sentence)
    return counter


def _count_slab(slab: List[Sequence[str]]) -> "collections.Counter[str]":
    """Count one slab of sentences. ``Counter`` preserves FIRST-SEEN key
    order, which the slab-order merge relies on (the descending-count
    tie-break in :meth:`Vocabulary.from_counter` ranks equal-count words by
    first appearance, mllib:266). A sort-based ``np.unique`` slab counter was
    measured SLOWER than ``Counter`` for string tokens (hash counting is
    O(n), the string sort O(n log n) with worse constants — hostbench), so
    the hash path stays."""
    counter: "collections.Counter[str]" = collections.Counter()
    for s in slab:
        counter.update(s.tolist() if isinstance(s, np.ndarray) else s)
    return counter


def merge_counts(counters: Iterable["collections.Counter[str]"]) -> "collections.Counter[str]":
    total: "collections.Counter[str]" = collections.Counter()
    for c in counters:
        total.update(c)
    return total


def count_words_parallel(
    sentences: Iterable[Sequence[str]],
    workers: int = 1,
    slab_sentences: int = 50_000,
) -> "collections.Counter[str]":
    """Per-slab parallel word counting with an ordered merge (PERF.md §10).

    Slabs of ``slab_sentences`` sentences are counted independently
    (:func:`_count_slab`) on a ``workers``-thread pool and merged IN SLAB
    ORDER, so the result — counts AND Counter iteration order (first-seen;
    the descending-count tie-break) — is identical to the serial
    :func:`count_words` at any worker count (tested).

    Honesty note (PERF.md §10): counting PYTHON string tokens is GIL-bound —
    ``Counter.update`` never releases the lock — so on stock CPython this
    fan-out is contention, not speedup (measured 0.66x at workers=4;
    a GIL-releasing np.unique slab counter measured slower outright), and
    :func:`build_vocab` therefore routes here only on free-threaded builds.
    The genuinely parallel cold path for file corpora remains the native C++
    counter (``ingest_native``, already multithreaded), which
    :func:`build_vocab` prefers when available."""
    from glint_word2vec_tpu.data.pipeline import ordered_pool_map

    def slabs():
        slab: List[Sequence[str]] = []
        for s in sentences:
            slab.append(s)
            if len(slab) >= slab_sentences:
                yield slab
                slab = []
        if slab:
            yield slab

    return merge_counts(ordered_pool_map(_count_slab, slabs(), workers))


def build_vocab(sentences: Iterable[Sequence[str]], min_count: int = 5,
                workers: int = 1) -> Vocabulary:
    """Count → filter(min_count) → sort desc → index (mllib:258-279).

    Token-file corpora take the native C++ counting pass when available
    (``native/ingest.cpp``, ~4-5× the Python tokenizer) — it returns words in
    the same first-seen order a Python ``Counter`` iterates, so the
    filter/sort below is shared and the vocabulary is identical either way.
    ``workers > 1`` routes the Python path through
    :func:`count_words_parallel` (bit-identical vocabulary, see there).
    The whole of it is the pinned span ``vocab.build`` (obs/spans.py)."""
    from glint_word2vec_tpu.data.corpus import TokenFileCorpus
    with _tracer().span("vocab.build", pinned=True) as span:
        counter = None
        if isinstance(sentences, TokenFileCorpus) and not sentences.lowercase:
            from glint_word2vec_tpu.data import ingest_native, native
            if ingest_native.ingest_available():
                res = ingest_native.count_words_native(
                    sentences.path, native.default_threads())
                if res is not None:
                    words, counts = res
                    counter = collections.Counter(
                        dict(zip(words, (int(c) for c in counts))))
        if counter is None:
            counter = (count_words_parallel(sentences, workers)
                       if parallel_counting_profitable(workers)
                       else count_words(sentences))
        vocab = Vocabulary.from_counter(counter, min_count)
        span.set(words=vocab.size)
    return vocab


def parallel_counting_profitable(workers: int = 2) -> bool:
    """Should :func:`build_vocab` fan token counting across ``workers`` threads?

    The ONE owner of this decision (config.py's ``io_workers`` note points
    here). The evidence, so the next session on a different runtime re-measures
    instead of guessing:

    - Stock CPython: ``Counter.update`` over python string tokens never
      releases the GIL, so the slab fan-out is pure contention — MEASURED
      0.66× at ``workers=4`` on the hostbench small tier (PERF.md §10). A
      GIL-releasing ``np.unique`` slab counter measured slower outright
      (string sort O(n log n) vs hash counting O(n)). Verdict: False.
    - Free-threaded CPython (3.13+ ``--disable-gil`` builds,
      ``sys._is_gil_enabled() == False``): the contention argument vanishes
      by construction; the fan-out is expected to scale like the other slab
      pools (NOT yet measured — no free-threaded host has run hostbench).
      Verdict: True, provisionally — the first free-threaded session should
      confirm with ``tools/hostbench.py --scale small`` and update this
      docstring with the number.

    Correctness is not at stake either way: :func:`count_words_parallel` is
    bit-identical to the serial counter at any worker count (tested), so this
    helper only gates throughput.
    """
    if workers <= 1:
        return False
    import sys
    try:
        return not sys._is_gil_enabled()  # free-threaded CPython 3.13+
    except AttributeError:
        return False  # stock CPython: GIL always on


def read_corpus(path: str, lowercase: bool = False) -> Iterator[List[str]]:
    """Whitespace-tokenized line-per-sentence reader (the format of the reference's toy
    corpus, which ships pre-tokenized and lowercased; it spec:22-37)."""
    from glint_word2vec_tpu.train.faults import retry_io

    # only the open retries (graftlint R5): the line iteration is one-shot —
    # re-reading a partially consumed stream would silently duplicate lines
    with retry_io(lambda: open(path, "r", encoding="utf-8"),
                  what=f"open corpus {path!r}") as f:
        for line in f:
            toks = line.split()
            if not toks:
                continue
            yield [t.lower() for t in toks] if lowercase else toks
