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
import contextlib
import ctypes
import itertools
import os
import weakref
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

# around the native walk where the caller hands over no span for it
_NO_SPAN = contextlib.nullcontext()


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
        statement a token. A batch of :data:`NATIVE_LOOKUP_TOKENS` or more
        goes to the native table (``native/lookup.cpp``, built from the words
        at the first such batch): one C pass over the list as it lies copies
        the tokens' UTF-8 bytes under the interpreter lock (~11 ns a token on
        the chip's host), and the lookup runs OUTSIDE it, so callers on several threads resolve their batches
        side by side (``dict.get`` mapped over the list holds the lock for
        ~220 ns a token over 3M words; PERF.md §6, PR 48 and PR 51). Smaller
        batches, a batch with a token that is no ``str`` or does not encode
        (a lone surrogate), and a host without the toolchain take the mapped
        ``dict.get``: the same ids. The C pass asks every ``str`` for its
        UTF-8 form, which CPython keeps ON a non-ASCII ``str`` from then on:
        memory that lives as long as the caller's string."""
        if len(tokens) >= NATIVE_LOOKUP_TOKENS:
            found = self._native_lookup(tokens, None, _NO_SPAN)
            if found is not None:
                return found[0]
        return self._dict_lookup(tokens)

    def lookup_sentences(self, sentences: Sequence[Sequence[str]], walk_span=_NO_SPAN
                         ) -> Tuple[np.ndarray, np.ndarray, int, bool]:
        """:meth:`lookup` of a slide of sentences as the caller holds it, no
        flattened list made, as a reader of rows wants it: the ``int32`` ids
        of its in-vocabulary tokens, sentence after sentence in the order
        sent; every sentence's count of them (``int32[len(sentences)]``; 0
        for an empty or all-missing one); the tokens the vocabulary lacks;
        and whether the native table answered (False: ``dict.get`` did, by
        :meth:`lookup`'s rule over the slide's tokens). A sentence is a
        list, or any sequence (a tuple, an array), which the C pass makes a
        list of. ``walk_span``: a context manager to enter around the C pass
        alone, the part of a native lookup that holds the interpreter lock."""
        n = len(sentences)
        if n >= NATIVE_LOOKUP_TOKENS or sum(map(len, sentences)) >= NATIVE_LOOKUP_TOKENS:
            counts = np.empty(n, np.int32)
            found = self._native_lookup(sentences, counts, walk_span)
            if found is not None:
                ids, tokens = found
                return ids, counts, tokens - len(ids), True
        _, ids, counts, _ = self._dict_lookup_sentences(sentences, n)
        live = ids >= 0
        return ids[live], counts, int(len(ids) - live.sum()), False

    def lookup_sentences_misses(self, sentences: Sequence[Sequence[str]],
                                walk_span=_NO_SPAN):
        """:meth:`lookup_sentences` with the tokens the vocabulary lacks
        handed on and not only dropped, for a reader that gives them a vector
        of their own (a subword model's ``sentence_vectors``): the ids and
        the sentences' counts as there; every sentence's count of missing
        tokens (``int32[len(sentences)]``); the missing tokens themselves in
        the order sent; and whether the native table answered. Where it did,
        the tokens are their UTF-8 bytes back to back and every token's byte
        end (``uint8[...]``, ``int64[n]``), copied out of the walk's buffer
        with the interpreter lock released: what ``data/subword.ngram_rows``
        hashes without making a ``str``. Where ``dict.get`` did, a list of
        the ``str`` objects."""
        n = len(sentences)
        if n >= NATIVE_LOOKUP_TOKENS or sum(map(len, sentences)) >= NATIVE_LOOKUP_TOKENS:
            counts, missing = np.empty(n, np.int32), np.empty(n, np.int32)
            found = self._native_lookup(sentences, counts, walk_span, missing)
            if found is not None:
                return found[0], counts, missing, found[2], True
        tokens, ids, counts, lengths = self._dict_lookup_sentences(sentences, n)
        return (ids[ids >= 0], counts, (lengths - counts).astype(np.int32),
                [tokens[i] for i in np.flatnonzero(ids < 0).tolist()], False)

    def _dict_lookup_sentences(self, sentences: Sequence[Sequence[str]], n: int):
        """``dict.get``'s route over a slide of ``n`` sentences (counted before
        a native walk that may have run the caller's code): its flattened
        tokens, an id each (-1: missing), every sentence's count of ids found
        (``int32``) and of tokens (``int64``)."""
        lengths = np.fromiter(map(len, sentences), np.int64, count=n)
        tokens = list(itertools.chain.from_iterable(sentences))
        ids = self._dict_lookup(tokens)
        before = np.concatenate([[0], np.cumsum(ids >= 0)])
        ends = np.cumsum(lengths)
        return (tokens, ids, (before[ends] - before[ends - lengths]).astype(np.int32),
                lengths)

    def _dict_lookup(self, tokens: Sequence[str]) -> np.ndarray:
        return np.fromiter(
            map(self.index.get, tokens, itertools.repeat(-1)), np.int32,
            count=len(tokens))

    def _native_lookup(self, batch, counts: Optional[np.ndarray], walk_span,
                       missing: Optional[np.ndarray] = None):
        """``batch`` through ``native/lookup.cpp``: its ids and the number of
        its tokens, or None where that cannot answer for the dict. Without
        ``counts`` a list of tokens, an id each; with ``counts`` to fill,
        sentences, whose missing tokens' ids are dropped; with ``missing`` to
        fill too (every sentence's count of them), a third result: the
        missing tokens' bytes and byte ends."""
        from glint_word2vec_tpu.data.native import default_threads
        lib = _load_native()
        if lib is None:
            return None
        table = self._native
        if table is None:
            # once a vocabulary: a pinned span (obs/spans.py)
            with _tracer().span("vocab.native_table", pinned=True,
                                words=len(self.words)):
                table = self._native = _NativeTable(lib, self.words)
        if table.handle is None:
            return None
        n_tokens = ctypes.c_int64()
        with walk_span:
            walk = lib.glint_lookup_walk(
                batch, -1 if counts is None else len(counts), ctypes.byref(n_tokens))
        if not walk:
            return None     # a token that is no str, or a lone surrogate
        try:
            out = np.empty(n_tokens.value, np.int32)
            if missing is not None:
                raw = np.empty(lib.glint_lookup_walk_bytes(walk), np.uint8)
                end = np.empty(n_tokens.value, np.int64)
        except BaseException:
            lib.glint_lookup_walked(None, walk, None, None, 0)  # frees the walk
            raise
        threads = min(default_threads(), _LOOKUP_THREADS)
        if missing is None:
            kept = lib.glint_lookup_walked(
                table.handle, walk, out.ctypes.data,
                None if counts is None else counts.ctypes.data, threads)
            return out[:kept], n_tokens.value
        n_missed = ctypes.c_int64()
        kept = lib.glint_lookup_walked_misses(
            table.handle, walk, out.ctypes.data, counts.ctypes.data,
            missing.ctypes.data, raw.ctypes.data, end.ctypes.data,
            ctypes.byref(n_missed), threads)
        end = end[:n_missed.value]
        return (out[:kept], n_tokens.value,
                (raw[:int(end[-1]) if n_missed.value else 0], end))

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
# under it the two calls cost more than the mapped dict.get saves, and a
# model that only looks up a few words never builds the table
NATIVE_LOOKUP_TOKENS = 4096

# threads one native lookup splits its tokens over, at most. Four callers'
# slides side by side on the chip's 13-core host, since the walk took the
# interpreter lock out of the way (PERF.md §6, PR 51): 837,000 sentences/s at
# 1, 1,025,000 at 2, 1,193,000-1,221,000 at 4, 1,198,000 at 6, 1,197,000-
# 1,225,000 at 8, 1,212,000 at 13: the lookup matters again up to 4 and is
# level from there (with the lock as the wall, PR 48: 540,000 / 622,000 /
# 633,000 / 618,000 at 1 / 2 / 4 / 8). One caller alone encodes a slide in
# 28.9 / 19.8 / 12.9 / 11.6 ms at 1 / 2 / 4 / 8.
_LOOKUP_THREADS = 4

# the interpreter's entry points native/lookup.cpp's walk calls, in the order
# of its struct Interpreter: stable-ABI symbols of the running process
_INTERPRETER_SYMBOLS = (
    "PyList_Type", "PyObject_Type", "Py_DecRef", "PyList_Size", "PyList_GetItem",
    "PySequence_Check", "PySequence_List", "PyUnicode_AsUTF8AndSize", "PyErr_Clear")

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
    contract, its walk bound to the interpreter, or None (``dict.get`` then
    answers every batch)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib

    from glint_word2vec_tpu.data.native import build_or_reload
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "native", "lookup.cpp")
    lib = None
    if not os.environ.get("GLINT_DISABLE_NATIVE"):
        lib = build_or_reload(src, os.path.join(os.path.dirname(src), "liblookup"),
                              "glint_lookup_abi_version", 3, "c++17", "lookup")
    if lib is not None:
        try:
            symbols = (ctypes.c_void_p * len(_INTERPRETER_SYMBOLS))(*(
                ctypes.cast(getattr(ctypes.pythonapi, name), ctypes.c_void_p)
                for name in _INTERPRETER_SYMBOLS))
            # the walk reads Python objects: a second handle on the same
            # library, whose calls keep the interpreter lock
            lib.glint_lookup_walk = ctypes.PyDLL(lib._name).glint_lookup_walk
        except (AttributeError, OSError):   # an interpreter without them
            lib = None
    if lib is None or not lib.glint_lookup_bind(symbols, len(symbols)):
        _lib_failed = True
        return None
    lib.glint_lookup_build.restype = ctypes.c_void_p
    lib.glint_lookup_build.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64]   # bytes, end, words
    lib.glint_lookup_free.restype = None
    lib.glint_lookup_free.argtypes = [ctypes.c_void_p]
    lib.glint_lookup_walk.restype = ctypes.c_void_p
    lib.glint_lookup_walk.argtypes = [
        ctypes.py_object, ctypes.c_int64, ctypes.c_void_p]  # batch, sentences, tokens (out)
    lib.glint_lookup_walked.restype = ctypes.c_int64
    lib.glint_lookup_walked.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # table, walk, out
        ctypes.c_void_p, ctypes.c_int32]                    # counts, threads
    lib.glint_lookup_walk_bytes.restype = ctypes.c_int64
    lib.glint_lookup_walk_bytes.argtypes = [ctypes.c_void_p]
    lib.glint_lookup_walked_misses.restype = ctypes.c_int64
    lib.glint_lookup_walked_misses.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # table, walk, out
        ctypes.c_void_p, ctypes.c_void_p,                   # counts, missing
        ctypes.c_void_p, ctypes.c_void_p,                   # the missing tokens' bytes, ends
        ctypes.c_void_p, ctypes.c_int32]                    # tokens missed (out), threads
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
