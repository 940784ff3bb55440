"""ctypes binding for the native corpus-ingestion passes (``native/ingest.cpp``).

Same degradation contract as :mod:`.native` (the pair generator): built on
first use with ``g++``, plain C ABI, falls back to the pure-Python path when
the toolchain is unavailable or ``GLINT_DISABLE_NATIVE=1``.

Scope: the HOT LOOPS only — tokenize+count and tokenize+encode over a token
file. The vocabulary filter/sort rules (count desc, stable on first-seen order,
the reference's sortWith contract mllib:266) and the encode metadata stay in
Python, consuming the native passes' output, so both paths share one ordering
implementation. Native applies only to ``lowercase=False`` ASCII-whitespace
corpora (the word2vec norm); anything else takes the Python path, which also
handles unicode whitespace and invalid-UTF-8 replacement.
"""

from __future__ import annotations

import ctypes
import logging
import os
import tempfile
import threading
from typing import Optional

import numpy as np

from glint_word2vec_tpu.train.faults import maybe_fail_ingest, retry_io
from glint_word2vec_tpu.lockcheck import make_lock

logger = logging.getLogger("glint_word2vec_tpu")

_ABI_VERSION = 2
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "ingest.cpp")
_LIB_STEM = os.path.join(os.path.dirname(_SRC), "libingest")

_lock = make_lock("data.ingest_native.load")
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("GLINT_DISABLE_NATIVE"):
            _load_failed = True
            return None
        from glint_word2vec_tpu.data.native import build_or_reload
        lib = build_or_reload(_SRC, _LIB_STEM, "glint_ingest_abi_version",
                              _ABI_VERSION, "c++20", "ingest")
        if lib is None:
            _load_failed = True
            return None
        lib.glint_ingest_count.restype = ctypes.c_int64
        lib.glint_ingest_count.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32]
        lib.glint_ingest_encode.restype = ctypes.c_int64
        lib.glint_ingest_encode.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64)]
        _lib = lib
        return _lib


def ingest_available() -> bool:
    return _load() is not None


def count_words_native(corpus_path: str, n_threads: int):
    """Tokenize+count ``corpus_path``; returns ``(words, counts)`` in FIRST-SEEN
    file order — exactly the iteration order of the Python ``Counter`` the
    fallback builds, so ``Vocabulary.from_counter``'s stable sort gives
    identical vocabularies either way. Returns None on native failure."""
    lib = _load()
    assert lib is not None, "call ingest_available() first"
    with tempfile.TemporaryDirectory(prefix="glint_ingest_") as td:
        wpath = os.path.join(td, "words")
        cpath = os.path.join(td, "counts")

        def attempt() -> int:
            maybe_fail_ingest(f"native ingest count {corpus_path!r}")
            return lib.glint_ingest_count(
                corpus_path.encode(), wpath.encode(), cpath.encode(),
                np.int32(n_threads))

        n = retry_io(attempt, what=f"native ingest count {corpus_path!r}")
        if n == -2:
            logger.info("corpus %r needs Python tokenization semantics "
                        "(unicode whitespace / lone CR / invalid UTF-8); "
                        "using the Python pass", corpus_path)
            return None
        if n < 0:
            logger.warning("native ingest count failed on %r; falling back "
                           "to the Python pass", corpus_path)
            return None
        # idempotent reads of the native pass's finished outputs — safe to
        # retry, unlike the encode passes themselves (graftlint R5)
        with retry_io(lambda: open(wpath, "rb"),
                      what=f"native ingest words {wpath!r}") as f:
            raw = f.read()
        words = raw.decode("utf-8", errors="replace").split("\n")[:-1]
        counts = retry_io(lambda: np.fromfile(cpath, dtype=np.int64),
                          what=f"native ingest counts {cpath!r}")
    if len(words) != n or counts.shape[0] != n:
        logger.warning("native ingest count output inconsistent "
                       "(%d words / %d counts / %d reported); falling back",
                       len(words), counts.shape[0], n)
        return None
    return words, counts


def encode_corpus_native(corpus_path: str, words, max_sentence_length: int,
                         tokens_path: str, offsets_path: str,
                         n_threads: int):
    """Tokenize+encode ``corpus_path`` against the FINAL vocabulary ``words``
    (id == position), writing the tokens.bin/offsets.bin pair EncodedCorpus
    mmaps. Returns ``(total_tokens, n_sentences)``, or None on native
    failure / Python-semantics fallback."""
    lib = _load()
    assert lib is not None, "call ingest_available() first"
    with tempfile.NamedTemporaryFile(prefix="glint_vocab_", suffix=".txt",
                                     delete=False) as tf:
        vocab_path = tf.name
        tf.write("\n".join(words).encode("utf-8") + b"\n")
    try:
        nsents = ctypes.c_int64(0)

        def attempt() -> int:
            # the C pass truncates its output files on open, so a retried
            # attempt restarts clean — same restart-from-scratch contract as
            # the Python pass in corpus.py
            maybe_fail_ingest(f"native ingest encode {corpus_path!r}")
            return lib.glint_ingest_encode(
                corpus_path.encode(), vocab_path.encode(),
                np.int32(max_sentence_length), tokens_path.encode(),
                offsets_path.encode(), np.int32(n_threads),
                ctypes.byref(nsents))

        total = retry_io(attempt, what=f"native ingest encode {corpus_path!r}")
    finally:
        os.unlink(vocab_path)
    if total == -2:
        logger.info("corpus %r needs Python tokenization semantics "
                    "(unicode whitespace / lone CR / invalid UTF-8); "
                    "using the Python pass", corpus_path)
        return None
    if total < 0:
        logger.warning("native ingest encode failed on %r; falling back to "
                       "the Python pass", corpus_path)
        return None
    return int(total), int(nsents.value)
