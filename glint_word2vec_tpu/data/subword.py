"""Character n-grams of a word, hashed into bucket rows: fastText's subwords.

Bojanowski et al. 2017 (arXiv:1607.04606) and ``fasttext``'s
``Dictionary::computeSubwords``: a word w is written ``"<" + w + ">"``; every
substring of ``min_n`` to ``max_n`` characters of it (characters are code
points; the whole ``"<w>"`` is one of them where it is that short) is hashed
with 32-bit FNV-1a over its UTF-8 bytes, each byte sign-extended before the
xor as fastText's ``int8_t`` cast does, and lands in bucket ``hash % buckets``.
The word's list of input rows is its own row followed by ``vocab_size +
bucket`` for every n-gram, a bucket that two of its n-grams share listed
twice, as fastText lists it. At ``min_n`` >= 2 fastText's one exclusion (a
lone ``<`` or ``>``) never applies, so none is dropped here.

:func:`ngram_buckets` is the plain form for one string (a query for a word the
vocabulary has never seen), :func:`ngram_lists` a batch of them as one
padded block and :func:`ngram_rows` a batch as one flat list (a slide's
unseen tokens: ``native/subword.cpp`` where it builds). :func:`build_subword_table` is the same function
over a whole vocabulary (2.5M words, 76M n-grams: seconds, where a Python loop
takes minutes), laid out for the step (ops/subword.py).
"""

import ctypes
import itertools
import os
from typing import List, NamedTuple, Sequence, Tuple, Union

import numpy as np

FNV_OFFSET = 2166136261
FNV_PRIME = 16777619

# rows of a word's list are stored in groups of this many, the last group of a
# word padded: the step reads a word's list as a few 32-byte rows of ids
# (ops/subword._lists), and the table stays near the lists' own size (343 MB
# at the published vocabulary, where one row a word of the longest list's
# length would be 403)
GROUP = 8
# padding slot of a group: out of bounds of any table (gathers fill 0 for it,
# scatters drop it)
NO_ROW = np.int32(2**31 - 1)


def fnv1a(data: bytes) -> int:
    """fastText's hash: 32-bit FNV-1a with each byte sign-extended."""
    h = FNV_OFFSET
    for b in data:
        h ^= b if b < 128 else b | 0xFFFFFF00
        h = (h * FNV_PRIME) & 0xFFFFFFFF
    return h


def ngram_buckets(word: str, min_n: int, max_n: int, buckets: int) -> List[int]:
    """Bucket of every n-gram of ``"<word>"``, by start then by length. The
    n-gram of n + 1 characters from a start is that of n continued, so a
    start's characters are hashed once."""
    chars = [c.encode("utf-8") for c in "<" + word + ">"]
    out: List[int] = []
    for i in range(len(chars) - min_n + 1):
        h = FNV_OFFSET
        for n, char in enumerate(chars[i:i + max_n], 1):
            for b in char:
                h = ((h ^ (b if b < 128 else b | 0xFFFFFF00)) * FNV_PRIME) & 0xFFFFFFFF
            if n >= min_n:
                out.append(h % buckets)
    return out


def list_capacity(longest: int, min_n: int, max_n: int) -> int:
    """Slots of a query's bucket list (:func:`ngram_lists`): the n-grams of a
    string one character longer than ``longest``, the vocabulary's longest
    word (a misspelling inserts one), in whole groups of :data:`GROUP`."""
    marked = longest + 3
    count = sum(max(marked - n + 1, 0) for n in range(min_n, max_n + 1))
    return max(GROUP, -(-count // GROUP) * GROUP)


def ngram_lists(strings: Sequence[str], min_n: int, max_n: int, buckets: int,
                capacity: int):
    """The bucket lists of a batch's unseen strings as one block for the
    scan's program (models/word2vec.py): int32 [len(strings), capacity], row
    i the buckets of strings[i] (ids into the BUCKET rows, not into syn0),
    :data:`NO_ROW` past them; and the positions of the strings whose list
    is longer than ``capacity`` (their rows stay empty: the caller sends them
    round another way)."""
    out = np.full((len(strings), capacity), NO_ROW, np.int32)
    over: List[int] = []
    for i, s in enumerate(strings):
        ids = ngram_buckets(s, min_n, max_n, buckets)
        if len(ids) > capacity:
            over.append(i)
        else:
            out[i, :len(ids)] = ids
    return out, over


def ngram_rows(strings: Union[Sequence[str], Tuple[np.ndarray, np.ndarray]],
               min_n: int, max_n: int, buckets: int):
    """The buckets of a batch of strings as ONE FLAT ``int32`` array (ids into
    the BUCKET rows), string after string in :func:`ngram_buckets`' order, no
    padded block; every string's count of them (``int32[len(strings)]``; 0
    for one too short to have an n-gram, ``""`` at ``min_n`` 3); and whether
    ``native/subword.cpp`` hashed them (with the interpreter lock released:
    no Python statement a string, a character or an n-gram) or, where it does
    not build, :func:`ngram_buckets` a string. ``strings`` is a sequence of
    ``str``, or the strings' UTF-8 bytes back to back and every string's byte
    end (``uint8[...]``, ``int64[n]``: what ``Vocabulary.lookup_sentences_misses``
    hands on from its walk)."""
    lib = _load_native()
    packed = isinstance(strings, tuple)
    if lib is None:
        if packed:
            raw, end = strings
            data = raw.tobytes()
            strings = [data[s:e].decode("utf-8")
                       for s, e in zip([0] + end[:-1].tolist(), end.tolist())]
        lists = [ngram_buckets(s, min_n, max_n, buckets) for s in strings]
        counts = np.fromiter(map(len, lists), np.int32, count=len(lists))
        return (np.fromiter(itertools.chain.from_iterable(lists), np.int32,
                            count=int(counts.sum())), counts, False)
    if packed:
        raw, end = strings
    else:
        encoded = [s.encode("utf-8") for s in strings]
        raw = np.frombuffer(b"".join(encoded), np.uint8)
        end = np.cumsum(np.fromiter(map(len, encoded), np.int64, count=len(encoded)))
    n = int(end.shape[0])
    raw, end = np.ascontiguousarray(raw), np.ascontiguousarray(end, np.int64)
    if n and int(end[-1]) > raw.shape[0]:
        raise ValueError("ngram_rows: the strings' ends pass their bytes")
    # an n-gram of every length from every byte of every marked string, at most
    ids = np.empty(max(max_n - min_n + 1, 0) * ((int(end[-1]) if n else 0) + 2 * n),
                   np.int32)
    counts = np.empty(n, np.int32)
    total = lib.glint_subword_hash_strings(
        raw.ctypes.data, end.ctypes.data, n, min_n, max_n, ctypes.c_uint32(buckets),
        ids.ctypes.data, counts.ctypes.data)
    return ids[:total], counts, True


class SubwordRows(NamedTuple):
    """Every word's list of input rows, in groups of :data:`GROUP`.

    ``offsets`` int32 [V + 2]: word w's groups are ``rows[offsets[w]:
    offsets[w + 1]]``; index V stands for "no word" and has none.
    ``rows`` int32 [N, GROUP]: row ids into syn0 (the word's own row first),
    padding slots :data:`NO_ROW`. ``counts`` int32 [V + 1]: live rows of the
    word's list, |G(w)|; 0 for "no word". ``max_groups``: the longest list's
    groups."""

    offsets: np.ndarray
    rows: np.ndarray
    counts: np.ndarray
    max_groups: int

    @property
    def slots(self) -> int:
        return int(self.counts.sum())

    def rows_of(self, w: int) -> np.ndarray:
        """Word w's live rows (the host's view; tests and the model use it)."""
        lo, hi = int(self.offsets[w]), int(self.offsets[w + 1])
        return self.rows[lo:hi].reshape(-1)[:int(self.counts[w])]


# a row table's groups go to the device in whole units of this many: their
# count is a shape of the programs that read the table (the trainer's step,
# the model's composing block), and vocabularies a few words apart, as the
# benchmark's are from seed to seed, then share one compiled program (2^20
# groups are 3% of the published subword vocabulary's 10.7 M)
GROUPS_UNIT = 1 << 20


def groups_in_whole_units(rows: np.ndarray) -> np.ndarray:
    """``rows`` [N, GROUP] padded with :data:`NO_ROW` groups to the next
    multiple of :data:`GROUPS_UNIT`."""
    out = np.full((-(-rows.shape[0] // GROUPS_UNIT) * GROUPS_UNIT,
                   rows.shape[1]), NO_ROW, np.int32)
    out[:rows.shape[0]] = rows
    return out


def build_subword_table(words: Sequence[str], min_n: int, max_n: int,
                        buckets: int, native: bool = True) -> SubwordRows:
    """:class:`SubwordRows` of a vocabulary: the layout in NumPy (every word
    written ``"<w>"`` in one byte buffer, its characters counted, its slots
    sized by the rule), the n-gram hashes filled in by ``native/subword.cpp``
    where it builds (2.5M words, 76M n-grams: a second or two) and by the
    bit-identical NumPy form below where it does not (``native=False`` forces
    it: the tests hold the two together)."""
    v = len(words)
    if not v:
        raise ValueError("build_subword_table: empty vocabulary")
    raw = np.frombuffer("\n".join(words).encode("utf-8"), np.uint8)
    is_sep = raw == 10
    if int(is_sep.sum()) != v - 1:
        raise ValueError("build_subword_table: a word holds a newline")
    # "<w>" for every word, back to back
    word_of_raw = np.cumsum(is_sep, dtype=np.int64)
    at = np.flatnonzero(~is_sep)
    wlen = np.bincount(word_of_raw[at], minlength=v).astype(np.int64) + 2
    wend = np.cumsum(wlen)                      # byte end of each marked word
    buf = np.empty(int(wend[-1]), np.uint8)
    buf[wend - wlen] = ord("<")
    buf[wend - 1] = ord(">")
    buf[at + word_of_raw[at] + 1] = raw[at]
    del raw, is_sep, at, word_of_raw

    # characters are code points: every byte but UTF-8's continuation bytes
    starts = np.cumsum((buf & 0xC0) != 0x80, dtype=np.int64)
    nchars = np.diff(starts[wend - 1], prepend=0)
    del starts
    # the layout: own row, then the n-grams by start and length
    counts = 1 + sum(np.maximum(nchars - n + 1, 0) for n in range(min_n, max_n + 1))
    groups = -(-counts // GROUP)
    goff = np.zeros(v + 2, np.int64)
    np.cumsum(groups, out=goff[1:v + 1])
    goff[v + 1] = goff[v]
    if goff[v] * GROUP >= 2**31:
        raise ValueError("build_subword_table: the row table passes 2^31 slots")
    flat = np.full(int(goff[v]) * GROUP, NO_ROW, np.int32)
    base = goff[:v] * GROUP
    flat[base] = np.arange(v, dtype=np.int32)
    from glint_word2vec_tpu.data.native import default_threads
    lib = _load_native() if native else None
    if lib is not None:
        slot0 = np.ascontiguousarray(base + 1)
        lib.glint_subword_fill(
            buf.ctypes.data, wend.ctypes.data, v, min_n, max_n,
            ctypes.c_uint32(buckets), v, slot0.ctypes.data, flat.ctypes.data,
            default_threads())
    else:
        _fill_numpy(buf, wend, nchars, base, min_n, max_n, buckets, flat)
    return SubwordRows(
        offsets=goff.astype(np.int32), rows=flat.reshape(-1, GROUP),
        counts=np.append(counts, 0).astype(np.int32),
        max_groups=int(groups.max()))


def _fill_numpy(buf, wend, nchars, base, min_n: int, max_n: int, buckets: int,
                flat) -> None:
    """The n-gram rows of every word into ``flat``, by start then by length.
    Every character of every ``"<w>"`` is a possible start; the hash of the
    n-gram of k characters from a start is that of k - 1 characters continued,
    so ``max_n`` passes over the characters (each as many passes over bytes as
    the widest character has) give every n-gram of every length."""
    v = nchars.shape[0]
    cpos = np.flatnonzero((buf & 0xC0) != 0x80)
    cword = np.repeat(np.arange(v, dtype=np.int64), nchars)
    clen = np.diff(np.append(cpos, buf.shape[0]))
    cidx = np.arange(cpos.shape[0], dtype=np.int64) - (np.cumsum(nchars) - nchars)[cword]
    left = nchars[cword] - cidx                 # characters from a start to the end
    widest = int(clen.max())
    sbyte = buf.view(np.int8).astype(np.int32).view(np.uint32)   # sign-extended
    # an n-gram's slot: after the own row, by start, then by length among the
    # lengths the start has room for
    room = np.clip(left - min_n + 1, 0, max_n - min_n + 1)
    first = np.cumsum(room) - room
    slot = base[cword] + 1 + first - first[np.cumsum(nchars) - nchars][cword]
    live = np.arange(cpos.shape[0], dtype=np.int64)
    hl = np.full(cpos.shape[0], FNV_OFFSET, np.uint32)
    prime = np.uint32(FNV_PRIME)
    for k in range(max_n):
        keep = left[live] > k                   # the k-th character exists
        live, hl = live[keep], hl[keep]
        p, length = cpos[live + k], clen[live + k]
        for b in range(widest):
            nxt = (hl ^ sbyte[np.minimum(p + b, buf.shape[0] - 1)]) * prime
            hl = nxt if b == 0 else np.where(length > b, nxt, hl)
        if k + 1 >= min_n:
            flat[slot[live] + (k + 1 - min_n)] = (
                nchars.shape[0] + (hl % np.uint32(buckets)).astype(np.int64))


_lib = None
_lib_failed = False


def _load_native():
    """``native/subword.cpp`` under data/native.py's build-on-first-use
    contract, or None (the NumPy form then fills the table)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    from glint_word2vec_tpu.data.native import build_or_reload
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "native", "subword.cpp")
    lib = None
    if not os.environ.get("GLINT_DISABLE_NATIVE"):
        lib = build_or_reload(src, os.path.join(os.path.dirname(src), "libsubword"),
                              "glint_subword_abi_version", 2, "c++17", "subword")
    if lib is None:
        _lib_failed = True
        return None
    lib.glint_subword_fill.restype = None
    lib.glint_subword_fill.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,      # buf, wend, words
        ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32,       # min_n, max_n, buckets
        ctypes.c_int32, ctypes.c_void_p, ctypes.c_void_p,      # row0, slot0, flat
        ctypes.c_int32]                                        # threads
    lib.glint_subword_hash_strings.restype = ctypes.c_int64
    lib.glint_subword_hash_strings.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,      # bytes, end, strings
        ctypes.c_int32, ctypes.c_int32, ctypes.c_uint32,       # min_n, max_n, buckets
        ctypes.c_void_p, ctypes.c_void_p]                      # ids, counts (out)
    _lib = lib
    return _lib
