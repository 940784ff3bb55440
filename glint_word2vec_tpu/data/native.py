"""ctypes binding for the native pair generator (``native/pairgen.cpp``).

The shared library is built on first use with ``g++`` (no Python headers, no
pybind11 — plain C ABI) and cached next to the source under a name keyed by the
source's hash, so a copied tree can never run a stale build. Everything degrades
gracefully: if the toolchain or build is unavailable, :func:`native_available`
returns False and the pipeline stays on the bit-identical numpy path.

Set ``GLINT_DISABLE_NATIVE=1`` to force the numpy path (e.g. for A/B testing);
``GLINT_NATIVE_THREADS`` overrides the generator's thread count (default: up to 8,
capped by the host's cores).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
import time
from typing import Optional, Tuple

import numpy as np
from glint_word2vec_tpu.lockcheck import make_lock

logger = logging.getLogger("glint_word2vec_tpu")

_ABI_VERSION = 1
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "pairgen.cpp")
_LIB_STEM = os.path.join(os.path.dirname(_SRC), "libpairgen")

_lock = make_lock("data.native.load")
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def build_or_reload(src: str, lib_stem: str, abi_symbol: str, abi_version: int,
                    std: str, what: str) -> Optional[ctypes.CDLL]:
    """The shared build-on-first-use contract for every native component:
    the cached library is ``<lib_stem>.<sha256(source)[:16]>.so`` — compile
    with g++ when that file is missing (mtimes do not survive a copy, a
    content hash does), load, verify the ABI symbol, and rebuild once on a
    broken cache. Returns the CDLL or None (with a logged warning — callers
    fall back to their pure-Python path). Argtype configuration and caching
    stay with the calling module. The whole of it is the pinned span
    ``native.load`` (obs/spans.py): ``lib``, and ``built``, whether g++ ran."""
    from glint_word2vec_tpu.obs.spans import default_tracer
    from glint_word2vec_tpu.train.faults import retry_io
    with default_tracer().span("native.load", pinned=True, lib=what,
                               built=False) as span:
        with retry_io(lambda: open(src, "rb"), what=f"native source {src!r}") as f:
            lib_path = f"{lib_stem}.{hashlib.sha256(f.read()).hexdigest()[:16]}.so"

        def build() -> bool:
            span.set(built=True)
            # per-process temp name: co-hosted builders (multi-process JAX workers,
            # parallel pytest) must not interleave g++ output into one file before
            # the atomic publish below
            tmp = f"{lib_path}.tmp.{os.getpid()}"
            # sweep temp objects orphaned by builders killed mid-compile (unique
            # names mean nothing ever overwrites them); only files older than the
            # build timeout — younger ones may belong to a live concurrent builder
            # glob.escape: a cache path containing [, ?, * must match literally —
            # unescaped it would silently sweep nothing (orphans accumulate) or
            # match unrelated files for deletion
            for stale in glob.glob(glob.escape(lib_stem) + ".*.tmp*"):
                try:
                    if time.time() - os.path.getmtime(stale) > 300:
                        os.unlink(stale)
                except OSError:
                    pass
            # _FILE_OFFSET_BITS=64: the ingest loader seeks with fseeko/off_t,
            # which is only 64-bit on ILP32 glibc with this macro
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", f"-std={std}",
                   "-D_FILE_OFFSET_BITS=64", "-o", tmp, src]
            try:
                subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError) as e:
                err = getattr(e, "stderr", b"") or b""
                logger.warning("native %s build failed (%s); using the Python "
                               "path. stderr: %s", what, e,
                               err.decode(errors="replace")[-500:])
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                return False
            os.replace(tmp, lib_path)
            return True

        if not os.path.exists(lib_path) and not build():
            return None
        try:
            lib = ctypes.CDLL(lib_path)
            if getattr(lib, abi_symbol)() != abi_version:
                raise OSError(f"stale {os.path.basename(lib_path)} ABI; rebuild")
        except OSError:
            # stale or broken cache: rebuild once
            if not build():
                return None
            lib = ctypes.CDLL(lib_path)
        return lib


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
        lib = build_or_reload(_SRC, _LIB_STEM, "glint_pairgen_abi_version",
                              _ABI_VERSION, "c++17", "pairgen")
        if lib is None:
            _load_failed = True
            return None
        lib.glint_block_pairs.restype = ctypes.c_int64
        lib.glint_block_pairs.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,   # tokens, n_tokens
            ctypes.c_void_p, ctypes.c_int64,   # lengths, n_sents
            ctypes.c_void_p,                   # keep [V] f32
            ctypes.c_int32, ctypes.c_int32,    # window, legacy
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,  # seed, iter, shard
            ctypes.c_uint64,                   # token_base
            ctypes.c_int32,                    # n_threads
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,  # out c/x/clock
            ctypes.c_int64,                    # cap
            ctypes.c_void_p,                   # out_kept
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def default_threads() -> int:
    env = os.environ.get("GLINT_NATIVE_THREADS")
    if env:
        return max(1, int(env))
    return max(1, min(8, os.cpu_count() or 1))


def block_pairs_native(
    tokens: np.ndarray,
    lengths: np.ndarray,
    keep: np.ndarray,
    window: int,
    seed: int,
    iteration: int,
    shard: int,
    token_base: int,
    legacy_asymmetric_window: bool,
    n_threads: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Drop-in replacement for ``pipeline._block_pairs`` (same stream, bit-identical).

    The caller is the pipeline's producer thread; the C++ side fans out over
    sentence ranges and releases the GIL for the whole call (ctypes does).
    ``n_threads`` overrides :func:`default_threads` (0 = default) — the
    parallel slab producer divides the thread budget across its concurrent
    calls so pools never multiply (pipeline.epoch_batches). The emitted
    stream is deterministic at ANY thread count (ranges are position-keyed
    and written to disjoint output slices)."""
    lib = _load()
    assert lib is not None, "call native_available() first"
    N = int(tokens.shape[0])
    empty = (np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0, np.int64), 0)
    if N == 0:
        return empty
    tokens = np.ascontiguousarray(tokens, dtype=np.int32)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    keep = np.ascontiguousarray(keep, dtype=np.float32)
    cap = N * max(2 * window - 2, 1)
    centers = np.empty(cap, np.int32)
    contexts = np.empty(cap, np.int32)
    clock = np.empty(cap, np.int64)
    kept = ctypes.c_int64(0)
    n = lib.glint_block_pairs(
        tokens.ctypes.data, N,
        lengths.ctypes.data, int(lengths.shape[0]),
        keep.ctypes.data,
        int(window), int(bool(legacy_asymmetric_window)),
        ctypes.c_uint32(seed & 0xFFFFFFFF), ctypes.c_uint32(iteration & 0xFFFFFFFF),
        ctypes.c_uint32(shard & 0xFFFFFFFF),
        ctypes.c_uint64(token_base),
        int(n_threads) if n_threads > 0 else default_threads(),
        centers.ctypes.data, contexts.ctypes.data, clock.ctypes.data,
        cap, ctypes.byref(kept))
    if n < 0:  # cannot happen under the documented cap bound; belt and braces
        raise RuntimeError("native pairgen capacity overflow")
    if n == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32),
                np.empty(0, np.int64), int(kept.value))
    return centers[:n], contexts[:n], clock[:n], int(kept.value)
