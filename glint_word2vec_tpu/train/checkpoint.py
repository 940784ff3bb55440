"""Model persistence (reference components G9/C13) with mid-training checkpointing.

On-disk layout keeps the reference's composite-model contract (mllib:493-498,714-715,
ml:504-507) while replacing HDFS matrix shards with array files:

    path/
      words          one word per line, line order == embedding row order (exact parity
                     with the reference's sidecar, mllib:495-496)
      counts.npy     per-word corpus counts (needed to rebuild the negative-sampling
                     table on resume; the reference re-broadcasts vocabCns instead)
      syn0.npy       input embeddings [V, D] float32            (dense layout)
      syn1.npy       output embeddings [V, D] float32 (present iff trainable state saved)
      syn0.shards/rows-<start>-<stop>.npy                       (row-shards layout)
      syn1.shards/rows-<start>-<stop>.npy
      metadata.json  config + format version + train_state — the analog of the ML layer's
                     DefaultParamsWriter metadata (ml:504-507)

Two matrix layouts behind one directory contract:

- **dense** — host numpy arrays, one ``.npy`` per matrix. Fine up to a few GB.
- **row-shards** — the G9 analog of the reference's PS-side shard write
  (``matrix.save``, mllib:493-497): every process writes only the row ranges its own
  devices hold (``Array.addressable_shards``), so nothing is ever gathered to one host
  — at the 10M×300 north star each of 16 hosts writes ~0.75 GB instead of one host
  materializing 12 GB per matrix. Shards are written PADDED (as sharded in HBM) with
  the real (vocab_size, vector_size) recorded in metadata; readers slice.

``load_model`` reads either layout into host arrays; :func:`load_params_into_plan`
streams row-shards straight into a (possibly different) target mesh through
``make_array_from_callback`` + memory-mapped shard files — load never needs a full host
copy either (the "retarget a different PS topology" load path, mllib:696-725).

Improvement over the reference: ``train_state`` records (iteration, words_processed), so a
``numIterations`` run is resumable mid-way — the reference is all-or-nothing (SURVEY §5).

Integrity (docs/robustness.md): both writers record a per-file SHA-256 digest map in
``metadata.json`` (additive — older readers ignore it, so no format bump); readers
verify what they read, :func:`verify_checkpoint` audits a checkpoint without loading
the matrices into device memory, and :func:`load_latest_valid` scans a directory of
checkpoints, reclaims interrupted-save debris, and returns the newest one that
verifies — the recovery entry point after a crash or preemption.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import shutil
from typing import Any, Dict, List, Optional

import numpy as np

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.train import faults


def _traced(name: str):
    """Record this function as a host trace span on the process-wide tracer
    (obs/spans.py) — a no-op until a telemetry-on run enables it. Imported
    lazily at CALL time: this module sits on the train package's import path
    and obs pulls train.faults back in."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            from glint_word2vec_tpu.obs.spans import default_tracer
            with default_tracer().span(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco

logger = logging.getLogger("glint_word2vec_tpu")

# Per-layout format stamps: the dense .npy layout is unchanged since round 1 and stays
# at 1 (readers pinned to 1 keep working); the row-shards layout introduced 2; a
# checkpoint whose TrainState carries shard_progress (mid-run, sharded-input feed)
# stamps 3 so that older readers — whose TrainState.from_dict would silently DROP the
# field and mis-position the resume — refuse it instead.
DENSE_FORMAT_VERSION = 1
SHARDED_FORMAT_VERSION = 2
SHARD_PROGRESS_FORMAT_VERSION = 3
_READABLE_VERSIONS = (1, 2, 3)


class CheckpointCorruptError(ValueError):
    """A checkpoint failed integrity verification: missing/unparseable
    metadata, a file named in the digest map absent, or content whose SHA-256
    does not match the digest recorded at save time."""


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


class _HashingWriter:
    """File-object proxy that SHA-256-hashes every byte as it is written —
    the single-pass digest path (PERF.md §10): writers used to write each
    file and then RE-READ it through :func:`_sha256_file`, one full extra
    I/O pass over multi-GB matrices."""

    def __init__(self, f):
        self._f = f
        self.sha = hashlib.sha256()

    def write(self, data) -> int:
        self.sha.update(data)
        return self._f.write(data)

    def flush(self) -> None:
        self._f.flush()

    def tell(self) -> int:
        return self._f.tell()


def _save_npy_hashed(path: str, arr: np.ndarray) -> str:
    """``np.save`` through a hashing proxy: returns the file's SHA-256 from
    the same pass that wrote it."""
    with open(path, "wb") as f:
        w = _HashingWriter(f)
        np.save(w, arr)
    return w.sha.hexdigest()


def _save_words_hashed(path: str, words: List[str]) -> str:
    with open(path, "wb") as f:
        w = _HashingWriter(f)
        for word in words:
            w.write((word + "\n").encode("utf-8"))
    return w.sha.hexdigest()


def _run_io(tasks, workers: int) -> list:
    """Run independent no-arg I/O callables, returning their results in task
    order — a thin eager adapter over the feed plane's
    :func:`..data.pipeline.ordered_pool_map` (ONE pool primitive to
    maintain). ``workers <= 1`` runs them serially on the calling thread;
    outputs never depend on the worker count, only wall clock does
    (config.io_workers)."""
    from glint_word2vec_tpu.data.pipeline import ordered_pool_map
    tasks = list(tasks)
    return list(ordered_pool_map(
        lambda t: t(), tasks, min(workers, len(tasks))))


def _format_version(base: int, train_state: Optional["TrainState"]) -> int:
    if train_state is not None and train_state.shard_progress is not None:
        return SHARD_PROGRESS_FORMAT_VERSION
    return base


# keys the checkpoint writers own; extra_metadata may not shadow them — a
# caller-supplied "digests" or "config" would silently corrupt the contract
_RESERVED_META_KEYS = frozenset({
    "format_version", "framework", "layout", "vocab_size", "vector_size",
    "padded_vocab", "padded_dim", "config", "train_state", "digests"})


def _merge_extra_metadata(meta: Dict[str, Any],
                          extra: Optional[Dict[str, Any]]) -> None:
    if not extra:
        return
    clash = sorted(_RESERVED_META_KEYS & set(extra))
    if clash:
        raise ValueError(
            f"extra_metadata may not shadow writer-owned metadata keys "
            f"{clash}; pick different names")
    meta.update(extra)


@dataclasses.dataclass
class TrainState:
    """Mid-training progress: which iteration we are in and how many (subsampled) words
    the lr-decay clock has consumed (mllib:405-413 semantics).

    ``global_step`` is the hash-PRNG counter (ops/prng.py): persisting it keeps the
    (seed, counter) negative-sample lattice from repeating across a checkpoint resume.
    ``batches_done`` is the number of batches of the *current* iteration already trained —
    the deterministic batch-stream position that makes resume exact-step (the stream is a
    pure function of (seed, iteration, shard), so skipping ``batches_done`` batches
    reproduces the interrupted run's position).

    ``shard_progress`` records sharded stream positions; what an entry indexes depends
    on ``shard_feed``:

    - ``"pairs"`` (host-feed sharded runs, feeds.GatheredPairs): per-PROCESS
      ``[[iteration, local pair-batches done], ...]`` indexed by process id — resume
      needs the same process count.
    - ``"tokens"`` (device-feed runs): per-SEGMENT
      ``[[iteration, blocks consumed], ...]`` indexed by data segment. Segments are
      deterministic and process-independent, so resume is ELASTIC: any process count
      dividing the mesh data degree (including 1) can pick the positions up.
      Single-process device-feed checkpoints carry these alongside their own exact
      ``batches_done``.

    None on replicated-feed and host-feed single-process runs.
    """

    iteration: int = 1
    words_processed: int = 0
    finished: bool = False
    global_step: int = 0
    batches_done: int = 0
    shard_progress: Optional[List[List[int]]] = None
    # which stream shard_progress positions index: "pairs" (feeds.GatheredPairs'
    # per-process pair-batch streams) or "tokens" (per-SEGMENT device-feed
    # block positions — written by EVERY device-feed run, single-process
    # included, for elastic resume). The two count different things, so
    # resuming one with the other would silently mis-position; None on
    # host-feed single-process checkpoints and on pre-round-4 sharded ones
    # (accepted as "pairs", the only kind then)
    shard_feed: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "TrainState":
        return cls(**{k: d[k]
                      for k in ("iteration", "words_processed", "finished",
                                "global_step", "batches_done", "shard_progress",
                                "shard_feed")
                      if k in d})


@_traced("checkpoint_save")
def save_model(
    path: str,
    words: List[str],
    counts: np.ndarray,
    syn0: np.ndarray,
    syn1: Optional[np.ndarray],
    config: Word2VecConfig,
    train_state: Optional[TrainState] = None,
    extra_metadata: Optional[Dict[str, Any]] = None,
    subword_buckets: Optional[np.ndarray] = None,
    position_weights: Optional[np.ndarray] = None,
) -> None:
    """Atomic save: everything is written to a sibling temp directory first and swapped
    into place, so a crash mid-save never corrupts an existing checkpoint (the whole point
    of ``checkpoint_every_steps``-style periodic saves). Every data file's SHA-256 rides
    in ``metadata.json["digests"]`` so readers (and :func:`load_latest_valid`) can tell
    a torn or bit-rotted checkpoint from a good one.

    I/O plane (PERF.md §10): digests are computed IN the write pass
    (:class:`_HashingWriter` — one sequential pass per file, not write + re-
    read), and the four independent file writes fan out over
    ``config.io_workers`` threads. The bytes on disk and the digest map are
    identical at any worker count.

    ``extra_metadata``: additive keys merged into ``metadata.json`` (readers
    ignore unknown keys — no format bump). The continual subsystem rides
    this for the ``vocab_lineage`` chain (continual/extend.py); reserved
    keys (anything :func:`load_model_header` already reads) are refused.

    ``subword_buckets``: a subword model's bucket rows (config.subword: the
    rows of syn0 after the vocabulary's), a file ``syn0_buckets.npy`` with a
    digest of its own; ``syn0.npy`` keeps the words' own rows, so every reader
    of the dense layout still finds one row a word.

    ``position_weights``: a position-weighted CBOW model's third leaf
    (config.cbow_position_weights), [2·window, D], a file
    ``position_weights.npy`` with a digest of its own. Trained state that a
    resume needs; no part of a served vector."""
    bad = [w for w in words if (not w) or ("\n" in w)]
    if bad:
        raise ValueError(
            f"cannot save vocabulary: {len(bad)} token(s) are empty or contain newlines "
            f"(first: {bad[0]!r}); the words sidecar is newline-delimited")
    parent = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    tmp = os.path.join(parent, f".{os.path.basename(path)}.tmp-{os.getpid()}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        def stage(name: str) -> str:
            return os.path.join(tmp, name)

        syn0 = np.asarray(syn0, dtype=np.float32)
        tasks = [
            lambda: _save_words_hashed(stage("words"), words),
            lambda: _save_npy_hashed(stage("counts.npy"),
                                     np.asarray(counts, dtype=np.int64)),
            lambda: _save_npy_hashed(stage("syn0.npy"), syn0),
        ]
        names = ["words", "counts.npy", "syn0.npy"]
        if syn1 is not None:
            tasks.append(lambda: _save_npy_hashed(
                stage("syn1.npy"), np.asarray(syn1, dtype=np.float32)))
            names.append("syn1.npy")
        if subword_buckets is not None:
            tasks.append(lambda: _save_npy_hashed(
                stage("syn0_buckets.npy"),
                np.asarray(subword_buckets, dtype=np.float32)))
            names.append("syn0_buckets.npy")
        if position_weights is not None:
            tasks.append(lambda: _save_npy_hashed(
                stage("position_weights.npy"),
                np.asarray(position_weights, dtype=np.float32)))
            names.append("position_weights.npy")
        digests: Dict[str, str] = dict(
            zip(names, _run_io(tasks, getattr(config, "io_workers", 1))))
        faults.crash_point("save:arrays-written")
        meta = {
            "format_version": _format_version(DENSE_FORMAT_VERSION, train_state),
            "framework": "glint_word2vec_tpu",
            "vocab_size": int(syn0.shape[0]),
            "vector_size": int(syn0.shape[1]),
            "config": config.to_dict(auto_markers=False),
            "train_state": (train_state or TrainState(finished=True)).to_dict(),
            "digests": digests,
        }
        _merge_extra_metadata(meta, extra_metadata)
        with open(stage("metadata.json"), "w", encoding="utf-8") as f:
            json.dump(meta, f, indent=2)
        faults.crash_point("save:staged")
        old = None
        if os.path.exists(path):
            old = path + f".old-{os.getpid()}"
            os.rename(path, old)
        faults.crash_point("save:swap")  # the torn window: path absent, old+tmp live
        os.rename(tmp, path)
        if old is not None:
            shutil.rmtree(old)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    faults.corrupt_checkpoint(path)


def _write_array_shards(dirpath: str, arr, workers: int = 1) -> Dict[str, str]:
    """Write the row ranges THIS process owns (replica 0 only) as individual .npy
    files. ``arr`` is a (possibly multi-process) row-sharded jax.Array; no full-array
    host materialization happens — each shard's ``.data`` is device-local. The
    filenames carry the row ranges; readers list the directory (no manifest).
    Returns {checkpoint-relative path: sha256} for the files this process wrote.

    Each shard hashes in its own write pass (:class:`_HashingWriter`) and the
    independent shard writes — device→host fetch included — fan out over
    ``workers`` threads; the digest map is assembled in shard order, so bytes
    and metadata are identical at any worker count."""
    os.makedirs(dirpath, exist_ok=True)
    jobs = []  # (relative name, task) in shard order
    for sh in arr.addressable_shards:
        if sh.replica_id != 0:
            continue  # rows replicated over the data axis: first replica writes
        rows = sh.index[0]
        start = rows.start or 0
        stop = rows.stop if rows.stop is not None else arr.shape[0]
        cols = sh.index[1] if len(sh.index) > 1 else slice(None)
        if (cols.start or 0) != 0 or (cols.stop not in (None, arr.shape[1])):
            raise ValueError(
                "row-shards layout requires row sharding (full rows per shard); got "
                f"column slice {cols} — use the dense layout for other shardings")
        fname = f"rows-{start:010d}-{stop:010d}.npy"

        def task(sh=sh, fname=fname):
            return _save_npy_hashed(os.path.join(dirpath, fname),
                                    np.asarray(sh.data))

        jobs.append((f"{os.path.basename(dirpath)}/{fname}", task))
    return dict(zip([rel for rel, _ in jobs],
                    _run_io([t for _, t in jobs], workers)))


@_traced("checkpoint_save_sharded")
def save_model_sharded(
    path: str,
    words: List[str],
    counts: np.ndarray,
    syn0,
    syn1,
    config: Word2VecConfig,
    train_state: Optional[TrainState] = None,
    vocab_size: Optional[int] = None,
    vector_size: Optional[int] = None,
    extra_metadata: Optional[Dict[str, Any]] = None,
) -> None:
    """Row-shards save: every process writes its own rows, process 0 writes metadata
    and swaps the directory into place after a cross-process barrier. Single-process
    runs degenerate to the same protocol with one writer.

    ``syn0``/``syn1`` are the PADDED sharded jax.Arrays exactly as trained;
    ``vocab_size``/``vector_size`` record the real extents for readers.

    Failure model (shared fate, like every barrier in a SPMD program): if any process
    raises between the barriers, the survivors block in ``sync_global_devices`` until the
    JAX coordination service detects the dead process and fails the whole job — there is
    no per-process timeout here by design, because a partial save must never be swapped
    into place. Garbage left in ``.tmp-sharded`` by a failed attempt is reclaimed by the
    next save: process 0 rmtree's the staging dir before the first barrier. The atomic
    ``os.rename`` swap means an existing checkpoint at ``path`` is never corrupted by a
    mid-save crash.
    """
    import jax

    bad = [w for w in words if (not w) or ("\n" in w)]
    if bad:
        raise ValueError(
            f"cannot save vocabulary: {len(bad)} token(s) are empty or contain "
            f"newlines (first: {bad[0]!r}); the words sidecar is newline-delimited")
    multi = jax.process_count() > 1
    if multi:
        from jax.experimental import multihost_utils
    parent = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(parent, exist_ok=True)
    # deterministic tmp name: all processes write into the SAME staging dir (shared
    # filesystem contract, like the reference's HDFS target)
    tmp = os.path.join(parent, f".{os.path.basename(path)}.tmp-sharded")
    if jax.process_index() == 0:
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
    if multi:
        multihost_utils.sync_global_devices("glint-ckpt-staged")
    io_workers = getattr(config, "io_workers", 1)
    try:
        # shard lists are NOT collected into metadata: readers list the directory, and
        # the filenames carry the row ranges (a cross-process reduce would buy nothing)
        digests = _write_array_shards(os.path.join(tmp, "syn0.shards"), syn0,
                                      workers=io_workers)
        if syn1 is not None:
            digests.update(
                _write_array_shards(os.path.join(tmp, "syn1.shards"), syn1,
                                    workers=io_workers))
        # per-process digest sidecars ride the shared filesystem (the same
        # contract the shard files themselves rely on); process 0 merges them
        # into metadata after the write barrier — cheaper and simpler than
        # allgathering variable-length digest maps through the device mesh
        sidecar = os.path.join(tmp, f".digests-{jax.process_index()}.json")
        with open(sidecar, "w", encoding="utf-8") as f:
            json.dump(digests, f)
        faults.crash_point("save:arrays-written")
        if multi:
            multihost_utils.sync_global_devices("glint-ckpt-written")
        if jax.process_index() == 0:
            for name in sorted(os.listdir(tmp)):
                if name.startswith(".digests-"):
                    with open(os.path.join(tmp, name), encoding="utf-8") as f:
                        digests.update(json.load(f))
                    os.unlink(os.path.join(tmp, name))
            digests["words"] = _save_words_hashed(
                os.path.join(tmp, "words"), words)
            digests["counts.npy"] = _save_npy_hashed(
                os.path.join(tmp, "counts.npy"),
                np.asarray(counts, dtype=np.int64))
            meta = {
                "format_version": _format_version(SHARDED_FORMAT_VERSION,
                                                  train_state),
                "framework": "glint_word2vec_tpu",
                "layout": "row-shards",
                "vocab_size": int(vocab_size if vocab_size is not None
                                  else syn0.shape[0]),
                "vector_size": int(vector_size if vector_size is not None
                                   else syn0.shape[1]),
                "padded_vocab": int(syn0.shape[0]),
                "padded_dim": int(syn0.shape[1]),
                "config": config.to_dict(auto_markers=False),
                "train_state": (train_state or TrainState(finished=True)).to_dict(),
                "digests": digests,
            }
            _merge_extra_metadata(meta, extra_metadata)
            with open(os.path.join(tmp, "metadata.json"), "w", encoding="utf-8") as f:
                json.dump(meta, f, indent=2)
            faults.crash_point("save:staged")
            old = None
            if os.path.exists(path):
                old = path + ".old-swap"
                if os.path.exists(old):
                    shutil.rmtree(old)
                os.rename(path, old)
            faults.crash_point("save:swap")
            os.rename(tmp, path)
            if old is not None:
                shutil.rmtree(old)
        if multi:
            multihost_utils.sync_global_devices("glint-ckpt-done")
    except BaseException:
        if jax.process_index() == 0:
            shutil.rmtree(tmp, ignore_errors=True)
        raise
    if jax.process_index() == 0:
        faults.corrupt_checkpoint(path)


class ShardedMatrixReader:
    """Memory-mapped reader over a ``*.shards/`` directory: row-range reads without
    assembling the full matrix."""

    # np.save writes bfloat16 (an ml_dtypes type numpy has no descr for) as raw
    # 2-byte void '|V2', and np.load hands the void dtype back — assignments and
    # math on it then fail with "No cast function available". The bf16 trainer
    # is the only 2-byte-void producer in this codebase, so reads re-view the
    # bytes as bfloat16. (The dense layout is unaffected: save_model converts
    # to float32 on write.)
    _VOID2 = np.dtype("V2")

    @classmethod
    def _undo_void(cls, arr: np.ndarray) -> np.ndarray:
        if arr.dtype == cls._VOID2:
            import ml_dtypes
            return arr.view(ml_dtypes.bfloat16)
        return arr

    def __init__(self, dirpath: str):
        self.dirpath = dirpath
        self._mmap_cache: Optional[List[tuple]] = None
        self._spans: List[tuple] = []
        for fname in sorted(os.listdir(dirpath)):
            if not fname.startswith("rows-"):
                continue
            stem = fname[len("rows-"):-len(".npy")]
            start, stop = (int(x) for x in stem.split("-"))
            self._spans.append((start, stop, fname))
        if not self._spans:
            raise FileNotFoundError(f"no shard files under {dirpath!r}")
        self._spans.sort()
        self.rows = self._spans[-1][1]
        probe = self._undo_void(
            np.load(os.path.join(dirpath, self._spans[0][2]), mmap_mode="r"))
        self.cols = probe.shape[1]
        self.dtype = probe.dtype
        prev = 0
        for start, stop, _ in self._spans:
            if start != prev:
                raise ValueError(
                    f"shard gap/overlap at row {prev} (next shard starts {start}) "
                    f"under {dirpath!r}")
            prev = stop

    def read(self, start: int, stop: int, workers: int = 1) -> np.ndarray:
        """Rows [start, stop) assembled from the overlapping shard files (mmap-backed,
        so only the requested pages are touched). ``workers > 1`` copies the
        per-shard row ranges concurrently (disjoint destination slices, so the
        result is identical at any worker count)."""
        out = np.empty((stop - start, self.cols), dtype=self.dtype)

        def copy_span(span):
            s, e, fname = span
            lo, hi = max(start, s), min(stop, e)
            if lo >= hi:
                return
            m = self._undo_void(
                np.load(os.path.join(self.dirpath, fname), mmap_mode="r"))
            out[lo - start:hi - start] = m[lo - s:hi - s]

        _run_io([lambda sp=sp: copy_span(sp) for sp in self._spans], workers)
        return out

    def read_all(self, workers: int = 1) -> np.ndarray:
        return self.read(0, self.rows, workers=workers)

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Scattered rows by id, in ``ids`` order, gathered through
        cached per-shard mmap handles — ``read()`` reopens every shard
        file per call, which is fine for block streaming but dominates
        when the serving tier's re-rank stage fetches a few hundred
        scattered rows per query (serve/quant.py). Only the requested
        rows' pages are touched."""
        ids = np.asarray(ids)
        if self._mmap_cache is None:
            self._mmap_cache = [
                (s, e, self._undo_void(np.load(
                    os.path.join(self.dirpath, fname), mmap_mode="r")))
                for s, e, fname in self._spans]
        out = np.empty((ids.size, self.cols), dtype=self.dtype)
        for s, e, m in self._mmap_cache:
            mask = (ids >= s) & (ids < e)
            if mask.any():
                out[mask] = m[ids[mask] - s]
        return out


@_traced("checkpoint_load_plan")
def load_params_into_plan(path: str, plan, padded_vocab: int, padded_dim: int,
                          dtype=np.float32, verify: bool = False,
                          io_workers: Optional[int] = None):
    """Stream a row-shards checkpoint straight onto a target mesh (which may differ
    from the one that wrote it — the reference's load-onto-new-PS-topology path,
    mllib:696-725): each device's row block is read from the mmap'd shard files by a
    ``make_array_from_callback`` callback, zero-padded to the target padded shape.
    Returns (syn0, syn1) as global jax.Arrays; syn1 is None if not saved.

    ``verify=True`` checks the recorded shard digests first — one extra
    sequential read of every shard file, so it is off by default on this
    streaming path (the 10M-row north star); recovery flows that just survived
    a crash should pass True or call :func:`verify_checkpoint` themselves."""
    import jax

    meta_path = os.path.join(path, "metadata.json")
    with open(meta_path, "r", encoding="utf-8") as f:
        meta = json.load(f)
    if meta.get("layout") != "row-shards":
        raise ValueError(f"{path!r} is not a row-shards checkpoint")
    if io_workers is None:
        # fallback only — the RESUMING run's live config should set this (the
        # saved value reflects the writing host, not the loading one)
        io_workers = int(meta.get("config", {}).get("io_workers", 1))
    if verify:
        _verify_digests(path, meta, workers=io_workers)
    V, Dr = meta["vocab_size"], meta["vector_size"]

    def make(name: str):
        dirpath = os.path.join(path, f"{name}.shards")
        if not os.path.isdir(dirpath):
            return None
        reader = ShardedMatrixReader(dirpath)

        def cb(idx):
            rows = idx[0]
            start = rows.start or 0
            stop = rows.stop if rows.stop is not None else padded_vocab
            block = np.zeros((stop - start, padded_dim), dtype=dtype)
            lo, hi = start, min(stop, V)  # rows beyond the real vocab stay zero
            if lo < hi:
                src = reader.read(lo, hi, workers=io_workers)
                block[:hi - lo, :min(Dr, padded_dim)] = \
                    src[:, :min(Dr, padded_dim)]
            cols = idx[1] if len(idx) > 1 else slice(None)
            return block[:, cols]

        return jax.make_array_from_callback(
            (padded_vocab, padded_dim), plan.embedding, cb)

    return make("syn0"), make("syn1")


def _verify_digests(path: str, meta: Dict[str, Any],
                    workers: int = 1) -> None:
    """Check every recorded SHA-256 digest against the on-disk bytes.
    Checkpoints written before the digest map existed pass vacuously.
    ``workers > 1`` hashes the files concurrently (config.io_workers);
    failures are reported in sorted-name order either way."""
    digests = meta.get("digests") or {}
    items = sorted(digests.items())
    for rel, _ in items:
        if not os.path.exists(os.path.join(path, rel.replace("/", os.sep))):
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: {rel!r} is recorded in the digest map "
                f"but missing on disk — torn or partially deleted checkpoint")
    got_all = _run_io(
        [lambda rel=rel: _sha256_file(
            os.path.join(path, rel.replace("/", os.sep)))
         for rel, _ in items], workers)
    for (rel, want), got in zip(items, got_all):
        if got != want:
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: {rel!r} content digest {got[:12]}… does "
                f"not match the recorded {want[:12]}… — corrupt (bit rot, torn "
                f"write, or hand-edited); refusing to load it")


def verify_checkpoint(path: str, io_workers: int = 1) -> Dict[str, Any]:
    """Integrity audit of one checkpoint directory without loading matrices
    into device memory: metadata parses, the format version is readable, every
    required data file for the layout exists, shard spans are gapless, and all
    recorded digests match the bytes on disk. Returns the parsed metadata.
    Raises :class:`CheckpointCorruptError` (or ``FileNotFoundError`` when no
    metadata exists at all). ``io_workers > 1`` hashes files concurrently."""
    meta_path = os.path.join(path, "metadata.json")
    if not os.path.exists(meta_path):
        raise FileNotFoundError(f"no metadata.json under {path!r}")
    try:
        with open(meta_path, "r", encoding="utf-8") as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: metadata.json unreadable ({e})") from e
    version = meta.get("format_version")
    if version not in _READABLE_VERSIONS:
        raise CheckpointCorruptError(
            f"checkpoint {path!r}: unsupported format_version {version}")
    required = ["words", "counts.npy"]
    if meta.get("layout") == "row-shards":
        shard_dirs = ["syn0.shards"]
        if os.path.isdir(os.path.join(path, "syn1.shards")):
            shard_dirs.append("syn1.shards")
        for dirname in shard_dirs:
            try:
                ShardedMatrixReader(os.path.join(path, dirname))
            except (OSError, ValueError) as e:
                raise CheckpointCorruptError(
                    f"checkpoint {path!r}: {dirname} unreadable ({e})") from e
    else:
        required.append("syn0.npy")
    for name in required:
        if not os.path.exists(os.path.join(path, name)):
            raise CheckpointCorruptError(
                f"checkpoint {path!r}: required file {name!r} missing — "
                f"partial or torn checkpoint")
    _verify_digests(path, meta, workers=io_workers)
    return meta


def load_latest_valid(directory: str, reclaim: bool = True) -> str:
    """Recovery entry point: scan ``directory`` for checkpoint directories and
    return the path of the newest one that passes :func:`verify_checkpoint`.

    "Newest" orders by the recorded train progress (global_step, then
    words_processed), falling back to mtime — progress is what a resume cares
    about, and mtimes lie across filesystems and restores.

    Interrupted-save debris is reclaimed along the way (``reclaim=True``):

    - ``.\\*.tmp-\\*`` staging directories (never swapped into place) are deleted
      outright — even a complete one was never committed.
    - ``\\*.old-\\*`` directories (the previous checkpoint, renamed aside during
      the swap window) are *candidates*: if one is the newest verifiable state
      — the SIGKILL-between-renames case, where the live path vanished — it is
      renamed back into place and its path returned; superseded or corrupt
      ones are deleted.

    With ``reclaim=True`` this is a RECOVERY operation for a dead writer: it
    deletes staging directories and renames swap debris, so it must NOT race a
    live saver (it would destroy an in-flight save). Readers that may overlap
    a running trainer — a serving process polling the directory — pass
    ``reclaim=False``: nothing is touched, and a winning ``*.old-*`` candidate
    is returned at its debris path instead of being renamed back.

    Raises ``FileNotFoundError`` when nothing under ``directory`` verifies."""
    try:
        entries = sorted(os.listdir(directory))
    except OSError as e:
        raise FileNotFoundError(
            f"cannot scan checkpoint directory {directory!r}: {e}") from e
    candidates: List[tuple] = []  # (kind, name, path)
    for name in entries:
        p = os.path.join(directory, name)
        if not os.path.isdir(p):
            continue
        if ".tmp-" in name:
            if reclaim:
                logger.info("reclaiming interrupted-save staging dir %s", p)
                shutil.rmtree(p, ignore_errors=True)
            continue
        kind = "old" if ".old-" in name else "normal"
        candidates.append((kind, name, p))
    best = None  # (sort_key, kind, name, path)
    for kind, name, p in candidates:
        try:
            meta = verify_checkpoint(p)
        except (FileNotFoundError, CheckpointCorruptError, ValueError) as e:
            logger.warning("skipping unverifiable checkpoint %s: %s", p, e)
            continue
        ts = meta.get("train_state") or {}
        key = (int(ts.get("global_step") or 0),
               int(ts.get("words_processed") or 0),
               1 if kind == "normal" else 0,
               os.path.getmtime(p))
        if best is None or key > best[0]:
            best = (key, kind, name, p)
    if best is None:
        raise FileNotFoundError(
            f"no verifiable checkpoint under {directory!r} "
            f"({len(candidates)} candidate(s) scanned)")
    _, kind, name, p = best
    if not reclaim:
        return p
    if kind == "old":
        # the swap was interrupted after the previous checkpoint was renamed
        # aside: restore it to its base name so resume paths see a normal
        # checkpoint (anything sitting at the base name failed verification,
        # or it would have outranked this debris)
        base = os.path.join(directory, name.split(".old-")[0])
        if os.path.exists(base):
            shutil.rmtree(base)
        os.rename(p, base)
        logger.warning("recovered checkpoint %s from interrupted-save "
                       "debris %s", base, name)
        p = base
    for kind2, _, p2 in candidates:
        if kind2 == "old" and p2 != best[3] and os.path.exists(p2):
            logger.info("reclaiming superseded swap debris %s", p2)
            shutil.rmtree(p2, ignore_errors=True)
    return p


def load_model_header(path: str) -> Dict[str, Any]:
    """Read everything EXCEPT the matrices: metadata, words sidecar, counts. This is
    the cheap half of the reference's load contract (the ``/words`` read + params
    metadata, mllib:714-715, ml:514-519) — used by the sharded model-load path so the
    [V, D] matrices never materialize on one host."""
    meta_path = os.path.join(path, "metadata.json")
    if not os.path.exists(meta_path):
        raise FileNotFoundError(f"no metadata.json under {path!r}")
    with open(meta_path, "r", encoding="utf-8") as f:
        meta = json.load(f)
    version = meta.get("format_version")
    if version not in _READABLE_VERSIONS:
        raise ValueError(f"unsupported checkpoint format_version {version}")
    with open(os.path.join(path, "words"), "r", encoding="utf-8") as f:
        words = [line.rstrip("\n") for line in f if line.rstrip("\n")]
    counts = np.load(os.path.join(path, "counts.npy"))
    declared = meta.get("vocab_size")
    if declared is not None and declared != len(words):
        raise ValueError(
            f"words sidecar has {len(words)} entries but metadata declares "
            f"vocab_size {declared} — corrupt or hand-edited checkpoint")
    return {
        "words": words,
        "counts": counts,
        "layout": meta.get("layout", "dense"),
        "vocab_size": meta.get("vocab_size", len(words)),
        "vector_size": meta.get("vector_size"),
        "config": Word2VecConfig.from_dict(meta["config"]),
        "train_state": TrainState.from_dict(meta.get("train_state", {})),
        # continual-training provenance (continual/extend.py): the chain of
        # vocabulary migrations this checkpoint descends from; [] on
        # checkpoints that never grew
        "vocab_lineage": meta.get("vocab_lineage", []),
    }


@_traced("checkpoint_load")
def load_model(path: str, header: Optional[Dict[str, Any]] = None,
               verify: bool = True,
               io_workers: Optional[int] = None) -> Dict[str, Any]:
    """Read a saved model directory. Returns dict with words, counts, syn0, syn1 (may be
    None), config, train_state. Mirrors the reference's load contract (mllib:710-725:
    read /words in row order, load matrix shards, rebuild model).

    ``header``: a prior :func:`load_model_header` result to reuse — callers that
    already read it (to check the layout) pass it through so the words sidecar and
    counts are not parsed twice.

    ``verify`` (default True): check every file against the SHA-256 digests the
    writer recorded — a bit-flipped or torn checkpoint raises
    :class:`CheckpointCorruptError` instead of silently loading garbage rows.
    Costs one extra sequential read of the files; this full-materialization
    path is host-RAM-bound anyway (pre-digest checkpoints pass vacuously).

    ``io_workers`` (default: the saved config's ``io_workers``) fans digest
    hashing, per-shard reads, and the syn0/syn1 loads across a thread pool —
    the loaded arrays are identical at any worker count."""
    if header is None:
        header = load_model_header(path)
    if io_workers is None:
        io_workers = getattr(header["config"], "io_workers", 1)
    if verify:
        meta_path = os.path.join(path, "metadata.json")
        with open(meta_path, "r", encoding="utf-8") as f:
            _verify_digests(path, json.load(f), workers=io_workers)
    words = header["words"]
    if header["layout"] == "row-shards":
        V, Dr = header["vocab_size"], header["vector_size"]
        s1dir = os.path.join(path, "syn1.shards")
        # split the worker budget across the two matrices, each of which fans
        # its own per-shard copies (disjoint destination slices)
        per = max(1, io_workers // 2)
        syn0, syn1 = _run_io(
            [lambda: ShardedMatrixReader(
                os.path.join(path, "syn0.shards")).read(
                    0, V, workers=per)[:, :Dr],
             lambda: (ShardedMatrixReader(s1dir).read(
                 0, V, workers=per)[:, :Dr]
                      if os.path.isdir(s1dir) else None)],
            io_workers)
    else:
        syn1_path = os.path.join(path, "syn1.npy")
        syn0, syn1 = _run_io(
            [lambda: np.load(os.path.join(path, "syn0.npy")),
             lambda: (np.load(syn1_path) if os.path.exists(syn1_path)
                      else None)],
            io_workers)
    if syn0.shape[0] != len(words):
        raise ValueError(
            f"words sidecar has {len(words)} entries but syn0 has {syn0.shape[0]} rows")
    buckets_path = os.path.join(path, "syn0_buckets.npy")
    pos_path = os.path.join(path, "position_weights.npy")
    position_weights = np.load(pos_path) if os.path.exists(pos_path) else None
    cfg = header["config"]
    got = None if position_weights is None else position_weights.shape
    want = ((2 * cfg.window, syn0.shape[1]) if cfg.cbow_position_weights
            else None)
    if got != want:
        raise ValueError(
            f"checkpoint {path!r}: position weights of shape {got} where its "
            f"config (cbow_position_weights={cfg.cbow_position_weights}, "
            f"window={cfg.window}) needs {want}")
    return {
        "words": words,
        "counts": header["counts"],
        "syn0": syn0,
        "syn1": syn1,
        # a subword model's bucket rows (save_model), None for any other
        "subword_buckets": (np.load(buckets_path)
                            if os.path.exists(buckets_path) else None),
        # a position-weighted CBOW model's third leaf, None for any other
        "position_weights": position_weights,
        "config": header["config"],
        "train_state": header["train_state"],
    }
