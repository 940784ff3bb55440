"""The feeds of ``Trainer.fit``: its four ways of turning encoded sentences
into dispatches, behind one interface (:class:`Feed`).

``fit`` resolves the lr clock, builds ONE feed and runs ONE loop over its
rounds (a :class:`Round` is the K steps of one dispatch). What the loop does
once a round arrives is the same for every feed and lives in
``train/trainer.py``; how a round is made lives here: :class:`HostPairs`
(pairs or CBOW windows made on the host), :class:`TokenBlocks` (kept-token
blocks: device pair generation and banded CBOW), and their ``shard_input``
forms on several processes, :class:`GatheredPairs` and
:class:`GatheredTokenBlocks`, where each process makes its share and one
allgather a round assembles the batch. A feed keeps its own resume rules, its
own ``TrainState`` fields, and runs its ``producer`` / ``stage_put`` /
``allgather_fetch`` spans on the thread that does that work.
"""

from __future__ import annotations

import time
from typing import Any, Iterator, List, NamedTuple, Sequence

import jax
import numpy as np

from glint_word2vec_tpu.ops.sgns import alpha_schedule
from glint_word2vec_tpu.parallel.distributed import put_global
from glint_word2vec_tpu.train.checkpoint import TrainState


class Round(NamedTuple):
    """The K steps of one dispatch, as a feed hands them to the fit loop."""
    arrays: dict         # the chunk's feed arrays: on the host, or on the
                         # device where the feed is ``placed``
    meta: np.ndarray     # [1 + segments, K] f32: row 0 the alphas, then every
                         # segment's real count (pairs) or valid tokens
    real: int            # live steps (the rest pad the compiled chunk length)
    pairs: float         # pairs the round trains: counted by the pair feeds,
                         # the analytic estimate of the token feeds
    bases: tuple         # hash-lattice bases that ride with the meta (the
                         # token feeds' subsample and window streams)
    state: TrainState    # where a checkpoint taken after this round resumes
    touched: Any = None  # the stager's forcing op's output, kept alive with
                         # the round and never fetched (_stage_to_device)


def stack_rows(parts: Sequence, K: int, dtype=None, out=None) -> np.ndarray:
    """``[K, ...]``: at most K arrays (or numbers) of one shape stacked in
    place, the rest zero: the padding to the compiled chunk length, which the
    device masks out by its zero real / valid count. Filled in place because
    ``np.stack`` + ``astype`` copies the chunk twice more and measurably
    throttled the producer (~2x the raw pair generation at B=64k); only the
    padding is zeroed (a whole zeroed chunk is a second pass over 8 MB)."""
    if out is None:
        first = np.asarray(parts[0])
        out = np.empty((K,) + first.shape, dtype or first.dtype)
    for j, part in enumerate(parts):
        out[j] = part
    out[len(parts):] = 0
    return out


def stack_batches(batches: Sequence[tuple], K: int, index_dtype,
                  count_dtype) -> dict:
    """A round's pair arrays from at most K batches ``(centers, contexts)``
    (one contiguous [K, 2, B] array: see Trainer._build_step) or, CBOW,
    ``(centers, contexts, nctx)``."""
    parts = list(zip(*batches))
    if len(parts) == 3:
        return {"centers": stack_rows(parts[0], K, index_dtype),
                "contexts": stack_rows(parts[1], K, index_dtype),
                "nctx": stack_rows(parts[2], K, count_dtype)}
    pairs = np.empty((K, 2) + parts[0][0].shape, index_dtype)
    stack_rows(parts[0], K, out=pairs[:, 0])
    stack_rows(parts[1], K, out=pairs[:, 1])
    return {"pairs": pairs}


def round_meta(cfg, clocks: Sequence, total_words: float, K: int,
               rows: np.ndarray) -> np.ndarray:
    """A round's meta rows from its steps' word clocks: the alphas of the
    reference's schedule over ``rows`` ([segments, K] f32). Padding steps
    repeat the last clock (they train nothing)."""
    clocks = [*clocks, *[clocks[-1]] * (K - len(clocks))]
    alphas = np.asarray(
        [alpha_schedule(float(w), total_words, cfg.learning_rate,
                        cfg.min_alpha_factor) for w in clocks], np.float32)
    return np.concatenate([alphas[None, :], rows])


class _threaded_iter:
    """Run a generator on a background thread with a bounded buffer.

    Exceptions raised by the generator re-raise at the consumer's ``next()``.
    ``close()`` (also called on garbage collection) stops the producer promptly even
    if it is blocked on a full buffer.
    """

    _DONE = object()
    _thread_name = "glint-batch-producer"

    def __init__(self, gen, maxsize: int):
        import queue
        import threading

        self._q: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self._stop = threading.Event()
        self._queue_mod = queue

        def put_checked(item) -> bool:
            """Bounded put that gives up once the consumer signals stop — every put
            (including the terminal DONE/exception) must be preemptible or an
            abandoned iterator leaks a blocked producer thread."""
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def run():
            try:
                for item in self._gate(gen):
                    if not put_checked(item):
                        return
                put_checked(self._DONE)
            except BaseException as e:  # noqa: BLE001 — relayed to the consumer
                put_checked(e)

        self._thread = threading.Thread(
            target=run, daemon=True, name=self._thread_name)
        self._thread.start()

    def _gate(self, gen):
        """``gen``'s items as the producer thread may make them: freely."""
        return gen

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is self._DONE:
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        return item

    def close(self) -> None:
        self._stop.set()
        try:  # unblock a producer waiting on a full queue
            while True:
                self._q.get_nowait()
        except self._queue_mod.Empty:
            pass
        self._thread.join(timeout=5.0)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _one_ahead_iter(_threaded_iter):
    """Run a generator on a background thread exactly ONE item ahead of the
    consumer, under an explicit ``ack()`` ticket: after delivering item r the
    producer does not start producing item r+1 until the consumer acks r.

    This is the multi-process staging primitive (PERF.md §10). Producing a
    round launches device programs (the next round's allgather, the staging
    touch) and consuming one launches more (the step dispatch, heartbeat
    fetches, checkpoint collectives). Cross-host deadlock-freedom requires
    every process to enqueue collective programs in the same order, so the
    ticket serializes the two threads into ONE deterministic per-process
    launch order — [stage_r, dispatch_r + bookkeeping_r, stage_{r+1}, ...] —
    identical on every process because both sides are pure functions of
    allgathered values. The overlap win survives: stage_{r+1}'s HOST work
    (allgather result decode, feed assembly, device-put DMA) runs while chunk
    r executes on device.

    Generator exceptions re-raise at the consumer's ``next()``; ``close()``
    unblocks and joins the producer."""

    _thread_name = "glint-round-stager"

    def __init__(self, gen):
        import queue

        self._ack: "queue.Queue" = queue.Queue()
        super().__init__(gen, maxsize=1)

    def _gate(self, gen):
        for item in gen:
            yield item
            # the ack gate sits BEFORE producing item r+1 (before re-entering
            # the generator), so stage r+1's program launches come after the
            # consumer's round-r launches everywhere
            while not self._stop.is_set():
                try:
                    self._ack.get(timeout=0.1)
                    break
                except self._queue_mod.Empty:
                    continue
            else:
                return

    def ack(self) -> None:
        self._ack.put(None)


def _stage_to_device(trainer, rounds):
    """Generator stage: place each round's feed arrays on device and dispatch a
    tiny consuming op so the host→device wire transfer happens HERE — on the
    producer thread when prefetching — overlapped with the main thread's step
    dispatches. Argument upload is otherwise lazy and serializes with compute
    at dispatch time, which shows wherever the feed link is thin (a DCN feed,
    a slow PCIe hop).

    Single-process free-running only: with multiple processes, a
    producer-thread dispatch would race the main thread's step dispatch for
    cross-host program launch order and can deadlock the collectives — the
    multi-process token-block feed instead stages through the
    ``_one_ahead_iter`` ticket handshake (see GatheredTokenBlocks), which
    pins one deterministic launch order; the remaining multi-process feeds
    keep the consumer-thread put."""
    for rnd in rounds:
        with trainer._tracer.span("stage_put"):
            stacked = put_global(trainer._chunk_shardings, rnd.arrays)
        # retain the forcing op's output with the round (never fetched — a
        # blocking fetch here stalls the producer behind the device queue,
        # measured slower; the dispatch is enough to enqueue the upload).
        # NOT on the multi-device CPU mesh: the touch's tiny cross-shard
        # reduction lowers to collectives, and a producer-THREAD program
        # racing the main thread's chunk is exactly the rendezvous-
        # starvation deadlock Trainer.__init__ documents (this touch was the
        # racer observed live). There is no lazy-upload wire to force on
        # that backend anyway — device_put is a host memcpy.
        yield rnd._replace(
            arrays=stacked,
            touched=(None if trainer._sync_collectives
                     else trainer._touch(stacked)))


class Feed:
    """What the fit loop knows of a feed. Construction checks the resume
    state and may start the producer thread (before the run's bookkeeping,
    so a refused resume starts no run); ``rounds`` starts the round stream,
    after it."""

    # the device arguments that follow ``base`` in the step's call
    step_args: tuple = ()
    # the step counts its pairs itself and returns ``(metrics, dropped)``:
    # a round's ``pairs`` is then an estimate, settled at the end of the fit
    counts_on_device = False
    # rounds arrive with their arrays on the device (the loop puts nothing)
    placed = False
    # ``next()`` of the rounds gathers and assembles on the fit thread and
    # books its own host-wait / dispatch split (the loop adds no wait)
    books_own_time = False

    def __init__(self, trainer, train_words: float, total_words: float,
                 K: int):
        self.trainer = trainer
        # the lr clock as fit resolved it: expected kept words an iteration,
        # the schedule's denominator; and the steps of a dispatch
        self.train_words, self.total_words, self.K = (
            train_words, total_words, K)
        # ``words_processed`` of the finished fit
        self.final_words = int(trainer.config.num_iterations * train_words)
        self._chunks = None   # this process's producer: closed with the feed

    def rounds(self, beacons) -> Iterator[Round]:
        """The round stream. ``beacons``: the peer liveness board a gathering
        feed checks before each gather, on the thread that launches it."""
        return iter(self._chunks)

    def ack(self) -> None:
        """The loop has finished with the round it was last handed."""

    def close(self) -> None:
        self._chunks.close()

    def _produce(self, stream, stage: bool = False):
        """Run ``stream`` with each chunk's assembly timed (span ``producer``)
        on the thread that does it: when prefetching, a ``_threaded_iter``
        keeping a bounded buffer of ready chunks (numpy releases the GIL in
        its hot loops, so production genuinely overlaps dispatch), with the
        device staging on the same thread where ``stage``."""
        trainer = self.trainer
        depth = trainer.config.prefetch_chunks
        stream = trainer._tracer.wrap_iter("producer", stream)
        if stage:
            stream = _stage_to_device(trainer, stream)
        self._chunks = _threaded_iter(stream, depth) if depth > 0 else stream


def pair_chunks(trainer, sentences, K: int, start_iter: int, skip: int,
                shard: int = 0, num_shards: int = 1):
    """One shard's pair batches from ``(start_iter, skip)`` to the end of the
    fit, in chunks of at most K of one iteration. Pure numpy: safe on the
    producer thread. Yields ``(k, batches, reals, words, done)``: the batches
    as ``stack_batches`` takes them, their real counts, the iteration's
    ``words_seen`` BEFORE the chunk and after each of its batches, and the
    iteration's batches consumed so far (what exact-step resume skips: the
    stream is deterministic per (seed, iteration, shard), so fast-forwarding
    the recorded count reproduces the interrupted run's position)."""
    fields = ("centers", "contexts") + (
        ("nctx",) if trainer.config.cbow else ())
    for k in range(start_iter, trainer.config.num_iterations + 1):
        to_skip = done = skip if k == start_iter else 0
        batches, reals, words = [], [], [0]
        for batch in trainer._batch_stream(sentences, k, shard, num_shards):
            if to_skip:
                to_skip -= 1
                words[0] = batch["words_seen"]
                continue
            batches.append(tuple(batch[f] for f in fields))
            reals.append(batch["real"])
            words.append(batch["words_seen"])
            if len(batches) == K:
                done += K
                yield k, batches, reals, words, done
                batches, reals, words = [], [], words[-1:]
        if batches:
            yield k, batches, reals, words, done + len(batches)


class HostPairs(Feed):
    """Packed pairs (CBOW: centers / contexts / counts) made on the host,
    every process generating the whole stream: the replicated feed, and the
    only pair feed of one process.

    The reference pipelines one minibatch ahead of its RPC round-trips
    (mllib:428-429) for the reason this feed prefetches: host work must
    overlap accelerator work. Device staging rides the producer thread
    (_stage_to_device) so the feed's wire transfer overlaps device compute
    too — single-process prefetching only: multi-process runs must keep one
    cross-host dispatch order, and with prefetch off the put stays in the loop
    so the host-wait/dispatch split keeps its documented meaning."""

    def __init__(self, trainer, sentences, train_words, total_words, K):
        super().__init__(trainer, train_words, total_words, K)
        cfg, state = trainer.config, trainer.state
        if state.shard_progress is not None and not state.finished:
            # the recorded positions index a different stream than the
            # replicated pair feed — resuming here would silently mis-position
            if state.shard_feed == "tokens":
                raise ValueError(
                    "checkpoint was written by a token-block-feed run (its "
                    "positions index per-segment token streams); resume it "
                    "with the same feed — device_pairgen=True, or "
                    "cbow_update='banded' if it was a banded-CBOW run")
            raise ValueError(
                "checkpoint was written by a sharded-input multi-process run "
                f"({len(state.shard_progress)} shards); resume it with the "
                "same process count and shard_input=True, not on the "
                "replicated feed")
        self.step_args = trainer._sampler_args

        def chunk_stream():
            for k, batches, reals, words, done in pair_chunks(
                    trainer, sentences, K, state.iteration,
                    0 if state.finished else state.batches_done):
                clocks = [(k - 1) * train_words + w for w in words[1:]]
                reals = stack_rows(reals, K, np.float32)
                yield Round(
                    stack_batches(batches, K, trainer._pair_dtype, np.uint8),
                    round_meta(cfg, clocks, total_words, K, reals[None, :]),
                    # throughput counts real (unmasked) pairs, not padded
                    # batch slots
                    len(batches), float(reals.sum()), (),
                    TrainState(iteration=k, words_processed=int(clocks[-1]),
                               batches_done=done))

        self.placed = cfg.prefetch_chunks > 0 and jax.process_count() == 1
        self._produce(chunk_stream(), stage=self.placed)


def step_rows(trainer, sentences: Sequence[np.ndarray], k: int, segs,
              skips, counts):
    """One entry per step-row over the given data segments, stacked across
    them: (tokens [n, T], start_bits [n, ·], nvalid [n] f32, obase [n, 2]
    i32, exp_kept). A segment that exhausts before the others rides as zero
    blocks (nvalid 0 — masked on device); the stream ends when every listed
    segment is exhausted. The uint64→2×int32 ordinal-base split packing
    lives only here; both token-block feeds consume this shape.

    ``skips`` (resume): per-segment block counts to fast-forward before
    joining — -1 means the segment already finished this iteration (empty
    from the start, no production cost). ``counts``: a list updated in
    place with each segment's consumed-block total (skips included) —
    the per-SEGMENT positions elastic resume persists.

    Parallelism (config.producer_workers > 1): with multiple segments the
    per-segment block streams run on their own prefetching threads, gated
    by a shared semaphore so at most ``producer_workers`` segments produce
    concurrently (the ISSUE-3 multi-worker producer: segments are
    independent and deterministic per (seed, k, s), and the merge below
    consumes them in fixed segment order, so the joined step-row stream is
    bit-identical to the serial one). Single-segment calls parallelize at
    the slab level inside Trainer._device_seg_blocks instead."""
    segs = list(segs)
    T = trainer._tokens_per_step
    tok_dt = trainer._pair_dtype
    nbytes = (T + 7) // 8
    workers = trainer.config.producer_workers
    multi_seg = workers > 1 and len(segs) > 1
    # split the worker budget: up to `workers` segments produce at once
    # (the semaphore below), and each segment's slab work gets the
    # leftover share — with fewer segments than workers the slab fan-out
    # uses the rest instead of idling (workers=8 over 2 segments → 2
    # segment threads × 4 slab workers, not 2 × 1)
    inner_workers = max(1, workers // len(segs)) if multi_seg else workers
    iters = []
    for i, s in enumerate(segs):
        skip = skips[i]
        if skip < 0:
            iters.append(iter(()))
            continue
        it = trainer._device_seg_blocks(sentences, k, s, workers=inner_workers)
        consumed = 0
        for _ in range(skip):
            if next(it, None) is None:
                # shorter stream than the checkpointed position can only
                # mean the corpus changed since the checkpoint — replaying
                # silently would train the wrong data with wrong books
                raise ValueError(
                    f"device-feed resume: segment {s} iteration {k} has "
                    f"only {consumed} blocks but the checkpoint recorded "
                    f"{skip} — the corpus does not match the checkpoint")
            consumed += 1
        iters.append(it)
        counts[i] += consumed
    closers: List[_threaded_iter] = []
    if multi_seg:
        import threading
        sem = threading.Semaphore(workers)
        _DONE = object()

        def gated(gen):
            # hold the semaphore only while producing one block, so at
            # most `workers` segment streams burn CPU at once
            while True:
                with sem:
                    item = next(gen, _DONE)
                if item is _DONE:
                    return
                yield item

        wrapped = []
        for it in iters:
            ti = _threaded_iter(gated(it), maxsize=2)
            closers.append(ti)
            wrapped.append(iter(ti))
        iters = wrapped
    try:
        while True:
            rows = []
            exp_kept = 0.0
            exhausted = 0
            for i, it in enumerate(iters):
                blk = next(it, None)
                if blk is None:
                    exhausted += 1
                    rows.append((np.zeros(T, tok_dt),
                                 np.zeros(nbytes, np.uint8), 0, 0, 0.0))
                else:
                    rows.append(blk)
                    exp_kept += blk[4]
                    counts[i] += 1
            if exhausted == len(iters):
                return
            tokens = np.stack([r[0] for r in rows])
            starts = np.stack([r[1] for r in rows])
            nvalid = np.asarray([r[2] for r in rows], np.float32)
            obase = np.asarray(
                [[r[3] & 0xFFFFFFFF, r[3] >> 32] for r in rows],
                np.uint32).view(np.int32)
            yield (tokens, starts, nvalid, obase, exp_kept)
    finally:
        for c in closers:
            c.close()


def seg_resume_state(trainer) -> List[List[int]]:
    """Validated per-SEGMENT (iteration, blocks-consumed) resume positions
    for the token-block feeds — [plan.num_data] entries in segment order.
    Fresh runs (and finished states) start every segment at
    (state.iteration, 0). Entries are per segment, not per process, so any
    process count dividing the mesh data degree can consume them (elastic
    restart)."""
    Sd = trainer.plan.num_data
    st = trainer.state
    if st.shard_progress is None or st.finished:
        if st.batches_done and not st.finished and jax.process_count() > 1:
            # a pre-elastic single-process position counts joined step ROWS
            # (zero-filled segments included) — not mappable to per-segment
            # block positions
            raise ValueError(
                "checkpoint was written mid-iteration by a pre-elastic "
                "device-feed run (no per-segment positions); resume it "
                "single-process (or from an iteration boundary)")
        return [[st.iteration, 0] for _ in range(Sd)]
    if st.shard_feed != "tokens":
        # pairs-sharded positions count b_local PAIR-batches per process,
        # not token blocks; pre-round-4 checkpoints (shard_feed None) too
        raise ValueError(
            "checkpoint shard_progress indexes the host-feed pair streams "
            f"(shard_feed={st.shard_feed!r}); resume it with "
            "device_pairgen=False — token positions are a different stream")
    if len(st.shard_progress) != Sd:
        raise ValueError(
            f"checkpoint shard_progress has {len(st.shard_progress)} "
            f"entries but the mesh data degree is {Sd}; device-feed "
            "positions are per data segment — resume on a mesh with the "
            "same data degree")
    return [[int(a), int(b)] for a, b in st.shard_progress]


def token_chunks(trainer, sentences, K: int, segs, seg_state,
                 skip_rows: int = 0):
    """The step rows of data segments ``segs`` from their positions
    ``seg_state`` (per segment, ``(iteration, blocks consumed)``) to the end
    of the fit, in chunks of at most K of one iteration. Pure numpy: safe on
    the producer thread. Yields ``(k, bases, arrays, kept, sprog, skipped)``:
    the iteration's subsample and window hash bases of the segments; the
    rows' tokens / starts / nvalid / obase stacked to ``[K, ...]``; every
    row's expected kept words (the lr clock's deltas); the segments'
    positions AFTER the chunk (the elastic-resume snapshot); and the kept
    words of the ``skip_rows`` joined rows fast-forwarded before it (the
    row-level resume of a one-process checkpoint, first iteration only)."""
    from glint_word2vec_tpu.data.hashrng import (
        STREAM_SUBSAMPLE, STREAM_WINDOW, stream_base)
    cfg = trainer.config
    segs = list(segs)
    start_iter = min(it for it, _ in seg_state)
    for k in range(start_iter, cfg.num_iterations + 1):
        bases = tuple(
            np.asarray([stream_base(cfg.seed, stream, k, s) for s in segs],
                       np.uint32)
            for stream in (STREAM_SUBSAMPLE, STREAM_WINDOW))
        # per-segment fast-forward, recomputed for EVERY k (entries may sit
        # at different iterations, e.g. an exhausted process frozen an
        # iteration behind the rest): -1 = segment already past iteration k
        # (it finished it before the checkpoint) — no rows, and its entry
        # must survive the snapshot untouched
        skips = [blocks if it == k else (-1 if it > k else 0)
                 for it, blocks in seg_state]
        counts = [0] * len(segs)  # filled in place by step_rows
        to_skip = skip_rows if k == start_iter else 0
        rows: List[tuple] = []
        skipped = 0.0

        def flush():
            nonlocal rows, skipped
            out = (k, bases,
                   [stack_rows([r[i] for r in rows], K) for i in range(4)],
                   [r[4] for r in rows],
                   [seg_state[i] if skips[i] < 0 else [k, counts[i]]
                    for i in range(len(segs))],
                   skipped)
            rows, skipped = [], 0.0
            return out

        for row in step_rows(trainer, sentences, k, segs, skips, counts):
            if to_skip:
                to_skip -= 1
                skipped += row[4]
                continue
            rows.append(row)
            if len(rows) == K:
                yield flush()
        if rows:
            yield flush()


class TokenBlocks(Feed):
    """The token-block feeds on one process: the on-device pair generator
    (config.device_pairgen) and banded CBOW (config.cbow_update="banded",
    whose blocks overlap by ±window and whose "pairs" are CBOW examples —
    the chunk/step plumbing is shared unchanged).

    The host packs whole sentences into fixed [T]-token blocks per (step,
    data-segment) and ships raw tokens + packed sentence-start bits + ordinal
    bases — ~2.1 bytes/token ≈ 1 byte/pair vs 4 for packed pairs. Subsampling
    and window expansion happen inside the jitted chunk (ops/pairgen.py, same
    hash lattice → bit-identical stream). The lr clock advances on the
    *expected* kept-word count per step (keep_prob summed over shipped tokens) —
    deterministic, and no worse an approximation than the reference's
    ``numPartitions · wordCount`` clock (mllib:406-410); exact trained-pair and
    dropped-pair totals come back from the device at the end of the run.
    """

    counts_on_device = True

    def __init__(self, trainer, sentences, train_words, total_words, K):
        super().__init__(trainer, train_words, total_words, K)
        cfg, state = trainer.config, trainer.state
        Sd = trainer.plan.num_data
        seg_state = None
        if state.shard_progress is not None and not state.finished:
            if state.shard_feed != "tokens":
                raise ValueError(
                    "checkpoint was written by a host-feed sharded-input run "
                    "(its positions index per-process pair streams); resume it "
                    "with the same process count and device_pairgen=False")
            # elastic shrink: a multi-process device-feed checkpoint records
            # per-SEGMENT (iteration, blocks) positions — one process can pick
            # all of them up (seg_resume_state validates the count).
            # Single-process-written checkpoints (batches_done > 0) keep the
            # legacy row-level skip: it rebuilds the lr clock exactly, where
            # the per-segment path is exact to < 1 clock word
            if state.batches_done == 0:
                seg_state = seg_resume_state(trainer)
        self.step_args = (trainer._table_prob, trainer._table_alias,
                          trainer._keep_prob_dev)
        skip_steps = (state.batches_done
                      if not (state.finished or seg_state) else 0)

        def chunk_stream():
            at = None
            for k, bases, (tokens, starts, nvalid, obase), kept, sprog, \
                    skipped in token_chunks(
                        trainer, sentences, K, range(Sd),
                        seg_state or [[state.iteration, 0]] * Sd, skip_steps):
                prev_words = (k - 1) * train_words
                if k != at:
                    at = k
                    # the within-iteration lr clock. After an elastic resume
                    # it is rebuilt from the saved word count (exact to < 1
                    # word) for the iteration the checkpoint was saved in;
                    # earlier catch-up iterations yield no rows at all,
                    # later ones start fresh
                    clock = (max(0.0, float(state.words_processed)
                                 - prev_words)
                             if seg_state and k == state.iteration else 0.0)
                    steps_in_iter = skip_steps if k == state.iteration else 0
                clock += skipped
                clocks = []
                for w in kept:
                    clock += w
                    clocks.append(prev_words + clock)
                steps_in_iter += len(kept)
                yield Round(
                    {"tokens": tokens, "starts": starts, "obase": obase},
                    round_meta(cfg, clocks, total_words, K, nvalid.T),
                    len(kept),
                    # analytic pairs/step estimate — heartbeat display only;
                    # exact totals come back from the device
                    sum(kept) * trainer._est_pairs_per_token,
                    bases,
                    TrainState(
                        iteration=k, words_processed=int(clocks[-1]),
                        # after an elastic (per-segment) resume the joined
                        # rows are offset from the canonical stream, so a row
                        # count would mis-position a later row-level resume —
                        # persist 0 and let shard_progress stay the
                        # authoritative position
                        batches_done=0 if seg_state else steps_in_iter,
                        # per-segment positions, so a multi-process run can
                        # pick this checkpoint up (elastic grow, any process
                        # count); this feed's own resume uses batches_done
                        shard_progress=[[int(a), int(b)] for a, b in sprog],
                        shard_feed="tokens"))

        # one process (several go through GatheredTokenBlocks): staging may
        # ride the producer thread whenever there is one
        self.placed = cfg.prefetch_chunks > 0
        self._produce(chunk_stream(), stage=self.placed)


class GatheredPairs(Feed):
    """Multi-process pairs with the sentence stream sharded across processes — the
    repartition analog (mllib:345), replacing the every-process-regenerates-
    everything feed.

    Protocol, one dispatch round at a time (all processes in lockstep):

    1. each process pulls its next LOCAL chunk — K batches of B/N pairs from
       ``epoch_batches(shard=pid, num_shards=N)`` — off its producer thread;
       an exhausted process substitutes a zero chunk;
    2. ONE ``process_allgather`` ships every process's (pairs, real counts, word
       deltas, alive flag, stream position) to every process — the data rides the
       fast device interconnect, not a host-side side channel;
    3. every process deterministically assembles the identical global batch
       ([K, 2, B]: N contiguous per-process segments), derives the global word
       clock from the summed deltas, and computes identical per-batch alphas —
       SPMD consistency holds because every input to the jitted step is a pure
       function of allgathered values;
    4. the stream ends when the allgathered alive flags are all zero. Processes
       whose stream ended early keep offering fully-masked segments, so there
       is no "process 3 ran out one step early" deadlock class.

    Unequal per-process streams make a single (iteration, batches_done) pair
    meaningless, so TrainState.shard_progress records every process's position
    (from step 2, free) and resume requires the same process count.

    The gather and the assembly run on the fit thread inside ``next()`` and
    are booked as dispatch time; only the local chunk's wait is host wait.
    """

    books_own_time = True

    def __init__(self, trainer, sentences, train_words, total_words, K):
        super().__init__(trainer, train_words, total_words, K)
        cfg, state = trainer.config, trainer.state
        self.step_args = trainer._sampler_args
        S = trainer._feed_segments
        pid = jax.process_index()

        start_iter = state.iteration
        skip = state.batches_done if not state.finished else 0
        if state.shard_progress is not None:
            sp = state.shard_progress
            if state.shard_feed not in (None, "pairs"):
                # device-feed positions count token-step rows, not b_local
                # pair-batches (None = pre-round-4 checkpoint, always pairs)
                raise ValueError(
                    "checkpoint shard_progress indexes the device-feed token "
                    f"streams (shard_feed={state.shard_feed!r}); resume "
                    "it with device_pairgen=True — pair-batch positions are a "
                    "different stream")
            if len(sp) != S:
                raise ValueError(
                    f"checkpoint shard_progress has {len(sp)} entries but this run "
                    f"has {S} processes; resume sharded-input runs with the same "
                    "process count")
            start_iter, skip = int(sp[pid][0]), int(sp[pid][1])
        elif skip:
            # a replicated-feed checkpoint's batches_done counts full-B batches of the
            # unsharded stream — there is no exact mapping onto per-process local
            # streams, so refuse rather than silently mis-position the resume
            raise ValueError(
                "checkpoint was written mid-iteration by a replicated-feed run; it "
                "cannot be resumed exactly with shard_input=True — resume with "
                "shard_input=False (or from an iteration-boundary checkpoint)")
        self._position = (start_iter, skip)
        # this feed's word clock is the summed deltas of the rounds it handed
        # on, and the finished fit's ``words_processed`` is where it stood
        self.final_words = int(state.words_processed)

        def local_stream():
            """Local chunks ([K, 2, b_local] pairs, or centers/contexts/nctx arrays
            for CBOW) + per-batch real counts and word deltas (the allgather, a
            device collective, must run on the fit thread in identical order
            everywhere: not here)."""
            for k, batches, reals, words, done in pair_chunks(
                    trainer, sentences, K, start_iter, skip, pid, S):
                yield dict(
                    arrays=stack_batches(batches, K, np.int32, np.int32),
                    reals=stack_rows(reals, K, np.int32),
                    deltas=stack_rows(np.diff(words), K, np.int64),
                    iteration=k, batches_done=done)

        self._produce(local_stream())

    def rounds(self, beacons) -> Iterator[Round]:
        from jax.experimental import multihost_utils

        trainer = self.trainer
        cfg = trainer.config
        S, K = trainer._feed_segments, self.K
        B = cfg.pairs_per_batch
        b_local = B // S
        C = 2 * cfg.window
        if cfg.cbow:
            zero_arrays = {"centers": np.zeros((K, b_local), np.int32),
                           "contexts": np.zeros((K, b_local, C), np.int32),
                           "nctx": np.zeros((K, b_local), np.int32)}
        else:
            zero_arrays = {"pairs": np.zeros((K, 2, b_local), np.int32)}
        chunks = iter(self._chunks)
        cur_iter, cur_batches = self._position
        clock = float(trainer.state.words_processed)
        exhausted = False
        while True:
            t0 = time.perf_counter()
            local = None if exhausted else next(chunks, None)
            wait = time.perf_counter() - t0
            trainer.host_wait_time += wait
            trainer._phases.add("producer_wait", wait)
            if local is None:
                exhausted = True
                local = dict(arrays=zero_arrays,
                             reals=np.zeros(K, np.int32),
                             deltas=np.zeros(K, np.int64),
                             iteration=cur_iter, batches_done=cur_batches)
            else:
                cur_iter = local["iteration"]
                cur_batches = local["batches_done"]

            if beacons is not None:
                # a dead peer never reaches its allgather — entering ours
                # would hang forever; the beacon check converts that into
                # a clean abort the supervisor restarts the gang from
                beacons.check_or_raise()
            t0 = time.perf_counter()
            g = multihost_utils.process_allgather({
                **local["arrays"],
                "reals": local["reals"],
                "deltas": local["deltas"],
                "alive": np.asarray([0 if exhausted else 1], np.int32),
                "prog": np.asarray([cur_iter, cur_batches], np.int64),
            })  # every leaf gains a leading [S] process axis
            if int(g["alive"].sum()) == 0:
                return
            reals_all = g["reals"]                              # [S, K]
            # segment s of every batch is process s's slice, matching the
            # device-side per-segment prefix masks
            if cfg.cbow:
                feed = {
                    # [S, K, b(, C)] -> [K, S, b(, C)] -> [K, B(, C)]
                    "centers": np.transpose(g["centers"], (1, 0, 2)).reshape(
                        K, B).astype(trainer._pair_dtype),
                    "contexts": np.transpose(
                        g["contexts"], (1, 0, 2, 3)).reshape(
                            K, B, C).astype(trainer._pair_dtype),
                    "nctx": np.transpose(g["nctx"], (1, 0, 2)).reshape(
                        K, B).astype(np.uint8),
                }
            else:
                # [S, K, 2, b] -> [K, 2, S, b] -> [K, 2, B]
                feed = {"pairs": np.transpose(
                    g["pairs"], (1, 2, 0, 3)).reshape(K, 2, B).astype(
                        trainer._pair_dtype)}
            clocks = clock + np.cumsum(g["deltas"].sum(axis=0))
            clock = float(clocks[-1])
            self.final_words = int(clock)
            rnd = Round(
                feed, round_meta(cfg, clocks, self.total_words, K,
                                 reals_all.astype(np.float32)),
                # each local stream pads only its final chunk, so per-process real
                # slots are prefixes and "any segment live" is a prefix too
                int((reals_all > 0).any(axis=0).sum()),
                float(reals_all.sum()), (),
                TrainState(
                    iteration=int(g["prog"][:, 0].min()),
                    words_processed=self.final_words,
                    # batches_done is meaningless across shards (each process's
                    # local stream advances at its own rate); sharded-input
                    # resume MUST use shard_progress, so persist 0 here rather
                    # than the writing process's local count
                    batches_done=0,
                    shard_progress=[[int(a), int(b_)] for a, b_ in g["prog"]],
                    shard_feed="pairs"))
            trainer.dispatch_time += time.perf_counter() - t0
            yield rnd


class GatheredTokenBlocks(Feed):
    """Multi-process token blocks: BOTH input sharding and a token-block feed.
    Each process packs token blocks for its plan.num_data / process_count
    data segments only; one process_allgather per dispatch round
    ships (tokens, starts, ordinal bases, valid counts, expected-kept clock
    deltas, alive flags, stream positions) to every process, which assembles
    the identical [K, Sd, T] global token feed and derives identical alphas —
    the GatheredPairs lockstep protocol (see its docstring) carrying ~1
    byte/pair of raw tokens instead of 4 bytes/pair of packed pairs.

    Segment streams are deterministic per (seed, iteration, segment) and
    independent of the producing process (Trainer._device_seg_blocks), so the
    assembled feed — and therefore training — is bit-identical to the
    single-process TokenBlocks run on the same mesh (tested:
    tests/test_multiprocess.py).

    Unlike GatheredPairs (which lets local streams cross iteration boundaries
    freely), this feed holds an ITERATION BARRIER so the update sequence is
    bit-identical to the single-process run: every round, each process offers
    its next chunk, the round's iteration is the minimum over live offers,
    and only chunks AT that iteration are consumed — a process already in
    iteration k+1 contributes zeroed segments (exactly the zero blocks the
    single-process stream pads exhausted segments with) and retains its chunk
    for a later round. Alphas use the single-process convention
    ((k-1)·train_words + within-iteration kept cumsum), reconstructed
    identically everywhere from allgathered kept sums.

    ELASTIC RESUME: TrainState.shard_progress records, per DATA SEGMENT (not
    per process), the last consumed (iteration, blocks) position. Segments
    are the real stream unit — deterministic and process-independent — so a
    checkpoint written on N processes resumes on ANY M with
    mesh data degree % M == 0, including M=1 (TokenBlocks reads the same
    entries). The reference has no analog: its recovery
    story is Spark task retry against mutated PS state (SURVEY §5).

    STAGING (config.sharded_prefetch, PERF.md §10): with prefetching on,
    the per-round allgather/assembly/device-put runs one round ahead on a
    background thread under the _one_ahead_iter ticket handshake, which
    pins ONE deterministic per-process program-launch order — the
    determinism contract above is untouched because every staged value is
    still a pure function of allgathered data; only WHEN the host does the
    work moves. Unstaged, the same stream runs on the fit thread inside
    ``next()`` and books its own wait / dispatch split.
    """

    counts_on_device = True
    placed = True

    def __init__(self, trainer, sentences, train_words, total_words, K):
        super().__init__(trainer, train_words, total_words, K)
        cfg = trainer.config
        self.step_args = (trainer._table_prob, trainer._table_alias,
                          trainer._keep_prob_dev)
        S = jax.process_count()
        pid = jax.process_index()
        spp = trainer.plan.num_data // S
        own = list(range(pid * spp, (pid + 1) * spp))

        # per-own-segment last consumed (iteration, blocks) — the elastic-resume
        # positions; fresh runs start every segment at (state.iteration, 0)
        seg_state = self._seg_state = seg_resume_state(trainer)[
            pid * spp:(pid + 1) * spp]

        def local_stream():
            """This process's chunks: K step-rows of spp [T]-token segment blocks
            + per-row expected-kept counts, this iteration's hash bases, and the
            per-own-segment (iteration, blocks) positions AFTER the chunk (the
            allgather, a device collective, must launch in identical order
            everywhere: not here)."""
            for k, (sub_b, win_b), (tokens, starts, nvalid, obase), kept, \
                    sprog, _ in token_chunks(
                        trainer, sentences, K, own, seg_state):
                yield dict(
                    tokens=tokens, starts=starts, nvalid=nvalid, obase=obase,
                    kept=stack_rows(kept, K, np.float32),
                    sub_bases=sub_b, win_bases=win_b, iteration=k,
                    sprog=np.asarray(sprog, np.int64), real=len(kept))

        self._produce(local_stream())
        # stage one round ahead (config.sharded_prefetch): the round stream
        # runs on a _one_ahead_iter thread and launches the NEXT round's
        # allgather before yielding the current one, so the gather's wire
        # transfer sits ahead of the step dispatch in the device queue and the
        # host-side decode/assembly/put-DMA overlap chunk compute. The ticket
        # handshake keeps one deterministic cross-host launch order:
        # [gather_1, touch_1, gather_2], dispatch_1 + bookkeeping_1,
        # [touch_2, gather_3], dispatch_2, ... — identical on every process.
        self._staged = bool(cfg.sharded_prefetch and cfg.prefetch_chunks > 0)
        self.books_own_time = not self._staged
        self._rounds = None

    def rounds(self, beacons) -> Iterator[Round]:
        self._rounds = self._round_stream(beacons)
        if self._staged:
            self._rounds = _one_ahead_iter(self._rounds)
        return iter(self._rounds)

    def ack(self) -> None:
        if self._staged:
            # round fully consumed (dispatch + any heartbeat fetch /
            # checkpoint collectives launched) — release the stager
            self._rounds.ack()

    def close(self) -> None:
        if self._rounds is not None:
            self._rounds.close()
        super().close()

    def _round_stream(self, beacons):
        from glint_word2vec_tpu.parallel.distributed import (
            allgather_fetch, allgather_start)
        trainer = self.trainer
        cfg, state = trainer.config, trainer.state
        train_words, K, staged = self.train_words, self.K, self._staged
        S = jax.process_count()
        pid = jax.process_index()
        Sd = trainer.plan.num_data
        spp = Sd // S
        T = trainer._tokens_per_step
        tok_dt = trainer._pair_dtype
        nbytes = (T + 7) // 8
        chunks = iter(self._chunks)
        cur_sprog = np.asarray(self._seg_state, np.int64)  # [spp, 2] last CONSUMED
        # barrier state: the iteration currently training and its cumulative
        # kept-word clock. On resume the within-iteration clock is rebuilt
        # from the saved word count (exact to < 1 word — the int()
        # truncation of the analytic iteration base; same approximation
        # class as the saved clock itself, and resumed runs match
        # uninterrupted ones to the suite's 1e-4 standard, not bitwise)
        round_iter = state.iteration
        iter_kept = max(0.0, float(state.words_processed)
                        - (round_iter - 1) * train_words)
        held = None         # produced-but-not-yet-consumed local chunk
        exhausted = False
        zero = dict(tokens=np.zeros((K, spp, T), tok_dt),
                    starts=np.zeros((K, spp, nbytes), np.uint8),
                    nvalid=np.zeros((K, spp), np.float32),
                    obase=np.zeros((K, spp, 2), np.int32),
                    kept=np.zeros(K, np.float32),
                    sub_bases=np.zeros(spp, np.uint32),
                    win_bases=np.zeros(spp, np.uint32))

        def start_gather():
            """Collect this process's next offer and LAUNCH (not fetch) its
            allgather."""
            nonlocal held, exhausted
            if held is None and not exhausted:
                t0 = time.perf_counter()
                held = next(chunks, None)
                if not staged:
                    wait = time.perf_counter() - t0
                    trainer.host_wait_time += wait
                    trainer._phases.add("producer_wait", wait)
                if held is None:
                    exhausted = True
            offer = held if held is not None else dict(
                zero, iteration=int(cur_sprog[:, 0].max()),
                sprog=cur_sprog, real=0)
            return allgather_start({
                "tokens": offer["tokens"], "starts": offer["starts"],
                "nvalid": offer["nvalid"], "obase": offer["obase"],
                "kept": offer["kept"],
                "sub": offer["sub_bases"], "win": offer["win_bases"],
                "real": np.asarray([offer["real"]], np.int32),
                "iter": np.asarray([offer["iteration"]], np.int64),
                "sprog": np.asarray(offer["sprog"], np.int64),
                "alive": np.asarray([0 if exhausted else 1], np.int32),
                "prog": cur_sprog,
            })

        pending = start_gather()
        while True:
            if beacons is not None:
                # see GatheredPairs: a dead peer's collective never comes;
                # check (a file stat — safe on the stager thread) before
                # blocking on the fetch
                beacons.check_or_raise()
            t0 = time.perf_counter()
            with trainer._tracer.span("allgather_fetch"):
                g = allgather_fetch(pending)  # leading [S] process axis
            alive = g["alive"][:, 0] > 0                        # [S]
            if not alive.any():
                # every process observes the same all-dead round and stops
                # here; a pipelined gather for the round after may already
                # be launched — every process launched it identically, so
                # it executes consistently and nobody reads it
                return
            # iteration barrier: this round trains the minimum live
            # iteration; offers from a later iteration are NOT consumed —
            # their segments ride as zeros (exactly the zero blocks the
            # single-process stream pads exhausted segments with) and
            # their owners re-offer them next round
            round_it = int(g["iter"][alive, 0].min())
            use = alive & (g["iter"][:, 0] == round_it)         # [S]
            if round_it != round_iter:
                round_iter, iter_kept = round_it, 0.0
            usef = use.astype(np.float32)
            # segment axis assembly: [S, K, spp, ...] -> [K, S*spp=Sd, ...]
            arrays = {
                "tokens": np.transpose(
                    g["tokens"] * use[:, None, None, None].astype(tok_dt),
                    (1, 0, 2, 3)).reshape(K, Sd, T),
                "starts": np.transpose(
                    g["starts"] * use[:, None, None, None].astype(np.uint8),
                    (1, 0, 2, 3)).reshape(K, Sd, nbytes),
                "obase": np.transpose(
                    g["obase"] * use[:, None, None, None].astype(np.int32),
                    (1, 0, 2, 3)).reshape(K, Sd, 2),
            }
            nvalid = np.transpose(
                g["nvalid"] * usef[:, None, None], (1, 0, 2)).reshape(K, Sd)
            sub_bases = g["sub"].reshape(Sd)
            win_bases = g["win"].reshape(Sd)
            kept_step = (g["kept"].astype(np.float64)
                         * usef[:, None]).sum(axis=0)           # [K]
            # the single-process alpha convention: analytic iteration base
            # plus the within-iteration kept cumsum (identical on every
            # process — all inputs are allgathered values)
            clocks = ((round_it - 1) * train_words + iter_kept
                      + np.cumsum(kept_step))
            iter_kept += float(kept_step.sum())
            meta = round_meta(cfg, clocks, self.total_words, K, nvalid.T)
            # used processes pad only their final chunk per iteration, so
            # real rows are prefixes; the longest prefix is the row count
            real = int(g["real"][use, 0].max())

            if cfg.feed_consistency_check:
                trainer._assert_feed_consistent(
                    dict(arrays, sub=sub_bases, win=win_bases), meta)
            with trainer._tracer.span("stage_put"):
                stacked = put_global(trainer._chunk_shardings, arrays)
                if staged and not trainer._sync_collectives:
                    # force the upload DMA now, overlapped with chunk
                    # compute (skipped on the CPU mesh — see
                    # _stage_to_device; the gate condition is identical on
                    # every process, so the pinned cross-process launch
                    # order stays consistent)
                    trainer._touch(stacked)
            if use[pid] and held is not None:
                cur_sprog = np.asarray(held["sprog"], np.int64)
                held = None
            rnd = Round(
                stacked, meta, real,
                float(kept_step.sum()) * trainer._est_pairs_per_token,
                (sub_bases, win_bases),
                TrainState(
                    iteration=round_it,
                    words_processed=int(clocks[max(real - 1, 0)]),
                    # meaningless across segments — resume uses the
                    # per-segment shard_progress
                    batches_done=0,
                    # prog in THIS round's allgather predates the
                    # consumption above, so each SEGMENT's persisted
                    # position comes from its owner's offer if consumed,
                    # else from its last consumed snapshot — a held offer
                    # was not trained
                    shard_progress=[
                        [int(a), int(b)] for s in range(S) for a, b in
                        (g["sprog"][s] if use[s] else g["prog"][s])],
                    shard_feed="tokens"))
            if staged:
                # pipelining: LAUNCH the next round's gather before
                # yielding, so it precedes this round's dispatch in every
                # process's launch order and its transfer rides ahead of
                # the chunk in the device queue
                pending = start_gather()
            else:
                trainer.dispatch_time += time.perf_counter() - t0
            yield rnd
            if not staged:
                pending = start_gather()
