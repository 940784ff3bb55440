"""Request batching scheduler: a bounded queue + deadline micro-batcher.

The serving economics this exists for (PERF.md §6, ROADMAP item 1): one
exact synonym query at V=1M costs 230-375 ms through a thin host→device
link, but 64 queries coalesced into ONE device dispatch cost 13-16 ms
total — the per-query round trip dominates, not the math. This scheduler
turns N concurrent callers into that one dispatch:

- ``submit()`` enqueues a request and blocks the calling thread until its
  result is ready (clients are threads — the stdin CLI, the bench harness's
  closed-loop clients, the chaos storm);
- one worker thread pops the queue and coalesces up to ``max_batch``
  requests, waiting at most ``max_delay_ms`` past the FIRST request's
  arrival (latency is bounded by the deadline, throughput by the batch cap);
- the whole batch goes to the ``handler`` callable in one call; the handler
  returns one result per request (an ``Exception`` instance marks a
  per-request failure — an OOV word must fail ITS caller, not the batch);
- **a handler may come in two halves** (``handler`` + ``finish``): the first
  ends with the batch's device work enqueued and returns what is pending,
  the second fetches it and returns the results. The worker runs the first
  half and hands the batch to a second thread, the COMPLETER, which runs
  the second, releases the callers and runs the hooks, in the order the
  batches were closed. :data:`MAX_INFLIGHT` batches are between the two at
  a time: while the device scans for one batch the worker resolves, and
  enqueues, the next, and the chip does not wait for the host's half of a
  batch. The worker takes its slot of that bound BEFORE it closes a batch:
  a device program costs the same at 8 requests as at 64, so requests that
  arrive while both slots are taken wait in the queue and leave as one
  batch, not as pieces cut by the deadline. A handler with one half
  (``finish=None``) has done all its work when it returns, and the
  completer only releases its callers;
- **backpressure is a fast refusal, never unbounded memory**: a full queue
  raises :class:`ServerOverloaded` to the caller immediately (the 429-style
  contract) instead of queueing into latency collapse.

Determinism note (graftlint R1): the worker and the completer are
sanctioned owners — they only ORDER request/response pairing (each caller
gets exactly its own result back; one completer, so batches finish in the
order they were closed) and are read-only on model parameters; they never
produce or order training data, so the worker-count determinism contract is
untouched. Batch COMPOSITION is timing-dependent by design (that is what a
micro-batcher is); per-request results are not, because the handler maps
item i to result i.
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence
from glint_word2vec_tpu.lockcheck import make_condition
from glint_word2vec_tpu.obs.spans import default_tracer

logger = logging.getLogger("glint_word2vec_tpu")

# batches begun and not yet finished: one whose device program runs and one
# queued behind it. The device runs programs one after another, so a third
# would buy nothing and cost a third score block (0.4-0.5 GB at 3M rows);
# not a setting
MAX_INFLIGHT = 2


class ServerOverloaded(RuntimeError):
    """Admission refused: the bounded queue is full. The serving analog of
    HTTP 429 — callers should shed or retry with backoff; the server never
    buffers unboundedly.

    ``retry_after_s`` is the machine-readable backoff hint (the Retry-After
    header analog): queued batches ahead × the observed batch service time —
    how long the present backlog takes to drain at the measured rate. None
    when the server has not yet completed a batch to measure. Fleet routers
    honor it as "retry ELSEWHERE now, retry HERE after the hint"
    (serve/fleet.py)."""

    status = 429

    def __init__(self, message: str, retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class ServiceClosed(RuntimeError):
    """Submit refused: the scheduler is stopping or stopped. The typed
    shutdown refusal — before this class a submit racing ``stop()`` got
    whatever the dead worker queue produced (a bare RuntimeError at best, a
    forever-parked ticket at worst). Distinct from :class:`ServerOverloaded`
    on purpose: overload means "retry later / elsewhere", closed means "this
    replica is going away — re-resolve, don't retry here"."""


class _Ticket:
    """One in-flight request: payload in, result/error out, an event the
    submitting thread parks on. ``trace`` is the cross-process trace
    context (``{"tid": ..., "ps": ...}``, obs/trace.py) when the request is
    being traced, else None — the default path allocates nothing extra.
    ``enqueued`` is on the span recorder's clock (obs/spans.py ``now``);
    ``seq`` is the admission sequence number its ``serve.queue_wait`` span
    carries as ``request``."""

    __slots__ = ("payload", "enqueued", "done", "result", "error", "trace",
                 "seq")

    def __init__(self, payload: Any, trace: Optional[dict] = None,
                 seq: int = 0):
        self.payload = payload
        self.seq = seq
        self.enqueued = time.monotonic()
        self.done = threading.Event()
        self.result: Any = None
        self.error: Optional[BaseException] = None
        self.trace = trace


class _Begun:
    """One batch between the worker and the completer: its tickets, its
    ``serve.batch`` span (opened by the worker, closed by the completer),
    the start its service time counts from, and what the handler's first
    half returned, or the exception it raised."""

    __slots__ = ("tickets", "span", "t0", "pending", "error")

    def __init__(self, tickets: List[_Ticket], span, t0: float):
        self.tickets = tickets
        self.span = span
        self.t0 = t0
        self.pending: Any = None
        self.error: Optional[Exception] = None


class BatchingScheduler:
    """Deadline-based micro-batcher over a bounded queue (module doc)."""

    def __init__(
        self,
        handler: Callable[[List[Any]], Any],
        max_batch: int = 64,
        max_delay_ms: float = 2.0,
        max_queue: int = 256,
        name: str = "glint-serve-batcher",
        straggle_every: int = 0,
        straggle_ms: float = 0.0,
        span_emit: Optional[Callable[[dict, str, int, int], None]] = None,
        batch_observer: Optional[Callable[[int, float, float], None]] = None,
        finish: Optional[Callable[[Any], Sequence[Any]]] = None,
    ):
        """``handler(payloads)`` returns one result per request; or, where
        ``finish`` is given, whatever ``finish(pending)`` needs to return
        them: the handler's two halves (module doc). ``finish`` is called
        once for every batch whose first half returned, on the completer
        thread, in the order the batches were closed.

        ``straggle_every``/``straggle_ms`` are FAULT INJECTION (the
        serve-side analog of train/faults.py, off by default): every Nth
        closed batch sleeps ``straggle_ms`` before the handler runs — a
        deterministic tail-latency straggler. The fleet hedge A/B
        (tools/servebench.py --fleet) uses it to measure what hedging buys
        against a replica that stalls 1-in-N dispatches; production never
        sets it.

        ``span_emit(trace, name, start_mono_ns, dur_ns)``: the trace hook
        (obs/trace.py) the completer calls per TRACED ticket after each batch —
        a ``queue_wait`` span (submit → batch pop: the admission latency the
        micro-batching deadline trades) and a ``batch_service`` span (the
        handler's wall time), both parented to the context the request
        carried across the wire, both from the ``serve.batch`` span's own
        clock reads (obs/spans.py). Untraced tickets (trace=None — every
        ticket when tracing is off) never reach the hook: the zero-cost
        contract is "no trace, no call", not a no-op callee.

        ``batch_observer(batch_size, service_s, queue_wait_s)``: called once
        per dispatched batch (success or error) — the serving flight
        recorder's dispatch-ring feed (obs/blackbox.py note_dispatch via
        EmbeddingService). Both hooks run ON the completer thread, after
        the batch's callers were released; they must not block (the sink's
        locked append is the intended cost)."""
        if max_batch <= 0:
            raise ValueError(f"max_batch must be positive but got {max_batch}")
        if max_delay_ms < 0:
            raise ValueError(
                f"max_delay_ms must be nonnegative but got {max_delay_ms}")
        if max_queue <= 0:
            raise ValueError(f"max_queue must be positive but got {max_queue}")
        self._handler = handler
        self._finish = finish
        self.max_batch = int(max_batch)
        self.max_delay_s = float(max_delay_ms) / 1000.0
        self.max_queue = int(max_queue)
        self._straggle_every = int(straggle_every)
        self._straggle_s = float(straggle_ms) / 1000.0
        self._span_emit = span_emit
        self._batch_observer = batch_observer
        self._tracer = default_tracer()
        self._name = name
        self._q: collections.deque = collections.deque()
        self._cv = make_condition("serve.batcher.cv")
        self._stopping = False
        self._thread: Optional[threading.Thread] = None
        self._completer: Optional[threading.Thread] = None
        # worker → completer, FIFO (under _cv); a None ends the completer
        self._begun: collections.deque = collections.deque()
        # slots of MAX_INFLIGHT taken: from before a batch is closed until
        # its last caller is released (under _cv)
        self._inflight = 0
        # counters (all mutated under _cv)
        self._submitted = 0
        self._refused = 0
        self._completed = 0
        self._errors = 0
        self._batches = 0
        self._batched_items = 0
        self._closed_batches = 0   # handed to the completer (≥ _batches)
        self._overlapped = 0       # begun while an earlier one was unfinished
        # recent end-to-end latencies (seconds); deque append is atomic, so
        # submitters record lock-free and stats() snapshots a copy
        self._latencies: collections.deque = collections.deque(maxlen=4096)
        # EWMA of a batch's time from close to its results (seconds),
        # updated by the completer after every batch — feeds the
        # ServerOverloaded retry_after_s hint. None until the first batch
        # completes.
        self._batch_s_ewma: Optional[float] = None

    # -- lifecycle ---------------------------------------------------------------------

    def start(self) -> "BatchingScheduler":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name=self._name, daemon=True)
        self._completer = threading.Thread(
            target=self._complete, name=f"{self._name}-completer",
            daemon=True)
        self._thread.start()
        self._completer.start()
        return self

    def stop(self) -> int:
        """Drain-and-stop: requests already admitted are still served (the
        worker keeps batching until the queue is empty, the completer
        finishes the batches in flight), new submits are refused. Returns
        the number of leaked threads (those that miss the join bound) so
        close() paths can surface it in stats."""
        with self._cv:
            if self._stopping:
                return 0
            self._stopping = True
            self._cv.notify_all()
        leaked = 0
        threads = (self._thread, self._completer)
        self._thread = self._completer = None
        for t in threads:
            if t is not None:
                t.join(timeout=30)
                if t.is_alive():
                    leaked += 1
                    logger.warning("batcher thread %s leaked (join timeout)",
                                   t.name)
        return leaked

    # -- client side -------------------------------------------------------------------

    def submit_async(self, payload: Any,
                     trace: Optional[dict] = None) -> _Ticket:
        """Enqueue one request; returns the ticket to :meth:`wait` on.
        Raises :class:`ServiceClosed` once ``stop()`` has been called (during
        the drain AND after it) and :class:`ServerOverloaded` (with the
        ``retry_after_s`` drain-time hint) when the bounded queue is full.
        ``trace`` is the optional cross-process trace context the worker
        turns into queue_wait/batch_service spans (constructor docstring)."""
        with self._cv:
            if self._stopping:
                raise ServiceClosed(
                    "scheduler is stopped — admitted requests drain, new "
                    "submits are refused")
            if len(self._q) >= self.max_queue:
                self._refused += 1
                raise ServerOverloaded(
                    f"admission queue full ({self.max_queue} waiting)",
                    retry_after_s=self._retry_after_locked())
            self._submitted += 1
            t = _Ticket(payload, trace, self._submitted)
            self._q.append(t)
            self._cv.notify_all()
        return t

    def wait(self, ticket: _Ticket, timeout: float = 60.0) -> Any:
        """Block until the ticket's batch completed; re-raise its per-request
        error in the caller's thread."""
        if not ticket.done.wait(timeout):
            raise TimeoutError(f"request not served within {timeout:g}s")
        # under _cv like every other ring access: a lock-free append races
        # stats()'s iteration — deque.append is atomic, but iterating a
        # deque another thread appends to raises RuntimeError (the PR 12
        # class; graftlint R11 holds every access to the same lock)
        with self._cv:
            self._latencies.append(time.monotonic() - ticket.enqueued)
        if ticket.error is not None:
            raise ticket.error
        return ticket.result

    def submit(self, payload: Any, timeout: float = 60.0) -> Any:
        """Blocking submit: enqueue + wait (the one-call client surface)."""
        return self.wait(self.submit_async(payload), timeout)

    def _retry_after_locked(self) -> Optional[float]:
        """The drain-time estimate behind ``retry_after_s`` (called under
        ``_cv``): full batches queued ahead × the EWMA batch service time.
        None before the first completed batch — an honest "no data yet"
        beats a made-up constant."""
        if self._batch_s_ewma is None:
            return None
        batches_ahead = -(-len(self._q) // self.max_batch)  # ceil
        return round(max(1, batches_ahead) * self._batch_s_ewma, 4)

    # -- worker side -------------------------------------------------------------------

    def _collect(self) -> Optional[List[_Ticket]]:
        """Pop one batch: block for the first request (span ``serve.idle``),
        then coalesce until ``max_batch`` or ``max_delay_ms`` past the first
        arrival (span ``serve.coalesce``). None = stopped and drained."""
        with self._cv:
            if not self._q and not self._stopping:
                with self._tracer.span("serve.idle"):
                    while not self._q and not self._stopping:
                        self._cv.wait()
            if not self._q:
                return None  # stopping, queue drained
            with self._tracer.span("serve.coalesce"):
                batch = [self._q.popleft()]
                deadline = batch[0].enqueued + self.max_delay_s
                while len(batch) < self.max_batch:
                    if self._q:
                        batch.append(self._q.popleft())
                        continue
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or self._stopping:
                        break
                    self._cv.wait(remaining)
            return batch

    def _run(self) -> None:
        """The worker: a slot, a batch, the handler's first half, over to
        the completer."""
        while True:
            with self._cv:
                # the slot first (module doc): what arrives while both are
                # taken waits in the queue and leaves as one batch
                while self._inflight >= MAX_INFLIGHT:
                    self._cv.wait()
                self._inflight += 1
            batch = self._collect()
            if batch is None:
                with self._cv:
                    self._inflight -= 1
                    self._begun.append(None)  # the completer's last item
                    self._cv.notify_all()
                return
            # serve.batch: batch closed → last caller released, from here to
            # the completer. The one pair of clock reads per batch: the
            # service-time estimate, the batch observer and the fleet's
            # trace_span records are fed its times.
            sp = self._tracer.open("serve.batch", timed=True, size=len(batch))
            item = _Begun(batch, sp, sp.t0)
            if self._straggle_every:
                with self._cv:
                    nth = self._closed_batches + 1
                if nth % self._straggle_every == 0:
                    time.sleep(self._straggle_s)  # injected straggler
                    item.t0 = time.monotonic()
            try:
                item.pending = self._handler([t.payload for t in batch])
            except Exception as e:  # noqa: BLE001 — delivered to each caller
                item.error = e
            sp.detach()
            with self._cv:
                # earlier batches whose results are not in yet: the device
                # has this batch's work queued behind theirs
                earlier = self._closed_batches - self._batches
                self._closed_batches += 1
                self._overlapped += earlier > 0
                sp.set(inflight=earlier,
                       inflight_share=(earlier + 1) / MAX_INFLIGHT)
                self._begun.append(item)
                self._cv.notify_all()

    def _complete(self) -> None:
        """The completer: each begun batch in turn, the handler's second
        half, its callers released, its slot freed, then the hooks."""
        while True:
            with self._cv:
                while not self._begun:
                    self._cv.wait()
                item = self._begun.popleft()
            if item is None:
                return
            self._serve(item)
            item.span.close()
            with self._cv:
                self._inflight -= 1
                self._cv.notify_all()
            self._after_batch(item.tickets, item.span)

    def _serve(self, item: _Begun) -> None:
        """The results of one begun batch into its tickets, and its callers
        released."""
        batch = item.tickets
        n_err = 0
        try:
            if item.error is not None:
                raise item.error
            results = (item.pending if self._finish is None
                       else self._finish(item.pending))
            if len(results) != len(batch):
                raise RuntimeError(
                    f"handler returned {len(results)} results for a "
                    f"batch of {len(batch)}")
        except Exception as e:  # noqa: BLE001 — delivered to each caller
            n_err = len(batch)
            for t in batch:
                t.error = e
        else:
            for t, r in zip(batch, results):
                if isinstance(r, BaseException):
                    t.error = r
                    n_err += 1
                else:
                    t.result = r
        with self._cv:
            self._note_batch_seconds(time.monotonic() - item.t0)
            self._batches += 1
            self._batched_items += len(batch)
            self._errors += n_err
            self._completed += len(batch) - n_err
        for t in batch:
            t.done.set()

    def _after_batch(self, batch: List[_Ticket], sp) -> None:
        """Post-batch observability (completer thread, AFTER the callers were
        released — a slow sink must not sit inside any caller's latency),
        all from the ``serve.batch`` span ``sp``'s own times: a
        ``serve.queue_wait`` span per ticket (enqueued → its batch closed,
        child of ``sp``) where the recorder kept ``sp``, the per-batch
        dispatch observer, then queue_wait/batch_service ``trace_span``
        records for each TRACED ticket. Best-effort like every obs surface —
        a hook failure must never kill the completer."""
        if not (sp.recorded or self._batch_observer is not None
                or self._span_emit is not None):
            return
        try:
            if sp.recorded:
                for t in batch:
                    self._tracer.record(
                        "serve.queue_wait", t.enqueued,
                        max(0.0, sp.t0 - t.enqueued), parent=sp.id,
                        request=t.seq,
                        **({"tid": t.trace["tid"]} if t.trace else {}))
            if self._batch_observer is not None:
                self._batch_observer(
                    len(batch), sp.dur, max(0.0, sp.t0 - batch[0].enqueued))
            if self._span_emit is not None:
                pop_ns = int(sp.t0 * 1e9)
                dur_ns = int(sp.dur * 1e9)
                for t in batch:
                    if t.trace is None:
                        continue
                    enq_ns = int(t.enqueued * 1e9)
                    self._span_emit(t.trace, "queue_wait", enq_ns,
                                    max(0, pop_ns - enq_ns))
                    self._span_emit(t.trace, "batch_service", pop_ns, dur_ns)
        except Exception:  # noqa: BLE001 — observability is best-effort
            logger.warning("batcher trace/observer hook failed",
                           exc_info=True)

    def _note_batch_seconds(self, dt: float) -> None:
        """Fold one batch's time from close to results into the EWMA (under
        _cv).
        alpha=0.2: ~10 batches of memory — reactive enough that a reload's
        cold first dispatch doesn't poison the hint for long."""
        self._batch_s_ewma = (dt if self._batch_s_ewma is None
                              else 0.8 * self._batch_s_ewma + 0.2 * dt)

    # -- observability -----------------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Gauge snapshot: counters, queue depth, mean batch occupancy, and
        p50/p95/p99 end-to-end latency over the recent-latency ring."""
        with self._cv:
            snap = {
                "submitted": self._submitted,
                "refused": self._refused,
                "completed": self._completed,
                "errors": self._errors,
                "batches": self._batches,
                "overlapped_batches": self._overlapped,
                "queue_depth": len(self._q),
                "max_batch": self.max_batch,
                "max_queue": self.max_queue,
                "occupancy_mean": (round(self._batched_items / self._batches, 3)
                                   if self._batches else None),
                "batch_service_s": (round(self._batch_s_ewma, 5)
                                    if self._batch_s_ewma is not None
                                    else None),
            }
            lats = list(self._latencies)  # snapshot under _cv; sort outside
        lats.sort()
        if lats:
            def pct(p: float) -> float:
                return round(
                    lats[min(len(lats) - 1, int(p * len(lats)))] * 1000, 3)
            snap["latency_ms"] = {"p50": pct(0.50), "p95": pct(0.95),
                                  "p99": pct(0.99), "n": len(lats)}
        return snap
