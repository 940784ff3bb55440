"""Host trace spans: the program's one span recorder.

A span is one timed region on one thread (or begun on one and ended on
another: ``detach``): name, start, duration, a process-unique ``id``, the
``parent`` that caused it, and counts riding as args (``size=``,
``ops=``), so that ratios are measured where the work
happens. The fit loop, the feed threads, the checkpoint I/O, the serve
batcher, ``EmbeddingService``'s dispatch and the model's
``find_synonyms_begin`` / ``find_synonyms_finish`` all record through the
one process-wide :func:`default_tracer`.

When it records. ``Tracer.span()`` is active when ``enabled`` is set (run
telemetry: ``config.telemetry_path`` / ``status_port``) **or while a
``jax.profiler`` trace is live**. While a trace is live each span is entered
as a ``jax.profiler.TraceAnnotation`` too, so it lands in the ``.xplane.pb``'s
host plane on the clock of the device's operations, beside its record in the
ring: a profile of the program names the program's own regions, and a reader
in the same process (``benchmark/readers/program_spans.py``) reduces
``events()`` once the trace has stopped. "Live" is
``TraceAnnotation.is_enabled()``: true from ``start_trace`` until
``stop_trace`` *begins* (the session object outlives the export, which
takes seconds; spans taken then would have no counterpart in the trace). A
span that is open when the trace stops is not kept. tests/test_obs.py holds
that rule against a JAX upgrade.

The third case: a **pinned** span (``pinned=True`` on :meth:`Tracer.span`,
:meth:`Tracer.open` and :meth:`Tracer.record`) records ALWAYS, telemetry or
none, trace or none. It is for a region that happens once a process or once
an object and not once a round: the package's import, the vocabulary,
``Trainer()`` and its children, every compilation (obs/compile_spans.py), a
fit up to its first heartbeat, the model's constructor. Those run before any
trace is live and before a fit's ``run_start`` clears the ring, and there
are tens of them a process, so each reads the clock twice and appends once
to a small store of its own (:meth:`Tracer.setup_events`; the last
``max_setup``, oldest dropped) that :meth:`Tracer.clear` does not empty.
While telemetry is on or a trace is live a pinned span goes the normal way
as well, once: the ring, and for a ``with`` block the trace. Both stores
give ``ts_s`` against the one epoch, so their records can be put in order: a
pinned span that began before the ring's first record began before the
trace did. Nothing pinned belongs in a steady-state round, batch or slide.

The two aggregate timers of the trainer (``host_wait_time``,
``dispatch_time``) predate this and feed the sink's ``heartbeat`` /
``run_end`` records; spans are what says where the time of a dispatch, a
batch or a feed thread goes.

Design constraints:

- near-zero cost when inactive: ``span()`` returns a shared no-op context
  manager after one attribute read and one ``is_enabled()`` call (no
  allocation, no clock read): every path can instrument unconditionally
  (a pinned span is never inactive: it is not for a hot path);
- one clock: ``time.monotonic`` (:data:`now`), the clock of the serve
  tier's tickets and of the fleet's ``trace_span`` records, so a region is
  timed once and every consumer is fed the span's own ``t0`` / ``dur``;
- thread-safe and bounded: events land in a ring (oldest dropped past
  ``max_events``) under one lock held only for the append; the parent of a
  span is the enclosing span on the same thread (a ``threading.local``
  stack, never shared), or an explicit ``parent=`` for work caused on
  another thread;
- a region is a ``with`` block; the one that begins in one method and ends
  in another (the fit loop's heartbeat round, ``Trainer._finish_round`` to
  ``Trainer._after_dispatch``) is :meth:`Tracer.open` and ``close()``, the
  same span on the same stack and in the same trace, never a retroactive
  :meth:`Tracer.record`, which reaches the ring only; the one that begins
  on one thread and ends on another (a serve batch: begun by the batcher's
  worker, ended by its completer) is opened, ``detach()``-ed by the thread
  that began it and ``close()``-d by the one that ends it: one span in the
  ring, with the opener's ``t0``; in the trace, its annotation covers the
  opener's part;
- no ad-hoc threads (graftlint R1): this module only OBSERVES threads.

The Chrome-trace export (``chrome://tracing`` / Perfetto) renders nesting
and cross-thread overlap without a custom viewer.
"""

from __future__ import annotations

import functools
import itertools
import json
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from glint_word2vec_tpu.lockcheck import make_rlock

# the recorder's clock; ``Tracer.record`` takes its ``t0`` on it
now = time.monotonic

# process-unique span ids; ``next`` on a count is atomic under the GIL
_IDS = itertools.count(1)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **args):
        return None


_NOOP = _NoopSpan()

# span name → time-attribution phase (obs/phases.py): every recorded span
# whose name maps here also lands in the accumulator attached for the run,
# so the phase histograms need no second clock read at the span sites
_PHASE_OF = {
    "stage_put": "stage",
    "allgather_fetch": "stage",
    "dispatch": "dispatch",
    "heartbeat.drain": "device_block",
    "health_probe": "device_block",
    "device_block": "device_block",
}


class _Span:
    """One region. ``t0`` / ``dur`` (seconds on :data:`now`) are readable after
    exit, ``id`` from entry; ``recorded`` says whether it reached the ring."""

    __slots__ = ("_tracer", "name", "args", "parent", "id", "t0", "dur",
                 "recorded", "_keep", "_ann", "_on_stack")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict],
                 parent: Optional[int], keep: bool, live: bool):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.parent = parent
        self.id = None
        self.recorded = False
        self._keep = keep
        self._ann = TraceAnnotation(name) if live else None
        self._on_stack = False

    def set(self, **args) -> None:
        """Counts known only once the work is done (``ops=``)."""
        self.args = {**self.args, **args} if self.args else args

    def __enter__(self):
        if self._keep:
            stack = self._tracer._stack()
            if self.parent is None and stack:
                self.parent = stack[-1]
            self.id = next(_IDS)
            stack.append(self.id)
            self._on_stack = True
            if self._ann is not None:
                self._ann.__enter__()
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        self.close()
        return None

    def detach(self) -> None:
        """Leave the calling thread without ending the region: the thread
        that entered it steps out (its stack, its annotation) and another
        ends it with :meth:`close`. Children recorded after this name it by
        ``parent=``."""
        if self._on_stack:
            self._on_stack = False
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            self._tracer._stack().pop()

    def close(self, end: Optional[float] = None, keep: bool = True) -> None:
        """Leave the region; by hand for one that :meth:`Tracer.open`
        entered. ``end`` (on :data:`now`) puts its end before the call and
        ``keep=False`` leaves it out of the ring: a region known only
        afterwards to have been shorter, or not to have been one."""
        self.dur = (now() if end is None else end) - self.t0
        if self._keep:
            tracer = self._tracer
            self.detach()
            # a span the trace's stop cut short has no counterpart there
            if keep and (tracer.enabled or TraceAnnotation.is_enabled()):
                tracer._record(self.name, self.t0, self.dur, self.args,
                               self.id, self.parent)
                self.recorded = True


class _PinnedSpan(_Span):
    """A once-a-process or once-an-object region (module docstring): a span
    that reaches the set-up store whether or not it reached the ring."""

    __slots__ = ()

    def close(self, end: Optional[float] = None, keep: bool = True) -> None:
        super().close(end, keep)
        if keep:
            self._tracer._pin(self.name, self.t0, self.dur, self.args,
                              self.id, self.parent)


class Tracer:
    """Collects complete ("X") spans; exports the Chrome trace event format."""

    def __init__(self, enabled: bool = False, max_events: int = 200_000,
                 max_setup: int = 1024):
        from collections import deque
        self.enabled = enabled
        self.max_events = int(max_events)
        self.max_setup = int(max_setup)
        # RLock: the flight recorder's SIGTERM dump (main thread) reads
        # span_summary() — a plain Lock held by the interrupted thread's
        # own _record() would deadlock the handler (obs/blackbox.py)
        self._lock = make_rlock("obs.spans")
        # deque(maxlen): appending past capacity drops the OLDEST in O(1) —
        # the tail of a long run is what a hang/slowdown investigation needs
        self._events: "deque" = deque(maxlen=self.max_events)
        # the pinned spans' store (module docstring): start kept on ``now``
        # itself, so that clear() moving the epoch leaves them in order
        self._setup: "deque" = deque(maxlen=self.max_setup)
        self._epoch = now()
        self._phases = None  # PhaseAccumulator of the running trainer, or None
        self._local = threading.local()  # per-thread stack of open span ids

    def configure(self, enabled: bool) -> None:
        """Telemetry on or off. A live ``jax.profiler`` trace arms the
        recorder whatever this says (module docstring)."""
        self.enabled = enabled

    def attach_phases(self, acc) -> None:
        """Attach (or detach with None) the run's PhaseAccumulator — recorded
        spans whose names map to a phase tee their duration into it."""
        self._phases = acc

    def clear(self) -> None:
        """Empty the ring and move the epoch: a run's trace then describes
        that run. The pinned spans stay (module docstring)."""
        with self._lock:
            self._events.clear()
            self._epoch = now()

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def current(self) -> Optional[int]:
        """The ``id`` of the calling thread's innermost open span, or None:
        what a region that continues on another thread names as ``parent=``
        there."""
        stack = self._stack()
        return stack[-1] if stack else None

    def span(self, name: str, *, parent: Optional[int] = None,
             timed: bool = False, pinned: bool = False, **args):
        """Context manager timing one region on the calling thread. Its
        parent is the enclosing span of this thread, or ``parent`` (a span's
        ``id``) for work another thread caused. ``timed=True`` is for a
        caller that needs the region's ``t0`` / ``dur`` itself (the batcher's
        service-time estimate, the fleet's ``trace_span`` records): the clock
        is then read even when nothing is recorded, once, by the span.
        ``pinned=True`` is for a once-a-process or once-an-object region
        (module docstring): it records whatever else does."""
        live = TraceAnnotation.is_enabled()
        if pinned:
            return _PinnedSpan(self, name, args or None, parent, True, live)
        if self.enabled or live:
            return _Span(self, name, args or None, parent, True, live)
        if timed:
            return _Span(self, name, None, None, False, False)
        return _NOOP

    def open(self, name: str, *, pinned: bool = False,
             **args) -> Optional[_Span]:
        """A span already entered, for a region that begins in one method
        and ends in another, where no ``with`` block can hold it: the owner
        ends it with :meth:`_Span.close`, innermost first. ``None`` when
        nothing records."""
        span = self.span(name, pinned=pinned, **args)
        if span is _NOOP:
            return None
        return span.__enter__()

    def record(self, name: str, t0: float, dur: float, *,
               parent: Optional[int] = None, pinned: bool = False,
               **args) -> int:
        """A retroactive span: start (on :data:`now`) and duration known only
        afterwards, as a ticket's queue wait is. To the ring only; the caller
        asks first whether anything records (its enclosing span's
        ``recorded``). A ``pinned`` one asks nothing: it goes to the set-up
        store, and to the ring where telemetry is on or a trace is live.
        Returns the span's ``id``, for a child recorded after it."""
        span_id = next(_IDS)
        if not pinned or self.enabled or TraceAnnotation.is_enabled():
            self._record(name, t0, dur, args or None, span_id, parent)
        if pinned:
            self._pin(name, t0, dur, args or None, span_id, parent)
        return span_id

    def wrap_iter(self, name: str, it):
        """Wrap an iterator so each ``next()`` is a span ON THE CONSUMING
        THREAD — handed to a producer-thread iterator (``_threaded_iter``),
        this times production where it happens. Always wraps: ``span()``
        re-checks per item whether anything records (feed iterators are
        built before the run bookkeeping arms the tracer, and a profiler
        trace starts mid-run), and the per-chunk no-op cost is nothing next
        to chunk assembly."""

        def gen():
            src = iter(it)
            while True:
                with self.span(name):
                    try:
                        item = next(src)
                    except StopIteration:
                        return
                yield item

        return gen()

    def _record(self, name: str, t0: float, dur: float, args: Optional[dict],
                span_id: int, parent: Optional[int]) -> None:
        if self._phases is not None:
            phase = _PHASE_OF.get(name)
            if phase is not None:
                self._phases.add(phase, dur)
        ev = (name, threading.get_ident(), threading.current_thread().name,
              t0 - self._epoch, dur, args, span_id, parent)
        with self._lock:
            self._events.append(ev)

    def _pin(self, name: str, t0: float, dur: float, args: Optional[dict],
             span_id: int, parent: Optional[int]) -> None:
        ev = (name, threading.get_ident(), threading.current_thread().name,
              t0, dur, args, span_id, parent)
        with self._lock:
            self._setup.append(ev)

    # -- introspection / export -------------------------------------------------

    @staticmethod
    def _dicts(evs, epoch: float = 0.0) -> List[dict]:
        return [{"name": n, "tid": tid, "thread": tname, "ts_s": ts - epoch,
                 "dur_s": dur, "id": sid, "parent": parent,
                 **({"args": a} if a else {})}
                for n, tid, tname, ts, dur, a, sid, parent in evs]

    def events(self) -> List[dict]:
        with self._lock:
            evs = list(self._events)
        return self._dicts(evs)

    def setup_events(self) -> List[dict]:
        """The pinned spans, oldest first, as :meth:`events` gives the
        ring's: ``ts_s`` is against the ring's epoch as it stands now, so it
        is negative for what began before the last :meth:`clear`, and the
        two lists can be put in order."""
        with self._lock:
            evs, epoch = list(self._setup), self._epoch
        return self._dicts(evs, epoch)

    def setup_summary(self) -> Dict[str, dict]:
        """The pinned store's digest (the ``setup`` of a ``run_start`` record
        and of ``status_snapshot()``): ``spans`` by name {count, total_s},
        and ``compiles``: the programs built or loaded (``xla.compile`` at
        stage ``backend``), how many of them the persistent cache held and
        how many it lacked."""
        spans: Dict[str, dict] = {}
        compiles = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
        for ev in self.setup_events():
            s = spans.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
            s["count"] += 1
            s["total_s"] = round(s["total_s"] + ev["dur_s"], 6)
            args = ev.get("args", {})
            if ev["name"] == "xla.compile" and args.get("stage") == "backend":
                compiles["programs"] += 1
                if args.get("cache") in ("hit", "miss"):
                    key = "cache_hits" if args["cache"] == "hit" else "cache_misses"
                    compiles[key] += 1
        return {"spans": spans, "compiles": compiles}

    def span_summary(self) -> Dict[str, dict]:
        """Per-span-name {count, total_s, max_s} — the run_end digest."""
        out: Dict[str, dict] = {}
        for ev in self.events():
            s = out.setdefault(ev["name"],
                               {"count": 0, "total_s": 0.0, "max_s": 0.0})
            s["count"] += 1
            s["total_s"] = round(s["total_s"] + ev["dur_s"], 6)
            s["max_s"] = round(max(s["max_s"], ev["dur_s"]), 6)
        return out

    def export_chrome_trace(self, path: str) -> int:
        """Write the collected spans as a Chrome-trace JSON file; returns the
        event count. The pinned spans come first (one that the ring holds as
        well is written once), then the ring's. Thread ids are remapped to
        small ints in first-seen order, with metadata events naming each
        thread. Where a pinned span began before the ring's epoch, every
        ``ts`` is moved by the same amount, so that the earliest reads 0."""
        with self._lock:
            evs, epoch = list(self._events), self._epoch
            pinned = list(self._setup)
        in_store = {ev[6] for ev in pinned}
        evs = [(n, tid, tname, t0 - epoch, *rest)
               for n, tid, tname, t0, *rest in pinned] + [
            ev for ev in evs if ev[6] not in in_store]
        base = min([0.0] + [ev[3] for ev in evs])
        tid_map: Dict[int, int] = {}
        names: Dict[int, str] = {}
        trace = []
        for n, tid, tname, ts, dur, a, sid, parent in evs:
            small = tid_map.setdefault(tid, len(tid_map))
            names.setdefault(small, tname)
            trace.append({"ph": "X", "name": n, "pid": 0, "tid": small,
                          "ts": round((ts - base) * 1e6, 1),
                          "dur": round(dur * 1e6, 1),
                          "args": {"id": sid, "parent": parent, **(a or {})}})
        meta = [{"ph": "M", "name": "thread_name", "pid": 0, "tid": small,
                 "args": {"name": tname}} for small, tname in names.items()]
        doc = {"traceEvents": meta + trace, "displayTimeUnit": "ms"}
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return len(trace)


_default = Tracer()


def pinned_call(name: str, args_of=None):
    """Decorator: the whole of a method (a constructor) as one pinned span
    of the process-wide tracer, the parent of whatever the method records.
    ``args_of(self)`` gives the span's args once the method has returned."""

    def wrap(method):
        @functools.wraps(method)
        def inner(self, *a, **kw):
            with _default.span(name, pinned=True) as span:
                out = method(self, *a, **kw)
                if args_of is not None:
                    span.set(**args_of(self))
            return out

        return inner

    return wrap


def default_tracer() -> Tracer:
    """The process-wide tracer: records under run telemetry or a live
    ``jax.profiler`` trace, and is a no-op otherwise, but for its pinned
    spans."""
    return _default
