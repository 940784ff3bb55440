"""Pieces every kind of traffic shares: the compile log, the device stamp, the
memory peak, the traced slice and the table of compared numbers."""

import contextlib
import shutil
import tempfile
import time


class CompileLog:
    """Every backend compile of the process, timestamped, via jax.monitoring
    (``chip_smoke.py``'s, copied): a window can then show that it held none."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as monitoring
        self.compiles = []      # (perf_counter at end, fun_name)
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, seconds, **kw):
        if name == self._BACKEND:
            self.compiles.append((time.perf_counter(), kw.get("fun_name", "?")))

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def between(self, t_open: float, t_close: float) -> list:
        return [fn for t, fn in self.compiles if t_open < t <= t_close]


class Laps:
    """Where set-up goes: one printed line of seconds by phase."""

    def __init__(self, t_start: float):
        self.at, self.rows = t_start, []

    def lap(self, name: str):
        now = time.perf_counter()
        self.rows.append(f"{name} {now - self.at:.2f}")
        self.at = now

    def show(self):
        print("setup laps (s): " + ", ".join(self.rows), flush=True)


def memory_peak_bytes() -> int:
    """Peak on the fullest chip; 0 where the backend reports none (CPU)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class Checks:
    """The numbers ``correct`` compares, each printed beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float):
        ok = value <= limit
        self.rows.append((name, value, limit, ok))
        print(f"check {name}: {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


SLICE_SPAN = "bench.slice"


class TracedSlice:
    """The profiler, on for a slice of the window. ``start`` and ``stop`` are
    called by the kind at points where the device has drained; the slice is the
    host span ``bench.slice``, which puts its bounds on the trace's own clock."""

    def __init__(self):
        self.dir = None
        self._span = None
        self.started = self.stopped = False

    def start(self):
        import jax.profiler as jp
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0   # host TraceMes only: the python tracer
        opts.host_tracer_level = 2     # slows the host it measures
        jp.start_trace(self.dir, profiler_options=opts)
        self._span = jp.TraceAnnotation(SLICE_SPAN)
        self._span.__enter__()
        self.started = True

    def stop(self):
        import jax.profiler as jp
        self._span.__exit__(None, None, None)
        jp.stop_trace()
        self.stopped = True

    def reduce(self, platform: str):
        """Reduced slice for the readers, or None where no slice was traced."""
        from harness import trace as tr
        if not self.stopped:
            return None
        try:
            return tr.reduce_slice(tr.load(tr.newest_xplane(self.dir), platform),
                                   SLICE_SPAN)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

    def abandon(self):
        if self.started and not self.stopped:
            with contextlib.suppress(Exception):
                self.stop()
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)
