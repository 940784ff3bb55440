"""Every compilation of the process as a pinned span ``xla.compile``.

JAX reports the three stages of a program's way to the device through
``jax.monitoring``, each as a duration event fired where the stage ENDS, on
the thread that compiled, with the jitted function's ``fun_name``:

- ``/jax/core/compile/jaxpr_trace_duration``          stage ``trace``
- ``/jax/core/compile/jaxpr_to_mlir_module_duration`` stage ``lower``
- ``/jax/core/compile/backend_compile_duration``      stage ``backend``

One listener, registered once when the package is imported
(:func:`install`), turns each into a retroactive pinned span of the one
recorder (obs/spans.py) that ends at the event: ``fun``, ``stage``, and the
enclosing span of the compiling thread as ``parent`` (the listener runs on
it, inline), so a compile set off inside ``Trainer()`` names the phase that
caused it, and one set off where no span is open (the fit's first dispatch
with nothing recording its rounds, a benchmark calling ``trainer._step_fn``
directly) has no parent and is still the program's.

A jitted function's ``trace`` event fires around those of every jitted
function it calls (each ``jnp`` call is one: a step program's trace fires
some nine hundred), so a ``trace`` is held back until the ``lower`` event
that follows it on its thread, and the last one before it, the program's
own, is the one recorded, with the count of those it stands for as
``traced``. A program then leaves three records, and the pinned store holds
a process's. A reader that wants seconds still takes the union of a
thread's intervals, not their sum: an eager operation met while a function
is traced compiles inside that trace.

``backend`` fires on a persistent-cache hit too, and then holds the
retrieval time. Which it was is the ``cache`` arg (``hit`` / ``miss``): the
``/jax/compilation_cache/cache_hits`` or ``cache_misses`` event that
precedes the duration event on the same thread; absent where the cache was
not asked.
"""

from __future__ import annotations

import threading

from glint_word2vec_tpu.obs.spans import default_tracer, now

_PREFIX = "/jax/core/compile/"
_STAGES = {
    _PREFIX + "jaxpr_trace_duration": "trace",
    _PREFIX + "jaxpr_to_mlir_module_duration": "lower",
    _PREFIX + "backend_compile_duration": "backend",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

# per thread: .cache, the cache's last answer; .trace, the last trace event
# since a ``lower`` as (t0, seconds, fun); .traced, how many there were
_local = threading.local()
_installed = False


def _on_event(name: str, **kw) -> None:
    outcome = _CACHE.get(name)
    if outcome is not None:
        _local.cache = outcome


def _on_duration(name: str, seconds: float, **kw) -> None:
    stage = _STAGES.get(name)
    if stage is None:
        return
    t0, fun = now() - seconds, str(kw.get("fun_name", "?"))
    if stage == "trace":
        _local.trace = (t0, seconds, fun)
        _local.traced = getattr(_local, "traced", 0) + 1
        return
    tracer = default_tracer()
    parent = tracer.current()
    args = {}
    if stage == "lower":
        held = getattr(_local, "trace", None)
        if held is not None:
            tracer.record("xla.compile", held[0], held[1], parent=parent,
                          pinned=True, fun=held[2], stage="trace",
                          traced=_local.traced)
        _local.trace, _local.traced = None, 0
    elif getattr(_local, "cache", None) is not None:
        args["cache"], _local.cache = _local.cache, None
    tracer.record("xla.compile", t0, seconds, parent=parent, pinned=True,
                  fun=fun, stage=stage, **args)


def install() -> None:
    """Register the two listeners, once a process."""
    global _installed
    if _installed:
        return
    import jax.monitoring as monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True
