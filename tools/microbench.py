"""Slope-based microbenchmark harness for chained jitted steps.

Three rules keep a per-iteration cost honest when dispatch is asynchronous:

1. every iteration is data-dependent on the previous one (donated param chain), so the
   device cannot reorder/elide;
2. the timed region ends with a device→host fetch of a value that depends on the final
   iteration — a barrier as strong as ``block_until_ready`` on that value; and
3. the reported cost is the SLOPE between a short and a long run — constant overheads
   (dispatch, the fetch itself, launch latency) cancel.

Usage: time_chunked(fn, init_carry, args_for_iter, n_lo, n_hi, per_iter_units).
"""

from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp


def _run(fn: Callable, carry, args_for_iter: Callable, n: int, fetch: Callable):
    t0 = time.perf_counter()
    c = carry
    out = None
    for i in range(n):
        c, out = fn(c, *args_for_iter(i))
    # fetch a scalar that depends on the last iteration — this is the real barrier
    _ = float(fetch(c, out))
    return time.perf_counter() - t0


def time_chunked(
    fn: Callable,
    make_carry: Callable[[], object],
    args_for_iter: Callable[[int], tuple],
    n_lo: int = 4,
    n_hi: int = 16,
    fetch: Callable = None,
    warmup: int = 1,
) -> float:
    """Return seconds per iteration of ``fn(carry, *args) -> (carry, out)``,
    overhead-corrected by the two-point slope method."""
    if fetch is None:
        fetch = lambda c, out: jnp.asarray(  # noqa: E731
            jax.tree.leaves(out)[0]).reshape(-1)[0]
    for _ in range(warmup):
        c = make_carry()
        _run(fn, c, args_for_iter, 2, fetch)
    for attempt in range(3):
        t_lo = _run(fn, make_carry(), args_for_iter, n_lo, fetch)
        t_hi = _run(fn, make_carry(), args_for_iter, n_hi, fetch)
        if t_hi > t_lo:
            return (t_hi - t_lo) / (n_hi - n_lo)
    raise RuntimeError(
        f"two-point slope non-positive after 3 attempts "
        f"(t_lo={t_lo:.4f}s @ {n_lo}, t_hi={t_hi:.4f}s @ {n_hi}) — timing too "
        "noisy to report; refusing to publish a fantasy number")
