"""Multi-host (multi-process) scaffolding — the G1/G8 replacement at pod scale.

The reference bootstraps a parameter-server cluster across Spark executors
(``Client.runOnSpark``, mllib:354-360,718) and moves everything over Akka RPC. Here a
multi-host run is N identical JAX processes (one per TPU host) joined into ONE global
device mesh: ``jax.distributed.initialize`` wires the coordination service, training
collectives ride ICI/DCN inside the jitted step (GSPMD), and only the per-host input
feed crosses the host boundary.

Input-feed strategy: by default (``config.shard_input=True``) each process generates
only its own 1/N of the sentence stream — ``epoch_batches(shard=process_index,
num_shards=process_count)``, the repartition analog (mllib:345) — and one
``process_allgather`` per dispatch round assembles the identical global batch on every
process (``train/feeds.GatheredPairs``: the gather rides the device interconnect; word-clock
deltas travel with it so every process computes identical alphas, and per-process alive
flags give deadlock-free lockstep when streams end unevenly). Host pipeline work
therefore scales 1/N with hosts. ``shard_input=False`` selects the zero-coordination
fallback: every process regenerates the full stream and :func:`put_global` carves out
its devices' rows — redundant host work, no collectives outside the step.

Launch contract (one command per host, mirroring ``jax.distributed`` conventions):

    GLINT_COORDINATOR=host0:12355 GLINT_NUM_PROCESSES=16 GLINT_PROCESS_ID=$i \
        python train.py ...

or pass the same values to :func:`initialize` explicitly. On Cloud TPU VMs with the
standard metadata, plain ``initialize()`` auto-detects everything.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

import jax
import numpy as np

logger = logging.getLogger("glint_word2vec_tpu")

_ENV_COORD = "GLINT_COORDINATOR"
_ENV_NPROC = "GLINT_NUM_PROCESSES"
_ENV_PID = "GLINT_PROCESS_ID"


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids=None,
) -> None:
    """Join this process to the global mesh. Call before any other JAX use.

    Resolution order: explicit args → ``GLINT_*`` env vars → JAX auto-detection
    (Cloud TPU metadata). A plain single-process run (no args, no env) is a no-op, so
    library code can call this unconditionally.
    """
    coordinator_address = coordinator_address or os.environ.get(_ENV_COORD)
    if num_processes is None and _ENV_NPROC in os.environ:
        num_processes = int(os.environ[_ENV_NPROC])
    if process_id is None and _ENV_PID in os.environ:
        process_id = int(os.environ[_ENV_PID])
    if coordinator_address is None and num_processes is None:
        logger.debug("distributed.initialize: single-process run, nothing to do")
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids)
    logger.info("distributed: process %d/%d, %d local + %d global devices",
                jax.process_index(), jax.process_count(),
                jax.local_device_count(), jax.device_count())


def is_multiprocess() -> bool:
    return jax.process_count() > 1


_GATHER_JIT = None


def _gather_plumbing():
    """(mesh sharding for per-process slices, replicated-output identity jit) of
    the cross-process gather — built once; shapes recompile per feed geometry,
    which is constant over a run."""
    global _GATHER_JIT
    if _GATHER_JIT is None:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        devices = np.array(jax.devices()).reshape(
            jax.process_count(), jax.local_device_count())
        mesh = Mesh(devices, ("processes", "local_devices"))
        _GATHER_JIT = (
            NamedSharding(mesh, P("processes")),
            jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P())),
        )
    return _GATHER_JIT


def allgather_start(host_tree):
    """Split-phase ``multihost_utils.process_allgather(tiled=False)``: launch
    the gather program for a pytree of per-process host arrays and return the
    (async) global jax.Arrays; :func:`allgather_fetch` blocks for the stacked
    numpy result (leading [process_count] axis, exactly the process_allgather
    layout).

    Why split: the one-round-ahead feed stager (feeds._one_ahead_iter) must
    LAUNCH the next round's gather at a pinned point in the cross-host
    program-launch order — before the current round's step dispatch — and only
    later block for its bytes, so the gather's wire transfer and the host-side
    decode overlap device compute instead of serializing after it.
    Single-process: no program at all, the "handle" is the stacked numpy array
    (makes the staged code path testable without a pod)."""
    if not is_multiprocess():
        return jax.tree.map(
            lambda x: np.expand_dims(np.asarray(x), 0), host_tree)
    sharding, ident = _gather_plumbing()

    def start(x):
        h = np.expand_dims(np.asarray(x), 0)
        bufs = [jax.device_put(h, d) for d in jax.local_devices()]
        garr = jax.make_array_from_single_device_arrays(
            (jax.process_count(),) + h.shape[1:], sharding, bufs)
        return ident(garr)

    return jax.tree.map(start, host_tree)


def allgather_fetch(handles):
    """Block for and decode the result of :func:`allgather_start`."""
    if not is_multiprocess():
        return handles
    return jax.tree.map(
        lambda a: np.asarray(a.addressable_data(0)), handles)


def local_sgd_delta_merge(start, local, axis: str, num_shards: int):
    """The local-SGD delta-merge collective (config.sync_every, docs/
    sharding.md §Local-SGD): reconcile ``num_shards`` diverged per-shard
    replicas with ONE psum over the named mesh ``axis``::

        merged = start + psum(local − start, axis) · (1 / num_shards)

    i.e. the mean of the per-shard deltas applied to the common window-start
    state. Call INSIDE a shard_map body (per-device view, named-axis psum) at
    the end of a ``sync_every=k`` owner-local window. Properties the callers
    rely on:

    - **Deterministic and replica-consistent.** The all-reduce delivers the
      bitwise-identical sum to every participant, and ``start`` is replicated
      across the axis, so the merged replicas are bit-identical — the data
      axis leaves the window exactly replicated again (the out_spec contract
      of the window program).
    - **Exact mean at power-of-2 shard counts.** ``1/num_shards`` is exact in
      binary for every mesh this repo ships (1/2/4/8 data shards), so the f64
      oracle tests can demand ~1e-11 agreement, not "close".
    - **Stabilizer-aware by construction.** Per-row clamps (max_row_norm)
      hold under the merge: each shard's rows satisfy ‖row‖ ≤ c, and the
      merged row is a convex combination of rows each within the ball, so
      ‖merged row‖ ≤ c — no post-merge re-clamp pass needed.
    - **One collective program at a time.** The psum rides inside the jitted
      window program that produced ``local`` — never a separate dispatch —
      so the XLA:CPU rendezvous-serialization rule the trainer enforces
      (trainer._sync_collectives) is preserved: the merge cannot race another
      program's collectives.

    ``num_shards == 1`` returns ``local`` unchanged (no collective compiled).
    The delta/psum/scale run in the params' own dtype — the same class of
    reduction the GSPMD backward's data-axis all-reduce performs per step,
    paid here once per k steps.
    """
    if num_shards == 1:
        return local
    import jax.numpy as jnp
    scale = 1.0 / float(num_shards)

    def merge(s, loc):
        delta = jax.lax.psum(loc - s, axis)
        return s + delta * jnp.asarray(scale, loc.dtype)

    return jax.tree.map(merge, start, local)


def put_global(sharding, host_arrays: Dict[str, np.ndarray]):
    """Place a dict of full (global-shape) host arrays onto sharding(s) that may span
    processes. ``sharding`` is either one sharding for every array or a dict keyed
    like ``host_arrays`` (arrays of different ranks need different specs).

    Single-process: plain ``device_put``. Multi-process: every process holds the same
    full host array (see module docstring) and ``make_array_from_callback`` carves out
    exactly the shards its local devices own — the ``make_array_from_process_local_data``
    pattern specialized to the replicated-pipeline feed.
    """
    def spec(k):
        return sharding[k] if isinstance(sharding, dict) else sharding

    if not is_multiprocess():
        return {k: jax.device_put(v, spec(k)) for k, v in host_arrays.items()}
    out = {}
    for k, v in host_arrays.items():
        arr = np.asarray(v)
        out[k] = jax.make_array_from_callback(
            arr.shape, spec(k), lambda idx, a=arr: a[idx])
    return out
