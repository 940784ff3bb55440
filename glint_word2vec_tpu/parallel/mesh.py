"""Device mesh and sharding layout — the TPU-native replacement for the Glint PS topology.

The reference shards the two embedding matrices across ``numParameterServers`` JVMs
(README.md:69) and moves data to them over Akka/Aeron RPC (G1/G8). Here the "servers" are
the devices of one ``jax.sharding.Mesh`` and the "transport" is XLA collectives over ICI:

- mesh axis ``"model"`` — embedding rows sharded ``P("model", None)`` (the BASELINE north
  star's row-sharding; each device owns ``V / num_model_shards`` rows in HBM, the analog of
  "each PS holds 1/n of the matrix").
- mesh axis ``"data"``  — the batch sharded ``P("data")``: synchronous data parallelism
  replacing the reference's async Hogwild partitions (mllib:392, accuracy caveat mllib:120).

Under ``jit``, GSPMD inserts the collectives the reference did by hand over RPC: the
minibatch row gather becomes an all-gather/all-to-all over ICI, gradient scatter-adds are
reduce-scattered back — no payload caps, no message chunking (G6 is deleted, not ported).

Multi-host: the same mesh spans processes (``jax.distributed.initialize``); per-host batch
slices are assembled into one global array with ``make_array_from_process_local_data`` so
the input pipe rides DCN while the training collectives ride ICI.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclass(frozen=True)
class MeshPlan:
    """A mesh plus the canonical shardings for this workload."""

    mesh: Mesh

    @property
    def num_data(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def num_model(self) -> int:
        return self.mesh.shape[MODEL_AXIS]

    @property
    def embedding(self) -> NamedSharding:
        """Row-sharded [V, D] embeddings over the model axis, replicated over data."""
        return NamedSharding(self.mesh, P(MODEL_AXIS, None))

    @property
    def embedding_cols(self) -> NamedSharding:
        """Column-sharded [V, D] embeddings — the CIKM'16 scheme the reference's PS
        uses (G2: each server computes partial dot products over its slice of every
        vector; SURVEY §7.4 asks for both layouts). Under GSPMD the per-shard partial
        dots become a psum over the model axis instead of row gathers/scatters
        crossing devices. Same math, different collective profile:

        - rows: minibatch row fetch/update is an all-to-all over the model axis
          (each device owns V/N full rows); collective bytes scale with the number
          of OFF-SHARD rows touched.
        - cols: every device computes f_pos/f_neg partials on its D/N slice of every
          touched row, then one psum of [B(, P)] scalars; row access is device-local.

        Which wins depends on batch size vs vector width and the interconnect —
        measure on real multi-chip hardware via config.embedding_partition."""
        return NamedSharding(self.mesh, P(None, MODEL_AXIS))

    @property
    def batch(self) -> NamedSharding:
        """[B, ...] batches split over the data axis, replicated over model."""
        return NamedSharding(self.mesh, P(DATA_AXIS))

    @property
    def batch_stacked(self) -> NamedSharding:
        """[K, B, ...] chunk-of-batches: leading scan axis replicated, batch axis split
        over data."""
        return NamedSharding(self.mesh, P(None, DATA_AXIS))

    @property
    def pairs_stacked(self) -> NamedSharding:
        """[K, 2, B] packed (centers, contexts) chunk: scan and stream axes replicated,
        batch axis split over data. One contiguous transfer per dispatch — over PCIe
        or a DCN feed the per-transfer overhead dominates small puts, so the whole
        chunk ships as a single array."""
        return NamedSharding(self.mesh, P(None, None, DATA_AXIS))

    @property
    def ctx_stacked(self) -> NamedSharding:
        """[K, B, C] CBOW context chunk: batch axis split over data."""
        return NamedSharding(self.mesh, P(None, DATA_AXIS, None))

    @property
    def tokens_stacked(self) -> NamedSharding:
        """[K, S, T] raw-token chunk for the on-device pair generator
        (ops/pairgen.py): scan axis replicated, segment axis split over data (each
        data shard expands its own token blocks into pairs locally — no cross-shard
        traffic in the generator), token axis local."""
        return NamedSharding(self.mesh, P(None, DATA_AXIS, None))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_mesh(
    num_data: int = 1,
    num_model: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> MeshPlan:
    """Build a (data, model) mesh over the given (default: all) devices.

    ``num_model=None`` uses all remaining devices. This is the replacement for the Glint
    client's executor introspection (``Client.getNumExecutors/getExecutorCores``,
    mllib:356,718): topology comes from ``jax.devices()``, not Spark.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if num_model is None:
        if n % num_data:
            raise ValueError(f"{n} devices not divisible by num_data={num_data}")
        num_model = n // num_data
    if num_data * num_model > n:
        raise ValueError(
            f"mesh {num_data}x{num_model} needs {num_data * num_model} devices, have {n}")
    grid = np.array(devices[: num_data * num_model]).reshape(num_data, num_model)
    return MeshPlan(mesh=Mesh(grid, (DATA_AXIS, MODEL_AXIS)))


def embedding_sharding(plan: MeshPlan) -> NamedSharding:
    return plan.embedding


def batch_sharding(plan: MeshPlan) -> NamedSharding:
    return plan.batch


def replicated_sharding(plan: MeshPlan) -> NamedSharding:
    return plan.replicated


def shard_params(params, plan: MeshPlan):
    """Place an EmbeddingPair (or any pytree of [V, ...] arrays) row-sharded on the mesh."""
    return jax.tree.map(
        lambda a: jax.device_put(a, plan.embedding if a.ndim == 2 else plan.replicated),
        params)


def shard_batch(batch, plan: MeshPlan):
    """Place a pytree of [B, ...] host arrays on the mesh, split over the data axis."""
    return jax.tree.map(lambda a: jax.device_put(a, plan.batch), batch)


def pad_dim_to_lanes(vector_size: int, enabled: bool = True) -> int:
    """Physical embedding minor dim: padded up to the TPU lane width (128) when
    enabled. Trainer and every streamed-load path MUST agree on this value — a
    mismatch silently falls back to host-side re-padding of the full matrices."""
    return -(-vector_size // 128) * 128 if enabled else vector_size


def classify_replica_groups(
    num_data: int, num_model: int, groups: Sequence[Sequence[int]],
) -> str:
    """Which mesh axis a collective's replica groups span — the bridge between
    compiled-HLO collectives and the (data, model) mesh for the collective
    audit (tools/collectives.py).

    Devices are laid out row-major ``arange(nd*nm).reshape(nd, nm)``
    (:func:`make_mesh`), so a collective over:

    - ``model``: groups are the mesh ROWS — ``{0..nm-1}, {nm..2nm-1}, ...``
    - ``data``:  groups are the mesh COLUMNS — ``{0, nm, 2nm, ...}, ...``
    - ``all``:   one group covering every device (either axis trivial, or a
      collective over both axes)
    - ``other``: anything else (a partitioner rewrite this audit must surface,
      not silently bucket)

    Groups are compared as SETS: XLA may order ids within a group arbitrarily.
    """
    n = num_data * num_model
    got = sorted((frozenset(int(i) for i in g) for g in groups),
                 key=lambda s: min(s) if s else -1)
    grid = np.arange(n).reshape(num_data, num_model)
    if got == [frozenset(range(n))]:
        return "all"
    rows = sorted(frozenset(int(i) for i in r) for r in grid)
    if got == rows:
        return "model"
    cols = sorted(frozenset(int(i) for i in c) for c in grid.T)
    if got == cols:
        return "data"
    return "other"


def pad_vocab_for_sharding(vocab_size: int, num_model: int, multiple: int = 8) -> int:
    """Smallest padded row count divisible by num_model (and a lane-friendly multiple).

    Padded rows are real but never referenced by any index the pipeline emits, so they
    train to nothing and are dropped on export.
    """
    lcm = np.lcm(num_model, multiple)
    return int(-(-vocab_size // lcm) * lcm)
