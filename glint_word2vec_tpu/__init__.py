"""glint_word2vec_tpu — a TPU-native framework for very-large-vocabulary word2vec.

A ground-up JAX/XLA/pjit redesign of the capabilities of glint-word2vec
(Spark + Glint parameter servers, see /root/reference): skip-gram negative
sampling (SGNS) and CBOW trained fully in-core on a TPU mesh.

Architecture (vs. the reference, cited as file:line into the reference repo):

- The async parameter-server ``dotprod``/``adjust`` round-trips
  (mllib/feature/ServerSideGlintWord2Vec.scala:417-429) collapse into a single
  synchronous ``jax.jit`` SGNS step (:mod:`glint_word2vec_tpu.ops.sgns`).
- The PS-sharded input/output embedding matrices (``BigWord2VecMatrix``,
  README.md:69) become GSPMD-sharded ``jax.Array`` pairs over an ICI mesh
  (:mod:`glint_word2vec_tpu.parallel`).
- The server-resident unigram negative-sampling table (unigramTableSize,
  mllib:81,234-244) becomes an O(vocab) on-device alias table sampled with
  ``jax.random`` (:mod:`glint_word2vec_tpu.ops.sampler`).
- The Spark RDD subsample/window pipeline (mllib:371-390) becomes a vectorized
  NumPy host pipeline emitting fixed-shape padded batches
  (:mod:`glint_word2vec_tpu.data.pipeline`).
- Model ops — transform, sentence averaging, findSynonyms/analogy, norms,
  matvec (mllib:460-669, ml:322-497) — are jitted gathers/reductions on the
  sharded arrays (:mod:`glint_word2vec_tpu.models`).
- Persistence keeps the reference's on-disk contract: matrix shards + a
  ``words`` one-word-per-line sidecar + params metadata (mllib:493-498,714-715).

Module map: ``data/`` (vocab + host pipeline), ``ops/`` (SGNS/CBOW steps,
sampler), ``parallel/`` (mesh + sharding), ``models/`` (model & estimator API),
``train/`` (trainer, checkpoint).
"""

from glint_word2vec_tpu.config import Word2VecConfig
from glint_word2vec_tpu.data.vocab import Vocabulary, build_vocab
from glint_word2vec_tpu.models import (
    ServerSideGlintWord2Vec,
    ServerSideGlintWord2VecModel,
    Word2Vec,
    Word2VecModel,
)

__version__ = "0.1.0"

__all__ = [
    "Word2VecConfig",
    "Vocabulary",
    "build_vocab",
    "Word2Vec",
    "Word2VecModel",
    "ServerSideGlintWord2Vec",
    "ServerSideGlintWord2VecModel",
    "__version__",
]
