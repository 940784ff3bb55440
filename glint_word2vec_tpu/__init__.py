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

# the pinned span ``import`` (obs/spans.py): this file's first line to its
# last, and whether jax was imported before it (then jax's own import lies
# outside the span)
import sys as _sys
import time as _time

_t0, _jax_preloaded = _time.monotonic(), "jax" in _sys.modules

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


def _record_import() -> None:
    from glint_word2vec_tpu.obs import compile_spans
    from glint_word2vec_tpu.obs.spans import default_tracer, now
    # every compilation from here on is a pinned span too
    compile_spans.install()
    default_tracer().record(
        "import", _t0, now() - _t0, pinned=True, jax_preloaded=_jax_preloaded,
        modules=sum(m.startswith("glint_word2vec_tpu.") for m in list(_sys.modules)))


_record_import()
