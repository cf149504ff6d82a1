"""Analytics-Zoo-TPU: a TPU-native analytics + AI framework.

A ground-up re-design of the capabilities of Analytics Zoo
(reference: louie-tsai/analytics-zoo) for TPU hardware: JAX/XLA is the
compute engine (the role BigDL+MKL played on CPU), ``jax.sharding`` over
a device ``Mesh`` is the distribution fabric (the role Spark's
BlockManager allreduce played), and Pallas provides hand-written kernels
where XLA needs help.

Top-level layout (mirrors the reference's layer map, SURVEY.md §1):

- ``common``    : context init, config layering, triggers
                  (ref: zoo/common/NNContext.scala, ZooTrigger.scala)
- ``parallel``  : mesh/topology, sharding strategies, collectives,
                  the distributed training engine
                  (ref: BigDL DistriOptimizer + AllReduceParameter)
- ``feature``   : FeatureSet input pipeline, image/text pipelines
                  (ref: zoo/feature/FeatureSet.scala, ImageSet, TextSet)
- ``pipeline``  : Keras-style model API, autograd, estimator, inference
                  (ref: zoo/pipeline/api/keras, pipeline/estimator, ...)
- ``models``    : built-in model zoo (NCF, Wide&Deep, AnomalyDetector,
                  TextClassifier, Seq2seq, image models, ...)
- ``ops``       : low-level JAX/Pallas ops shared by layers and models
- ``serving``   : cluster-serving service (Redis streams protocol)
- ``utils``     : summaries (TensorBoard-style), file IO, logging
"""

import os

import jax

from analytics_zoo_tpu.version import __version__
from analytics_zoo_tpu.common.zoo_context import (
    init_zoo_context,
    get_zoo_context,
    ZooContext,
)

__all__ = [
    "__version__",
    "init_zoo_context",
    "get_zoo_context",
    "ZooContext",
]


def _place_compile_cache() -> None:
    """ONE persistent XLA compilation cache for every entry point, placed
    from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    and nothing is set here; otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (the directory is part of the cache key, so
    it is derived from this package's location — never a temp dir, a pid
    or the time)."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))


_place_compile_cache()
