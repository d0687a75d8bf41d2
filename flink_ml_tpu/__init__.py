"""flink_ml_tpu — a TPU-native ML framework with the capabilities of Apache Flink ML.

Built from scratch on JAX/XLA/pjit/Pallas. The architecture translation (see SURVEY.md):
Flink job graph -> single-controller Python driving jit-compiled SPMD programs over a
``jax.sharding.Mesh``; the iteration feedback edge -> the host training loop; stream-shuffle
AllReduce -> ``jax.lax.psum`` over ICI; the JVM BLAS -> XLA-compiled kernels.

Layer map (mirrors the reference's Maven layering, reference SURVEY.md section 1):
  - ``linalg``      : runtime-free dense/sparse linear algebra (ref flink-ml-servable-core/linalg)
  - ``params``      : typed Param/WithParams system (ref flink-ml-servable-core/param)
  - ``api``         : Stage/Estimator/Model/Transformer/AlgoOperator + DataFrame
  - ``builder``     : Pipeline/PipelineModel/Graph composition (ref flink-ml-core/builder)
  - ``iteration``   : the iterative-training runtime (ref flink-ml-iteration)
  - ``parallel``    : mesh, shardings, collectives (ref Flink shuffles/AllReduceImpl)
  - ``ops``         : losses, optimizers, distance measures, quantiles, windows
  - ``models``      : the algorithm library (ref flink-ml-lib)
  - ``servable``    : runtime-free inference (ref flink-ml-servable-core/servable)
  - ``serving``     : online serving runtime (micro-batching, hot swap, fast path)
  - ``loop``        : continuous learning loop — closed train → publish → serve
                      with drift detection and rollback (docs/continuous.md)
  - ``trace``       : graftscope structured tracing + goodput attribution
                      across all tiers (docs/observability.md)
  - ``benchmark``   : JSON-config benchmark harness (ref flink-ml-benchmark)
"""

__version__ = "0.2.0"

from flink_ml_tpu.api.core import AlgoOperator, Estimator, Model, Stage, Transformer
from flink_ml_tpu.api.dataframe import DataFrame, Row

__all__ = [
    "AlgoOperator",
    "DataFrame",
    "Estimator",
    "Model",
    "Row",
    "Stage",
    "Transformer",
    "__version__",
]
