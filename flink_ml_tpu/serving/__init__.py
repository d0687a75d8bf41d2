"""Online serving runtime — the third pillar (train → supervise → serve).

Turns the passive servable tier (``flink_ml_tpu/servable/``) into a running,
concurrent, versioned service: dynamic micro-batching onto a fixed set of
padded XLA shapes, versioned hot model swap with warm-before-serve, bounded
admission control with typed overload rejection, and ``ml.serving.*``
observability. See docs/serving.md.

Runtime-free like the servable tier it wraps: importing this package never
pulls the training stack (enforced by graftcheck's ``layer-deps`` rule).
"""
from flink_ml_tpu.serving.batcher import MicroBatcher, bucket_for, pad_to, power_of_two_buckets
from flink_ml_tpu.serving.controller import AdaptiveController, ControllerAction, GoodputLedger
from flink_ml_tpu.serving.plan import CompiledServingPlan, PlanExecution
from flink_ml_tpu.serving.errors import (
    NoModelError,
    ServingClosedError,
    ServingDeadlineError,
    ServingError,
    ServingOverloadedError,
)
from flink_ml_tpu.serving.registry import ModelRegistry, ModelVersionPoller, publish_servable
from flink_ml_tpu.serving.server import InferenceServer, ServingConfig, ServingResponse

__all__ = [
    "InferenceServer",
    "ServingConfig",
    "ServingResponse",
    "MicroBatcher",
    "AdaptiveController",
    "ControllerAction",
    "GoodputLedger",
    "CompiledServingPlan",
    "PlanExecution",
    "ModelRegistry",
    "ModelVersionPoller",
    "publish_servable",
    "power_of_two_buckets",
    "bucket_for",
    "pad_to",
    "ServingError",
    "ServingOverloadedError",
    "ServingDeadlineError",
    "ServingClosedError",
    "NoModelError",
]
