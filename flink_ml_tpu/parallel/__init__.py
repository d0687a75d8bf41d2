"""Distributed substrate: device mesh, shardings, collectives.

This package replaces the reference's entire communication stack (SURVEY.md §2.9/§5.8):
Flink's Netty network shuffles + ``AllReduceImpl``'s 3-stage chunked dataflow become XLA
collectives over the ICI mesh, and the broadcast-variable machinery becomes replicated
shardings. There is no hand-written transport: the XLA runtime is the native backend.
"""
from flink_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshContext,
    get_mesh_context,
    set_mesh_context,
    mesh_context,
)
from flink_ml_tpu.parallel.collectives import (
    BLOCK_ROWS,
    all_reduce_sum,
    all_reduce_mean,
    block_partials,
    mapreduce_sum,
    psum_tree,
    shard_batch_spec,
    tree_fold_sum,
)
from flink_ml_tpu.parallel.train_sharding import (
    ShardedTrainCache,
    TrainSharding,
    ensure_distributed,
    resolve_train_sharding,
)
from flink_ml_tpu.parallel.quantile import QuantileSummary
from flink_ml_tpu.parallel.ring import ring_attention, ring_attention_sharded
from flink_ml_tpu.parallel.moe import moe_dropless, route_top_k
from flink_ml_tpu.parallel.datastream_utils import (
    aggregate,
    co_group,
    co_group_cache,
    distributed_quantiles,
    distributed_sort,
    distributed_sort_cache,
    map_partition,
    reduce,
    sample,
    sample_cache,
)

__all__ = [
    "moe_dropless",
    "route_top_k",
    "ring_attention",
    "ring_attention_sharded",
    "DATA_AXIS",
    "MODEL_AXIS",
    "MeshContext",
    "get_mesh_context",
    "set_mesh_context",
    "mesh_context",
    "all_reduce_sum",
    "all_reduce_mean",
    "psum_tree",
    "shard_batch_spec",
    "BLOCK_ROWS",
    "block_partials",
    "mapreduce_sum",
    "tree_fold_sum",
    "TrainSharding",
    "ShardedTrainCache",
    "resolve_train_sharding",
    "ensure_distributed",
    "QuantileSummary",
    "aggregate",
    "co_group",
    "co_group_cache",
    "distributed_quantiles",
    "distributed_sort",
    "distributed_sort_cache",
    "map_partition",
    "reduce",
    "sample",
    "sample_cache",
]
