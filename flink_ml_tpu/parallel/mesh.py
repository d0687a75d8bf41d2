"""Device-mesh management: the framework's "cluster".

Reference mapping (SURVEY.md §2.9): a Flink cluster is JobManager + TaskManager slots and
the parallelism of a job is its slot count; here the "cluster" is a
``jax.sharding.Mesh`` over TPU chips and the parallelism is the mesh's ``data`` axis
size. The single-controller Python process plays the JobManager role (globally aligned
by construction — the whole SharedProgressAligner/epoch-watermark machinery of
``flink-ml-iteration`` collapses, see SURVEY.md §7.3); SPMD programs under ``jit`` play
the TaskManager role.

Axes:
  - ``slice`` — optional outermost axis modelling multi-slice (DCN-connected)
    topologies: devices within a slice talk over ICI, across slices over DCN.
    Size 1 by default (single slice; the axis then never appears in specs).
  - ``data``  — batch (data-parallel) axis; every algorithm shards its input batch here.
    The analogue of ``rebalance()`` partitioning in the reference (SGD.java:90).
  - ``model`` — optional axis for sharding very wide coefficient vectors /
    expert dims (tensor parallelism). Size 1 by default.

Multi-slice placement rules (SURVEY §2.9 comm backend): the batch shards over
``(slice, data)`` jointly (``data_axes``), so the ONLY per-step collective
that crosses DCN is the gradient/stat psum's slice-level reduction stage —
XLA lowers ``psum(x, ("slice", "data"))`` hierarchically: reduce-scatter/
all-reduce over ICI within each slice, then the slice-count-sized exchange
over DCN, then broadcast back over ICI. Model-axis collectives (TP margins,
one-hot crossings) and minibatch compute never leave a slice — the model
axis is always innermost. Programs that ignore the slice axis (specs naming
only ``data``/``model``) still run correctly on a multi-slice mesh: shard_map
replicates their inputs across slices and every slice computes identically —
correct, just redundant; the flagship trainers (SGD, MLP) scale across it.

The mesh is process-global state (like the reference's StreamExecutionEnvironment),
managed via ``set_mesh_context``/``get_mesh_context`` or the ``mesh_context`` context
manager. Multi-host: construct with ``jax.devices()`` spanning hosts and identical code
runs SPMD over ICI/DCN — collectives are inserted by XLA from the sharding annotations.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "SLICE_AXIS",
    "MeshContext",
    "get_mesh_context",
    "set_mesh_context",
    "mesh_context",
]

DATA_AXIS = "data"
MODEL_AXIS = "model"
SLICE_AXIS = "slice"

_lock = threading.Lock()
_current: Optional["MeshContext"] = None


class MeshContext:
    """A device mesh plus the sharding vocabulary every algorithm uses.

    ``n_data`` × ``n_model`` device grid. All helpers return ``NamedSharding``s bound to
    this mesh, so jit'd programs get their collectives from XLA's SPMD partitioner.
    """

    def __init__(
        self,
        devices: Optional[Sequence[Any]] = None,
        n_data: Optional[int] = None,
        n_model: Optional[int] = None,
        n_slices: int = 1,
    ):
        # Unspecified axis sizes come from the runtime config tier (the
        # job-parallelism role of the reference's cluster config).
        from flink_ml_tpu.config import Options, config

        if n_model is None:
            n_model = config.get(Options.MESH_MODEL_AXIS_SIZE)
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if n_data is None:
            n_data = config.get(Options.MESH_DATA_AXIS_SIZE)
        if n_data is None:
            n_data = len(devices) // (n_model * n_slices)
        # ``n_data`` is the PER-SLICE data width; devices must arrive
        # slice-major (jax.devices() orders multi-slice topologies that way),
        # so contiguity along the trailing axes stays intra-slice ICI.
        need = n_slices * n_data * n_model
        if need > len(devices):
            raise ValueError(
                f"mesh {n_slices}x{n_data}x{n_model} needs {need} devices, "
                f"got {len(devices)}"
            )
        grid = np.asarray(devices[:need]).reshape(n_slices, n_data, n_model)
        self.mesh = Mesh(grid, (SLICE_AXIS, DATA_AXIS, MODEL_AXIS))
        self.n_slices = n_slices
        # Total data-parallel shard count: row partitioning, local batches and
        # cache layouts all see slices as extra data shards.
        self.n_data = n_slices * n_data
        self.n_model = n_model

    # --- sharding vocabulary -------------------------------------------------
    @property
    def replicated(self) -> NamedSharding:
        """Model/broadcast sharding — every device holds a full copy.

        The analogue of ``.broadcast()`` + BroadcastUtils variables (SGD.java:89,
        KMeans.java:154): instead of shipping the model over the network each epoch,
        it is laid out replicated and XLA keeps the copies coherent."""
        return NamedSharding(self.mesh, P())

    @property
    def data_axes(self):
        """The mesh axes a batch dim shards over — ``("slice", "data")`` on a
        multi-slice mesh, plain ``"data"`` otherwise. Programs that scale
        across slices use this in their specs and gradient psums; XLA then
        lowers the reduction hierarchically (ICI within a slice, DCN across)."""
        return (SLICE_AXIS, DATA_AXIS) if self.n_slices > 1 else DATA_AXIS

    @property
    def batch(self) -> NamedSharding:
        """Leading-dim sharded over the data axes — for [n, ...] batches."""
        return NamedSharding(self.mesh, P(self.data_axes))

    @property
    def model_dim(self) -> NamedSharding:
        """Leading-dim sharded over ``model`` — for very wide coefficients (TP)."""
        return NamedSharding(self.mesh, P(MODEL_AXIS))

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    # --- data placement ------------------------------------------------------
    def pad_batch(self, n: int) -> int:
        """Rows of padding needed to make ``n`` divisible by the data-axis size."""
        r = n % self.n_data
        return 0 if r == 0 else self.n_data - r

    def shard_batch(self, array, pad_value=0.0) -> Tuple[jax.Array, int]:
        """Place a host [n, ...] array onto the mesh sharded over ``data``.

        Pads the batch to a multiple of the data-axis size (XLA requires even
        shards); returns (device_array, n_valid). Callers carry ``n_valid`` (or a
        weight column zeroed on padding) so padded rows never affect results — the
        moral equivalent of the reference's per-partition record counts.
        """
        array = np.asarray(array)
        pad = self.pad_batch(array.shape[0])
        if pad:
            array = np.concatenate(
                [array, np.full((pad,) + array.shape[1:], pad_value, array.dtype)]
            )
        return jax.device_put(array, self.batch), array.shape[0] - pad

    def replicate(self, array) -> jax.Array:
        return jax.device_put(array, self.replicated)

    def __repr__(self) -> str:
        extra = f", slices={self.n_slices}" if self.n_slices > 1 else ""
        return f"MeshContext(data={self.n_data}, model={self.n_model}{extra})"


def is_tpu_backend(devices) -> bool:
    """Whether every device is a TPU (the Mosaic/Pallas compile target)."""
    devices = list(devices)
    return bool(devices) and all(
        "TPU" in getattr(d, "device_kind", "") for d in devices
    )


def vma_of(x):
    """Varying-mesh-axes of a value (shard_map tracks these; Pallas
    out_shapes must declare them explicitly), or None when it varies over
    none — outside shard_map, or replicated inside it."""
    return jax.typeof(x).vma or None


def get_mesh_context() -> MeshContext:
    """The process-global mesh; lazily created over all visible devices."""
    global _current
    with _lock:
        if _current is None:
            _current = MeshContext()
        return _current


def set_mesh_context(ctx: Optional[MeshContext]) -> None:
    global _current
    with _lock:
        _current = ctx


@contextlib.contextmanager
def mesh_context(ctx: MeshContext):
    """Temporarily install ``ctx`` as the global mesh (tests, multi-mesh programs)."""
    global _current
    with _lock:
        prev, _current = _current, ctx
    try:
        yield ctx
    finally:
        with _lock:
            _current = prev
