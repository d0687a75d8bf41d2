"""Distributed minibatch SGD.

Reference: ``flink-ml-lib/.../common/optimizer/`` — ``Optimizer.java`` (interface
``optimize(initModel, trainData, lossFunc)``), ``SGD.java`` (the only implementation):
each subtask caches its partition (ListStateWithCache), per epoch takes the next
``globalBatchSize/parallelism`` rows of its local cache (``nextBatchOffset`` cycling,
SGD.java:246-285), computes the local [gradSum, weightSum, lossSum] feedback array,
allReduces it (SGD.java:126-132), and every worker applies the identical update
``coef -= lr/totalWeight · grad`` followed by regularization (``updateModel``
SGD.java:231, ``RegularizationUtils.regularize:47``). Terminates via
``TerminateOnMaxIterOrTol`` on loss/totalWeight.

TPU-native shape (SURVEY.md §7.4): the dataset lives in HBM sharded over the ``data``
mesh axis (DeviceDataCache); one epoch is ONE jit'd SPMD step — minibatch gather,
two-matmul loss/grad, a single ``lax.psum`` replacing the reference's 3-stage
AllReduce, and the model update computed redundantly (and identically) on every
device. The feedback edge is the (coef, offset) device arrays handed to the next
epoch; nothing leaves HBM during training.

Whole-run fusion: when no checkpointing or listeners are attached, epochs run in
fused chunks — ``lax.scan`` over a host-precomputed minibatch schedule,
budget-capped dispatches for the maxIter-only path (one cheap host sync per
chunk; see ``fused_chunk_len``), and
_TOL_CHUNK-epoch chunks when a tol criteria is active, with the criteria replayed
*on device* via a carried ``done`` flag (the psum'd loss is replicated across
shards, so every device takes the same branch — the single-controller analogue of
SharedProgressAligner deciding termination) and observed on the host between
chunks. One dispatch per chunk instead of one per epoch removes the host dispatch
overhead that dominates small steps. The host loop remains for
checkpoint/listener runs, where the driver must observe state between epochs.

Deviations from the reference, deliberate:
  - regularization *loss* terms use the standard elastic-net form (L1 = reg·Σ|c|);
    the reference's reported L1/L2 reg-loss uses sign(c)/‖c‖₂ (RegularizationUtils
    .java:47 comment vs code) which looks like a reporting bug. The coefficient
    *updates* match the reference exactly.
  - the local batch is ceil(globalBatchSize/p) on every shard (static SPMD shapes)
    instead of floor+remainder-spread; the effective global batch is ≥ the requested
    size by < p rows.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.iteration import (
    DeviceDataCache,
    IterationBodyResult,
    IterationConfig,
    TerminateOnMaxIterOrTol,
    iterate_bounded_until_termination,
)
from flink_ml_tpu.metrics import MLMetrics, metrics
from flink_ml_tpu.ops.lossfunc import LossFunc

# Re-exported for the fused-trainer callers (models, iteration.streaming);
# the schedules themselves live at the compute tier so linalg can plan
# windows without importing this runtime-coupled module.
from flink_ml_tpu.ops.schedule import chunked_schedule, offset_schedule
from flink_ml_tpu.parallel.collectives import mapreduce_sum
from flink_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshContext,
    get_mesh_context,
    is_tpu_backend,
)
from flink_ml_tpu.parallel.train_sharding import (
    TrainSharding,
    resolve_train_sharding,
)
from flink_ml_tpu.trace import (
    CAT_COMPILE,
    CAT_INGEST,
    CAT_PRODUCTIVE,
    CAT_READBACK,
    tracer,
)

__all__ = ["Optimizer", "SGD", "regularize"]


def regularize(coef, reg: float, elastic_net: float, learning_rate: float):
    """Prox-style regularization update; returns (new_coef, reg_loss).

    Ref RegularizationUtils.regularize:47 — three branches (L2-only, L1-only,
    elastic net); the coefficient updates are identical to the reference.
    ``reg``/``elastic_net`` are static Python floats, so the branch is resolved at
    trace time and costs nothing under jit.
    """
    if reg == 0.0:
        return coef, jnp.asarray(0.0, coef.dtype)
    if elastic_net == 0.0:  # pure L2
        loss = reg / 2.0 * jnp.sum(coef * coef)
        return coef * (1.0 - learning_rate * reg), loss
    if elastic_net == 1.0:  # pure L1
        loss = reg * jnp.sum(jnp.abs(coef))
        return coef - learning_rate * reg * jnp.sign(coef), loss
    l1 = elastic_net * reg
    l2 = (1.0 - elastic_net) * reg
    loss = l1 * jnp.sum(jnp.abs(coef)) + l2 / 2.0 * jnp.sum(coef * coef)
    update = learning_rate * (l1 * jnp.sign(coef) + l2 * coef)
    return coef - update, loss


class Optimizer:
    """Ref Optimizer.java — optimize(initModel, trainData, lossFunc)."""

    def optimize(self, init_model, train_data, loss_func: LossFunc) -> np.ndarray:
        raise NotImplementedError


def _sgd_epoch_math(
    coef,
    start,
    offset,
    feats,
    y,
    w,
    mask,
    loss_func,
    local_batch,
    lr,
    reg,
    elastic_net,
    dtype,
    model_sharded: bool = False,
    data_axes=DATA_AXIS,
    deterministic: bool = False,
    n_data: int = 1,
):
    """One epoch of the per-shard SGD update (shared by the host-loop step and the
    fused whole-run program). ``start`` is the clamped slice start and ``offset``
    the logical batch offset (start == min(offset, m - local_batch)); both are
    supplied by the caller so the fused path can feed a *precomputed* schedule.
    ``feats`` is either a dense [m, d] array or a padded-CSR
    ``(indices [m, K], values [m, K])`` pair (linalg/sparse_batch.py).
    Returns (new_coef, mean_loss).

    ``deterministic`` (dense data-parallel only — the train.mesh tier) swaps
    the psum/jnp.sum reduction for ``collectives.mapreduce_sum``'s
    width-invariant block/tree fold over per-row contributions: the update is
    bit-identical at every mesh width for the same global schedule
    (docs/distributed_training.md). Requires ``local_batch`` a multiple of
    8·``n_data`` (TrainSharding.round_batch) and a block-cyclically dealt
    batch (ShardedTrainCache)."""
    if deterministic and (model_sharded or isinstance(feats, tuple)):
        raise ValueError(
            "deterministic reduction covers the dense data-parallel layout "
            "only (train.mesh with train.mesh.model == 1, dense features)"
        )
    # The minibatch is a *contiguous* window, so a dynamic_slice (cheap on TPU)
    # instead of a row gather (slow scatter/gather path). At the cache tail the
    # slice start clamps to m - local_batch; rows before ``offset`` in the clamped
    # window are re-reads and get zero weight, reproducing the reference's short
    # tail batch (SGD.java:265-268) exactly.
    yb = jax.lax.dynamic_slice_in_dim(y, start, local_batch)
    tail_valid = (start + jnp.arange(local_batch) >= offset).astype(dtype)
    wb = (
        jax.lax.dynamic_slice_in_dim(w, start, local_batch)
        * jax.lax.dynamic_slice_in_dim(mask, start, local_batch)
        * tail_valid
    )
    if isinstance(feats, tuple):
        # Sparse: dot = gather + row-sum, grad = scatter-add — both static-shaped.
        # Padding slots (index 0 / value 0) and zero-weight rows contribute 0.
        ib = jax.lax.dynamic_slice_in_dim(feats[0], start, local_batch)
        vb = jax.lax.dynamic_slice_in_dim(feats[1], start, local_batch)
        if model_sharded:
            # Tensor-parallel coefficient: this shard owns the index range
            # [lo, lo + |coef_local|). Each shard gathers/scatters only its
            # range (dividing the serialized scatter cost across the model
            # axis) and the full margin assembles with one psum over it.
            local_d = coef.shape[0]
            lo = jax.lax.axis_index(MODEL_AXIS) * local_d
            local_idx = ib - lo
            in_range = (local_idx >= 0) & (local_idx < local_d)
            safe_idx = jnp.where(in_range, local_idx, 0)
            vb_local = jnp.where(in_range, vb, 0.0)
            # flat 1-D gather: 2-D index tensors at this size send the XLA
            # TPU backend into minutes of compilation
            gathered = coef[safe_idx.reshape(-1)].reshape(safe_idx.shape)
            dot = jax.lax.psum(jnp.sum(vb_local * gathered, axis=1), MODEL_AXIS)
            loss_sum, mult = loss_func.loss_and_mult(dot, yb, wb)
            grad_sum = (
                jnp.zeros_like(coef)
                .at[safe_idx.ravel()]
                .add((vb_local * mult[:, None]).ravel())
            )
        else:
            # flat 1-D gather (2-D index gathers of this size cost minutes
            # of XLA TPU compile time; flat is ~1 s)
            dot = jnp.sum(vb * coef[ib.reshape(-1)].reshape(ib.shape), axis=1)
            loss_sum, mult = loss_func.loss_and_mult(dot, yb, wb)
            grad_sum = (
                jnp.zeros_like(coef).at[ib.ravel()].add((vb * mult[:, None]).ravel())
            )
    else:
        Xb = jax.lax.dynamic_slice_in_dim(feats, start, local_batch)
        if model_sharded:
            # Dense tensor parallelism: this shard holds a column slice of X
            # and the matching coefficient slice. Partial margins assemble
            # with one psum over the model axis; the gradient slice
            # Xbᵀ·mult is local by construction (mult is replicated across
            # the model axis once dot is).
            dot = jax.lax.psum(Xb @ coef, MODEL_AXIS)
            loss_sum, mult = loss_func.loss_and_mult(dot, yb, wb)
            grad_sum = Xb.T @ mult
        elif deterministic:
            # Per-row contributions [mult·x | w | loss] reduced with the
            # width-invariant block/tree fold: same 8-row blocks, same global
            # block order (all_gather unpermute), same pairwise tree at every
            # mesh width — so grad, weight and loss are bit-identical to the
            # mesh=1 fold by construction, unlike X.T@mult + psum whose
            # association varies with the local batch and the ring.
            dot = Xb @ coef
            row_loss, mult = loss_func.row_loss_and_mult(dot, yb, wb)
            contrib = jnp.concatenate(
                [mult[:, None] * Xb, wb[:, None], row_loss[:, None]], axis=1
            )
            packed = mapreduce_sum(
                contrib, data_axes if n_data > 1 else None, n_data
            )
            grad, weight_sum, loss_sum = packed[:-2], packed[-2], packed[-1]
        else:
            loss_sum, grad_sum = loss_func.loss_and_grad_sum(coef, Xb, yb, wb)
    if deterministic:
        pass  # reduced width-invariantly above; no psum on this path
    elif model_sharded:
        # The grad shard varies over the model axis while the scalar stats are
        # replicated across it — keep their psums separate so the replication
        # stays statically visible to shard_map (and the loss/done plumbing).
        grad = jax.lax.psum(grad_sum, data_axes)
        stats = jax.lax.psum(jnp.stack([jnp.sum(wb), loss_sum]), data_axes)
        weight_sum, loss_sum = stats[0], stats[1]
    else:
        packed = jnp.concatenate(
            [grad_sum, jnp.stack([jnp.sum(wb), loss_sum]).astype(grad_sum.dtype)]
        )
        # The whole AllReduceImpl; on a multi-slice mesh data_axes is
        # ("slice", "data") and XLA lowers the reduction hierarchically —
        # ICI within each slice, one slice-count exchange over DCN.
        packed = jax.lax.psum(packed, data_axes)
        grad, weight_sum, loss_sum = packed[:-2], packed[-2], packed[-1]
    safe_w = jnp.maximum(weight_sum, 1e-30)
    new_coef = jnp.where(weight_sum > 0, coef - (lr / safe_w) * grad, coef)
    new_coef, _reg_loss = regularize(new_coef, reg, elastic_net, lr)
    # Criteria uses the un-regularized batch loss mean, like the reference's
    # loss/totalWeight map over the feedback stream (SGD.java:137-143).
    mean_loss = jnp.where(weight_sum > 0, loss_sum / safe_w, jnp.inf)
    return new_coef, mean_loss


_TOL_CHUNK = 64  # epochs per dispatch when a tol criteria is active
# Upper bound on epochs per dispatch without a criteria. The values below
# were calibrated on a retired setup, not re-measured on the current
# toolchain. Two regimes:
#
# - Epochs built from dense matmuls run microseconds each; a multi-thousand-
#   epoch scan is a sub-second dispatch and chunking it only buys host-sync
#   round-trips (chunking dense at 64 cost an 18x steady-state throughput
#   regression there).
# - Epochs containing serialized gather/scatter instructions run ~7-10 ns per
#   element; a 250-epoch scan over the Criteo-shape sparse program (~5M
#   serialized elements/epoch) ran past that setup's per-dispatch watchdog,
#   while dispatches under ~3e8 total elements ran fine.
#
# So the cap is budget-based: callers report the per-epoch serialized-element
# count (and, for matmul-heavy epochs like the MLP's, a FLOP estimate) and the
# chunk length keeps each dispatch under both budgets.
_MAX_CHUNK_DENSE = 4096
_SERIAL_BUDGET = 300_000_000
_FLOP_BUDGET = 5e14  # ~3-5 s of MXU work per dispatch at realistic MFU


def fused_chunk_len(
    max_iter: int,
    check_loss: bool,
    serial_elems_per_epoch: int = 0,
    flops_per_epoch: float = 0.0,
) -> int:
    """Epochs per dispatch for every fused trainer (SGD, MLPClassifier):
    tol runs sync every ``_TOL_CHUNK`` epochs so early convergence wastes at
    most a chunk of cheap epochs; maxIter-only runs are capped so one dispatch
    stays under the serialized-op watchdog budget (see above), with
    ``serial_elems_per_epoch`` the caller's count of gather/scatter elements
    one epoch executes (0 for purely dense epochs) and ``flops_per_epoch``
    its matmul FLOP estimate (bounds wide-MLP dispatches to seconds)."""
    cap = _MAX_CHUNK_DENSE
    if serial_elems_per_epoch > 0:
        cap = min(cap, max(1, _SERIAL_BUDGET // int(serial_elems_per_epoch)))
    if flops_per_epoch > 0:
        cap = min(cap, max(1, int(_FLOP_BUDGET / flops_per_epoch)))
    if check_loss:
        cap = min(cap, _TOL_CHUNK)
    return max(1, min(max_iter, cap))


def _host_ram_bytes() -> int:
    """MemTotal from /proc/meminfo, or 0 when unreadable (non-Linux)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _hbm_bytes_limit(ctx: Optional[MeshContext] = None) -> int:
    """Per-device accelerator memory budget for the mesh's devices: the
    ``bytes_limit`` an accelerator reports through ``memory_stats()``. This
    number picks the sparse route (stacks/premat gates below), so an
    accelerator that does not report it is an error, never a guess. CPU
    devices (virtual test meshes) have no HBM: they get host RAM split
    across the mesh's devices — they all share it, so a per-device stand-in
    times n_devices could promise more memory than the host has — capped at
    the 16 GiB v5e-class HBM size the layouts are designed for."""
    devices = list(ctx.mesh.devices.flat) if ctx is not None else jax.devices()
    if devices[0].platform != "cpu":
        limit = int((devices[0].memory_stats() or {}).get("bytes_limit", 0))
        if limit <= 0:
            raise RuntimeError(
                f"{devices[0]} reports no memory_stats()['bytes_limit']; the "
                "sparse one-hot route is sized from it"
            )
        return limit
    ram = _host_ram_bytes()
    if ram:
        return min(16 << 30, ram // max(1, len(devices)))
    return 16 << 30


@functools.lru_cache(maxsize=8)
def _premat_materialize_jit(sh):
    """One jitted ``premat_row_onehots`` wrapper per output sharding — the
    resident path AND every streamed window load share it, so the one-hot
    materialization traces once per (sharding, shape) instead of
    constructing (and re-tracing) a fresh jit wrapper per call."""
    from flink_ml_tpu.linalg.onehot_sparse import premat_row_onehots

    return jax.jit(premat_row_onehots, static_argnums=1, out_shardings=(sh, sh))


_FUSED_CACHE: Dict[tuple, object] = {}
_FUSED_CACHE_MAX = 32  # FIFO-bounded: hyperparameter sweeps must not leak executables


def _cache_put(cache: Dict[tuple, object], key: tuple, value) -> None:
    if len(cache) >= _FUSED_CACHE_MAX:
        cache.pop(next(iter(cache)))
    cache[key] = value


def _drain_losses(losses, n_exec) -> List[float]:  # graftcheck: readback
    """The chunk-boundary loss fetch every fused loop funnels through — the
    ONE designated host sync per dispatched chunk (never per epoch). The
    losses buffer rides back with the chunk anyway, so this costs a single
    device_get pair at a point where the host must observe ``done``."""
    n = int(jax.device_get(n_exec))
    chunk_losses = np.asarray(jax.device_get(losses), np.float64)
    return [float(x) for x in chunk_losses[:n]]


def _fused_sgd_program(
    ctx: MeshContext,
    loss_func: LossFunc,
    local_batch: int,
    chunk_len: int,
    lr: float,
    reg: float,
    elastic_net: float,
    tol: Optional[float],
    dtype,
    sparse: bool = False,
    model_sharded: bool = False,
    deterministic: bool = False,
):
    """A chunk of ``chunk_len`` SGD epochs as ONE jit'd SPMD program.

    ``lax.scan`` consumes a per-epoch schedule passed as *arguments* —
    (starts, offsets, active) int/bool[chunk_len] — so one compiled executable
    serves every chunk of a run (and every run with the same hyperparameters;
    see ``offset_schedule`` for why the schedule must not be loop-carried).

    The carried ``done`` flag replays ``TerminateOnMaxIterOrTol`` on device:
    after epoch e, done once loss_e < tol (NaN keeps going, like the host
    criteria). Once done — or on ``active=False`` padding epochs — updates
    freeze and the epoch is a no-op, so the caller wastes at most chunk_len - 1
    epochs before observing ``done`` on the host and stopping. The psum'd loss
    is replicated across shards, so every device flips ``done`` on the same
    epoch.

    Returns a callable ``(coef, done, starts, offsets, active, *data)
    -> (coef, done, losses, n_executed)`` where ``data`` is ``(X, y, w, mask)``
    dense or ``(indices, values, y, w, mask)`` sparse, and ``losses`` a
    [chunk_len] buffer (non-executed entries +inf). Programs are FIFO-cached
    per (mesh, loss, shapes, hyperparameters) so repeated fits skip retracing.

    With ``model_sharded`` (sparse only) the coefficient is sharded over the
    mesh's ``model`` axis — tensor parallelism for wide models: each shard
    gathers/scatters only its index range (dividing the serialized-scatter
    cost), margins assemble with a psum over the model axis, and the returned
    coefficient stays model-sharded.

    Dense + ``model_sharded``: the features arrive 2D-sharded
    ``P(data, model)`` (column slices per model shard) and the margin
    assembles with a psum over the model axis.

    ``deterministic`` (dense data-parallel only, single-slice): the epoch
    math reduces through ``collectives.mapreduce_sum`` instead of psum —
    the train.mesh bit-stability tier (``_sgd_epoch_math``).
    """
    if deterministic and (sparse or model_sharded):
        raise ValueError(
            "deterministic fused SGD covers the dense data-parallel layout only"
        )
    key = (
        ctx.mesh,
        loss_func,  # the instance: custom losses may carry parameters (e.g. Huber delta)
        local_batch,
        chunk_len,
        lr,
        reg,
        elastic_net,
        tol,
        jnp.dtype(dtype).name,
        sparse,
        model_sharded,
        deterministic,
    )
    cached = _FUSED_CACHE.get(key)
    if cached is not None:
        return cached

    data_axes = ctx.data_axes
    if deterministic and not isinstance(data_axes, str):
        raise ValueError(
            "the deterministic train.mesh tier is single-slice; a multi-slice "
            "mesh reduces hierarchically through the psum paths"
        )

    def per_shard(coef, done, starts, offsets, active, *data):  # graftcheck: hot-root
        feats = (data[0], data[1]) if sparse else data[0]
        y, w, mask = data[2:5] if sparse else data[1:4]

        def body(carry, schedule):
            c, done = carry
            start, offset, act = schedule
            new_c, mean_loss = _sgd_epoch_math(
                c, start, offset, feats, y, w, mask, loss_func, local_batch, lr,
                reg, elastic_net, dtype, model_sharded=model_sharded,
                data_axes=data_axes, deterministic=deterministic,
                n_data=ctx.n_data,
            )
            executed = ~done & act
            new_c = jnp.where(executed, new_c, c)
            recorded = jnp.where(executed, mean_loss, jnp.inf)
            if tol is not None:
                # stop iff loss < tol (NaN continues, like the host criteria)
                done = done | (executed & (mean_loss < tol))
            return (new_c, done), (recorded, executed)

        (coef, done), (losses, executed) = jax.lax.scan(
            body, (coef, done), (starts, offsets, active)
        )
        return coef, done, losses, jnp.sum(executed.astype(jnp.int32))

    n_data_args = 5 if sparse else 4
    data_specs = (P(data_axes),) * n_data_args
    if model_sharded and not sparse:
        # dense TP: features are column-sliced over the model axis too
        data_specs = (P(data_axes, MODEL_AXIS),) + data_specs[1:]
    coef_spec = P(MODEL_AXIS) if model_sharded else P()
    program = jax.jit(
        jax.shard_map(
            per_shard,
            mesh=ctx.mesh,
            in_specs=(coef_spec, P(), P(), P(), P()) + data_specs,
            out_specs=(coef_spec, P(), P(), P()),
        ),
        donate_argnums=(0, 1),
    )
    _cache_put(_FUSED_CACHE, key, program)
    return program


def _fused_onehot_program(
    ctx: MeshContext,
    loss_func: LossFunc,
    layout,
    chunk_len: int,
    lr: float,
    reg: float,
    elastic_net: float,
    tol: Optional[float],
    use_pallas: bool,
    premat: bool = False,
    hoist: bool = False,
):
    """A chunk of sparse SGD epochs on the one-hot matmul path — the same
    scan/done/losses contract as ``_fused_sgd_program``, but the coefficient
    is carried *permuted* (``OneHotSparseLayout`` class-major blocks) and
    every per-element gather/scatter is replaced by dense one-hot algebra
    (linalg/onehot_sparse.py). Per-epoch xs are ``(win_idx, offsets,
    active)``: the window index selects that minibatch's static layout
    slice, and ``offsets`` drives the reference's tail-batch gating exactly
    like the scatter path.

    With ``layout.n_model > 1`` (tensor parallelism) the coefficient and
    the layout stacks are sharded over the model axis (each shard owns the
    same-shaped slice of every occupancy class — OneHotSparsePlan deals
    blocks round-robin), the row-crossing dot assembles with a psum over
    ``model`` inside ``onehot_batch_step``, and the gradient stays
    block-local.

    On a multi-slice mesh the batch (and with it the stacks) shards over
    ``(slice, data)`` jointly, so stacks and crossings stay intra-slice —
    the model axis is innermost and its crossing psum never leaves a
    slice. The ONLY DCN-crossing collective is the final gradient/stats
    psum over ``ctx.data_axes``, which XLA lowers hierarchically (ICI
    within a slice, then the slice-count exchange over DCN) exactly like
    the scatter path (cf. AllReduceImpl.java:54-102 serving every config).

    ``premat=True`` (resident fast path, HBM-gated by the caller): the
    program takes two extra stack args — this run's materialized bf16 row
    one-hots (``premat_row_onehots``: ``[n_data, n_model, n_windows, n_sub,
    row_hi | 128, n_pad]``, the entries minor like the packed stacks'),
    sharded like the packed stacks over the two leading axes —
    and the crossings run product+matmul-only kernels instead of
    rebuilding the one-hots every minibatch (measured 1.86x on the
    crossings at the headline unit shape; docs/benchmarks.md).

    ``hoist=True`` (a program that visits a window more than once, HBM-gated
    by the caller: ``SGD._hoists_lane_ids``): the lane ids are unpacked like
    the row one-hots are built, outside the scan, once a program over all its
    windows (``unpack_lane_ids``: int32, one array a class), and the body
    picks the step's window from each class's array as it picks ``lvals`` and
    ``rowid``. Otherwise the same function runs in the body on the step's
    window and nothing but the packed stacks is held across the scan.
    """
    from flink_ml_tpu.linalg.onehot_sparse import onehot_batch_step, unpack_lane_ids

    model_sharded = layout.n_model > 1
    key = (
        ctx.mesh, loss_func, "onehot", layout.class_meta, layout.n_flat,
        layout.n_sub, layout.nblk_local, layout.n_model, layout.sub_batch,
        layout.local_batch, tuple(layout.window_starts), chunk_len, lr, reg,
        elastic_net, tol, use_pallas, premat, hoist,
    )
    cached = _FUSED_CACHE.get(key)
    if cached is not None:
        return cached

    lb = layout.local_batch
    sub = layout.sub_batch
    padded_b = layout.n_sub * sub
    win_starts = jnp.asarray(layout.window_starts, jnp.int32)
    nblk_local = layout.nblk_local
    class_meta, row_hi = layout.class_meta, layout.row_hi
    model_axis = MODEL_AXIS if model_sharded else None
    data_axes = ctx.data_axes  # ("slice", "data") on a multi-slice mesh

    def per_shard(coef_perm, done, win_idx, offsets, active, lidx, rowid, lvals, *rest):
        # stacks arrive [1, 1, n_windows, n_sub, n_flat] per (data, model) shard
        lidx, rowid, lvals = lidx[0, 0], rowid[0, 0], lvals[0, 0]
        if premat:
            oh_hi, oh_lo, y, w, mask = rest
            oh_hi, oh_lo = oh_hi[0, 0], oh_lo[0, 0]  # [n_windows, n_sub, ., n_pad]
        else:
            y, w, mask = rest
        if hoist:  # every window's ids, a class [n_windows, n_sub, f_c, wdt]
            with jax.named_scope("lin.unpack"):
                lane_ids = unpack_lane_ids(lidx, class_meta)

        def body(carry, sched):
            cp, done = carry
            wi, offset, act = sched
            # The step names its parts (``lin.*``; docs/observability.md, "The
            # linear step's scopes"); ``onehot_batch_step`` opens the rounds',
            # the crossings' and the loss's.
            with jax.named_scope("lin.unpack"):  # the minibatch's cut of the window
                start = win_starts[wi]
                sel = lambda a: jax.lax.dynamic_index_in_dim(a, wi, 0, keepdims=False)
                yb = jax.lax.dynamic_slice_in_dim(y, start, lb)
                tail_valid = (start + jnp.arange(lb) >= offset).astype(jnp.float32)
                wb = (
                    jax.lax.dynamic_slice_in_dim(w, start, lb)
                    * jax.lax.dynamic_slice_in_dim(mask, start, lb)
                    * tail_valid
                )
                if padded_b > lb:
                    yb = jnp.pad(yb, (0, padded_b - lb))
                    wb = jnp.pad(wb, (0, padded_b - lb))
                rowid_w, lvals_w = sel(rowid), sel(lvals)
                lane_ids_w = (
                    [sel(ids) for ids in lane_ids]
                    if hoist
                    else unpack_lane_ids(sel(lidx), class_meta)
                )
            grad, loss_sum, wsum = onehot_batch_step(
                cp, lane_ids_w, rowid_w, lvals_w, yb, wb,
                loss_func, class_meta, nblk_local, sub, row_hi, use_pallas,
                model_axis=model_axis,
                # full stacks + wi: the window is selected inside the premat
                # kernels (scalar-prefetch BlockSpec), never via a
                # dynamic_index that would copy a multi-GB window per step
                premat=(oh_hi, oh_lo, wi) if premat else None,
            )
            with jax.named_scope("lin.reduce"):
                if model_sharded:
                    # The grad shard varies over the model axis while the scalar
                    # stats are replicated across it (computed from the
                    # model-psum'd dot) — keep their psums separate so the
                    # replication stays statically visible to shard_map.
                    grad = jax.lax.psum(grad, data_axes)
                    stats = jax.lax.psum(jnp.stack([wsum, loss_sum]), data_axes)
                    weight_sum, loss_sum = stats[0], stats[1]
                else:
                    packed = jnp.concatenate(
                        [grad, jnp.stack([wsum, loss_sum]).astype(grad.dtype)]
                    )
                    packed = jax.lax.psum(packed, data_axes)
                    grad, weight_sum, loss_sum = packed[:-2], packed[-2], packed[-1]
            with jax.named_scope("lin.update"):
                safe_w = jnp.maximum(weight_sum, 1e-30)
                new_cp = jnp.where(weight_sum > 0, cp - (lr / safe_w) * grad, cp)
                new_cp, _reg_loss = regularize(new_cp, reg, elastic_net, lr)
                mean_loss = jnp.where(weight_sum > 0, loss_sum / safe_w, jnp.inf)
                executed = ~done & act
                new_cp = jnp.where(executed, new_cp, cp)
                recorded = jnp.where(executed, mean_loss, jnp.inf)
                if tol is not None:
                    done = done | (executed & (mean_loss < tol))
            return (new_cp, done), (recorded, executed)

        (coef_perm, done), (losses, executed) = jax.lax.scan(
            body, (coef_perm, done), (win_idx, offsets, active)
        )
        return coef_perm, done, losses, jnp.sum(executed.astype(jnp.int32))

    # On a model-less mesh the stacks ride P(data) only — marking the size-1
    # model dim would tag every downstream value varying-over-model and trip
    # shard_map's carry typing for the replicated coefficient.
    stack_spec = (
        (P(data_axes, MODEL_AXIS),) if model_sharded else (P(data_axes),)
    ) * (5 if premat else 3)  # +2: the premat oh_hi/oh_lo stacks
    row_spec = (P(data_axes),) * 3  # y/w/mask
    coef_spec = P(MODEL_AXIS) if model_sharded else P()
    program = jax.jit(
        jax.shard_map(
            per_shard,
            mesh=ctx.mesh,
            in_specs=(coef_spec, P(), P(), P(), P()) + stack_spec + row_spec,
            out_specs=(coef_spec, P(), P(), P()),
        ),
        donate_argnums=(0, 1),
    )
    _cache_put(_FUSED_CACHE, key, program)
    return program


def streamed_onehot_plan(cache, n_rows, n_data, window, local_batch, dim, n_model=1):
    """One counting pass over a host-tier cache → the window-stable
    ``OneHotSparsePlan`` serving every (shard, window, minibatch, sub) unit
    of a streamed run. ``window`` must be the batch-aligned width the
    matching ``WindowSchedule`` computes. Reads the cache once, one
    minibatch at a time. Shared by ``SGD._optimize_streaming_onehot`` and
    the benchmark probes (a plan built from less than the full cache would
    reject units loudly at fill time)."""
    from flink_ml_tpu.linalg.onehot_sparse import (
        BLOCK,
        SUB_ROWS,
        OneHotSparsePlan,
        block_counts,
        validate_indices,
    )

    m = -(-n_rows // n_data)
    b = local_batch
    sub = min(SUB_ROWS, b)
    nblk = -(-dim // BLOCK)
    max_count = np.zeros(nblk, np.int64)
    for k in range(n_data):
        lo_s = k * m
        hi_s = min(lo_s + m, n_rows)
        for w0 in range(0, m, window):
            for b0 in range(w0, min(w0 + window, m), b):
                r0 = lo_s + b0
                r1 = min(lo_s + b0 + b, hi_s)
                if r1 <= r0:
                    continue
                got = cache.rows(r0, r1)
                idx_mb = np.asarray(got["indices"])
                val_mb = np.asarray(got["values"])
                validate_indices(idx_mb, dim)
                for s0 in range(0, r1 - r0, sub):
                    np.maximum(
                        max_count,
                        block_counts(
                            idx_mb[s0 : s0 + sub], val_mb[s0 : s0 + sub], nblk
                        ),
                        out=max_count,
                    )
    return OneHotSparsePlan.from_max_counts(max_count, dim, sub, n_model)


class _StreamedOnehotLayout:
    """The layout identity `_fused_onehot_program` is keyed on, for the
    streamed path: an ``OneHotSparsePlan`` plus this run's minibatch grid.
    Within one resident window the minibatches play the resident layout's
    window role (``window_starts[i] = i * local_batch``)."""

    __slots__ = ("plan", "n_sub", "local_batch", "window_starts")

    def __init__(self, plan, n_sub, local_batch, window_starts):
        self.plan = plan
        self.n_sub = n_sub
        self.local_batch = local_batch
        self.window_starts = window_starts

    @property
    def class_meta(self):
        return self.plan.class_meta

    @property
    def n_flat(self):
        return self.plan.n_flat

    @property
    def nblk(self):
        return self.plan.nblk

    @property
    def nblk_local(self):
        return self.plan.nblk_local

    @property
    def n_model(self):
        return self.plan.n_model

    @property
    def sub_batch(self):
        return self.plan.sub_batch

    @property
    def row_hi(self):
        return self.plan.row_hi


class _OneHotWindowStream:
    """Streamed-window loader for the one-hot kernel: reads a host-cache
    window, transposes every minibatch into plan-conformant stacks (on the
    host, inside ``run_windows``'s prefetch gap — overlapping the device
    compute of the previous window), and places stacks + labels/weights/mask
    on the mesh. Drop-in for ``WindowedStream`` in ``run_windows``.

    With ``premat=True`` it additionally materializes the window's row
    one-hots ON DEVICE from the just-landed rowid stacks (``win["oh"]``:
    the stacks' leading axes, then ``[row_hi | 128, n_pad]``; one elementwise
    jit pass, queued in the prefetch gap so it hides behind the previous
    window's compute). Nothing extra rides ingest — the host still ships
    7 B/slot packed stacks; storage stays bounded at the two prefetch-live
    windows regardless of dataset size. This is what lets the streamed
    (larger-than-HBM) route run the premat product+matmul-only crossings."""

    def __init__(self, cache, ctx, plan, window, local_batch, n_sub, m, n,
                 premat: bool = False):
        self.cache = cache
        self.ctx = ctx
        self.plan = plan
        self.window = int(window)
        self.local_batch = int(local_batch)
        self.n_sub = int(n_sub)
        self.m = int(m)  # per-shard logical rows
        self.n = int(n)
        self.premat = bool(premat)

    def load(self, j: int):
        nd = self.ctx.n_data
        nm = self.plan.n_model
        W, b, m, n = self.window, self.local_batch, self.m, self.n
        n_mb = -(-min(W, m) // b)
        nf = self.plan.n_flat
        shape = (nd, nm, n_mb, self.n_sub, nf)
        lidx = np.zeros(shape, np.int8)
        rowid = np.zeros(shape, np.int16)
        lvals = np.zeros(shape, np.float32)
        y = np.zeros(nd * W, np.float32)
        w = np.zeros(nd * W, np.float32)
        mask = np.zeros(nd * W, np.float32)
        for k in range(nd):
            lo = k * m + j * W
            hi = min(k * m + min((j + 1) * W, m), n)
            if hi <= lo:
                continue
            got = self.cache.rows(lo, hi)
            rows = hi - lo
            sl = slice(k * W, k * W + rows)
            y[sl] = np.asarray(got["labels"], np.float32)
            w[sl] = (
                np.asarray(got["weights"], np.float32)
                if "weights" in got
                else 1.0
            )
            mask[sl] = 1.0
            idx_w = np.asarray(got["indices"])
            val_w = np.asarray(got["values"])
            sub = self.plan.sub_batch
            for mb in range(n_mb):
                r0 = mb * b
                if r0 >= rows:
                    break
                r1 = min(r0 + b, rows)
                # fill the preallocated window arrays in place (no per-
                # minibatch staging copies on the prefetch-gap ingest path)
                for bi in range(self.n_sub):
                    s0 = r0 + bi * sub
                    if s0 >= r1:
                        break
                    s1 = min(s0 + sub, r1)
                    self.plan.fill_unit(
                        idx_w[s0:s1], val_w[s0:s1],
                        lidx[k, :, mb, bi], rowid[k, :, mb, bi],
                        lvals[k, :, mb, bi],
                    )
        sh = self.ctx.sharding(self.ctx.data_axes, MODEL_AXIS)
        rowid_dev = jax.device_put(rowid, sh)
        win = {
            "stacks": (
                jax.device_put(lidx, sh),
                rowid_dev,
                jax.device_put(lvals, sh),
            ),
            "labels": jax.device_put(y, self.ctx.batch),
            "weights": jax.device_put(w, self.ctx.batch),
            "__mask__": jax.device_put(mask, self.ctx.batch),
        }
        if self.premat:
            win["oh"] = _premat_materialize_jit(sh)(rowid_dev, self.plan.row_hi)
        return win


class SGD(Optimizer):
    """Distributed minibatch SGD over the data-parallel mesh."""

    def __init__(
        self,
        max_iter: int = 20,
        learning_rate: float = 0.1,
        global_batch_size: int = 32,
        tol: float = 1e-6,
        reg: float = 0.0,
        elastic_net: float = 0.0,
        dtype=jnp.float32,
        ctx: Optional[MeshContext] = None,
        checkpoint_manager=None,
        checkpoint_interval: int = 0,
        listeners=(),
        stream_window_rows: Optional[int] = None,
        sparse_kernel: str = "auto",
        onehot_premat: str = "auto",
        sharding: Optional[TrainSharding] = None,
    ):
        if sparse_kernel not in ("auto", "onehot", "scatter"):
            raise ValueError(
                f"sparse_kernel must be 'auto', 'onehot' or 'scatter', got {sparse_kernel!r}"
            )
        if onehot_premat not in ("auto", "on", "off"):
            raise ValueError(
                f"onehot_premat must be 'auto', 'on' or 'off', got {onehot_premat!r}"
            )
        self.sparse_kernel = sparse_kernel
        self.onehot_premat = onehot_premat
        self.onehot_premat_active = False  # set per fit; the smoke and perfbench read it
        self.max_iter = max_iter
        self.learning_rate = learning_rate
        self.global_batch_size = global_batch_size
        self.tol = tol
        self.reg = reg
        self.elastic_net = elastic_net
        self.dtype = dtype
        self.ctx = ctx
        # The deterministic train.mesh tier: an explicit TrainSharding, or
        # (when neither it nor ctx is given) whatever ``train.mesh`` resolves
        # per fit. Mutually exclusive with ctx — one mesh authority per run.
        if sharding is not None and ctx is not None:
            raise ValueError("pass ctx or sharding, not both")
        self.sharding = sharding
        if stream_window_rows is None:  # runtime config tier decides
            from flink_ml_tpu.config import Options, config

            stream_window_rows = config.get(Options.TRAIN_STREAM_WINDOW_ROWS)
        self.stream_window_rows = stream_window_rows
        self.checkpoint_manager = checkpoint_manager
        self.checkpoint_interval = checkpoint_interval
        self.listeners = list(listeners)
        self.loss_history: List[float] = []

    def _run_fingerprint(self, loss_func, ctx, rows: int, dim: int, extra=None) -> str:
        """Run/config identity recorded with checkpoints: a different job
        pointed at the same directory must fail loudly, not resume stale state.
        Single source for both the host-loop and streamed paths. The mesh
        shape is part of the identity — per-shard batch cycling depends on
        n_data, and coefficient sharding on n_model."""
        import hashlib
        import json as _json

        sig = {
            "loss": type(loss_func).__name__,
            "max_iter": self.max_iter,
            "lr": self.learning_rate,
            "batch": self.global_batch_size,
            "tol": self.tol,
            "reg": self.reg,
            "elastic_net": self.elastic_net,
            "rows": rows,
            "dim": dim,
            "n_data": ctx.n_data,
            "n_model": ctx.n_model,
        }
        sig.update(extra or {})
        return hashlib.sha256(
            _json.dumps(sig, sort_keys=True).encode()
        ).hexdigest()[:16]

    @staticmethod
    def _tp_features(train_data: DeviceDataCache, ctx: MeshContext):
        """The dense feature matrix column-padded to the model-axis size and
        sharded ``P(data, model)`` for dense tensor parallelism. Padded
        columns are zero, so they produce zero margins and zero gradients
        (and the matching padded coefficient entries stay zero under
        regularization: sign(0) = 0).

        If the cache already holds the column in that layout (``optimize``'s
        dict path ingests it TP-sharded directly when the mesh has a model
        axis) it is used as-is — no second copy ever exists in HBM. Only a
        cache built elsewhere with row-only sharding pays a transient
        per-fit reshard; that duplicate is deliberately NOT memoized so it
        dies with the fit instead of doubling resident memory for the
        largest array in the job."""
        X = train_data["features"]
        tp_sharding = ctx.sharding(ctx.data_axes, MODEL_AXIS)
        if X.shape[1] % ctx.n_model == 0 and X.sharding == tp_sharding:
            return X
        pad = (-X.shape[1]) % ctx.n_model
        if pad:
            X = jnp.pad(X, ((0, 0), (0, pad)))
        return jax.device_put(X, tp_sharding)

    @staticmethod
    def _place_coef(ctx, host_coef, dtype, model_sharded: bool):
        """Place an unpadded host coefficient on the mesh — replicated, or
        padded to the model-axis size and sharded over it. The single source
        for both the resident and streamed paths."""
        host_coef = np.asarray(host_coef, dtype)
        if not model_sharded:
            return ctx.replicate(host_coef)
        pad = (-host_coef.shape[0]) % ctx.n_model
        if pad:
            host_coef = np.concatenate([host_coef, np.zeros(pad, dtype)])
        return jax.device_put(host_coef, ctx.model_dim)

    # -- the one SPMD program -------------------------------------------------
    def _build_step(
        self,
        ctx: MeshContext,
        loss_func: LossFunc,
        local_batch: int,
        sparse: bool = False,
        model_sharded: bool = False,
    ):
        lr = self.learning_rate
        reg, elastic_net = self.reg, self.elastic_net
        dtype = self.dtype
        data_axes = ctx.data_axes

        def per_shard(coef, offset, *data):
            feats = (data[0], data[1]) if sparse else data[0]
            y, w, mask = data[2:5] if sparse else data[1:4]
            m = y.shape[0]
            start = jnp.minimum(offset, m - local_batch)
            new_coef, mean_loss = _sgd_epoch_math(
                coef, start, offset, feats, y, w, mask, loss_func, local_batch,
                lr, reg, elastic_net, dtype, model_sharded=model_sharded,
                data_axes=data_axes,
            )
            next_offset = jnp.where(offset + local_batch >= m, 0, offset + local_batch)
            return new_coef, next_offset, mean_loss

        n_data_args = 5 if sparse else 4
        data_specs = (P(data_axes),) * n_data_args
        if model_sharded and not sparse:
            data_specs = (P(data_axes, MODEL_AXIS),) + data_specs[1:]
        coef_spec = P(MODEL_AXIS) if model_sharded else P()
        return jax.jit(
            jax.shard_map(
                per_shard,
                mesh=ctx.mesh,
                in_specs=(coef_spec, P()) + data_specs,
                out_specs=(coef_spec, P(), P()),
            ),
            donate_argnums=(0,),
        )

    def optimize(
        self,
        init_model: np.ndarray,
        train_data: Union[DeviceDataCache, Dict[str, np.ndarray]],
        loss_func: LossFunc,
    ) -> np.ndarray:
        """Train and return the final coefficient (host array).

        ``train_data``: DeviceDataCache (or dict of host columns) with ``labels``
        [n], optional ``weights`` [n], and either dense ``features`` [n, d] or
        padded-CSR ``indices``/``values`` [n, K] (SparseBatch layout — the
        SparseVector.java training path without densifying).
        """
        ts = self.sharding
        if ts is None and self.ctx is None:
            ts = resolve_train_sharding()
        ctx = self.ctx or (ts.ctx if ts is not None else get_mesh_context())
        from flink_ml_tpu.iteration.streaming import is_host_cache

        self.onehot_premat_active = False  # set by _optimize_onehot when used
        if is_host_cache(train_data):
            return self._optimize_streaming(init_model, train_data, loss_func, ctx)
        if not isinstance(train_data, DeviceDataCache):
            cols = dict(train_data)
            if "indices" not in cols and self.sparse_kernel == "onehot":
                # fail before ingestion — the misconfigured fit must not pay
                # a full device upload of the dense matrix first
                raise ValueError(
                    "sparse_kernel='onehot' applies to sparse (indices/values) "
                    "training data; this fit has dense features"
                )
            if "weights" not in cols:
                cols["weights"] = np.ones(np.asarray(cols["labels"]).shape[0])
            if (
                ts is not None
                and ts.n_model == 1
                and "features" in cols
                and self.checkpoint_manager is None
                and not self.checkpoint_interval
                and not self.listeners
            ):
                # The deterministic sharded tier: dense fused data-parallel
                # fits ingest under the block-cyclic deal and reduce width-
                # invariantly. Sparse / TP / checkpointed / listener fits run
                # the standard psum paths below on the SAME ts mesh (ctx).
                return self._optimize_deterministic(init_model, cols, loss_func, ts)
            # On a TP mesh, dense features ingest directly in their training
            # layout P(data, model) — no row-only duplicate ever lands in HBM.
            specs = (
                {"features": (ctx.data_axes, MODEL_AXIS)}
                if "features" in cols and ctx.n_model > 1
                else None
            )
            train_data = DeviceDataCache(
                {
                    k: np.asarray(v, np.int32 if k == "indices" else self.dtype)
                    for k, v in cols.items()
                },
                ctx=ctx,
                column_specs=specs,
            )
        sparse = "indices" in train_data.arrays
        # A forced kernel that cannot apply to this data must fail loudly on
        # every path (fused, host-loop, listeners) — not just where the kernel
        # choice happens to be consulted.
        if not sparse and self.sparse_kernel == "onehot":
            raise ValueError(
                "sparse_kernel='onehot' applies to sparse (indices/values) "
                "training data; this fit has dense features"
            )
        # Wide models shard the coefficient over the model axis when the mesh
        # has one (tensor parallelism): sparse shards the index range, dense
        # column-slices the feature matrix.
        model_sharded = ctx.n_model > 1
        dim = int(np.asarray(init_model).shape[0])
        y = train_data["labels"]
        w = train_data["weights"]
        mask = train_data.mask.astype(self.dtype)
        if sparse:
            data_args = (train_data["indices"], train_data["values"], y, w, mask)
            # Wide coefficients route to the one-hot matmul path above;
            # this scatter-add remains for narrow models, non-f32 dtypes,
            # and the model-sharded (TP) layout.
        else:
            feats_dev = train_data["features"]
            if model_sharded:
                feats_dev = self._tp_features(train_data, ctx)
            data_args = (feats_dev, y, w, mask)

        local_batch = -(-self.global_batch_size // ctx.n_data)  # ceil
        local_batch = min(local_batch, train_data.local_rows)
        check_loss = np.isfinite(self.tol) and self.tol > 0

        fused = (
            self.checkpoint_manager is None
            and not self.checkpoint_interval
            and not self.listeners
        )
        if fused:
            if self._pick_onehot(sparse, train_data, local_batch, dim):
                result = self._optimize_onehot(
                    init_model, train_data, loss_func, ctx, local_batch, check_loss, dim
                )
                if result is not None:
                    return result
                # auto-picked layout would not fit HBM; fall through to scatter
            # One program runs a chunk of epochs; the host observes the on-device
            # ``done`` flag between chunks (see fused_chunk_len for the policy).
            # sparse epochs: the forward gather + the gradient scatter
            serial = 2 * local_batch * int(train_data["indices"].shape[-1]) if sparse else 0
            chunk = fused_chunk_len(self.max_iter, check_loss, serial)
            with tracer.phase("train.program", CAT_COMPILE) as phase:
                known = tuple(_FUSED_CACHE.values())
                program = _fused_sgd_program(
                    ctx,
                    loss_func,
                    local_batch,
                    chunk,
                    self.learning_rate,
                    self.reg,
                    self.elastic_net,
                    self.tol if check_loss else None,
                    self.dtype,
                    sparse=sparse,
                    model_sharded=model_sharded,
                )
                phase.set_metadata(built=int(program not in known))
            starts, offsets = offset_schedule(train_data.local_rows, local_batch, self.max_iter)
            coef = self._place_coef(ctx, init_model, self.dtype, model_sharded)
            done = ctx.replicate(np.asarray(False))
            self.loss_history = []
            for starts_c, offsets_c, active_c, n_active in chunked_schedule(
                starts, offsets, self.max_iter, chunk
            ):
                with tracer.phase("train.dispatch", CAT_PRODUCTIVE, steps=n_active):
                    coef, done, losses, n_exec = program(
                        coef, done, starts_c, offsets_c, active_c, *data_args
                    )
                # Loss history is recorded unconditionally — the reference always
                # streams loss through the feedback edge (SGD.java:137-143), tol
                # or not. The losses buffer already comes back with the chunk, so
                # this costs one fetch per chunk boundary. The host waits in it
                # while the chunk's steps run on the device: productive time.
                with tracer.phase("train.drain", CAT_PRODUCTIVE, steps=n_active):
                    got = _drain_losses(losses, n_exec)
                self.loss_history.extend(got)
                if check_loss and len(got) < n_active:  # done flipped mid-chunk
                    break
            with tracer.phase("train.readback", CAT_READBACK, bytes=int(coef.nbytes)):
                final = np.asarray(jax.device_get(coef))
            return final[:dim] if model_sharded else final

        if sparse and self.sparse_kernel == "onehot":
            raise ValueError(
                "sparse_kernel='onehot' runs only on the fused path; remove "
                "checkpoint managers/listeners or use 'auto'"
            )
        step = self._build_step(
            ctx, loss_func, local_batch, sparse=sparse, model_sharded=model_sharded,
        )
        return self._optimize_host_loop(
            init_model, train_data, loss_func, ctx, step, local_batch,
            check_loss, dim, sparse, model_sharded, data_args,
        )

    # -- the deterministic sharded tier (train.mesh) --------------------------

    def _optimize_deterministic(
        self, init_model, cols, loss_func, ts: TrainSharding
    ) -> np.ndarray:
        """Dense fused SGD on the deterministic sharded tier.

        Rows ingest once under the block-cyclic deal (ShardedTrainCache) and
        every epoch reduces through ``collectives.mapreduce_sum`` — so for a
        fixed rounded global batch B the fit is *bit-identical* at every mesh
        width (the 8·N row-remainder discipline rounds B up to the mesh's
        quantum; pick B a multiple of 8·N_max to compare widths directly).
        The schedule is global: epoch e consumes window [e·B mod n', +B) of
        the padded set, which the deal makes a contiguous local window on
        every shard — same dynamic_slice minibatching as the legacy path,
        same compiled program shape, one extra all_gather per epoch.
        """
        ctx = ts.ctx
        dim = int(np.asarray(init_model).shape[0])
        n = int(np.asarray(cols["labels"]).shape[0])
        B = ts.round_batch(min(self.global_batch_size, max(n, 1)))
        cache = ts.deal_cache(
            {k: np.asarray(v, self.dtype) for k, v in cols.items()},
            global_batch=B,
            dtype=self.dtype,
        )
        local_batch = cache.local_batch
        check_loss = np.isfinite(self.tol) and self.tol > 0
        chunk = fused_chunk_len(self.max_iter, check_loss)
        program = _fused_sgd_program(
            ctx,
            loss_func,
            local_batch,
            chunk,
            self.learning_rate,
            self.reg,
            self.elastic_net,
            self.tol if check_loss else None,
            self.dtype,
            deterministic=True,
        )
        # n' is a multiple of B, so the window never wraps or clamps:
        # starts == offsets and the tail-batch gating is inert.
        global_starts = (
            np.arange(self.max_iter, dtype=np.int64) * B
        ) % cache.n_padded
        starts = (global_starts // ts.n_data).astype(np.int32)
        data_args = (
            cache["features"],
            cache["labels"],
            cache["weights"],
            cache.mask.astype(self.dtype),
        )
        coef = ts.replicate(np.asarray(init_model, self.dtype))
        done = ctx.replicate(np.asarray(False))
        self.loss_history = []
        for starts_c, offsets_c, active_c, n_active in chunked_schedule(
            starts, starts, self.max_iter, chunk
        ):
            coef, done, losses, n_exec = program(
                coef, done, starts_c, offsets_c, active_c, *data_args
            )
            got = _drain_losses(losses, n_exec)
            self.loss_history.extend(got)
            if check_loss and len(got) < n_active:
                break
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_SHARDED_FITS)
        return np.asarray(jax.device_get(coef))

    # -- one-hot matmul sparse path ------------------------------------------

    _ONEHOT_MIN_DIM = 1 << 14
    _ONEHOT_MAX_WINDOWS = 64

    def _pick_onehot(self, sparse, train_data, local_batch, dim) -> bool:
        """Whether the fused sparse fit runs on the one-hot matmul path
        (linalg/onehot_sparse.py) instead of gather/scatter instructions.

        ``sparse_kernel='onehot'`` forces it (tests), ``'scatter'`` forbids
        it; ``'auto'`` picks it for wide coefficients — where XLA's
        serialized ~7-10 ns/element scatter dominates — with a bounded
        window set (the static layout is built per distinct minibatch) and
        host-readable sparse columns to transpose. f32 only: the MXU path
        carries values as split-bf16 pairs, which reconstruct f32-grade
        precision but not f64. Composes with tensor parallelism: on a TP
        mesh the occupancy-class blocks shard over the model axis
        (OneHotSparsePlan round-robin deal) and the crossing dot psums
        over it. Composes with multi-slice: stacks/crossings stay
        intra-slice and the final gradient psum reduces hierarchically
        over ``ctx.data_axes`` (ICI then DCN).
        """
        if not sparse:  # dense + forced 'onehot' already raised in optimize()
            return False
        if self.sparse_kernel == "scatter":
            return False
        host = getattr(train_data, "host_columns", None)
        feasible = (
            bool(host)
            and "indices" in host
            and jnp.dtype(self.dtype) == jnp.dtype(jnp.float32)
        )
        if self.sparse_kernel == "onehot":
            if not feasible:
                raise ValueError(
                    "sparse_kernel='onehot' requires a fused f32 fit with "
                    "host-readable sparse columns; "
                    "use 'auto' or 'scatter' for this configuration"
                )
            return True
        n_windows = -(-train_data.local_rows // local_batch)
        return (
            feasible
            and int(train_data["indices"].size) >= 1 << 16
            and n_windows <= self._ONEHOT_MAX_WINDOWS
            and dim >= self._ONEHOT_MIN_DIM
        )

    # Fraction of reported HBM the one-hot stacks may claim under 'auto':
    # the CSR columns, labels/weights, coefficient and program workspace share
    # the rest, and the packed stacks cost 7 B per padded slot (int8 lane +
    # int16 rowid + f32 value) times the pow2 padding ratio — a dataset near
    # HBM capacity that trains fine on the scatter path must not OOM by
    # auto-switching.
    _ONEHOT_HBM_FRACTION = 0.35

    # Fraction of reported HBM the materialized premat row one-hots plus the
    # packed stacks may jointly claim under onehot_premat='auto'. The
    # one-hots cost (row_hi + 128) * 2 B per packed slot — ~73x the 7 B/slot
    # stacks — so only the resident regime ever fits: at the headline Criteo
    # shape one 65536-row window is ~2.2 GB and its full 4-window run
    # ~8.7 GB, which fits a 16 GiB v5e alongside the CSR columns and the
    # coefficient with >40% headroom. A resident many-window run whose
    # whole-run one-hots exceed the budget falls back to the build-form
    # kernels; the STREAMED route materializes per window on device instead
    # (`_premat_streamed` budgets the two prefetch-live windows). The lane ids
    # a step program holds unpacked across its scan come out of the same
    # share, after the one-hots (`_hoists_lane_ids`).
    _ONEHOT_PREMAT_HBM_FRACTION = 0.55

    def _premat_onehots(self, lay, stacks, ctx, train_data):
        """Decide the premat fast path (onehot_premat 'on'/'off'/'auto' with
        the HBM budget above) and materialize this run's row one-hots on
        device from the already-resident rowid stacks — one elementwise
        device pass, sharded exactly like the stacks, nothing rides the
        host link. The multi-GB arrays are memoized on the cache next to
        the stacks (same key) — a hyperparameter sweep over one cache must
        materialize once, not per fit. They stay resident as long as the
        cache lives (like the stacks); to release them without dropping
        the cache, ``del train_data._onehot_premat_memo``. Returns
        ``(premat, oh_stacks)`` with ``oh_stacks`` empty when the path is
        off."""
        from flink_ml_tpu.linalg.onehot_sparse import premat_bytes

        n_units = lay.n_windows * lay.n_sub
        oh_bytes = premat_bytes(n_units, lay.n_flat, lay.row_hi)
        active = self.onehot_premat != "off" and (
            self.onehot_premat == "on"
            or oh_bytes + 7 * n_units * lay.n_flat
            <= self._ONEHOT_PREMAT_HBM_FRACTION * _hbm_bytes_limit(ctx)
        )
        key = (ctx.n_data, ctx.n_model, lay.dim, lay.local_batch, lay.row_hi)
        memo = getattr(train_data, "_onehot_premat_memo", None)
        reused = active and memo is not None and memo[0] == key
        with tracer.phase(
            "train.premat", CAT_INGEST, reused=int(reused), active=int(active), bytes=oh_bytes
        ):
            if not active:
                self._drop_premat_memo(train_data)
                return False, ()
            if reused:
                return True, memo[1]
            if memo is not None:  # free the stale config's one-hots BEFORE
                train_data._onehot_premat_memo = None  # allocating the new ones
                memo = None  # the local ref would keep the buffers alive too
            oh_stacks = _premat_materialize_jit(
                ctx.sharding(ctx.data_axes, MODEL_AXIS)
            )(stacks[1], lay.row_hi)
            train_data._onehot_premat_memo = (key, oh_stacks)
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_PREMAT_BUILDS)
        return True, oh_stacks

    @staticmethod
    def _drop_premat_memo(train_data) -> None:
        """Release memoized premat one-hots when a fit decides AGAINST the
        premat path ('off', or the auto gate rejecting): the one-hots cost
        ~73x the packed stacks, so an A/B 'off' fit must not run with a
        previous 'on' fit's multi-GB arrays still resident on the cache."""
        if getattr(train_data, "_onehot_premat_memo", None) is not None:
            train_data._onehot_premat_memo = None

    def _hoists_lane_ids(self, lay, chunk_len: int, premat: bool, ctx) -> bool:
        """Whether the resident step program unpacks its windows' lane ids
        before its scan (``_fused_onehot_program``'s ``hoist``) and not in the
        scan's body. Two conditions, both read off the layout and the
        schedule's shape: the program visits a window more than once (else
        hoisting saves nothing), and the int32 ids it would hold across the
        scan (``lane_ids_bytes``) fit, beside the packed stacks and, on the
        premat route, the row one-hots, the share of HBM the one-hot route
        may claim. Decided after premat and never against it: the one-hots
        are worth 1.86x on the crossings, the hoist about a fifth of the
        step, so a fit that cannot hold both keeps the one-hots and unpacks
        in the body. A build-route fit whose stacks were admitted near
        ``_ONEHOT_HBM_FRACTION`` is refused likewise: its ids would take what
        that budget leaves to the CSR columns and the workspace."""
        from flink_ml_tpu.linalg.onehot_sparse import lane_ids_bytes, premat_bytes

        if chunk_len <= lay.n_windows:
            return False
        n_units = lay.n_windows * lay.n_sub
        held = 7 * n_units * lay.n_flat + lane_ids_bytes(n_units, lay.class_meta)
        if premat:
            held += premat_bytes(n_units, lay.n_flat, lay.row_hi)
        return held <= self._ONEHOT_PREMAT_HBM_FRACTION * _hbm_bytes_limit(ctx)

    def _premat_streamed(self, plan, n_mb, n_sub, ctx) -> bool:
        """The streamed route's premat decision. Unlike the resident gate,
        nothing is memoized — each window's one-hots are materialized on
        device by `_OneHotWindowStream.load` (inside the prefetch gap) and
        freed when the window rotates out, so the budget covers the TWO
        prefetch-live windows' one-hots plus their packed stacks. Ingest
        is unchanged: the host still ships 7 B/slot stacks."""
        from flink_ml_tpu.linalg.onehot_sparse import premat_bytes

        if self.onehot_premat == "off":
            return False
        if self.onehot_premat == "on":
            return True
        n_units = n_mb * n_sub
        per_dev = 2 * (
            premat_bytes(n_units, plan.n_flat, plan.row_hi)
            + 7 * n_units * plan.n_flat
        )
        return per_dev <= self._ONEHOT_PREMAT_HBM_FRACTION * _hbm_bytes_limit(ctx)

    def _onehot_layout(self, train_data, ctx, dim, local_batch, force: bool):
        """Build (once per cache/config) the blocked one-hot layout and its
        device-resident stacks, memoized like the data itself. Returns
        ``(layout, stacks)``; stacks is None when ``force`` is False and the
        stacks would overrun the auto path's HBM budget (the caller then
        falls back to the scatter kernel)."""
        from flink_ml_tpu.linalg.onehot_sparse import OneHotSparseLayout

        key = (ctx.n_data, ctx.n_model, dim, local_batch)
        memo = getattr(train_data, "_onehot_memo", None)
        reused = memo is not None and memo[0] == key and (memo[2] is not None or not force)
        with tracer.phase(
            "train.layout", CAT_INGEST, reused=int(reused), rows=train_data.n_valid
        ) as phase:
            if reused:
                lay = memo[1]
            else:
                host = train_data.host_columns
                # Stacks shard over the (data, model) axes — each device holds
                # 1/(n_data*n_model) of the packed 7 B/slot total;
                # budget the per-device slice. The bound is applied inside build()
                # right after the counting pass, BEFORE any stack materializes — an
                # oversized layout must not cost a multi-GiB transient host
                # allocation just to be rejected.
                budget = (
                    None
                    if force
                    else int(self._ONEHOT_HBM_FRACTION * _hbm_bytes_limit(ctx))
                    * ctx.n_data * ctx.n_model
                )
                lay = OneHotSparseLayout.build(
                    host["indices"], host["values"], dim, ctx.n_data, local_batch,
                    max_stack_bytes=budget, n_model=ctx.n_model,
                )
            phase.set_metadata(
                units=lay.n_shards * lay.n_windows * lay.n_sub if lay is not None else 0
            )
        if reused:
            metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_REUSES)
            return lay, memo[2]
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_BUILDS)
        if lay is None:
            train_data._onehot_memo = (key, None, None)
            return None, None
        metrics.counter(
            MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_LAYOUT_CHUNKS, lay.plan.n_chunks
        )
        # Leading stack dim over (slice, data) jointly on multi-slice meshes:
        # stacks never cross DCN.
        sh = ctx.sharding(ctx.data_axes, MODEL_AXIS)
        nbytes = lay.lidx.nbytes + lay.rowid.nbytes + 4 * lay.lvals.size
        with tracer.phase("train.layout_put", CAT_INGEST, bytes=nbytes):
            dev = (
                jax.device_put(lay.lidx, sh),
                jax.device_put(lay.rowid, sh),
                jax.device_put(np.asarray(lay.lvals, np.float32), sh),
            )
        metrics.counter(MLMetrics.TRAIN_GROUP, MLMetrics.TRAIN_H2D_BYTES, nbytes)
        train_data._onehot_memo = (key, lay, dev)
        return lay, dev

    def _optimize_onehot(
        self, init_model, train_data, loss_func, ctx, local_batch, check_loss, dim
    ):
        from flink_ml_tpu.linalg.onehot_sparse import BLOCK

        from flink_ml_tpu.parallel.mesh import is_tpu_backend

        lay, stacks = self._onehot_layout(
            train_data, ctx, dim, local_batch, force=self.sparse_kernel == "onehot"
        )
        if stacks is None:
            return None  # auto: stacks would overrun HBM — scatter instead
        use_pallas = is_tpu_backend(ctx.mesh.devices.flat)
        premat, oh_stacks = self._premat_onehots(lay, stacks, ctx, train_data)
        self.onehot_premat_active = premat
        # Crossing MACs bound the dispatch length (split-bf16 doubles them).
        flops = 4.0 * lay.n_sub * lay.n_flat * (lay.sub_batch + 2 * BLOCK)
        chunk = fused_chunk_len(self.max_iter, check_loss, 0, flops)
        hoist = self._hoists_lane_ids(lay, chunk, premat, ctx)
        with tracer.phase(
            "train.program", CAT_COMPILE, steps=chunk,
            lane_unpacks=lay.n_windows if hoist else chunk,  # of a window's ids, a program
        ) as phase:
            known = tuple(_FUSED_CACHE.values())
            program = _fused_onehot_program(
                ctx, loss_func, lay, chunk, self.learning_rate, self.reg,
                self.elastic_net, self.tol if check_loss else None, use_pallas,
                premat=premat, hoist=hoist,
            )
            phase.set_metadata(built=int(program not in known))
        starts, offsets = offset_schedule(
            train_data.local_rows, local_batch, self.max_iter
        )
        win_of = {s: i for i, s in enumerate(lay.window_starts)}
        win_idx = np.asarray([win_of[int(s)] for s in starts], np.int32)
        coef_host = lay.permute_coef(np.asarray(init_model, np.float32))
        coef = (
            jax.device_put(coef_host, ctx.model_dim)
            if ctx.n_model > 1
            else ctx.replicate(coef_host)
        )
        done = ctx.replicate(np.asarray(False))
        y = train_data["labels"]
        w = train_data["weights"]
        mask = train_data.mask.astype(jnp.float32)
        self.loss_history = []
        for win_c, offsets_c, active_c, n_active in chunked_schedule(
            win_idx, offsets, self.max_iter, chunk
        ):
            with tracer.phase("train.dispatch", CAT_PRODUCTIVE, steps=n_active):
                coef, done, losses, n_exec = program(
                    coef, done, win_c, offsets_c, active_c, *stacks, *oh_stacks,
                    y, w, mask
                )
            # the host waits here while the chunk's steps run on the device
            with tracer.phase("train.drain", CAT_PRODUCTIVE, steps=n_active):
                got = _drain_losses(losses, n_exec)
            self.loss_history.extend(got)
            if check_loss and len(got) < n_active:
                break
        # Same caller-visible dtype as the scatter fused path (self.dtype —
        # f32 here, the only dtype this kernel admits): auto-selection must
        # not change the output dtype for a float64 init_model.
        with tracer.phase("train.readback", CAT_READBACK, bytes=int(coef.nbytes)):
            return lay.unpermute_coef(np.asarray(jax.device_get(coef)))

    def _pick_onehot_streamed(self, n_rows, K, dim) -> bool:
        """Whether a streamed sparse fit runs the one-hot matmul kernel.

        The streamed layout contract is an ``OneHotSparsePlan`` built from a
        counting pass over the whole cache, so one compiled program serves
        every window (see OneHotSparsePlan). Same feasibility rules as the
        resident gate: f32 only; composes with TP and multi-slice like the
        resident path."""
        if self.sparse_kernel == "scatter":
            return False
        feasible = jnp.dtype(self.dtype) == jnp.dtype(jnp.float32)
        if self.sparse_kernel == "onehot":
            if not feasible:
                raise ValueError(
                    "sparse_kernel='onehot' on the streamed path requires an "
                    "f32 fit; use 'auto' or 'scatter' for this configuration"
                )
            return True
        return feasible and n_rows * K >= 1 << 16 and dim >= self._ONEHOT_MIN_DIM

    def _optimize_streaming_onehot(
        self, init_model, cache, loss_func, ctx, local_batch, dim, check_loss, n_rows
    ):
        """The north-star combination: larger-than-HBM streamed sparse SGD on
        the one-hot matmul kernel.

        One counting pass over the cache sizes a global ``OneHotSparsePlan``
        (per-block max entry count over every (shard, window, minibatch, sub)
        unit); every window's stacks are then host-built against that plan —
        during the prefetch gap, overlapping device compute — and executed by
        ONE compiled program (`_fused_onehot_program` keyed on the plan, with
        the window's minibatches playing the resident path's window role).
        Returns None when 'auto' finds the resident per-window stacks would
        overrun HBM (the caller falls back to the scatter kernel).

        Ref: SGD.java:157-364 caches + replays per-partition data for every
        training config; BASELINE.json's north star is exactly this shape.
        """
        from flink_ml_tpu.iteration.streaming import WindowSchedule, run_windows
        from flink_ml_tpu.linalg.onehot_sparse import BLOCK, SUB_ROWS

        nd = ctx.n_data
        m = -(-n_rows // nd)
        b = local_batch
        # Window width: the same batch-aligned rule WindowSchedule applies.
        W = max(b, min(int(self.stream_window_rows), m))
        W = -(-W // b) * b
        n_mb = -(-min(W, m) // b)
        sub = min(SUB_ROWS, b)
        n_sub = -(-b // sub)
        plan = streamed_onehot_plan(cache, n_rows, nd, W, b, dim, ctx.n_model)

        # Two windows of stacks are HBM-resident at once (prefetch overlap);
        # stack_bytes counts all model shards, so divide by n_model for the
        # per-device slice.
        if self.sparse_kernel != "onehot":
            per_dev = 2 * plan.stack_bytes(n_mb * n_sub) // max(1, ctx.n_model)
            if per_dev > self._ONEHOT_HBM_FRACTION * _hbm_bytes_limit(ctx):
                return None

        flops = 4.0 * n_sub * plan.n_flat * (sub + 2 * BLOCK)
        sched = WindowSchedule(
            m, b, self.stream_window_rows, self.max_iter,
            check_loss=check_loss, flops_per_epoch=flops,
        )
        assert sched.window == W, (sched.window, W)
        # Within one resident window, the minibatches ARE the program's
        # "windows": start of minibatch i is i*b, selected by win_idx = start//b.
        layout_view = _StreamedOnehotLayout(
            plan=plan, n_sub=n_sub, local_batch=b,
            window_starts=tuple(i * b for i in range(n_mb)),
        )
        premat = self._premat_streamed(plan, n_mb, n_sub, ctx)
        self.onehot_premat_active = premat
        # At most one step a minibatch of the resident window (``WindowSchedule``):
        # no window visited twice, so the ids are unpacked in the body.
        with tracer.phase(
            "train.program", CAT_COMPILE, steps=sched.chunk_len, lane_unpacks=sched.chunk_len
        ) as phase:
            known = tuple(_FUSED_CACHE.values())
            program = _fused_onehot_program(
                ctx, loss_func, layout_view, sched.chunk_len, self.learning_rate,
                self.reg, self.elastic_net, self.tol if check_loss else None,
                use_pallas=is_tpu_backend(ctx.mesh.devices.flat),
                premat=premat,
            )
            phase.set_metadata(built=int(program not in known))
        stream = _OneHotWindowStream(
            cache, ctx, plan, W, b, n_sub, m, n_rows, premat=premat
        )

        mgr = self.checkpoint_manager
        start_run = 0
        coef_host = np.asarray(init_model, np.float32)[:dim]
        done_host = np.asarray(False)
        self.loss_history = []
        if mgr is not None:
            mgr.set_fingerprint(
                self._run_fingerprint(
                    loss_func, ctx, n_rows, dim,
                    extra={"window": W, "streamed": True, "kernel": "onehot"},
                )
            )
            restored = mgr.restore_latest()
            if restored is not None:
                _, st = restored
                start_run = int(st["next_run"])
                coef_host = np.asarray(st["coef"], np.float32)
                done_host = np.asarray(bool(st["done"]))
                self.loss_history = [float(x) for x in st["loss_history"]]

        state = {
            "coef": (
                jax.device_put(plan.permute_coef(coef_host), ctx.model_dim)
                if ctx.n_model > 1
                else ctx.replicate(plan.permute_coef(coef_host))
            ),
            "done": ctx.replicate(done_host),
            "epochs": sum(len(s) for _, s in sched.runs[:start_run]),
            "last_saved": None,
        }
        pending_losses: List[tuple] = []

        def dispatch(i, win, starts_c, active_c, n_active):
            win_idx_c = (starts_c // b).astype(np.int32)
            # starts double as offsets, like the scatter streamed path: the
            # window's zero-mask padding realizes the short tail batch.
            state["coef"], state["done"], losses, n_exec = program(
                state["coef"], state["done"], win_idx_c, starts_c, active_c,
                *win["stacks"], *win.get("oh", ()),
                win["labels"], win["weights"], win["__mask__"],
            )
            state["epochs"] += n_active

            def observe():
                stop = False
                if check_loss:
                    got = _drain_losses(losses, n_exec)
                    self.loss_history.extend(got)
                    stop = len(got) < n_active
                else:
                    pending_losses.append((losses, n_exec))
                if mgr is not None and self.checkpoint_interval > 0:
                    last = state["last_saved"]
                    if last is None or state["epochs"] - last >= self.checkpoint_interval:
                        mgr.save(
                            state["epochs"],
                            {
                                "next_run": i + 1,
                                # store the logical (unpermuted, unpadded)
                                # coefficient: restores must not depend on a
                                # particular plan's block permutation
                                "coef": plan.unpermute_coef(
                                    np.asarray(jax.device_get(state["coef"]))
                                ),
                                "done": state["done"],
                                "loss_history": np.asarray(self.loss_history, np.float64),
                            },
                        )
                        state["last_saved"] = state["epochs"]
                return stop

            return observe

        run_windows(stream, sched, dispatch, start_run=start_run)
        for losses, n_exec in pending_losses:
            self.loss_history.extend(_drain_losses(losses, n_exec))
        return plan.unpermute_coef(np.asarray(jax.device_get(state["coef"])))

    def _optimize_host_loop(
        self, init_model, train_data, loss_func, ctx, step, local_batch,
        check_loss, dim, sparse, model_sharded, data_args,
    ):

        if self.checkpoint_manager is not None:
            self.checkpoint_manager.set_fingerprint(
                self._run_fingerprint(
                    loss_func,
                    ctx,
                    int(train_data.n_valid),
                    int(np.asarray(init_model).shape[0]),
                )
            )

        coef = self._place_coef(ctx, init_model, self.dtype, model_sharded)
        offset = ctx.replicate(np.asarray(0, np.int32))
        criteria = TerminateOnMaxIterOrTol(self.max_iter, self.tol)
        self.loss_history = []

        def body(variables, epoch):
            cur_coef, cur_offset = variables
            new_coef, new_offset, mean_loss = step(cur_coef, cur_offset, *data_args)
            if check_loss:
                # The criteria needs the value now; fetch (and sync) per epoch.
                self.loss_history.append(float(jax.device_get(mean_loss)))
                cont = criteria(epoch, self.loss_history[-1])
            else:
                # Record the device scalar without blocking — dispatch stays
                # pipelined; the epilogue below fetches the whole history once.
                self.loss_history.append(mean_loss)
                cont = criteria(epoch, None)
            return IterationBodyResult(
                [new_coef, new_offset], outputs=[new_coef], termination_criteria=cont
            )

        config = IterationConfig(
            checkpoint_manager=self.checkpoint_manager,
            checkpoint_interval=self.checkpoint_interval,
        )
        outputs = iterate_bounded_until_termination(
            [coef, offset], body, config=config, listeners=self.listeners
        )
        if not check_loss:  # resolve the deferred device scalars in one sync
            self.loss_history = [
                float(x) for x in jax.device_get(self.loss_history)
            ]
        final = np.asarray(jax.device_get(outputs[0]))
        # A model-sharded coefficient fetches as the padded [d_pad] vector;
        # checkpoints store the same padded form, so restore round-trips.
        return final[:dim] if model_sharded else final

    def _optimize_streaming(self, init_model, cache, loss_func: LossFunc, ctx) -> np.ndarray:
        """Train out of a host-tier cache larger than HBM.

        Streams per-shard windows (``iteration/streaming.py``) through the same
        fused chunk program as the resident path: every epoch whose minibatch
        falls inside the HBM-resident window runs in one dispatch, and the next
        window is gathered + device_put while the device computes. With
        batch-aligned shards every epoch consumes exactly the rows and weights
        the DeviceDataCache path would (equal up to XLA fusion-order ULPs).

        Checkpoints are taken at run (window-visit) boundaries — the coarsest
        grain at which the coefficient exists on the host side — whenever at
        least ``checkpoint_interval`` epochs have elapsed since the last one;
        restore resumes at the saved run index. Per-epoch listeners need the
        host loop and are rejected loudly rather than silently dropped.
        """
        from flink_ml_tpu.iteration.streaming import plan_windows, run_windows

        if self.listeners:
            raise ValueError(
                "per-epoch listeners are not supported on the streamed "
                "(larger-than-HBM) path; train from a DeviceDataCache instead"
            )
        local_batch = -(-self.global_batch_size // ctx.n_data)  # ceil
        n_rows = int(cache.num_rows)
        local_batch = min(local_batch, -(-n_rows // ctx.n_data))
        row0 = cache.rows(0, 1)
        sparse = "indices" in row0
        if not sparse and self.sparse_kernel == "onehot":
            raise ValueError(
                "sparse_kernel='onehot' applies to sparse (indices/values) "
                "training data; this fit has dense features"
            )
        dim = int(np.asarray(init_model).shape[0])
        check_loss = np.isfinite(self.tol) and self.tol > 0
        # Model-axis sharding on the streamed path covers the sparse layout
        # only (a wide streamed coefficient divides its scatter cost across
        # n_model shards); streamed *dense* features keep a replicated
        # coefficient — windows are ingested row-sharded, and resharding
        # every window over the model axis would serialize the stream.
        model_sharded = sparse and ctx.n_model > 1
        if sparse:
            K0 = int(np.asarray(row0["indices"]).shape[-1])
            if self._pick_onehot_streamed(n_rows, K0, dim):
                result = self._optimize_streaming_onehot(
                    init_model, cache, loss_func, ctx, local_batch, dim,
                    check_loss, n_rows,
                )
                if result is not None:
                    return result
                # auto: per-window stacks would overrun HBM — scatter instead
        if sparse:
            columns = {
                "indices": "indices",
                "values": "values",
                "labels": "labels",
                "weights": "weights",
            }
            feat_keys = ("indices", "values")
        else:
            columns = {"features": "features", "labels": "labels", "weights": "weights"}
            feat_keys = ("features",)
        K = int(np.asarray(row0["indices"]).shape[-1]) if sparse else 0
        stream, sched = plan_windows(
            cache,
            columns,
            ctx,
            self.stream_window_rows,
            local_batch,
            self.max_iter,
            dtype=self.dtype,
            dtypes={"indices": np.int32} if sparse else None,
            # the streamed sparse epoch keeps the gather + scatter gradient
            serial_elems_per_epoch=2 * local_batch * K,
            check_loss=check_loss,
        )
        program = _fused_sgd_program(
            ctx,
            loss_func,
            local_batch,
            sched.chunk_len,
            self.learning_rate,
            self.reg,
            self.elastic_net,
            self.tol if check_loss else None,
            self.dtype,
            sparse=sparse,
            model_sharded=model_sharded,
        )

        mgr = self.checkpoint_manager
        start_run = 0
        coef_host = np.asarray(init_model, self.dtype)
        done_host = np.asarray(False)
        self.loss_history = []
        if mgr is not None:
            mgr.set_fingerprint(
                self._run_fingerprint(
                    loss_func,
                    ctx,
                    n_rows,
                    dim,
                    extra={"window": sched.window, "streamed": True},
                )
            )
            restored = mgr.restore_latest()
            if restored is not None:
                _, state = restored
                start_run = int(state["next_run"])
                coef_host = state["coef"]
                done_host = np.asarray(bool(state["done"]))
                self.loss_history = [float(x) for x in state["loss_history"]]

        state = {
            "coef": self._place_coef(ctx, np.asarray(coef_host)[:dim], self.dtype, model_sharded),
            "done": ctx.replicate(done_host),
            "epochs": sum(len(s) for _, s in sched.runs[:start_run]),
            "last_saved": None,
        }
        # Without a tol criteria the loss values are not needed until the run
        # ends; keep the (losses, n_exec) device buffers pending so window-run
        # boundaries never stall the host, and resolve them in one sync below.
        pending_losses: List[tuple] = []

        def dispatch(i, win, starts_c, active_c, n_active):
            # starts double as offsets: no clamped re-read in the streamed path —
            # the window's zero-mask padding realizes the short tail batch.
            state["coef"], state["done"], losses, n_exec = program(
                state["coef"],
                state["done"],
                starts_c,
                starts_c,
                active_c,
                *[win[k] for k in feat_keys],
                win["labels"],
                win["weights"],
                win["__mask__"],
            )
            state["epochs"] += n_active

            def observe():
                stop = False
                if check_loss:
                    got = _drain_losses(losses, n_exec)
                    self.loss_history.extend(got)
                    stop = len(got) < n_active  # done flipped mid-chunk
                else:
                    pending_losses.append((losses, n_exec))
                if mgr is not None and self.checkpoint_interval > 0:
                    last = state["last_saved"]
                    if last is None or state["epochs"] - last >= self.checkpoint_interval:
                        mgr.save(
                            state["epochs"],
                            {
                                "next_run": i + 1,
                                # store the logical (unpadded) coefficient so
                                # a restore never leaks model-axis padding
                                "coef": np.asarray(jax.device_get(state["coef"]))[:dim],
                                "done": state["done"],
                                "loss_history": np.asarray(self.loss_history, np.float64),
                            },
                        )
                        state["last_saved"] = state["epochs"]
                return stop

            return observe

        run_windows(stream, sched, dispatch, start_run=start_run)
        for losses, n_exec in pending_losses:
            # One sync over already-finished buffers: the reference always
            # streams loss through the feedback edge (SGD.java:137-143), so
            # maxIter-only runs get a full history too.
            self.loss_history.extend(_drain_losses(losses, n_exec))
        final = np.asarray(jax.device_get(state["coef"]))
        return final[:dim] if model_sharded else final
