"""Self-attention sequence classifier — the sequence-parallel flagship stage.

No analogue exists in the reference (its models are coefficient vectors;
SURVEY.md §2.9 records no deep nets anywhere in the tree), but the Stage
contract is the reference's: an ``Estimator`` whose ``fit`` returns a
``Model`` (Estimator.java:31,38), the standard param plumbing, save/load and
model-data access like every other algorithm here.

What makes it the *library consumer* of the sequence-parallel machinery: a
document is a token sequence far longer than one chip wants to hold
attention scores for, so both ``fit`` and ``transform`` run their attention
through ``parallel.ring.ring_attention`` with the sequence axis sharded over
the mesh's data axis — KV blocks rotate over ICI via ppermute while every
shard computes, no [T, T] score matrix ever materializes, and gradients flow
through the ring (pinned against dense attention in
tests/test_ring_attention.py).

Architecture (deliberately compact — the point is the parallelism contract,
not SOTA accuracy): embedding -> one multi-head self-attention block with a
residual -> masked mean-pool over real positions -> softmax head; adam
training with the full step (fwd + ring + bwd + update) in ONE jit'd
program per minibatch.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.params.param import IntParam, ParamValidators, update_existing_params
from flink_ml_tpu.params.shared import (
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasPredictionCol,
    HasRawPredictionCol,
    HasSeed,
)
from flink_ml_tpu.parallel.mesh import DATA_AXIS, MeshContext, get_mesh_context
from flink_ml_tpu.parallel.ring import ring_attention
from flink_ml_tpu.utils import read_write as rw

__all__ = ["SelfAttentionClassifier", "SelfAttentionClassifierModel"]


class _AttnParams(
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasRawPredictionCol,
    HasMaxIter,
    HasLearningRate,
    HasGlobalBatchSize,
    HasSeed,
):
    EMBEDDING_DIM = IntParam(
        "embeddingDim", "Token embedding width.", 32, ParamValidators.gt(0)
    )
    NUM_HEADS = IntParam(
        "numHeads",
        "Attention heads; embeddingDim must divide evenly by it.",
        4,
        ParamValidators.gt(0),
    )
    VOCAB_SIZE = IntParam(
        "vocabSize",
        "Token vocabulary size; 0 infers max(token) + 1 from the training data.",
        0,
        ParamValidators.gt_eq(0),
    )

    def get_embedding_dim(self) -> int:
        return self.get(self.EMBEDDING_DIM)

    def set_embedding_dim(self, value: int):
        return self.set(self.EMBEDDING_DIM, value)

    def get_num_heads(self) -> int:
        return self.get(self.NUM_HEADS)

    def set_num_heads(self, value: int):
        return self.set(self.NUM_HEADS, value)

    def get_vocab_size(self) -> int:
        return self.get(self.VOCAB_SIZE)

    def set_vocab_size(self, value: int):
        return self.set(self.VOCAB_SIZE, value)


def _init_params(rng: np.random.Generator, vocab: int, emb: int, n_classes: int):
    def glorot(shape):
        scale = np.sqrt(2.0 / sum(shape))
        return (rng.normal(size=shape) * scale).astype(np.float32)

    return {
        "emb": glorot((vocab, emb)),
        "wq": glorot((emb, emb)),
        "wk": glorot((emb, emb)),
        "wv": glorot((emb, emb)),
        "wo": glorot((emb, emb)),
        "w_cls": glorot((emb, n_classes)),
        "b_cls": np.zeros(n_classes, np.float32),
    }


def _forward(params, tok, n_valid, n_heads: int, flash: bool = False):
    """Logits for token sequences ``tok [B, T_pad]`` with real length
    ``n_valid``. The attention is sequence-sharded: the surrounding shard_map
    splits T over the mesh's data axis, and ``ring_attention`` rotates KV
    around the ring. Padding positions beyond ``n_valid`` are masked out of
    both the attention keys and the mean-pool."""
    B, T = tok.shape
    E = params["emb"].shape[1]
    h = params["emb"][tok]  # [B, T, E]
    q = (h @ params["wq"]).reshape(B, T, n_heads, E // n_heads)
    k = (h @ params["wk"]).reshape(B, T, n_heads, E // n_heads)
    v = (h @ params["wv"]).reshape(B, T, n_heads, E // n_heads)
    attn = ring_attention(
        q, k, v, DATA_AXIS, causal=False, n_valid=n_valid, flash=flash
    )
    a = attn.reshape(B, T, E) @ params["wo"] + h  # residual
    # masked mean-pool over real positions (global position = shard offset +
    # local index, exactly ring_attention's convention)
    my_idx = jax.lax.axis_index(DATA_AXIS)
    pos = my_idx * T + jnp.arange(T)
    valid = (pos < n_valid).astype(a.dtype)  # [T]
    pooled = jax.lax.psum(jnp.sum(a * valid[None, :, None], axis=1), DATA_AXIS)
    pooled = pooled / jnp.asarray(n_valid, a.dtype)
    return pooled @ params["w_cls"] + params["b_cls"]  # [B, C]


@functools.cache
def _train_step(mesh, n_heads: int, lr: float, flash: bool = False):
    optimizer = optax.adam(lr)
    seq = P(None, DATA_AXIS)

    def per_shard(params, opt_state, tok, y, w, n_valid):
        def loss_fn(p):
            logits = _forward(p, tok, n_valid, n_heads, flash)
            losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            # w zero-weights clamped tail re-reads (the SGD.java:265 short
            # tail batch, same scheme as _sgd_epoch_math's tail_valid)
            return jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1e-30)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # Params are replicated while activations vary over the sequence
        # axis; every shard computes the identical loss (the pool is psum'd),
        # but each shard's grads carry only its sequence slice's
        # contribution — one psum makes the adam update identical everywhere.
        grads = jax.lax.psum(grads, DATA_AXIS)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return optimizer, jax.jit(
        jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P(), seq, P(), P(), P()),
            out_specs=(P(), P(), P()),
        ),
        donate_argnums=(0, 1),
    )


@functools.cache
def _predict_step(mesh, n_heads: int, flash: bool = False):
    seq = P(None, DATA_AXIS)

    def per_shard(params, tok, n_valid):
        logits = _forward(params, tok, n_valid, n_heads, flash)
        return logits, jax.nn.softmax(logits, axis=-1)

    return jax.jit(
        jax.shard_map(
            per_shard, mesh=mesh, in_specs=(P(), seq, P()), out_specs=(P(), P())
        )
    )


def _use_flash(ctx: MeshContext, tok: np.ndarray, emb: int, n_heads: int) -> bool:
    """Fused-fold gate for serving this (mesh, sequence) shape — the
    activations on this path are f32, so only the tiling/VMEM/device
    conditions apply."""
    from flink_ml_tpu.parallel.flash import flash_available

    return flash_available(
        tok.shape[1] // ctx.n_data, emb // n_heads, list(ctx.mesh.devices.flat)
    )


def _use_flash_train(
    ctx: MeshContext, tok: np.ndarray, emb: int, n_heads: int, batch: int
) -> bool:
    """Fused-fold gate for the TRAINING step: the fused backward's pallas
    outputs scale with batch*heads and hit the scoped-VMEM envelope before
    the forward does (flash.flash_train_available); past it the step trains
    on the jnp fold — identical numbers through HBM, never a compile
    failure."""
    from flink_ml_tpu.parallel.flash import flash_train_available

    return flash_train_available(
        tok.shape[1] // ctx.n_data,
        emb // n_heads,
        batch,
        n_heads,
        list(ctx.mesh.devices.flat),
    )


def _pad_tokens(tok: np.ndarray, ctx: MeshContext):
    """Pad the sequence axis to the mesh's data-axis size; token 0 is safe
    padding because every padded position is masked from attention keys and
    the pool by ``n_valid``."""
    T = tok.shape[1]
    pad = (-T) % ctx.n_data
    if pad:
        tok = np.concatenate([tok, np.zeros((tok.shape[0], pad), tok.dtype)], axis=1)
    return tok, T


class SelfAttentionClassifierModel(Model, _AttnParams):
    """Serving side: the same sequence-sharded forward, one jit per mesh."""

    def __init__(self):
        super().__init__()
        self.params: Optional[dict] = None
        self.labels: Optional[np.ndarray] = None

    def transform(self, *inputs):
        (df,) = inputs
        ctx = get_mesh_context()
        tok = np.asarray(df.vectors(self.get_features_col()), np.int32)
        vocab = int(self.params["emb"].shape[0])
        if tok.size and (tok.min() < 0 or tok.max() >= vocab):
            # without this, out-of-range ids would silently clamp through
            # JAX's out-of-bounds gather and predict from the wrong embedding
            raise ValueError(
                f"token ids must be in [0, {vocab}); got "
                f"[{tok.min()}, {tok.max()}]"
            )
        tok, t_real = _pad_tokens(tok, ctx)
        params = {k: jnp.asarray(v) for k, v in self.params.items()}
        n_heads = self.get_num_heads()
        emb = int(self.params["emb"].shape[1])
        logits, probs = _predict_step(
            ctx.mesh, n_heads, _use_flash(ctx, tok, emb, n_heads)
        )(
            params, jax.device_put(tok, ctx.sharding(None, DATA_AXIS)),
            jnp.asarray(t_real, jnp.int32),
        )
        pred = self.labels[np.asarray(jnp.argmax(logits, axis=-1), np.int64)]
        out = df.clone()
        out.add_column(
            self.get_prediction_col(), DataTypes.DOUBLE, np.asarray(pred, np.float64)
        )
        out.add_column(
            self.get_raw_prediction_col(),
            DataTypes.vector(BasicType.DOUBLE),
            np.asarray(probs, np.float64),
        )
        return out

    # --- persistence ---------------------------------------------------------
    def save(self, path: str) -> None:
        rw.save_metadata(self, path)
        rw.save_model_arrays(path, {"labels": self.labels, **self.params})

    @classmethod
    def load(cls, path: str):
        metadata = rw.load_metadata(path, rw.stage_class_name(cls))
        model = cls()
        model.load_param_map_from_json(metadata["paramMap"])
        arrays = rw.load_model_arrays(path)
        model.labels = arrays.pop("labels")
        model.params = dict(arrays)
        return model

    def get_model_data(self):
        from flink_ml_tpu.api.dataframe import DataFrame

        return [DataFrame(["params", "labels"], None, [[self.params], [self.labels]])]

    def set_model_data(self, *model_data):
        df = model_data[0]
        self.params = df.column("params")[0]
        self.labels = np.asarray(df.column("labels")[0])
        return self


class SelfAttentionClassifier(Estimator, _AttnParams):
    """Adam training with the sequence axis sharded over the mesh."""

    def fit(self, *inputs) -> SelfAttentionClassifierModel:
        (df,) = inputs
        ctx = get_mesh_context()
        emb, n_heads = self.get_embedding_dim(), self.get_num_heads()
        if emb % n_heads:
            raise ValueError(
                f"embeddingDim {emb} must divide evenly by numHeads {n_heads}"
            )
        tok = np.asarray(df.vectors(self.get_features_col()), np.int32)
        if tok.min() < 0:
            raise ValueError("token ids must be non-negative")
        labels = np.unique(np.asarray(df.scalars(self.get_label_col())))
        y_idx = np.searchsorted(labels, np.asarray(df.scalars(self.get_label_col())))
        vocab = self.get_vocab_size() or int(tok.max()) + 1
        if tok.max() >= vocab:
            raise ValueError(f"token id {tok.max()} >= vocabSize {vocab}")

        tok, t_real = _pad_tokens(tok, ctx)
        rng = np.random.default_rng(self.get_seed())
        params = jax.tree_util.tree_map(
            jnp.asarray, _init_params(rng, vocab, emb, len(labels))
        )
        n = tok.shape[0]
        batch = min(self.get_global_batch_size(), n)
        optimizer, step = _train_step(
            ctx.mesh,
            n_heads,
            self.get_learning_rate(),
            _use_flash_train(ctx, tok, emb, n_heads, batch),
        )
        opt_state = optimizer.init(params)
        tok_dev = jax.device_put(tok, ctx.sharding(None, DATA_AXIS))
        y_dev = ctx.replicate(y_idx.astype(np.int32))
        nv = jnp.asarray(t_real, jnp.int32)
        offset = 0
        windows = {}  # (lo, offset) -> device tensors; the cycle is short
        losses = []  # device scalars: fetched once after the loop, never per step
        for _ in range(self.get_max_iter()):
            # contiguous example window per epoch, cycling like SGD.java:265;
            # at the clamped tail, rows before the logical offset are re-reads
            # and get zero weight (the reference's short tail batch). Window
            # tensors are built once per distinct (lo, offset) — at most
            # ceil(n/batch) of them — so steady-state epochs do no host work.
            lo = min(offset, n - batch)
            key = (lo, offset)
            if key not in windows:
                windows[key] = (
                    jax.lax.slice_in_dim(tok_dev, lo, lo + batch, axis=0),
                    jax.lax.slice_in_dim(y_dev, lo, lo + batch, axis=0),
                    ctx.replicate(
                        (np.arange(batch) + lo >= offset).astype(np.float32)
                    ),
                )
            tok_w, y_w, w_w = windows[key]
            params, opt_state, loss = step(
                params, opt_state, tok_w, y_w, w_w, nv
            )
            losses.append(loss)
            offset = 0 if offset + batch >= n else offset + batch
        # per-step observability for callers, like LinearEstimatorBase.fit
        self.loss_history = [float(x) for x in jax.device_get(losses)]

        model = SelfAttentionClassifierModel()
        update_existing_params(model, self)
        model.set_vocab_size(vocab)
        model.params = {
            k: np.asarray(jax.device_get(v)) for k, v in params.items()
        }
        model.labels = labels.astype(np.float64)
        return model
