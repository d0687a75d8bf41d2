"""Shared jit'd inference kernels.

Single source for kernels used by several surfaces (training-side model, online
model, runtime-free servable) so prediction semantics cannot diverge and each
kernel has one jit cache entry.

Each kernel's math lives in a plain (unjitted) ``*_fn`` function; the
``*_kernel`` factories jit exactly that function. The serving fast path
(``serving/plan.py``) composes the same ``*_fn``s into one fused per-bucket
program, so the fused and per-stage paths trace identical operations — the
bit-exactness contract between the two paths holds at the op level, not just
by test.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "dot_kernel",
    "sparse_dot_kernel",
    "logistic_from_dots_fn",
    "logistic_from_dots_kernel",
    "logistic_predict_kernel",
    "compute_dots",
    "kmeans_assign_fn",
    "kmeans_predict_kernel",
    "mlp_predict_fn",
    "mlp_predict_kernel",
    "scale_fn",
    "scale_kernel",
    # feature-transform bodies (batch fast path, docs/batch_transform.md)
    "binarize_fn",
    "binarize_kernel",
    "normalize_fn",
    "normalize_kernel",
    "elementwise_product_fn",
    "elementwise_product_kernel",
    "poly_expand_fn",
    "poly_expand_kernel",
    "interaction_fn",
    "interaction_kernel",
    "dct_basis",
    "dct_fn",
    "dct_kernel",
    "impute_fn",
    "impute_kernel",
    "bucketize_fn",
    "bucketize_kernel",
    "kbins_transform_fn",
    "kbins_transform_kernel",
    "vector_slice_fn",
    "vector_slice_kernel",
    "assemble_fn",
    "assemble_kernel",
    "idf_scale_fn",
    "idf_scale_kernel",
    # sparse segment-reduce bodies (the ELL fast path, docs/sparse.md)
    "segment_sum",
    "sparse_dot_fn",
    "sparse_idf_scale_fn",
    "sparse_idf_scale_kernel",
    "sparse_compact_fn",
    "sparse_combine_fn",
    "sparse_combine_kernel",
    "sparse_threshold_fn",
    "sparse_threshold_kernel",
    "onehot_encode_fn",
    "onehot_encode_kernel",
    "sparse_to_dense_fn",
    "sparse_to_dense_kernel",
    "sparse_interaction_fn",
    "sparse_interaction_kernel",
    # retrieval top-K bodies (device-resident candidate scoring, docs/retrieval.md)
    "swing_score_fn",
    "swing_topk_fn",
    "swing_topk_kernel",
    "lsh_share_fn",
    "lsh_jaccard_fn",
    "lsh_topk_fn",
    "lsh_topk_kernel",
    "topk_pad_fn",
]


@functools.cache
def dot_kernel():
    """Dense margins: one MXU matmul (the BLAS.java dot loop, batched)."""

    @jax.jit
    def kernel(X, coef):
        return X @ coef

    return kernel


def segment_sum(terms):
    """Row segment-sum of ``terms [n, K]`` as a strictly sequential left fold
    over the slot axis (``lax.scan``) — THE reduction primitive of the sparse
    calling convention (docs/sparse.md).

    Why not ``jnp.sum``: XLA's row-sum strategy is *width-dependent* (measured
    on XLA CPU: widths < 64 accumulate sequentially, ≥ 64 in blocks — bits
    differ between K=32 and K=64 on the same real entries), so the same row
    packed at two different nnz caps would produce different margins. A
    sequential fold is width-invariant by construction: appending padding
    slots (index 0 / value 0) appends exact identity adds, so a row's result
    is bit-identical at EVERY cap on the nnz ladder — the property the
    fused-vs-per-stage parity contract rests on. graftcheck's
    elementwise-claim treats ``segment_sum`` as a reduction primitive: a
    sparse spec composing it may never claim ``elementwise=True``.
    """
    import jax.lax as lax

    def step(acc, t):
        acc = acc + t
        return acc, None

    acc, _ = lax.scan(step, jnp.zeros_like(terms[:, 0]), terms.T)
    return acc


def sparse_dot_fn(values, indices, coef):
    """Padded-CSR margins: gather-scale-segment-sum (the BLAS.java sparse-dot
    branch, batched; padding slots are index 0 / value 0 and contribute
    exact-identity adds under :func:`segment_sum`, so the margin is
    bit-invariant to the nnz cap the batch happened to pack at)."""
    return segment_sum(values * coef[indices])


@functools.cache
def sparse_dot_kernel():
    """Jitted :func:`sparse_dot_fn` — one cache entry for every surface
    (training-side transforms via ``compute_dots``, the LR servable's
    per-stage sparse path, and the fused sparse specs compose the same
    body)."""

    @jax.jit
    def kernel(indices, values, coef):
        return sparse_dot_fn(values, indices, coef)

    return kernel


def logistic_from_dots_fn(dots):
    """prediction = dot ≥ 0, rawPrediction = [1−p, p] with p = sigmoid(dot).

    Ref LogisticRegressionModelServable.java:62 (shared by
    LogisticRegressionModel, OnlineLogisticRegressionModel and the servable,
    for both dense and sparse margins). Pure — composable into fused serving
    programs.
    """
    prob = jax.nn.sigmoid(dots)
    pred = (dots >= 0).astype(dots.dtype)
    return pred, jnp.stack([1.0 - prob, prob], axis=1)


@functools.cache
def logistic_from_dots_kernel():
    """Jitted ``logistic_from_dots_fn`` — one cache entry for every surface."""
    return jax.jit(logistic_from_dots_fn)


@functools.cache
def logistic_predict_kernel():
    """Dense-input convenience wrapper over ``logistic_from_dots_kernel``."""

    @jax.jit
    def kernel(X, coef):
        return logistic_from_dots_kernel()(X @ coef)

    return kernel


def compute_dots(df, features_col: str, coefficient) -> np.ndarray:
    """Margins ``x·coef`` for a DataFrame features column, dense or sparse.

    Sparse columns stay in the padded-CSR layout end-to-end (gather + row-sum
    kernel) — a Criteo-width transform never materializes an [n, d] array.
    Shared by every linear-family transform — training-side Models AND the
    runtime-free servables — so the two layouts (and the two surfaces) cannot
    produce different margins. Lives here (not models/) because the servable
    tier must stay importable without the training stack.
    """
    coef = jnp.asarray(np.asarray(coefficient), jnp.float32)
    if df.is_sparse(features_col):
        batch = df.sparse_batch(features_col)
        if batch.dim != coef.shape[0]:
            raise ValueError(
                f"features dim {batch.dim} != model dim {coef.shape[0]}"
            )
        return sparse_dot_kernel()(
            jnp.asarray(batch.indices), jnp.asarray(batch.values), coef
        )
    X = df.vectors(features_col).astype(np.float32)
    return dot_kernel()(X, coef)


def kmeans_assign_fn(measure_name: str):
    """Pure closest-centroid assignment ``(X, centroids) -> [n] indices`` for
    ``measure_name`` — the unjitted body of ``kmeans_predict_kernel``."""
    from flink_ml_tpu.ops.distance import DistanceMeasure

    measure = DistanceMeasure.get_instance(measure_name)
    return measure.find_closest


@functools.cache
def kmeans_predict_kernel(measure_name: str):
    """Closest-centroid assignment (ref KMeansModel.java predict). One cache
    entry per distance measure, shared by KMeansModel, OnlineKMeansModel and
    KMeansModelServable."""
    fn = kmeans_assign_fn(measure_name)
    return jax.jit(lambda X, centroids: fn(X, centroids))


def mlp_predict_fn(layers, X):
    """Pure float32 MLP forward: relu hidden layers, softmax head; returns
    ``(argmax class index as f32, [n, classes] probabilities)``.

    The identical op sequence to the training-side
    ``mlp_classifier._forward`` + predict head at ``compute_type='float32'``
    (matmul, add, relu per hidden layer; softmax/argmax on f32 logits), so
    the weight-resident serving path and the training-side model cannot
    diverge. ``layers`` is a sequence of ``(W, b)`` pairs — any length; jit
    retraces per layer-count, which is one trace per architecture.

    The matmuls pin ``Precision.HIGHEST``: float32 means float32 on every
    backend. A TPU's default multiplies f32 operands in ONE bf16 pass — and
    XLA computes a 1-row bucket exactly instead — so on a v5e the unpinned
    head sat up to 530,682 ulps from the float64 forward, moved by 2e-2
    relative between serving buckets, and put the Pallas megakernel (always
    the bf16 pass) 197,958 ulps from the exact tier at bucket 1. Pinned,
    XLA and Mosaic agree within 22 ulps at every bucket and both sit within
    58 of float64. CPU results do not change.
    """
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    h = X
    for W, b in layers[:-1]:
        h = jax.nn.relu(dot(h, W) + b)
    W, b = layers[-1]
    logits = (dot(h, W) + b).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.float32), probs


@functools.cache
def mlp_predict_kernel():
    """Jitted ``mlp_predict_fn`` — the per-stage path of
    ``MLPClassifierModelServable`` (the fused path composes the same body)."""
    return jax.jit(lambda layers, X: mlp_predict_fn(layers, X))


def scale_fn(X, mean, inv_std, *, with_mean: bool, with_std: bool):
    """Pure standardization math (ref StandardScalerModel.java:60-97): subtract
    mean if ``with_mean``, multiply by inv_std if ``with_std``."""
    out = X
    if with_mean:
        out = out - mean[None, :]
    if with_std:
        out = out * inv_std[None, :]
    return out


@functools.cache
def scale_kernel(with_mean: bool, with_std: bool):
    """Jitted ``scale_fn`` at fixed flags. Shared by the batch model, the
    online model and StandardScalerModelServable."""

    @jax.jit
    def kernel(X, mean, inv_std):
        return scale_fn(X, mean, inv_std, with_mean=with_mean, with_std=with_std)

    return kernel


# ---------------------------------------------------------------------------
# Feature-transform bodies — the batch fast path (builder/batch_plan.py).
#
# Each transformer in models/feature/ that exports a KernelSpec routes its
# per-stage ``transform`` through the jitted ``*_kernel`` here, and its spec's
# ``kernel_fn`` composes the matching ``*_fn`` body — so the fused
# device-resident chain and the per-stage fallback trace identical operations
# (enforced by graftcheck's kernel-spec-consistency rule).
# ---------------------------------------------------------------------------


def binarize_fn(x, threshold: float):
    """values > threshold → 1 else 0, in the input's dtype (ref Binarizer.java)."""
    return (x > threshold).astype(x.dtype)


@functools.cache
def binarize_kernel(threshold: float):
    """Jitted ``binarize_fn`` at a fixed threshold — one cache entry per
    threshold, shared by Binarizer.transform and its kernel spec."""
    return jax.jit(lambda x: binarize_fn(x, threshold))


def normalize_fn(X, p: float):
    """Scale each row to unit p-norm; zero rows stay zero (ref Normalizer.java)."""
    norm = jnp.sum(jnp.abs(X) ** p, axis=1, keepdims=True) ** (1.0 / p)
    return X / jnp.where(norm == 0.0, 1.0, norm)


@functools.cache
def normalize_kernel(p: float):
    """Jitted ``normalize_fn`` at a fixed p."""
    return jax.jit(lambda X: normalize_fn(X, p))


def elementwise_product_fn(X, scaling):
    """Hadamard product with the scaling vector (ref ElementwiseProduct.java)."""
    return X * scaling[None, :]


@functools.cache
def elementwise_product_kernel():
    """Jitted ``elementwise_product_fn``."""
    return jax.jit(elementwise_product_fn)


@functools.cache
def _poly_combos(d: int, degree: int):
    out = []
    for deg in range(1, degree + 1):
        out.extend(itertools.combinations_with_replacement(range(d), deg))
    return tuple(out)


def poly_expand_fn(X, degree: int):
    """All monomials of degree 1..degree over the row, combos grouped by degree
    (ref PolynomialExpansion.java; ordering documented in that module). The
    combo set derives from the static trace-time width ``X.shape[1]``."""
    combos = _poly_combos(X.shape[1], degree)
    cols = [jnp.prod(X[:, jnp.asarray(c)], axis=1) for c in combos]
    return jnp.stack(cols, axis=1)


@functools.cache
def poly_expand_kernel(degree: int):
    """Jitted ``poly_expand_fn`` at a fixed degree (per-width programs come
    from jit's shape specialization)."""
    return jax.jit(lambda X: poly_expand_fn(X, degree))


def interaction_fn(*cols):
    """Batched outer product across columns: [n,d1] x [n,d2] ... -> [n,d1*d2*...]
    with the first column's index varying slowest (ref Interaction.java)."""
    acc = cols[0]
    for c in cols[1:]:
        acc = acc[:, :, None] * c[:, None, :]
        acc = acc.reshape(acc.shape[0], -1)
    return acc


@functools.cache
def interaction_kernel():
    """Jitted ``interaction_fn`` (variadic; shape-specialized by jit)."""
    return jax.jit(interaction_fn)


@functools.cache
def dct_basis(d: int, inverse: bool) -> np.ndarray:
    """Orthonormal DCT-II basis B[k, j] = s_k cos(pi (j + 1/2) k / d), already
    transposed for the forward direction so ``dct_fn`` is a plain matmul in
    both directions (orthonormal: the inverse is the transpose)."""
    j = np.arange(d)
    k = np.arange(d)[:, None]
    basis = np.cos(np.pi * (j + 0.5) * k / d)
    scale = np.full(d, np.sqrt(2.0 / d))
    scale[0] = np.sqrt(1.0 / d)
    mat = (basis * scale[:, None]).astype(np.float64)
    return mat if inverse else np.ascontiguousarray(mat.T)


def dct_fn(X, basis):
    """Cosine-basis matmul — the whole-batch MXU form of the reference's
    per-row FFT call (ref DCT.java). ``basis`` is the [d, d] matrix from
    :func:`dct_basis`, embedded as a trace-time constant by both the
    per-stage kernel and the fused spec."""
    return X @ jnp.asarray(basis)


@functools.cache
def dct_kernel(d: int, inverse: bool):
    """Jitted ``dct_fn`` with the basis for dimension ``d`` burned in as a
    compile-time constant — one cache entry per (d, direction)."""
    basis = dct_basis(d, inverse)
    return jax.jit(lambda X: dct_fn(X, basis))


def impute_fn(x, surrogate, missing_is_nan: bool, missing_value: float):
    """Replace missing entries with the surrogate (ref ImputerModel.java).
    The missing-value test is static: NaN placeholders compare via isnan."""
    miss = jnp.isnan(x) if missing_is_nan else (x == missing_value)
    return jnp.where(miss, surrogate, x)


@functools.cache
def impute_kernel(missing_is_nan: bool, missing_value: float):
    """Jitted ``impute_fn`` at a fixed missing-value placeholder. NaN
    placeholders must be canonicalized to ``(True, 0.0)`` by the caller so the
    cache key stays hashable-equal."""
    return jax.jit(lambda x, s: impute_fn(x, s, missing_is_nan, missing_value))


def bucketize_fn(x, splits, keep_invalid: bool):
    """Bucket ids for [splits[j], splits[j+1]) with a right-inclusive last
    bucket, plus the invalid mask (ref Bucketizer.java). ``keep_invalid``
    maps invalid entries to the extra bucket numSplits-1 (the 'keep' mode);
    otherwise they keep their clamped id and the caller handles the mask
    (raise for 'error', row-drop for 'skip') on the host."""
    n = splits.shape[0]
    idx = jnp.searchsorted(splits, x, side="right") - 1
    idx = jnp.where(x == splits[n - 1], n - 2, idx)
    invalid = (x < splits[0]) | (x > splits[n - 1]) | jnp.isnan(x)
    if keep_invalid:
        idx = jnp.where(invalid, n - 1, idx)
    return idx.astype(jnp.float32), invalid


@functools.cache
def bucketize_kernel(keep_invalid: bool):
    """Jitted ``bucketize_fn`` at a fixed invalid-handling mode."""
    return jax.jit(lambda x, splits: bucketize_fn(x, splits, keep_invalid))


def kbins_transform_fn(X, edges, n_edges):
    """Per-dimension bin ids with out-of-range clamping (ref
    KBinsDiscretizerModel.java). ``edges`` is [d, E] right-padded with +inf
    (ragged per-dim edge counts padded to the max), ``n_edges`` [d] the real
    counts — finite values never land in the padding, and the per-dim clip
    bound comes from the real count."""

    def per_dim(x_col, e, ne):
        idx = jnp.searchsorted(e, x_col, side="right") - 1
        return jnp.clip(idx, 0, ne - 2)

    idx = jax.vmap(per_dim, in_axes=(1, 0, 0), out_axes=1)(X, edges, n_edges)
    return idx.astype(X.dtype)


@functools.cache
def kbins_transform_kernel():
    """Jitted ``kbins_transform_fn``."""
    return jax.jit(kbins_transform_fn)


def vector_slice_fn(X, indices: tuple):
    """Select the given feature indices, in order (ref VectorSlicer.java)."""
    return X[:, jnp.asarray(indices)]


@functools.cache
def vector_slice_kernel(indices: tuple):
    """Jitted ``vector_slice_fn`` at a fixed index set."""
    return jax.jit(lambda X: vector_slice_fn(X, indices))


def assemble_fn(*blocks):
    """Concatenate per-column [n, size] blocks into one vector column
    (ref VectorAssembler.java); scalar columns arrive as [n] and reshape."""
    n = blocks[0].shape[0]
    return jnp.concatenate([b.reshape(n, -1) for b in blocks], axis=1)


@functools.cache
def assemble_kernel():
    """Jitted ``assemble_fn`` (variadic; shape-specialized by jit)."""
    return jax.jit(assemble_fn)


def idf_scale_fn(X, idf):
    """Term-frequency vectors scaled elementwise by idf (ref IDFModel.java)."""
    return X * idf[None, :]


@functools.cache
def idf_scale_kernel():
    """Jitted ``idf_scale_fn``."""
    return jax.jit(idf_scale_fn)


# ---------------------------------------------------------------------------
# Sparse segment-reduce bodies — the ELL/padded-CSR fast path (docs/sparse.md).
#
# The sparse calling convention (servable/sparse.py) moves a ragged column
# through compiled chains as three dense arrays: values [n, K] f32,
# ids [n, K] i32, nnz [n] i32, with K a power-of-two nnz cap from the bucket
# ladder and padding slots id 0 / value 0. The bodies below are the device
# half of every sparse transformer: per-row duplicate-combine (a sorted-run
# segment reduce), compaction, thresholding, one-hot encode, densify, outer
# interaction, and the gather-scale-segment-sum margin. Per-stage transforms
# jit the ``*_kernel`` factories; the fused specs compose the ``*_fn`` bodies
# — one math, two paths, the kernel-spec-consistency contract.
# ---------------------------------------------------------------------------


def _valid_slots(shape_like, nnz):
    """[n, K] mask of real (non-padding) entry slots: slot index < row nnz."""
    return jnp.arange(shape_like.shape[1])[None, :] < nnz[:, None]


def sparse_idf_scale_fn(values, ids, idf):
    """Sparse term-frequency entries scaled by their dimension's idf —
    gather + per-entry multiply, no accumulation (ref IDFModel.java sparse
    branch). ids/nnz pass through unchanged: structure-preserving."""
    return values * idf[ids]


@functools.cache
def sparse_idf_scale_kernel():
    """Jitted ``sparse_idf_scale_fn`` — IDFModel's per-stage sparse path (the
    fused sparse spec composes the same body)."""
    return jax.jit(sparse_idf_scale_fn)


def sparse_compact_fn(values, ids, keep):
    """Compact the kept entries of each row to its leading slots, preserving
    their relative (id-sorted) order, and zero the padding tail:
    ``(values, ids, keep) -> (values, ids, nnz)``. The stable argsort on the
    drop mask moves every kept entry forward without reordering kept-vs-kept
    — the invariant every sparse column in the convention carries (real
    entries first, sorted by id, then id-0/value-0 padding)."""
    drop = (~keep).astype(jnp.int32)
    order = jnp.argsort(drop, axis=1)  # jax sorts are stable
    svals = jnp.take_along_axis(jnp.where(keep, values, 0.0), order, axis=1)
    sids = jnp.take_along_axis(jnp.where(keep, ids, 0), order, axis=1)
    nnz = jnp.sum(keep.astype(jnp.int32), axis=1)  # int sum: exact
    return svals, sids, nnz


def sparse_combine_fn(values, ids, nnz):
    """Per-row duplicate-combine — THE segment-reduce kernel of the sparse
    fast path: sort each row's entries by id (stable, padding last), sum the
    values of equal-id runs with a strictly sequential in-run fold (slot
    order — exactly the order the host dict accumulation of the per-stage
    reference path applies, and exact for single-entry runs), keep one entry
    per distinct id, compact. Used by HashingTF (term counts: values are
    1.0s), CountVectorizer (vocabulary counts) and FeatureHasher (collision
    accumulation)."""
    import jax.lax as lax

    valid = _valid_slots(ids, nnz)
    skey = jnp.where(valid, ids, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(skey, axis=1)  # stable: equal ids keep slot order
    sids = jnp.take_along_axis(skey, order, axis=1)
    svals = jnp.take_along_axis(jnp.where(valid, values, 0.0), order, axis=1)
    svalid = jnp.take_along_axis(valid, order, axis=1)
    # same[j]: slot j continues slot j-1's id run (padding never matches a
    # real id — the sort key is INT32_MAX there).
    prev = jnp.concatenate([jnp.full_like(sids[:, :1], -1), sids[:, :-1]], axis=1)
    same = sids == prev
    # Sequential run fold: acc restarts at each new id, so the run total
    # lands at the run's LAST slot in exact slot order.
    def step(acc, x):
        v, s = x
        acc = v + jnp.where(s, acc, 0.0)
        return acc, acc

    _, run = lax.scan(step, jnp.zeros_like(svals[:, 0]), (svals.T, same.T))
    run = run.T
    nxt = jnp.concatenate([same[:, 1:], jnp.zeros_like(same[:, :1])], axis=1)
    last = svalid & ~nxt  # last slot of each real id run
    return sparse_compact_fn(run, sids, last)


@functools.cache
def sparse_combine_kernel():
    """Jitted ``sparse_combine_fn`` — the per-stage path of HashingTF /
    CountVectorizer / FeatureHasher (their fused specs compose the body)."""
    return jax.jit(sparse_combine_fn)


def sparse_threshold_fn(values, ids, nnz, threshold):
    """Drop entries whose value falls below the per-row ``threshold [n]``
    (CountVectorizer's minTF filter), recompacting survivors."""
    keep = _valid_slots(ids, nnz) & (values >= threshold[:, None])
    return sparse_compact_fn(values, ids, keep)


@functools.cache
def sparse_threshold_kernel():
    """Jitted ``sparse_threshold_fn``."""
    return jax.jit(sparse_threshold_fn)


def onehot_encode_fn(idx, size: int, vec_len: int):
    """One scalar index column as sparse one-hot entries (ref
    OneHotEncoderModel.java, handleInvalid='keep' semantics): invalid indices
    (negative / fractional / ≥ size) map to the keep category ``size - 1``;
    an index ≥ ``vec_len`` (the dropLast category) encodes as the empty row.
    Purely elementwise — one entry slot per row."""
    invalid = (idx < 0) | (idx != jnp.floor(idx)) | (idx >= size)
    mapped = jnp.where(invalid, float(size - 1), idx)
    hit = mapped < vec_len
    ids = jnp.where(hit, mapped, 0.0).astype(jnp.int32)[:, None]
    values = jnp.where(hit, 1.0, 0.0).astype(jnp.float32)[:, None]
    nnz = hit.astype(jnp.int32)
    return values, ids, nnz


@functools.cache
def onehot_encode_kernel(size: int, vec_len: int):
    """Jitted ``onehot_encode_fn`` at a fixed category layout."""
    return jax.jit(lambda idx: onehot_encode_fn(idx, size, vec_len))


def sparse_to_dense_fn(values, ids, nnz, size: int):
    """Scatter sparse entries into a dense [n, size] block (the
    VectorAssembler densify). Entry ids are unique per row (the convention's
    sorted-unique invariant), so the scatter is a pure per-entry ``set`` —
    no accumulation; padding slots dump into a spare trailing column that is
    sliced off."""
    n = values.shape[0]
    valid = _valid_slots(ids, nnz)
    dump = jnp.where(valid, ids, size)
    dense = jnp.zeros((n, size + 1), values.dtype)
    dense = dense.at[jnp.arange(n)[:, None], dump].set(jnp.where(valid, values, 0.0))
    return dense[:, :size]


@functools.cache
def sparse_to_dense_kernel(size: int):
    """Jitted ``sparse_to_dense_fn`` at a fixed width."""
    return jax.jit(lambda v, i, z: sparse_to_dense_fn(v, i, z, size))


def sparse_interaction_fn(a_values, a_ids, a_nnz, b_values, b_ids, b_nnz, dim_b: int):
    """Sparse × sparse outer interaction (ref Interaction.java on one-hot /
    sparse inputs): out[id_a * dim_b + id_b] = v_a · v_b for every real entry
    pair, compacted. Both inputs carry sorted-unique ids, so the flattened
    (a-major) pair order is already id-sorted and the output keeps the
    convention's invariant."""
    n, ka = a_ids.shape
    kb = b_ids.shape[1]
    ids = (a_ids[:, :, None] * dim_b + b_ids[:, None, :]).reshape(n, ka * kb)
    values = (a_values[:, :, None] * b_values[:, None, :]).reshape(n, ka * kb)
    keep = (
        _valid_slots(a_ids, a_nnz)[:, :, None] & _valid_slots(b_ids, b_nnz)[:, None, :]
    ).reshape(n, ka * kb)
    return sparse_compact_fn(values, ids, keep)


@functools.cache
def sparse_interaction_kernel(dim_b: int):
    """Jitted ``sparse_interaction_fn`` at a fixed right-side width."""
    return jax.jit(
        lambda av, ai, an, bv, bi, bn: sparse_interaction_fn(av, ai, an, bv, bi, bn, dim_b)
    )


# -- retrieval top-K bodies (docs/retrieval.md) -------------------------------


def swing_score_fn(values, ids, nnz, sim_values, sim_ids):
    """Dense candidate scores from a sparse user history (the Swing full-score
    phase): ``score[r, c] = Σ_h w_h · sim[h][c]`` over the history's real
    slots, where ``sim`` is the candidate index's ELL neighbor table
    (``sim_ids/sim_values [C, M]``, padding slots id 0 / value 0).

    The history-slot axis folds STRICTLY SEQUENTIALLY (``lax.scan``, the
    ``segment_sum`` discipline): appending padding slots (id 0 / weight 0)
    appends exact-identity scatter-adds, so a row's scores are bit-identical
    at every nnz cap on the ladder — the fused path (batch-shared cap) and
    the per-stage reference (natural cap) agree bit for bit. Within one slot
    the scattered columns are the neighbor list's ids, sorted-unique by the
    index build, so no two real contributions collide and the scatter order
    inside a step cannot reorder a float sum. Alongside the scores the fold
    accumulates a history-hit mask; already-consumed candidates leave with
    score −inf (a request's own history is never recommended back to it).
    """
    import jax.lax as lax

    n = values.shape[0]
    C = sim_values.shape[0]
    rowsel = jnp.arange(n)
    valid = _valid_slots(ids, nnz).astype(jnp.float32)  # [n, K] 1.0 real slots

    def step(carry, slot):
        scores, hits = carry
        w, h, ok = slot  # [n] weight, history candidate row, validity
        contrib = (w * ok)[:, None] * sim_values[h]  # [n, M]; pad slots add 0
        scores = scores.at[rowsel[:, None], sim_ids[h]].add(contrib)
        hits = hits.at[rowsel, h].add(ok)
        return (scores, hits), None

    init = (jnp.zeros((n, C), jnp.float32), jnp.zeros((n, C), jnp.float32))
    (scores, hits), _ = lax.scan(
        step, init, (values.T, ids.T, valid.T)
    )
    return jnp.where(hits > 0, -jnp.inf, scores)


def topk_pad_fn(scores, rung: int, descending: bool = True):
    """``jax.lax.top_k`` at a ladder rung wider than the candidate axis:
    take the full top-C and pad the tail slots with row −1 / score ±inf (the
    typed "no candidate" slots the retrieval client trims away). Prefix
    stability of ``top_k`` (descending, ties to the lowest index) makes the
    rung padding exact: the top-10 of a row is the first 10 entries of its
    top-16."""
    C = scores.shape[1]
    kk = min(int(rung), C)
    vals, idx = jax.lax.top_k(scores if descending else -scores, kk)
    if not descending:
        vals = -vals
    pad = int(rung) - kk
    if pad:
        fill = -jnp.inf if descending else jnp.inf
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=fill)
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
    empty = jnp.isinf(vals)
    return vals, jnp.where(empty, -1, idx)


def swing_topk_fn(values, ids, nnz, sim_values, sim_ids, rung: int):
    """The fused Swing retrieval head: full-score then ``top_k`` at the K
    ladder rung. Returns ``(rows [n, rung] i32, scores [n, rung] f32)`` sorted
    best-first; slots past a row's scoreable candidates carry row −1 /
    score −inf."""
    scores = swing_score_fn(values, ids, nnz, sim_values, sim_ids)
    vals, idx = topk_pad_fn(scores, rung, descending=True)
    empty = (nnz <= 0)[:, None]  # no history: typed empty row, not zero-scores
    return jnp.where(empty, -1, idx), jnp.where(empty, -jnp.inf, vals)


def lsh_share_fn(q_lanes, cand_lanes, tables: int):
    """Bucket-share counts of the LSH prune phase: how many of the ``T`` hash
    tables each (query, candidate) pair fully agrees on. Hash values travel as
    2 exact f32 lanes each (hi/lo 16-bit split — a MinHash value < 2^31 does
    not fit f32's 24-bit mantissa, the split restores exact equality);
    ``q_lanes [n, T·F·2]``, ``cand_lanes [C, T·F·2]``. A query lane of −1 (the
    empty-feature sentinel) matches nothing."""
    n = q_lanes.shape[0]
    C = cand_lanes.shape[0]
    q = q_lanes.reshape(n, tables, -1)  # [n, T, F·2]
    c = cand_lanes.reshape(C, tables, -1)
    eq = (q[:, None] == c[None]).all(axis=3)  # [n, C, T] full-table agreement
    return eq.sum(axis=2).astype(jnp.int32)  # [n, C]


def lsh_jaccard_fn(q_ids, q_nnz, cand_ids, cand_nnz):
    """Exact 1 − Jaccard distances of the rank phase, over gathered candidate
    ELL index sets: ``q_ids [n, Kq]`` (validity ``q_nnz``) against
    ``cand_ids [n, P, M]`` (validity ``cand_nnz [n, P]``). Both sides carry
    sorted-unique ids, so every pair matches at most once and the
    intersection count is an exact integer."""
    qv = _valid_slots(q_ids, q_nnz)  # [n, Kq]
    slot = jnp.arange(cand_ids.shape[2])[None, None, :]
    cvalid = slot < cand_nnz[:, :, None]  # [n, P, M]
    eq = (
        (q_ids[:, None, :, None] == cand_ids[:, :, None, :])
        & qv[:, None, :, None]
        & cvalid[:, :, None, :]
    )  # [n, P, Kq, M]
    inter = eq.sum(axis=(2, 3)).astype(jnp.float32)  # [n, P]
    union = q_nnz[:, None].astype(jnp.float32) + cand_nnz.astype(jnp.float32) - inter
    union = jnp.maximum(union, 1.0)
    return 1.0 - inter / union


def lsh_topk_fn(
    q_lanes, q_ids, q_nnz, cand_lanes, cand_ids, cand_nnz, tables: int,
    prune_cap: int, rung: int,
):
    """The fused two-phase LSH retrieval head (bucket-prune → exact rank):

    1. **Prune**: ``top_k`` over the bucket-share counts keeps the
       ``prune_cap`` candidates sharing the most hash tables (ties to the
       lowest candidate row — the host reference's stable order). Candidates
       sharing zero buckets are non-candidates per the reference semantics.
    2. **Rank**: exact 1 − Jaccard on the pruned set only, then ``top_k``
       ascending at the K ladder rung.

    Returns ``(rows [n, rung] i32, distances [n, rung] f32)`` sorted
    nearest-first; slots past a row's true candidate set carry row −1 /
    distance +inf (the typed empty-result convention — a query sharing no
    bucket with any candidate yields a fully −1 row instead of erroring).
    Parity with the host reference is exact whenever a query's bucket-sharing
    candidate count fits ``prune_cap`` (docs/retrieval.md)."""
    C = cand_lanes.shape[0]
    share = lsh_share_fn(q_lanes, cand_lanes, tables)  # [n, C]
    P = min(int(prune_cap), C)
    share_top, pruned = jax.lax.top_k(share.astype(jnp.float32), P)  # [n, P]
    # Re-sort the kept set by candidate row (zero-share rows masked to C, past
    # every real row): the rank phase's top_k then breaks distance ties toward
    # the LOWEST candidate row — the host reference's stable ascending order —
    # instead of toward the higher bucket-share count the prune order carries.
    pruned = jnp.sort(jnp.where(share_top > 0, pruned, C), axis=1)
    valid = pruned < C
    rows_for_rank = jnp.where(valid, pruned, 0)
    dist = lsh_jaccard_fn(q_ids, q_nnz, cand_ids[rows_for_rank], cand_nnz[rows_for_rank])
    dist = jnp.where(valid, dist, jnp.inf)  # zero-share: not a candidate
    kk = min(int(rung), P)
    neg, pos = jax.lax.top_k(-dist, kk)
    out_dist = -neg
    rows = jnp.take_along_axis(pruned, pos, axis=1)
    pad = int(rung) - kk
    if pad:
        out_dist = jnp.pad(out_dist, ((0, 0), (0, pad)), constant_values=jnp.inf)
        rows = jnp.pad(rows, ((0, 0), (0, pad)), constant_values=-1)
    return jnp.where(jnp.isinf(out_dist), -1, rows), out_dist


@functools.cache
def swing_topk_kernel(rung: int):
    """Jitted ``swing_topk_fn`` at a fixed K ladder rung (the per-stage path —
    same op graph as the fused head, so fallback results match bit for bit)."""
    return jax.jit(
        lambda v, i, z, sv, si: swing_topk_fn(v, i, z, sv, si, rung)
    )


@functools.cache
def lsh_topk_kernel(tables: int, prune_cap: int, rung: int):
    """Jitted ``lsh_topk_fn`` at fixed table count / prune cap / K rung."""
    return jax.jit(
        lambda ql, qi, qz, cl, ci, cz: lsh_topk_fn(
            ql, qi, qz, cl, ci, cz, tables, prune_cap, rung
        )
    )
