"""Padded-CSR (ELL) sparse batches — the TPU layout for wide sparse features.

Reference: ``SparseVector.java`` + the sparse branches of ``BLAS.java:30-179``
(per-row index/value loops). On a TPU the per-row loop is replaced by two
static-shaped arrays covering the whole batch:

  ``indices [n, K] int32``, ``values [n, K] float32``

with ``K`` the max row nnz padded up (lane-aligned); padding slots carry
``index 0 / value 0.0`` so they contribute exactly zero to any dot or
gradient without masking. This keeps shapes static for XLA, makes the
forward pass a gather + row-sum (``values * coef[indices]``) and the
gradient a scatter-add — both batched, both compiled — instead of
dynamic-shape CSR, which XLA cannot tile.

The memory win is the point: a Criteo-class batch (n rows × 10^6+ dim,
tens of nnz per row) is ``n*K`` floats here vs ``n*dim`` densified.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu.linalg.vectors import SparseVector, Vector

__all__ = ["SparseBatch", "ladder_cap", "place_rows"]

_LANE = 8  # pad K to a multiple of this (TPU sublane-friendly)


def ladder_cap(max_nnz: int) -> int:
    """The nnz-per-row bucket ladder of the sparse fast path: the smallest
    power of two ≥ ``max_nnz`` (floor 1). Mirrors the dense serving buckets
    (power-of-two row counts): every ragged batch pads its row width K up to
    a ladder cap, so the compiled-executable set is ≤ 1 per (row bucket,
    nnz cap) instead of one per max-row-length seen (docs/sparse.md)."""
    cap = 1
    while cap < max(1, int(max_nnz)):
        cap *= 2
    return cap


def place_rows(
    flat_indices: np.ndarray, flat_values: np.ndarray, nnz: np.ndarray, K: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Rows laid end to end (``nnz[i]`` entries each, int32 / float32) into
    the zero-padded ``[n, K]`` pair, and the stored counts. A row longer than
    ``K`` keeps its leading ``K`` entries."""
    if nnz.size and nnz.max() > K:
        starts = np.cumsum(nnz) - nnz
        keep = np.arange(flat_indices.size) - np.repeat(starts, nnz) < K
        flat_indices, flat_values = flat_indices[keep], flat_values[keep]
        nnz = np.minimum(nnz, np.int32(K))
    stored = np.arange(K, dtype=np.int32) < nnz[:, None]  # row-major, as the flats are
    indices = np.zeros((nnz.size, K), np.int32)
    values = np.zeros((nnz.size, K), np.float32)
    indices[stored] = flat_indices
    values[stored] = flat_values
    return indices, values, nnz


class SparseBatch:
    """A batch of sparse rows in padded-CSR layout.

    ``dim`` is the feature width; ``indices``/``values`` are [n, K] with
    zero-index/zero-value padding.
    """

    __slots__ = ("dim", "indices", "values", "nnz")

    def __init__(
        self,
        dim: int,
        indices: np.ndarray,
        values: np.ndarray,
        nnz: Optional[np.ndarray] = None,
    ):
        indices = np.asarray(indices, np.int32)
        values = np.asarray(values, np.float32)
        if indices.shape != values.shape or indices.ndim != 2:
            raise ValueError(
                f"indices/values must be matching [n, K] arrays, got "
                f"{indices.shape} vs {values.shape}"
            )
        self.dim = int(dim)
        self.indices = indices
        self.values = values
        # Per-row stored-entry counts: lets row() round-trip explicit zeros
        # (which are indistinguishable from padding by value alone).
        if nnz is not None:
            nnz = np.asarray(nnz, np.int32)
            if nnz.shape != (indices.shape[0],):
                raise ValueError(
                    f"nnz must be [n={indices.shape[0]}], got {nnz.shape}"
                )
            if nnz.size and (nnz.min() < 0 or nnz.max() > indices.shape[1]):
                raise ValueError(
                    f"nnz entries must be in [0, K={indices.shape[1]}]"
                )
        self.nnz = nnz

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def width(self) -> int:
        return self.indices.shape[1]

    @classmethod
    def from_vectors(
        cls,
        vectors: Sequence[Vector],
        dim: Optional[int] = None,
        pad_to: int = _LANE,
        width: Optional[int] = None,
        truncate: bool = False,
    ) -> "SparseBatch":
        """Pack SparseVectors (ref SparseVector.java invariants) into one batch.

        The column is packed by whole-column numpy: the row objects are
        touched once to collect their arrays, and everything after that is
        one concatenate and one masked placement per output, with the casts
        a per-row assignment would make (to int32 / float32, unchecked).
        ``width`` forces K (the serving tier's nnz-cap rung); rows longer
        than it are an error unless ``truncate`` clips them."""
        n = len(vectors)
        if not n:
            raise ValueError("empty batch")
        dims = {v.n for v in vectors}
        if dim is None:
            if len(dims) != 1:
                raise ValueError(f"inconsistent vector sizes {dims}")
            (dim,) = dims
        elif any(s != dim for s in dims):
            raise ValueError(f"vector sizes {dims} != requested dim {dim}")
        row_indices = [v.indices for v in vectors]
        row_values = [v.values for v in vectors]
        nnz = np.fromiter(map(len, row_indices), np.int32, n)
        max_nnz = int(nnz.max())
        if width is None:
            K = -(-max(1, max_nnz) // pad_to) * pad_to
        else:
            K = int(width)
            if max_nnz > K and not truncate:
                raise ValueError(f"rows carry up to {max_nnz} entries > forced width {K}")
        total = int(nnz.sum())
        flat_indices = np.concatenate(
            row_indices, out=np.empty(total, np.int32), casting="unsafe"
        )
        flat_values = np.concatenate(
            row_values, out=np.empty(total, np.float32), casting="unsafe"
        )
        indices, values, nnz = place_rows(flat_indices, flat_values, nnz, K)
        return cls(dim, indices, values, nnz=nnz)

    def row(self, i: int) -> SparseVector:
        if self.nnz is not None:  # exact round-trip, explicit zeros included
            k = int(self.nnz[i])
            return SparseVector(self.dim, self.indices[i, :k], self.values[i, :k])
        nz = self.values[i] != 0.0
        return SparseVector(self.dim, self.indices[i][nz], self.values[i][nz])

    def densify(self) -> np.ndarray:
        """[n, dim] dense array — test/debug only; defeats the layout's purpose."""
        out = np.zeros((self.n, self.dim), np.float32)
        rows = np.repeat(np.arange(self.n), self.width)
        np.add.at(out, (rows, self.indices.ravel()), self.values.ravel())
        return out

    def __repr__(self) -> str:
        return f"SparseBatch(n={self.n}, dim={self.dim}, width={self.width})"
