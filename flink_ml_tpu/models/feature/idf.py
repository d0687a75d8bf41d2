"""IDF.

Reference: ``flink-ml-lib/.../feature/idf/IDF.java`` — fit: document frequency per
term dimension; idf[i] = log((numDocs + 1)/(df[i] + 1)), dims with df < minDocFreq
get idf 0; transform multiplies term-frequency vectors elementwise by idf.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from flink_ml_tpu.api.core import Estimator, Model
from flink_ml_tpu.api.types import BasicType, DataTypes
from flink_ml_tpu.linalg.vectors import SparseVector, Vector
from flink_ml_tpu.models.common import ModelArraysMixin
from flink_ml_tpu.ops.kernels import (
    idf_scale_fn,
    idf_scale_kernel,
    sparse_idf_scale_fn,
    sparse_idf_scale_kernel,
)
from flink_ml_tpu.params.param import IntParam, ParamValidators, update_existing_params
from flink_ml_tpu.params.shared import HasInputCol, HasOutputCol
from flink_ml_tpu.servable.kernel_spec import KernelSpec
from flink_ml_tpu.servable.sparse import sparse_names

__all__ = ["IDF", "IDFModel"]


class _IDFParams(HasInputCol, HasOutputCol):
    MIN_DOC_FREQ = IntParam(
        "minDocFreq",
        "Minimum number of documents that a term should appear for filtering.",
        0,
        ParamValidators.gt_eq(0),
    )

    def get_min_doc_freq(self) -> int:
        return self.get(self.MIN_DOC_FREQ)

    def set_min_doc_freq(self, value: int):
        return self.set(self.MIN_DOC_FREQ, value)


class IDFModel(ModelArraysMixin, Model, _IDFParams):
    """Ref IDFModel.java."""

    _MODEL_ARRAY_NAMES = ("idf", "doc_freq", "num_docs")

    def __init__(self):
        super().__init__()
        self.idf: Optional[np.ndarray] = None
        self.doc_freq: Optional[np.ndarray] = None
        self.num_docs: Optional[np.ndarray] = None

    @classmethod
    def load_servable(cls, path: str) -> "IDFModel":
        """The fitted model is its own runtime-free replica (state = the idf
        vector; ``transform`` is one jitted kernel) — published text
        pipelines load it directly on the serving tier (docs/sparse.md)."""
        return cls.load(path)

    def transform(self, *inputs):
        (df,) = inputs
        in_col = self.get_input_col()
        col = df.column(in_col)
        out = df.clone()
        if len(df) == 0:
            # An empty column normalizes to a shapeless (0,) array — nothing
            # to scale, and the kernels cannot infer a width from it.
            out.add_column(self.get_output_col(), DataTypes.vector(BasicType.DOUBLE), [])
            return out
        if isinstance(col, np.ndarray):
            vals = idf_scale_kernel()(col.astype(np.float64), self.idf)
            out.add_column(
                self.get_output_col(),
                DataTypes.vector(BasicType.DOUBLE),
                np.asarray(vals, np.float64),
            )
        elif df.is_sparse(in_col):
            # Sparse path: one batched gather-scale kernel over the padded-CSR
            # layout — the SAME ``sparse_idf_scale`` body the fused sparse
            # spec composes, so the two paths agree bit for bit (per-entry
            # f32 multiply, widened to the f64 storage dtype).
            batch = df.sparse_batch(in_col)
            vals = np.asarray(
                sparse_idf_scale_kernel()(
                    batch.values, batch.indices, np.asarray(self.idf, np.float32)
                ),
                np.float64,
            )
            new_col = []
            for i, v in enumerate(col):
                k = len(v.indices) if isinstance(v, SparseVector) else int(batch.nnz[i])
                new_col.append(
                    SparseVector(batch.dim, batch.indices[i, :k].astype(np.int64), vals[i, :k])
                )
            out.add_column(self.get_output_col(), DataTypes.vector(BasicType.DOUBLE), new_col)
        else:
            new_col = [
                SparseVector(v.size(), v.indices, v.values * self.idf[v.indices])
                if isinstance(v, SparseVector)
                else v.to_array() * self.idf
                for v in col
            ]
            out.add_column(self.get_output_col(), DataTypes.vector(BasicType.DOUBLE), new_col)
        return out

    def sparse_kernel_spec(self, known):
        """Sparse-convention spec (docs/sparse.md): when the input column is
        statically known sparse, idf scaling fuses as a per-entry
        gather-scale (``sparse_idf_scale_fn`` — the body the per-stage sparse
        path jits), structure (ids/nnz) passing through unchanged. No
        cross-entry accumulation, so the spec is elementwise and merges
        bit-exactly. (``sparse_idf`` is NOT in the megakernel vocabulary:
        Mosaic does not lower the table gather.)"""
        if self.idf is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        in_col, out_col = self.get_input_col(), self.get_output_col()
        dim = int(len(self.idf))
        if known.get(in_col) != dim:
            return None  # not sparse here (or a dim-mismatched model): dense spec
        in_v, in_i, in_z = sparse_names(in_col)
        out_v, out_i, out_z = sparse_names(out_col)

        def kernel_fn(model, cols):
            return {
                out_v: sparse_idf_scale_fn(cols[in_v], cols[in_i], model["idf"]),
                out_i: cols[in_i],
                out_z: cols[in_z],
            }

        return KernelSpec(
            input_cols=(in_col,),
            outputs=((out_col, DataTypes.vector(BasicType.DOUBLE)),),
            model_arrays={"idf": np.asarray(self.idf, np.float32)},
            kernel_fn=kernel_fn,
            input_kinds={in_col: "sparse"},
            sparse_outputs={out_col: dim},
            sparse_input_dims={in_col: dim},
            elementwise=True,  # per-entry gather + multiply: no accumulation
            fusion_op="sparse_idf",  # table gather: merged XLA only
        )

    def kernel_spec(self):
        """idf scaling as a fusable spec — ``idf_scale_fn``, the body
        ``transform``'s jitted kernel wraps, with the idf vector as a
        committed device buffer. Sparse columns stay per-stage (sparsity
        preserved there), so the input ingests as ``dense``."""
        if self.idf is None:
            raise RuntimeError("set_model_data must be called before kernel_spec")
        in_col, out_col = self.get_input_col(), self.get_output_col()

        def kernel_fn(model, cols):
            return {out_col: idf_scale_fn(cols[in_col], model["idf"])}

        return KernelSpec(
            input_cols=(in_col,),
            outputs=((out_col, DataTypes.vector(BasicType.DOUBLE)),),
            model_arrays={"idf": np.asarray(self.idf, np.float32)},
            kernel_fn=kernel_fn,
            input_kinds={in_col: "dense"},
            elementwise=True,  # per-term scaling: no FP accumulation
            fusion_op="idf",  # megakernel-safe
        )


class IDF(Estimator, _IDFParams):
    """Ref IDF.java."""

    def fit(self, *inputs) -> IDFModel:
        (df,) = inputs
        col = df.column(self.get_input_col())
        if isinstance(col, np.ndarray):
            docs = col.astype(np.float64)
            doc_freq = (docs != 0).sum(axis=0).astype(np.float64)
            num_docs = docs.shape[0]
        else:
            dim = col[0].size() if isinstance(col[0], Vector) else len(col[0])
            doc_freq = np.zeros(dim)
            for v in col:
                if isinstance(v, SparseVector):
                    doc_freq[v.indices[v.values != 0]] += 1
                else:
                    doc_freq[np.asarray(v.to_array()) != 0] += 1
            num_docs = len(col)
        min_df = self.get_min_doc_freq()
        idf = np.where(
            doc_freq >= min_df, np.log((num_docs + 1.0) / (doc_freq + 1.0)), 0.0
        )
        model = IDFModel()
        update_existing_params(model, self)
        model.idf = idf
        model.doc_freq = doc_freq
        model.num_docs = np.asarray([num_docs])
        return model
