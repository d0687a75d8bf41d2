"""Operations and bytes a kernel needs for one call, from its shapes.

``onehot_crossing_premat`` is ``bench.py::_crossing_roofline``'s arithmetic
for the two one-hot crossings of one SGD step (dot and mult) in the
materialized form: each crossing contracts ``n_flat`` entries against the
``sub_batch = row_hi x 128`` rows of a sub-batch as two split-bf16 halves of
2 flops per multiply-add, and streams that sub-batch's bf16 one-hots
(``row_hi + 128`` wide per entry) from HBM, with ``q`` in / ``u`` out (f32 per
entry) and the ``[row_hi, 128]`` f32 result. The kernel's own padding of the
entry axis is not counted: it is not work the algorithm needs.

``model`` is what one SGD step of the one-hot path needs on the MXU: its two
crossings. The rounds around them (the per-entry coefficient read, the sums
into the gradient, the update) are selects, sums and element-wise work and
count nothing, as an LM step's element-wise work counts nothing.
"""


def onehot_crossing_premat(n_sub, n_flat, sub_batch, row_hi, **_):
    flops = 8.0 * n_sub * n_flat * sub_batch
    nbytes = n_sub * (2.0 * n_flat * (row_hi + 128) * 2 + 2.0 * n_flat * 4 + 2.0 * sub_batch * 4)
    return flops, nbytes


model = onehot_crossing_premat
