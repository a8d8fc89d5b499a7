"""``{"kind": "k_hot", "k": k}``: 0/1 rows with exactly ``k`` ones along
the last axis (``k`` may be a symbol of the cell's dims)."""

import numpy as np


def host(rng, shape, dtype, gen, dims, made):
    k = dims[gen["k"]] if isinstance(gen["k"], str) else gen["k"]
    n, rows = shape[-1], int(np.prod(shape[:-1]))
    # the k smallest of n random keys: k distinct positions per row
    idx = np.argpartition(rng.rand(rows, n), k - 1, axis=1)[:, :k]
    out = np.zeros((rows, n), dtype)
    np.put_along_axis(out, idx, 1, axis=1)
    return out.reshape(shape)
