"""``{"kind": "masked_copy", "of": f, "mask": m, "fill": v}``: a copy of
field ``f`` with ``v`` where field ``m`` is non-zero (MLM inputs: the
original token everywhere but at the predicted positions).  Both fields
come earlier in the configuration's list."""

import numpy as np


def host(rng, shape, dtype, gen, dims, made):
    return np.where(made[gen["mask"]] != 0, gen["fill"],
                    made[gen["of"]]).astype(dtype)
