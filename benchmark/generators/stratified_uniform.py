"""``{"kind": "stratified_uniform", "low": a, "high": b}``: floats in [a, b),
the n values of a row (the last axis) STRATIFIED over it: one in each of the
n equal parts of [a, b), uniform inside its part, the parts dealt to the
row's places in a seeded order.  A row's mean is then (a + b) / 2 to within
(b - a) / (2 n) whatever the seed, where n independent draws would move it by
(b - a) / (12 n)^(1/2): the noise levels of a sequence's blocks, whose sum
decides how many of its tokens are masked."""

import numpy as np


def host(rng, shape, dtype, gen, dims, made):
    low, high = float(gen["low"]), float(gen["high"])
    n, rows = shape[-1], int(np.prod(shape[:-1]))
    # argsort of random keys: a permutation a row
    part = np.argsort(rng.random_sample((rows, n)), axis=1)
    inside = rng.random_sample((rows, n))
    return (low + (high - low) * (part + inside) / n).reshape(shape).astype(
        dtype)
