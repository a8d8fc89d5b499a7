"""``{"kind": "uniform"}``: floats uniform in [``low``, ``high``) (0 and 1
where not given), each drawn on its own."""


def host(rng, shape, dtype, gen, dims, made):
    low, high = gen.get("low", 0.0), gen.get("high", 1.0)
    return (low + (high - low) * rng.random_sample(shape)).astype(dtype)
