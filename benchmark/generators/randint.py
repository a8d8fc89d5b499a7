"""``{"kind": "randint", "low": a, "high": b}``: integers in [a, b)."""


def host(rng, shape, dtype, gen, dims, made):
    return rng.randint(gen["low"], gen["high"], shape).astype(dtype)


def device(key, shape, dtype, gen, dims):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(key, shape, gen["low"], gen["high"],
                              jnp.int32).astype(dtype)
