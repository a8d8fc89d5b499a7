"""The one general batch generator: every field of a batch is a row of
data in the configuration file (name, shape in the cell's symbols, dtype,
generator), and every batch follows from ``--seed``.

A generator kind is a file of its own, ``benchmark/generators/<kind>.py``,
found by the name in the field's ``gen.kind``; a new kind of traffic (Zipf
ids, packed lengths) is a new file there.  It gives

    host(rng, shape, dtype, gen, dims, made) -> numpy array

(``rng`` a ``numpy.random.RandomState`` of the seed and the batch's index,
``made`` the batch's fields made so far, by name) and, where the field can
be made on the device,

    device(key, shape, dtype, gen, dims) -> jax array

which runs inside one jitted call, straight into the field's sharding
(images are 38 MB a batch: 25 of them through ``rng.rand`` on the host
would be most of a run's set-up).
"""

import numpy as np

from . import manifest as mf


def resolve_shape(shape, dims):
    return tuple(int(dims[s] if isinstance(s, str) else s) for s in shape)


def host_batch(fields, dims, seed, index, feed=False):
    """Batch ``index`` of the seed's stream, as numpy arrays.  ``feed``
    makes each field in its ``feed_dtype`` where it has one (what a user's
    loader hands over, e.g. uint8 pixels)."""
    rng = np.random.RandomState((int(seed) * 1000003 + int(index)) % 2 ** 32)
    made = {}
    for f in fields:
        dtype = f.get("feed_dtype", f["dtype"]) if feed else f["dtype"]
        made[f["name"]] = mf.module("generators", f["gen"]["kind"]).host(
            rng, resolve_shape(f["shape"], dims), _np_dtype(dtype), f["gen"],
            dims, made)
    return made


def _np_dtype(name):
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(name)


def device_staged(field, dims, seed, n, sharding):
    """``n`` batches of one field, made on the device in one jitted call
    with the leading step axis, in the field's dtype."""
    import jax
    import jax.numpy as jnp

    gen = mf.module("generators", field["gen"]["kind"])
    if not hasattr(gen, "device"):
        raise ValueError("field %r: generator %r is not made on the device"
                         % (field["name"], field["gen"]["kind"]))
    shape = (n,) + resolve_shape(field["shape"], dims)
    dtype = jnp.dtype(field["dtype"])

    def make(key):
        return gen.device(key, shape, dtype, field["gen"], dims)

    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))
