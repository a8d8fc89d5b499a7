"""The selective-scan kernels compiled by Mosaic at the jamba cell's shapes
([1, 8192, 5120] channels of 16 state cells, bf16 x and z, float32 step
sizes; compared over the first 4,096 tokens) against the per-token
``lax.scan`` in float32 (``kernels/selective_scan.py:
selective_scan_reference``, whose gradient holds the ``[S, d, N]`` state in
HBM three times over: 4 GB at 4,096): the output and the seven gradients, with step sizes drawn as the cell's weights seed them
(log-uniform in [1e-3, 1e-1], rates -1..-16: a cell's decay runs from 0.9999
to 0.2 a token) and with every step a tenth of that (a state that still
weighs ten thousand tokens on, carried over every chunk edge).  What the
cell's ``correct`` cannot see (PERF.md section 7): the backward.

    chiprun -- python3 scripts/jamba_kernels_receipt.py [out.json] [chunk ...]

Each reading is ``|program - reference| / |reference|``; the limit is 2e-2
(bf16 x and z: 2^-8 a value) on every one, and a fault control (the state
dropped at chunk edges, put into the reference) has to read over it.  Also
times the forward and the forward with its backward at each chunk length
given (default 64 and 128): the host's clock around ``block_until_ready``,
the mean of ``CALLS`` calls after a warm one.  Exit 1 where a reading is
off, 2 off a TPU."""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import selective_scan as ss  # noqa: E402

S, D, N = 8192, 5120, 16
S_COMPARED = 4096       # the reference's gradient holds [S, d, N] three times
LIMIT = 2e-2
CALLS = 5
NAMES = ("x", "dt", "B", "C", "z", "a", "D")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def operands(seed, dt_scale, S=S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.nn.silu(jax.random.normal(ks[0], (1, S, D))).astype(jnp.bfloat16)
    z = jax.random.normal(ks[1], (1, S, D)).astype(jnp.bfloat16)
    dt = dt_scale * jnp.exp(jax.random.uniform(
        ks[2], (1, S, D), minval=np.log(1e-3), maxval=np.log(1e-1)))
    bmat = jax.random.normal(ks[3], (1, S, N))
    cmat = jax.random.normal(ks[4], (1, S, N))
    a = -jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (D, 1))
    return (x, dt, bmat, cmat, z, a, jnp.ones((D,), jnp.float32)), \
        jax.random.normal(ks[5], (1, S, D))


def dropped_at_edges(args, chunk, S=S_COMPARED):
    """The reference with the state dropped at every chunk edge."""
    x, dt, bmat, cmat, z, a, dskip = args
    parts = [ss.selective_scan_reference(
        x[:, at:at + chunk], dt[:, at:at + chunk], bmat[:, at:at + chunk],
        cmat[:, at:at + chunk], z[:, at:at + chunk], a, dskip)
        for at in range(0, S, chunk)]
    return jnp.concatenate(parts, axis=1)


def main(out_path=None, *chunks):
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU")
        return 2
    chunks = [int(c) for c in chunks] or [64, 128]
    out = {"device_kind": jax.devices()[0].device_kind, "readings": {},
           "seconds": {}}
    ok = True
    for label, scale in (("seeded", 1.0), ("slow_decay", 0.1)):
        args, w = operands(11, scale, S_COMPARED)

        def loss(fn):
            return lambda *a_: jnp.sum(fn(*a_).astype(jnp.float32) * w)

        want = jax.jit(ss.selective_scan_reference)(*args)
        want_g = jax.jit(jax.grad(loss(ss.selective_scan_reference),
                                  argnums=tuple(range(7))))(*args)
        for chunk in chunks:
            run = jax.jit(lambda *a_: ss.selective_scan(*a_, chunk=chunk))
            got = run(*args)
            got_g = jax.jit(jax.grad(loss(run), argnums=tuple(range(7))))(
                *args)
            key = "%s.chunk%d" % (label, chunk)
            out["readings"][key + ".out"] = _rel(got, want)
            for name, g, wg in zip(NAMES, got_g, want_g):
                out["readings"]["%s.d%s" % (key, name)] = _rel(g, wg)
            del got_g
        if label == "slow_decay":
            out["control_state_dropped"] = _rel(
                got, jax.jit(dropped_at_edges, static_argnums=1)(
                    args, chunks[0]))
        del want_g
    for key, reading in out["readings"].items():
        print(key, reading, flush=True)
        ok = ok and reading <= LIMIT
    ok = ok and out["control_state_dropped"] > LIMIT
    print("control (state dropped at chunk edges):",
          out["control_state_dropped"], flush=True)
    args, w = operands(12, 1.0)
    for chunk in chunks:
        fwd = jax.jit(lambda *a_: ss.selective_scan(*a_, chunk=chunk))
        both = jax.jit(jax.grad(
            lambda *a_: jnp.sum(ss.selective_scan(*a_, chunk=chunk).astype(
                jnp.float32) * w), argnums=tuple(range(7))))
        took = {}
        for name, fn in (("forward", fwd), ("forward_and_backward", both)):
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(CALLS):
                jax.block_until_ready(fn(*args))
            took[name] = (time.perf_counter() - t0) / CALLS
        out["seconds"][str(chunk)] = took
        print("chunk", chunk, "host seconds a call:", took, flush=True)
    worst = max(out["readings"].items(), key=lambda kv: kv[1])
    out["worst"], out["ok"] = list(worst), bool(ok)
    print(json.dumps({k: v for k, v in out.items() if k != "readings"}))
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
