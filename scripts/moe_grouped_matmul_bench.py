"""Which primitive carries the dropless MoE's grouped matmuls, measured on
the chip at the OLMoE cell's shape (one layer: 16,384 tokens, top-8 of 64
experts, E = 2048, F = 1024, bf16):

    chiprun -- python3 scripts/moe_grouped_matmul_bench.py [out.json]

Times, forward + backward (``jax.grad`` of a sum, so dX and dW of both
matmuls), host clock around ``block_until_ready`` over REPS calls after a
warm-up:

- ``experts.ragged_dot`` / ``experts.megablox_gmm`` / ``experts.as_shipped``:
  the expert FFN alone on rows already sorted by expert (gate/up grouped
  matmul, SiLU gate, down grouped matmul), by ``jax.lax.ragged_dot``, by the
  Pallas ``gmm`` under ``jax.experimental.pallas.ops.tpu.megablox`` (tilings
  tried: TILINGS) and by ``moe._grouped_matmul``, the one the program ships;
- ``layer.gather_backward`` / ``layer.scatter_backward``: the whole layer
  (``moe.dropless_moe_ffn``: router, sort, dispatch, experts, combine) as the
  program ships it, and with the dispatch and combine left to autodiff,
  whose transposes are scatter-adds;
- ``layer.remat``: the layer under ``jax.checkpoint``, as the cell runs it
  (``transformer.run_layers``): forward, the forward again as far as the
  backward needs it, backward.

Before any time is taken it holds the shipped primitive to
``jax.lax.ragged_dot`` at that shape and the real 512 x 1024 x 1024 tiling
(``equal``: the expert FFN's output and its three gradients against a random
cotangent, the relative error of each GROUP's rows or weights, so that a
kernel that lost one expert's rows cannot hide in the mean) and exits 1
where a group is off by more than EQUAL_TOLERANCE.  Both accumulate in
float32 and round to bf16 once, so they differ in a few last bits only:
the worst group read 1.1e-4 (out) to 1.4e-4 (d_gate_up) on the v5e (PR 27;
groups of 1,931 to 2,178 rows), and the bound is ten times that; a lost or
doubled row tile moves a group by tens of per cent.

Off a TPU it exits 2: a CPU time is not a device time."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T, K, N, E, F = 16384, 8, 64, 2048, 1024
REPS = 5
TILINGS = ((128, 128, 128), (512, 1024, 1024), (512, 512, 1024))
EQUAL_TOLERANCE = 2e-3


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS * 1e3


def _experts(matmul):
    def ffn(rows, w_gate_up, w_down, sizes):
        gate, up = jnp.split(matmul(rows, w_gate_up, sizes), 2, axis=-1)
        return matmul(jax.nn.silu(gate) * up, w_down, sizes)

    def loss(rows, w_gate_up, w_down, sizes):
        return jnp.sum(ffn(rows, w_gate_up, w_down, sizes).astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _ragged_dot(a, b, s):
    return jax.lax.ragged_dot(a, b, s, preferred_element_type=a.dtype)


def _equal(matmul, rows, w_gate_up, w_down, sizes, probe):
    """Largest relative error over the groups, of the expert FFN by
    ``matmul`` against the same by ``ragged_dot``: ``out`` and ``d_rows`` by
    the rows of each group, ``d_gate_up`` and ``d_down`` by expert."""
    group = jnp.repeat(jnp.arange(N), sizes, total_repeat_length=T * K)

    def both(matmul):
        def ffn(rows, w_gate_up, w_down):
            gate, up = jnp.split(matmul(rows, w_gate_up, sizes), 2, axis=-1)
            return matmul(jax.nn.silu(gate) * up, w_down, sizes)

        def run(rows, w_gate_up, w_down):
            out, vjp = jax.vjp(ffn, rows, w_gate_up, w_down)
            return (out,) + vjp(probe)

        return jax.jit(run)(rows, w_gate_up, w_down)

    def by_group(a, per_row):
        sq = jnp.sum(jnp.square(a.astype(jnp.float32)),
                     axis=tuple(range(1, a.ndim)))
        return jax.ops.segment_sum(sq, group, N) if per_row else sq

    worst = {}
    for name, a, b in zip(("out", "d_rows", "d_gate_up", "d_down"),
                          both(matmul), both(_ragged_dot)):
        per_row = a.shape[0] == T * K
        err = jnp.sqrt(by_group(a.astype(jnp.float32) - b.astype(jnp.float32),
                                per_row)
                       / jnp.maximum(by_group(b, per_row), 1e-30))
        worst[name] = float(jnp.max(err))
    return worst


def main(out_path=None):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("moe_grouped_matmul_bench: needs a TPU, found %s" % dev.platform,
              file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from paddle_tpu.parallel import moe

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = moe.init_dropless_moe_params(ks[0], N, E, F, jnp.bfloat16)
    x = jax.random.normal(ks[1], (T, E), jnp.float32).astype(jnp.bfloat16)
    rows = jax.random.normal(ks[2], (T * K, E), jnp.float32).astype(jnp.bfloat16)
    expert = jax.random.randint(ks[3], (T * K,), 0, N)
    sizes = jnp.bincount(expert, length=N).astype(jnp.int32)
    got = {"device_kind": dev.device_kind, "shape": dict(T=T, K=K, N=N, E=E, F=F),
           "reps": REPS, "ms": {}}

    def note(name, fn, *args):
        try:
            got["ms"][name] = _time(fn, *args)
        except Exception as e:          # a tiling the kernel refuses
            got["ms"][name] = "failed: %s" % (str(e).splitlines() or [""])[0][:200]
        print(name, got["ms"][name], flush=True)

    w = (params["we_gate_up"], params["we_down"])
    probe = jax.random.normal(ks[4], (T * K, E), jnp.float32).astype(jnp.bfloat16)
    got["equal"] = _equal(moe._grouped_matmul, rows, *w, sizes, probe)
    got["equal"]["rows_in_smallest_and_largest_group"] = [
        int(jnp.min(sizes)), int(jnp.max(sizes))]
    print("equal", got["equal"], flush=True)
    equal = all(v <= EQUAL_TOLERANCE for v in got["equal"].values()
                if isinstance(v, float))         # a nan is not equal
    note("experts.ragged_dot", _experts(_ragged_dot), rows, *w, sizes)
    note("experts.as_shipped", _experts(moe._grouped_matmul), rows, *w, sizes)
    for tiling in TILINGS:
        note("experts.megablox_gmm.%dx%dx%d" % tiling, _experts(
            lambda a, b, s, tiling=tiling: megablox.gmm(
                a, b, s, preferred_element_type=a.dtype, tiling=tiling)),
            rows, *w, sizes)

    def layer(p, x):
        return moe.dropless_moe_ffn(p, x, K)[0]

    def layer_grad():
        return jax.jit(jax.grad(lambda p, x: jnp.sum(
            layer(p, x).astype(jnp.float32)), argnums=(0, 1)))

    def remat(p, x, g):
        # the result is kept, so the first forward runs whole
        y, vjp = jax.vjp(jax.checkpoint(layer), p, x)
        return y, vjp(g)

    note("layer.gather_backward", layer_grad(), params, x)
    note("layer.remat", jax.jit(remat), params, x, probe[:T])
    note("layer.forward", jax.jit(layer), params, x)
    keep = moe._dispatch, moe._combine
    # plain gathers, no custom_vjp (the shipped sum back is a Pallas kernel,
    # which autodiff cannot transpose)
    moe._dispatch = lambda x, order, inv, k: x[order // k]
    moe._combine = lambda rows, order, inv, k: jnp.sum(
        rows[inv].reshape(-1, k, rows.shape[1]).astype(jnp.float32),
        axis=1).astype(rows.dtype)
    try:
        note("layer.scatter_backward", layer_grad(), params, x)
    finally:
        moe._dispatch, moe._combine = keep
    flops = 3 * K * 6.0 * E * F * T
    got["required_tflop_fwd_bwd"] = flops / 1e12
    got["least_ms_at_197_tflops"] = flops / 197e12 * 1e3
    print(json.dumps(got), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(got, f)
    if not equal:
        print("moe_grouped_matmul_bench: the shipped grouped matmul differs "
              "from ragged_dot: %s" % got["equal"], file=sys.stderr)
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
