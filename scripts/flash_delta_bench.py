"""The flash backward's ``delta = sum_d(o * do)``, measured on the chip: the
Pallas row kernel (``kernels.flash_delta.flash_delta``) against the ``jnp``
lines it replaces in ``flash_attention._bwd`` (XLA), at the eight decoder
cells' ``(B, S, H, D)`` (bf16):

    chiprun -- python3 scripts/flash_delta_bench.py [out.json]
        [--rows 128,64] [--block-mib 48]

For each shape, the result compared FIRST (both against the same lines in
float64 on the host; exit 1 where the kernel is further from it than twice
the lines' own float32 sum and over 1e-4), then device microseconds a call
of everything the device ran, off a profiler trace: the lines' (XLA's
fusions; standalone, so WITHOUT the float32 product's ride on ``wo``'s
matmul and with XLA's own choice of the result's layout: read the step's
traces for what the chain costs a layer) and the kernel's by name, beside
the least the bytes allow (o and do read, the lane-padded statistic
written: 512 bytes a number at a head a lane block; 819 GB/s).  ``--rows``
replaces ``kernels.flash_delta.ROW_BLOCKS`` and ``--block-mib`` its
``BLOCK_VMEM`` for a compile (this script's experiments; the program has no
such option).  Off a TPU it exits 2 (a CPU
time is not a device time)."""

import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: (batch, positions, query heads, head width)
SHAPES = {
    "smallthinker": (1, 16384, 28, 128),
    "trinity": (1, 6144, 48, 128),
    "mistral4": (1, 16384, 32, 128),
    "nemotron": (2, 8192, 32, 128),
    "olmoe": (4, 4096, 16, 128),
    "ouro": (2, 4096, 16, 128),
    "lfm2": (2, 8192, 32, 64),
    "jamba": (1, 8192, 20, 128),
}
HBM = 819e9
ITERS = 10


def device_us(fn, args):
    """(microseconds a call of everything on the device, {name: us})."""
    from benchmark.harness import trace_reduce, tracing

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        tracing._start(tmp, 0)
        for _ in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp))).devices[0]
    names = {name: t / ITERS / 1e3 for name, t in dev["by_name"].items()}
    return sum(names.values()), names


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    from paddle_tpu.kernels import flash_delta as K

    out_path = next((a for a in argv if a.endswith(".json")), None)
    if "--rows" in argv:
        K.ROW_BLOCKS = tuple(
            int(r) for r in argv[argv.index("--rows") + 1].split(","))
    if "--block-mib" in argv:
        K.BLOCK_VMEM = int(argv[argv.index("--block-mib") + 1]) * 2 ** 20
    report, bad = {}, False
    for name, (B, S, H, D) in SHAPES.items():
        W, hpb = H * D, max(1, 128 // D)
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 2)
        o = jax.random.normal(keys[0], (B, S, W), jnp.bfloat16)
        do = jax.random.normal(keys[1], (B, S, W), jnp.bfloat16)
        lines = jax.jit(lambda o, do: K.flash_delta_reference(o, do, D))
        fused = jax.jit(lambda o, do: K.flash_delta(o, do, head_dim=D))
        true = (np.asarray(o, np.float64) * np.asarray(do, np.float64)) \
            .reshape(B, S, W // (hpb * D), hpb, D).sum(-1) \
            .transpose(0, 2, 1, 3)
        row = {"rows": K.block_rows(S, W, 2),
               "err_xla": float(np.abs(np.asarray(lines(o, do)) - true).max()),
               "err": float(np.abs(np.asarray(fused(o, do)) - true).max())}
        row["xla_us"], row["xla_by_name"] = device_us(lines, (o, do))
        row["kernel_us"], row["by_name"] = device_us(fused, (o, do))
        row["least_us"] = (2 * B * S * W * 2
                           + B * S * (W // 128) * 512) / HBM * 1e6
        bad |= row["err"] > max(2 * row["err_xla"], 1e-4)
        report[name] = row
        print(name, json.dumps(row), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return int(bad)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
