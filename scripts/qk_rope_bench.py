"""The q/k norm and rotary positions of a packed projection, measured on the
chip: the Pallas row kernel (``kernels.qk_rope.qk_rope``) against the lines
it replaces in ``transformer._qkv`` (``rope(rms_norm(...))``, XLA), forward
and backward, at the five decoders' q and k shapes (bf16), and its
``pairs`` convention against ``_latent_qkv``'s lines at Mistral-Small-4's
row block (``LATENT``: q ``[1, 1024, 32 x (64 + 64)]`` rotated and scaled
by position; k assembled from ``k_nope`` ``[1, 1024, 32 x 64]`` and the ONE
rotary key ``kr`` ``[1, 1024, 64]``: the kernel over the zero-padded heads
``[k_nope_i | 0]`` that ``_latent_columns``' matmul writes, against
``rope_pairs``, the broadcast and the concatenate):

    chiprun -- python3 scripts/qk_rope_bench.py [out.json] [--rows 64,128]

For each shape: device microseconds a call (forward + backward under
``jax.vjp``) of everything the device ran, off a profiler trace, for XLA's
path and for the kernel's (the angles' tables included), the two kernels'
own time by name, the least time the bytes allow (x read and written
forward; dy and x read, dx written backward; 819 GB/s), and the largest
difference of output and gradients from the SAME lines in float32 (the
kernel rounds once, XLA's path twice: ``err`` beside ``err_xla``).
``--rows`` replaces ``kernels.qk_rope.ROW_BLOCKS`` for a compile (this
script's experiment; the program has no such option).  Exit 1 where the
kernel is further from float32 than four times XLA's path (and over 2e-2);
off a TPU it exits 2 (a CPU time is not a device time)."""

import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (batch, positions, heads, head width, norm, rotary)
SHAPES = {
    "trinity.q": (1, 6144, 48, 128, "head", True),
    "trinity.k": (1, 6144, 8, 128, "head", True),
    "trinity.q.full": (1, 6144, 48, 128, "head", False),
    "olmoe.q": (4, 4096, 16, 128, "whole", True),
    "lfm2.q": (2, 8192, 32, 64, "head", True),
    "lfm2.k": (2, 8192, 8, 64, "head", True),
    "smallthinker.q": (1, 16384, 28, 128, None, True),
    "smallthinker.k": (1, 16384, 4, 128, None, True),
    "brumby.q": (1, 2048, 40, 128, "head", True),
    "brumby.k": (1, 2048, 8, 128, "head", True),
}
# Mistral-Small-4's row block: name: (batch, positions, heads, dn, dr)
LATENT = {"mistral4.q": (1, 1024, 32, 64, 64),
          "mistral4.k": (1, 1024, 32, 64, 64)}
FREQS, FACTOR, ORIGINAL_MAX = 1e4, 1.0, 512
HBM = 819e9
ITERS = 10


def reference(x, w, heads, dh, norm, rotary, first):
    """Today's lines of ``_qkv`` on one projection."""
    from paddle_tpu.parallel.transformer import rms_norm, rope

    b, S, W = x.shape
    if norm == "head":
        x = rms_norm(x.reshape(b, S, heads, dh), w, 1e-5).reshape(b, S, W)
    elif norm:
        x = rms_norm(x, w, 1e-5)
    return rope(x, heads, 1e4, first) if rotary else x


def kernel(x, w, heads, dh, norm, rotary, first):
    from paddle_tpu.kernels import qk_rope as K

    tables = K.angle_tables(x.shape[1], dh, 1e4, first) if rotary else None
    return K.qk_rope(x, w, tables, head_dim=dh, norm=norm, eps=1e-5)


def _latent_angles(S, dr, first):
    pos = jnp.arange(S, dtype=jnp.float32) + first
    freqs = FREQS ** (-jnp.arange(dr // 2, dtype=jnp.float32) / (dr // 2))
    scale = 2.2 * (1.0 + 0.1 * jnp.log1p(jnp.floor(pos / ORIGINAL_MAX)))
    return pos, freqs, scale


def latent_reference(name, x, kr, heads, dn, dr, first):
    """``_latent_qkv``'s lines on q (rotation of a head's last ``dr`` lanes,
    the scale by position) or on k (x = k_nope: the ONE rotated key behind
    every head's own lanes)."""
    from paddle_tpu.parallel.transformer import rope_pairs

    b, S, _ = x.shape
    pos, freqs, scale = _latent_angles(S, dr, first)
    ang = pos[:, None] * freqs[None]
    if name.endswith(".q"):
        q = x.astype(jnp.float32).reshape(b, S, heads, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rope_pairs(q[..., dn:], ang, FACTOR)],
            axis=-1) * scale[None, :, None, None]
        return q.astype(x.dtype).reshape(b, S, -1)
    kr = rope_pairs(kr.astype(jnp.float32)[:, :, None, :], ang, FACTOR)
    return jnp.concatenate(
        [x.reshape(b, S, heads, dn), jnp.broadcast_to(
            kr.astype(x.dtype), (b, S, heads, dr))], axis=-1).reshape(b, S, -1)


def latent_kernel(name, x, kr, heads, dn, dr, first):
    """The kernel's pass; k's x is the padded ``[k_nope_i | 0]`` heads."""
    from paddle_tpu.kernels import qk_rope as K

    S = x.shape[1]
    _, freqs, scale = _latent_angles(S, dr, first)
    if name.endswith(".q"):
        return K.qk_rope(x, None, K.pair_tables(S, freqs, dn + dr, first,
                                                FACTOR, scale),
                         head_dim=dn + dr, pairs=True)
    return K.qk_rope(
        x, None, K.pair_tables(S, freqs, dn + dr, first, FACTOR),
        head_dim=dn + dr, pairs=True,
        shared=jnp.pad(kr, ((0, 0), (0, 0), (dn, 0))))


def both(fn, *static):
    def f(x, w, first, g):
        out, vjp = jax.vjp(lambda x, w: fn(x, w, *static, first), x, w)
        return (out,) + vjp(g)
    return jax.jit(f)


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
    names = {}
    for name, t in dev["by_name"].items():
        key = re.search(r"qk_rope_(fwd|bwd)|$", name).group() or "other"
        names[key] = names.get(key, 0.0) + t / ITERS / 1e3
    return sum(names.values()), names


def worst(outs, refs):
    return max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(outs, refs) if a is not None)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    from paddle_tpu.kernels import qk_rope as K

    out_path = next((a for a in argv if a.endswith(".json")), None)
    if "--rows" in argv:
        K.ROW_BLOCKS = tuple(
            int(r) for r in argv[argv.index("--rows") + 1].split(","))
    report, bad = {}, False
    for name, (b, S, heads, dh, norm, rotary) in SHAPES.items():
        W = heads * dh
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
        x = jax.random.normal(keys[0], (b, S, W), jnp.float32) * 2
        w = None if not norm else 1 + 0.2 * jax.random.normal(
            keys[1], (dh if norm == "head" else W,), jnp.float32)
        g = jax.random.normal(keys[2], (b, S, W), jnp.float32)
        first = jnp.int32(3)
        static = (heads, dh, norm, rotary)
        exact = both(reference, *static)(x, w, first, g)
        args = (x.astype(jnp.bfloat16), w, first, g.astype(jnp.bfloat16))
        xla, fused = both(reference, *static), both(kernel, *static)
        row = {"rows": K.block_rows(S, W, 2),
               "err_xla": worst(xla(*args), exact),
               "err": worst(fused(*args), exact)}
        row["xla_us"], _ = device_us(xla, args)
        row["kernel_us"], row["by_name"] = device_us(fused, args)
        row["least_us"] = 5 * b * S * W * 2 / HBM * 1e6
        bad |= row["err"] > max(4 * row["err_xla"], 2e-2)
        report[name] = row
        print(name, json.dumps(row), flush=True)
    for name, (b, S, heads, dn, dr) in LATENT.items():
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        is_q = name.endswith(".q")
        nope = jax.random.normal(keys[0], (b, S, heads, dn + dr if is_q
                                           else dn), jnp.float32) * 2
        kr = jax.random.normal(keys[1], (b, S, dr), jnp.float32) * 2
        g = jax.random.normal(keys[2], (b, S, heads * (dn + dr)), jnp.float32)
        first = jnp.int32(3 * S)
        padded = nope if is_q else jnp.pad(
            nope, ((0, 0), (0, 0), (0, 0), (0, dr)))
        static = (heads, dn, dr)
        flat = lambda a: a.reshape(b, S, -1)
        lines = both(lambda x, kr, *a: latent_reference(name, x, kr, *a),
                     *static)
        fused = both(lambda x, kr, *a: latent_kernel(name, x, kr, *a),
                     *static)
        exact = lines(flat(nope), kr, first, g)
        bf = lambda a: a.astype(jnp.bfloat16)
        args_lines = (bf(flat(nope)), bf(kr), first, bf(g))
        args_fused = (bf(flat(padded)), bf(kr), first, bf(g))
        got = fused(*args_fused)
        if not is_q:        # dx of the padded heads: their own lanes
            got = (got[0], flat(got[1].reshape(b, S, heads, -1)[..., :dn]),
                   got[2])
        row = {"rows": K.block_rows(S, heads * (dn + dr), 2),
               "err_xla": worst(lines(*args_lines), exact),
               "err": worst(got, exact)}
        row["xla_us"], _ = device_us(lines, args_lines)
        row["kernel_us"], row["by_name"] = device_us(fused, args_fused)
        # q read and written each way; k: the padded heads read and the
        # keys written, dy read and dx written over it
        row["least_us"] = 4 * b * S * heads * (dn + dr) * 2 / HBM * 1e6
        bad |= row["err"] > max(4 * row["err_xla"], 2e-2)
        report[name] = row
        print(name, json.dumps(row), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return int(bad)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
