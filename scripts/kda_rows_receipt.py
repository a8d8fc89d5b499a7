"""The KDA mixer's three row kernels (``kernels/kda_rows.py``: ``l2_heads``,
``log_decay``, ``norm_gate``; Pallas, forward and backward) on the chip at
the Kimi-Linear cell's shape, [1, 16384, 32 x 128] in bf16 with the decays
and the gate's pre-activation float32, against the mixer's ``jnp`` lines
they replace (the ``*_reference`` functions, re-laid to the flat arrays and
rounded as ``kda_mixer`` rounds them):

    chiprun -- python3 scripts/kda_rows_receipt.py [out.json]
        [--geometry 512x1024x128,128x2048x256,...]

For each kernel, FIRST the output and every gradient (the parameters'
``d dt_bias``, ``d a_log`` and ``d o_norm`` among them) held to the lines
evaluated on float32 operands by the HOST (the CPU backend's ``exp`` and
``log1p``, not the chip's): the kernel may stand no further from them than
the lines on the chip do, by 5 % and 2e-5 (exit 1 otherwise); then device microseconds
a call off a profiler trace, the kernels' by name (``kda_<part>_fwd`` /
``_bwd``; ``log_decay``'s forward is XLA's own lines, in the step the
epilogue of a matmul: no kernel, no time here) and the lines' forward and
forward + backward programs whole, beside the least their bytes allow at
819 GB/s.  ``--geometry
lanes x rows x walk`` replaces ``kda_rows.geometry`` for a compile (this
script's experiment, where the shipped blocks were chosen: the program has
no such option).  One JSON, kept under ``chiprun_out/pr60/``.  Off a TPU it
exits 2 (a CPU time is not a device time)."""

import json
import math
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import kda_rows as K  # noqa: E402

B, S, H, D = 1, 16384, 32, 128
P = H * D
EPS = 1e-5
HBM = 819e9
ITERS = 10
F32 = jnp.float32
OUT = os.path.join(ROOT, "chiprun_out", "pr60", "kda_rows_receipt.json")
# bytes an element: (forward, backward) of what each kernel reads and writes
BYTES = {"l2_heads": (4, 6), "log_decay": (8, 12), "norm_gate": (8, 14)}


def parts(dtype):
    """{part: (kernel, lines, operands, cotangent)}; the lines on the flat
    arrays, rounded where the mixer rounds them."""
    ks = jax.random.split(jax.random.PRNGKey(60), 8)
    flat = lambda a: a.reshape(B, S, P)     # noqa: E731
    x = jax.random.normal(ks[0], (B, S, P)).astype(dtype)
    g = jax.random.normal(ks[1], (B, S, P))
    pre = 3.0 * jax.random.normal(ks[2], (B, S, P))
    step = jnp.exp(jax.random.uniform(
        ks[3], (P,), F32, math.log(1e-3), math.log(1e-1)))
    dt_bias = step + jnp.log(-jnp.expm1(-step))
    a_log = jnp.log(jax.random.uniform(ks[4], (H,), F32, 1.0, 16.0))
    o = jax.random.normal(ks[5], (B, S, P)).astype(dtype)
    gate_pre = 2.0 * jax.random.normal(ks[6], (B, S, P))
    o_norm = 1.0 + 0.2 * jax.random.normal(ks[7], (D,))
    return {
        "l2_heads": (
            lambda x: K.l2_heads(x, scale=D ** -0.5),
            lambda x: flat(K.l2_heads_reference(x, H, D ** -0.5)
                           .astype(x.dtype)),
            (x,), g.astype(dtype)),
        "log_decay": (
            K.log_decay,
            lambda *a: flat(K.log_decay_reference(*a)),
            (pre, dt_bias, a_log), g),
        "norm_gate": (
            lambda o, z, w: K.norm_gate(o, z, w, eps=EPS),
            lambda o, z, w: flat(K.norm_gate_reference(
                o.reshape(B, S, H, D), z, w, EPS).astype(o.dtype)),
            (o, gate_pre, o_norm), g.astype(dtype)),
    }


def both(fn):
    """The output and every gradient of ``fn`` in one program."""
    def run(args, g):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(g)
    return jax.jit(run)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


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
        key = re.search(r"kda_\w+?_(fwd|bwd)|$", name).group() or name
        names[key] = names.get(key, 0.0) + t / ITERS / 1e3
    return sum(names.values()), names


def measure(part, kernel, args, g, exact, old):
    """One kernel at the geometry in force: its errors beside the lines'
    (``old``) against the lines on float32 operands (``exact``), and its
    microseconds."""
    got = both(kernel)(args, g)
    row = {"geometry": K.geometry(S, P, min(
        a.dtype.itemsize for a in args if a.ndim == 3)),
        "err": [_rel(a, e) for a, e in zip(got, exact)],
        "err_lines": [_rel(a, e) for a, e in zip(old, exact)]}
    # 2e-5: the chip's float32 ``exp`` / ``log1p`` stand 2.3e-5 from the
    # host's (g's own reading, kernel or lines)
    row["ok"] = all(a <= 1.05 * o + 2e-5
                    for a, o in zip(row["err"], row["err_lines"]))
    _, names = device_us(both(kernel), (args, g))
    row["fwd_us"] = names.get("kda_%s_fwd" % part)
    row["bwd_us"] = names.get("kda_%s_bwd" % part)
    row["least_us"] = [n * B * S * P / HBM * 1e6 for n in BYTES[part]]
    return row


def on_a_tpu():
    return jax.devices()[0].platform == "tpu"


def main(argv):
    if not on_a_tpu():
        print("no TPU: a CPU time is not a device time", file=sys.stderr)
        return 2
    out_path = next((a for a in argv if a.endswith(".json")), OUT)
    geometries = [None]
    if "--geometry" in argv:
        geometries += [tuple(int(n) for n in at.split("x")) for at in
                       argv[argv.index("--geometry") + 1].split(",")]
    shipped = K.geometry
    report, bad, lines_said = {}, False, {}
    made = parts(jnp.bfloat16)
    for part, (_, lines, args, g) in made.items():
        # the lines on float32 operands by the HOST's float32 functions
        # (the chip's own exp and log1p are what is being compared)
        lines_said[part] = (
            both(lines)(*jax.device_put(
                (tuple(a.astype(F32) for a in args), g.astype(F32)),
                jax.devices("cpu")[0])),
            both(lines)(args, g))
        report[part + ".lines"] = {
            "fwd_us": device_us(jax.jit(lines), args)[0],
            "both_us": device_us(both(lines), (args, g))[0]}
        print(part + ".lines", json.dumps(report[part + ".lines"]),
              flush=True)
    for at in geometries:
        if at is not None:      # (block rows, walk rows, block lanes)
            K.geometry = lambda S, P, itemsize, at=at: (at[1], at[2], at[0])
        for part, (kernel, _, args, g) in made.items():
            try:
                row = measure(part, kernel, args, g, *lines_said[part])
            except Exception as e:      # a geometry Mosaic has no VMEM for
                if at is None:
                    raise
                row = {"failed": str(e).splitlines()[0][:200], "ok": True}
            bad |= not row["ok"]
            name = part if at is None else "%s@%dx%dx%d" % ((part,) + at)
            report[name] = row
            print(name, json.dumps(row), flush=True)
        K.geometry = shipped
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    return int(bad)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
