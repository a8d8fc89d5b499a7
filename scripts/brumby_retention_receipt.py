"""The power-retention kernels compiled by Mosaic at the brumby cell's shapes
([1, 16384, 40 x 128] queries on 8 key/value heads, bf16) against the
score-matrix form in float32 (``benchmark/reference/brumby_14b.py``'s
``_retain_rows``, every pair's weight, no state): the output and the
gradients of q, k, v and the log-decay, a key/value head at a time, with
log-decays drawn from [-0.01, 0) (a state that still weighs a thousand
tokens on, carried over every chunk of the sequence) and from a seeded gate
(``logsigmoid`` of a unit normal: mean decay one half, what the cell's
weights give).  What the cell's ``correct`` cannot see (PERF.md section 7):
the carry over many chunks, and the backward.

    chiprun -- python3 scripts/brumby_retention_receipt.py [out.json] [chunk ...]

Each reading is ``|program - reference| / |reference|`` over one key/value
head's group; the limit is 2e-2 on every one (bf16 operands: 2^-8 a product,
a few of them in sequence), and a fault control (the state dropped at chunk
edges, put into the reference) has to read over it with the near-one
gates.  Also times the two kernels (device seconds by kernel name from a
trace) at each chunk length given (default 512, 1024 and 2048), beside the
grid each call runs, and fits a grid step's time to the chunk length
``c``: ``fixed + per_row * c + per_row_squared * c * c`` microseconds (three
lengths give the three exactly), the fixed part being what a step pays
whatever its rows (``a`` = a tile's share of it, ``b`` a tile's share of a
row's) and the square the chunk's own block.  Exit 1 where a reading is
off or a kernel's name matched no instruction of the trace, 2 off a TPU."""

import json
import math
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.reference import brumby_14b as reference  # noqa: E402
from paddle_tpu.kernels import power_retention as pr  # noqa: E402

KERNELS = ("power_retention_fwd", "power_retention_bwd")
S, HQ, HKV, DH = 16384, 40, 8, 128
GROUP = HQ // HKV
LIMIT = 2e-2
ROWS = 256


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _rows_out(q_rows, first, k, v, g, m, fault, chunk=1024):
    rel_q, rel_k = reference._exponents(g, ROWS)
    return reference._retain_rows(q_rows, first, rel_q[m], rel_k[m], k, v, g,
                                  pr.EPS, chunk, fault)


@jax.jit
def _rows_vjp(q_rows, first, k, v, g, m, w_rows):
    out, vjp = jax.vjp(lambda q_rows, k, v, g: _rows_out(
        q_rows, first, k, v, g, m, None), q_rows, k, v, g)
    return (out,) + vjp(w_rows)


def plain_head(q, k, v, g, w):
    """Output and the four gradients of ``sum(o * w)`` for ONE key/value
    head in the score-matrix form, a block of query rows at a time."""
    with jax.default_matmul_precision("highest"):
        outs, dqs = [], []
        dk, dv, dg = jnp.zeros_like(k), jnp.zeros_like(v), jnp.zeros_like(g)
        for m in range(S // ROWS):
            rows = slice(m * ROWS, (m + 1) * ROWS)
            o, dq, dk_, dv_, dg_ = _rows_vjp(q[rows], m * ROWS, k, v, g, m,
                                             w[rows])
            outs.append(o)
            dqs.append(dq)
            dk, dv, dg = dk + dk_, dv + dv_, dg + dg_
        return jax.block_until_ready((jnp.concatenate(outs),
                                      jnp.concatenate(dqs), dk, dv, dg))


def kernel_seconds(run, names):
    """Device seconds of one call of ``run`` by kernel name, from a trace."""
    from benchmark.harness import trace_reduce

    with tempfile.TemporaryDirectory() as d:
        jax.block_until_ready(run())
        jax.profiler.start_trace(d)
        jax.block_until_ready(run())
        jax.profiler.stop_trace()
        reduced = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(d)))
    # outside a scan the instruction carries the transformation's name too
    # (``jvp_power_retention_fwd_.1``): the kernel's name is a part of it
    return {name: sum(s for op, s in reduced.top_ops(1000) if name in op)
            for name in names}


def kernel_grids(fn, *args):
    """The grid of each kernel ``fn`` calls, by kernel name, read off the
    traced program as ``tests/test_chip_compile_scans.py`` reads it."""
    return {name: [int(n) for n in grid.split(",") if n.strip()]
            for grid, name in re.findall(
                r"grid=\(([\d, ]*)\).*?name=(power_retention_\w+)",
                str(fn.trace(*args).jaxpr), re.S)}


def fit_step(us_by_chunk, grids):
    """``fixed + per_row * c + per_row_squared * c^2`` microseconds a grid
    step, through three chunk lengths' readings (least squares past
    three); ``a`` and ``b`` are a tile's share of the first two.  Only
    the chunk lengths that run the shortest chunk's schedule (a grid of as
    many axes) are fitted: a step of a group in parts is another step.
    None with fewer than three."""
    chunks = sorted(us_by_chunk)
    chunks = [n for n in chunks if len(grids[n]) == len(grids[chunks[0]])]
    if len(chunks) < 3:
        return None
    c = np.array(chunks, np.float64)
    coef, *_ = np.linalg.lstsq(
        np.stack([np.ones_like(c), c, c * c], axis=1),
        np.array([us_by_chunk[n] for n in chunks], np.float64), rcond=None)
    fixed, per_row, per_row_squared = (float(x) for x in coef)
    return {"fixed_us": fixed, "per_row_us": per_row,
            "per_row_squared_us": per_row_squared,
            "a_us": fixed / pr.DIAGONALS, "b_us": per_row / pr.DIAGONALS}


def main(out_path=None, *chunks):
    if jax.devices()[0].platform != "tpu":
        print("the receipt is the chip's: no TPU here")
        return 2
    return run(out_path, [int(c) for c in chunks] or [512, 1024, 2048])


def run(out_path, chunks):
    ks = jax.random.split(jax.random.PRNGKey(20260929), 7)
    q = jax.random.normal(ks[0], (1, S, HQ * DH), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, HKV * DH), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, HKV * DH), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, S, HQ * DH), jnp.bfloat16)
    gates = {"near_one": -0.01 * jax.random.uniform(ks[4], (1, S, HKV)),
             "seeded": jax.nn.log_sigmoid(jax.random.normal(ks[5],
                                                            (1, S, HKV)))}
    out = {"device": jax.devices()[0].device_kind, "limit": LIMIT,
           "shape": [1, S, HQ, HKV, DH], "readings": {}, "seconds": {},
           "grid": {}, "step_us": {}, "fit": {}}
    ok = True

    def program(chunk):
        def loss(q, k, v, g):
            o = pr.power_retention(q, k, v, g, chunk=chunk)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True))

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    for name, g in gates.items():
        got = {}
        for chunk in chunks:
            t0 = time.perf_counter()
            (_, o), grads = jax.block_until_ready(program(chunk)(q, k, v, g))
            got[chunk] = (o,) + grads
            print("%s chunk %d: compiled and ran in %.1f s"
                  % (name, chunk, time.perf_counter() - t0), flush=True)
        for h in range(HKV):
            qs = slice(h * GROUP * DH, (h + 1) * GROUP * DH)
            kv = slice(h * DH, (h + 1) * DH)
            want = plain_head(
                f32(q[0, :, qs]).reshape(S, GROUP, DH), f32(k[0, :, kv]),
                f32(v[0, :, kv]), g[0, :, h],
                f32(w[0, :, qs]).reshape(S, GROUP, DH))
            for chunk in chunks:
                o, dq, dk, dv, dg = got[chunk]
                mine = (o[0, :, qs], dq[0, :, qs], dk[0, :, kv],
                        dv[0, :, kv], dg[0, :, h])
                for what, a, b in zip(("o", "dq", "dk", "dv", "dg"), mine,
                                      want):
                    err = _rel(np.asarray(f32(a)).reshape(-1),
                               np.asarray(b).reshape(-1))
                    out["readings"]["%s/c%d/h%d/%s" % (name, chunk, h,
                                                       what)] = err
                    ok = ok and err < LIMIT
            print(name, "head", h, {key.split("/", 2)[2]: round(val, 5)
                                    for key, val in out["readings"].items()
                                    if key.startswith("%s/c%d/h%d/" % (
                                        name, chunks[0], h))}, flush=True)
    # the control: the reference with the state dropped at chunk edges
    # must NOT agree with the program where the gates are near one
    g = gates["near_one"]
    with jax.default_matmul_precision("highest"):
        dropped = jnp.concatenate([_rows_out(
            f32(q[0, m * ROWS:(m + 1) * ROWS, :GROUP * DH]).reshape(
                ROWS, GROUP, DH), m * ROWS, f32(k[0, :, :DH]),
            f32(v[0, :, :DH]), g[0, :, 0], m,
            "state_dropped_at_chunk_edges", chunks[0])
            for m in range(S // ROWS)])
    o = pr.power_retention(q, k, v, g, chunk=chunks[0])
    control = _rel(np.asarray(f32(o[0, :, :GROUP * DH])).reshape(-1),
                   np.asarray(dropped).reshape(-1))
    out["control_state_dropped"] = control
    ok = ok and control > LIMIT
    print("control (state dropped at chunk edges):", control, flush=True)
    step_us = {name: {} for name in KERNELS}
    for chunk in chunks:
        run = program(chunk)
        seconds = kernel_seconds(lambda: run(q, k, v, gates["seeded"]),
                                 KERNELS)
        grids = kernel_grids(run, q, k, v, gates["seeded"])
        out["seconds"][str(chunk)], out["grid"][str(chunk)] = seconds, grids
        out["step_us"][str(chunk)] = {}
        for name in KERNELS:
            if not seconds[name] > 0 or name not in grids:
                print("chunk", chunk, name, "matched no instruction of the "
                      "trace or no call of the program", flush=True)
                ok = False
                continue
            step_us[name][chunk] = out["step_us"][str(chunk)][name] = \
                seconds[name] * 1e6 / math.prod(grids[name])
        print("chunk", chunk, "kernel seconds a layer:", seconds, "grids:",
              grids, "microseconds a grid step:", out["step_us"][str(chunk)],
              flush=True)
    out["fit"] = {name: fit_step(step_us[name], {
        chunk: out["grid"][str(chunk)][name] for chunk in step_us[name]})
        for name in KERNELS}
    worst = max(out["readings"].items(), key=lambda kv: kv[1])
    out["worst"], out["ok"] = list(worst), bool(ok)
    print(json.dumps({k_: v_ for k_, v_ in out.items() if k_ != "readings"}))
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
