"""Which primitive, and which tiles, carry the dropless MoE's grouped
matmuls, measured on the chip at ONE layer's shape of a sparse cell (the
default is OLMoE's: 16,384 tokens, top-8 of 64 experts all held, E = 2048,
F = 1024, bf16):

    chiprun -- python3 scripts/moe_grouped_matmul_bench.py [out.json]
        [--tokens T --top-k K --experts N --held H --hidden E --width F]
        [--tilings 128x128x128,512x1024x1024,...] [--skip-layer]

The rows are the layer's FIRST capacity where a share of the experts is held
(``moe._held_capacities``: Trinity's ``--tokens 6144 --top-k 4 --experts 256
--held 8 --hidden 3072 --width 3072`` gives 1,024 rows for 8 groups of about
96), every pair's where all are; the groups' sizes are a seeded uniform
routing's, so the rows past the held pairs belong to no group, as in a step.

``calls`` (device microseconds a call, read off a profiler trace by the
kernel's name, as the benchmark's ``moe_*roofline`` readers take them): each
of the six kernel calls a layer's forward and backward make (``gate_up.fwd``,
``down.fwd``: ``gmm``; ``gate_up.dx``, ``down.dx``: ``gmm`` with the weights
transposed; ``gate_up.dw``, ``down.dw``: ``tgmm``) at the tiles the program's
rule gives it (``rule``, ``moe._tiling``) and at every one of ``--tilings``
(a dimension larger than the call's is the call's, so ``128x8192x512`` asks
for the whole contraction), beside the least time its weights' and rows'
bytes and its routed rows' FLOPs allow on the chip.  EVERY (call, tiling) is
first held to ``jax.lax.ragged_dot`` group by group (EQUAL_TOLERANCE) and
reads ``failed: ...`` where the kernel refuses the tiles (VMEM).

``ms`` (forward + backward, ``jax.grad`` of a sum, so dX and dW of both
matmuls; host clock around ``block_until_ready`` over REPS calls after a
warm-up):

- ``experts.ragged_dot`` / ``experts.as_shipped`` /
  ``experts.megablox_gmm.<tiling>``: the expert FFN alone on rows already
  sorted by expert, by ``jax.lax.ragged_dot``, by ``moe._grouped_matmul``
  (the one the program ships, the rule's tiles a call) and by the Pallas
  ``gmm`` at each of ``--tilings`` for all six calls;
- ``layer.gather_backward`` / ``layer.remat`` / ``layer.forward`` /
  ``layer.scatter_backward``: the whole layer (``moe.dropless_moe_ffn``:
  router, sort, dispatch, experts, combine) as the program ships it, under
  ``jax.checkpoint`` as the cells run it, and (all experts held only) with
  the dispatch and combine left to autodiff, whose transposes are
  scatter-adds.

Before any time is taken it holds the shipped primitive to
``jax.lax.ragged_dot`` at that shape and the rule's tiles (``equal``: the
expert FFN's output and its three gradients against a random cotangent, the
relative error of each GROUP's rows or weights, so that a kernel that lost
one expert's rows cannot hide in the mean) and exits 1 where a group is off
by more than EQUAL_TOLERANCE.  Both accumulate in float32 and round to bf16
once, so they differ in a few last bits only: the worst group read 1.1e-4
(out) to 1.4e-4 (d_gate_up) on the v5e (PR 27; groups of 1,931 to 2,178
rows), and the bound is ten times that; a lost or doubled row tile moves a
group by tens of per cent.

Off a TPU it exits 2: a CPU time is not a device time."""

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

REPS = 5
TRACED = 5
TILINGS = ((128, 128, 128), (512, 1024, 1024), (512, 512, 1024))
EQUAL_TOLERANCE = 2e-3
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9         # one v5e chip


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS * 1e3


def _kernel_us(fn, *args):
    """Device microseconds an event of the megablox kernel ``fn`` calls."""
    from benchmark.harness import trace_reduce, tracing

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        tracing._start(tmp, 0)
        for _ in range(TRACED):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp))).devices[0]
    ns = sum(t for name, t in dev["by_name"].items() if "gmm" in name)
    events = sum(c for name, c in dev["count"].items() if "gmm" in name)
    assert events == TRACED, (events, sorted(dev["by_name"]))
    return ns / events / 1e3


def _ffn(matmul, sizes):
    def ffn(rows, w_gate_up, w_down):
        gate, up = jnp.split(matmul(rows, w_gate_up, sizes), 2, axis=-1)
        return matmul(jax.nn.silu(gate) * up, w_down, sizes)
    return ffn


def _experts(matmul):
    def loss(rows, w_gate_up, w_down, sizes):
        # rows past the groups are no group's: the kernels leave them
        # unwritten, so the sum stops at the last group's row
        live = jnp.arange(rows.shape[0]) < jnp.sum(sizes)
        out = _ffn(matmul, sizes)(rows, w_gate_up, w_down)
        return jnp.sum(jnp.where(live[:, None], out.astype(jnp.float32), 0.0))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _ragged_dot(a, b, s):
    return jax.lax.ragged_dot(a, b, s, preferred_element_type=a.dtype)


def _ragged_dot_t(a, w, s):
    """``_ragged_dot`` with each group's weights transposed: the dX."""
    return _ragged_dot(a, w.swapaxes(1, 2), s)


def _ragged_dw(a, g, s):
    """dW of ``_ragged_dot(a, w, s)`` under the cotangent ``g``."""
    w = jnp.zeros((s.shape[0], a.shape[1], g.shape[1]), a.dtype)
    return jax.vjp(lambda w: _ragged_dot(a, w, s), w)[1](g)[0]


def _name(tiling):
    return "%dx%dx%d" % tuple(tiling)


def _worst_group(got, want, sizes):
    """Largest relative error over the groups: an array with the rows'
    leading dimension by the rows of each group (rows past the groups are
    nobody's), one with the groups' by group."""
    groups, m = sizes.shape[0], got.shape[0]

    def by_group(a):
        sq = jnp.sum(jnp.square(a.astype(jnp.float32)),
                     axis=tuple(range(1, a.ndim)))
        if a.shape[0] == groups:
            return sq
        group = jnp.repeat(jnp.arange(groups + 1), jnp.append(
            sizes, m - jnp.sum(sizes)), total_repeat_length=m)
        return jax.ops.segment_sum(sq, group, groups + 1)[:groups]

    err = jnp.sqrt(by_group(got.astype(jnp.float32) - want.astype(jnp.float32))
                   / jnp.maximum(by_group(want), 1e-30))
    return float(jnp.max(err))


def _equal(matmul, rows, w_gate_up, w_down, sizes, probe):
    """The expert FFN by ``matmul`` against the same by ``ragged_dot``:
    ``out`` and ``d_rows`` by the rows of each group, ``d_gate_up`` and
    ``d_down`` by expert."""
    live = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]

    def both(matmul):
        def run(rows, w_gate_up, w_down):
            out, vjp = jax.vjp(_ffn(matmul, sizes), rows, w_gate_up, w_down)
            return (out,) + vjp(jnp.where(live, probe, 0))

        return jax.jit(run)(rows, w_gate_up, w_down)

    return {name: _worst_group(a, b, sizes) for name, a, b in zip(
        ("out", "d_rows", "d_gate_up", "d_down"), both(matmul),
        both(_ragged_dot))}


def _holds(equal):
    return all(v <= EQUAL_TOLERANCE for v in equal.values()
               if isinstance(v, float))          # a nan is not equal


def _calls(moe, rows, hidden, w_gate_up, w_down, sizes, probe, tilings):
    """{call: {"shape", "least_us", "rule", "us": {tiling: us or "failed"},
    "worst_group": {tiling: relative error}}} of the six kernel calls."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    interpret = not moe.on_tpu()         # the CPU rehearsal only
    groups, pad = sizes.shape[0], moe._whole_row_tiles
    d_hidden = jnp.concatenate([hidden, hidden], axis=1)     # [M, 2F]
    routed = float(jnp.sum(sizes))
    # (kernel, lhs, weights or cotangent, the same by ragged_dot)
    calls = {
        "gate_up.fwd": ("gmm", rows, w_gate_up, _ragged_dot),
        "down.fwd": ("gmm", hidden, w_down, _ragged_dot),
        "gate_up.dx": ("gmm_t", d_hidden, w_gate_up, _ragged_dot_t),
        "down.dx": ("gmm_t", probe, w_down, _ragged_dot_t),
        "gate_up.dw": ("tgmm", rows, d_hidden, _ragged_dw),
        "down.dw": ("tgmm", hidden, probe, _ragged_dw),
    }
    got = {}
    for name, (kernel, a, b, ref) in calls.items():
        m, k = a.shape
        n = b.shape[1] if kernel != "gmm" else b.shape[2]
        dw = kernel == "tgmm"
        want = jax.jit(ref)(a, b, sizes)
        rule = moe._tiling(m, k, n, groups, a.dtype.itemsize, dw=dw)
        weights = groups * k * n * a.dtype.itemsize
        moved = weights + routed * (k + n) * a.dtype.itemsize
        row = got[name] = {
            "kernel": kernel, "m_k_n_groups": [m, k, n, groups],
            "least_us": max(2 * routed * k * n / PEAK_FLOPS,
                            moved / PEAK_BYTES) * 1e6,
            "rule": _name(rule), "us": {}, "worst_group": {}}
        tried = [rule] + [t for t in (
            (min(tm, m), min(tk, k), min(tn, n)) for tm, tk, tn in tilings)
            if t != rule]
        for tiling in dict.fromkeys(tried):
            def call(a, b, sizes, tiling=tiling):
                if dw:
                    return tgmm(pad(a, tiling[0]).swapaxes(0, 1),
                                pad(b, tiling[0]), sizes, a.dtype, tiling,
                                num_actual_groups=groups, interpret=interpret)
                return gmm(pad(a, tiling[0]), b, sizes, a.dtype, tiling,
                           transpose_rhs=kernel == "gmm_t",
                           interpret=interpret)[:m]
            key = _name(tiling)
            try:
                fn = jax.jit(call)
                row["worst_group"][key] = _worst_group(
                    fn(a, b, sizes), want, sizes)
                row["us"][key] = (
                    _kernel_us(fn, a, b, sizes)
                    if row["worst_group"][key] <= EQUAL_TOLERANCE
                    else "differs from ragged_dot")
            except Exception as e:      # tiles the kernel refuses
                row["us"][key] = "failed: %s" % (
                    str(e).splitlines() or [""])[0][:160]
            print("calls", name, row["m_k_n_groups"], key,
                  "rule" if tiling == rule else "", row["us"][key],
                  "least %.1f" % row["least_us"],
                  "worst group %s" % row["worst_group"].get(key), flush=True)
    return got


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out", nargs="?")
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, default=None)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--tilings", default=",".join(map(_name, TILINGS)))
    ap.add_argument("--skip-layer", action="store_true")
    args = ap.parse_args(argv)
    T, K, N, E, F = (args.tokens, args.top_k, args.experts, args.hidden,
                     args.width)
    H = N if args.held is None else args.held
    tilings = [tuple(int(d) for d in t.split("x"))
               for t in args.tilings.split(",") if t]

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("moe_grouped_matmul_bench: needs a TPU, found %s" % dev.platform,
              file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from paddle_tpu.parallel import moe

    M = T * K if H == N else moe._held_capacities(T * K, H, N)[0]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    # the held experts' weights alone; the router ranks all N
    params = dict(moe.init_dropless_moe_params(ks[0], H, E, F, jnp.bfloat16),
                  router=moe._normal(ks[0], (E, N), E, jnp.float32))
    x = jax.random.normal(ks[1], (T, E), jnp.float32).astype(jnp.bfloat16)
    rows = jax.random.normal(ks[2], (M, E), jnp.float32).astype(jnp.bfloat16)
    expert = jax.random.randint(ks[3], (T * K,), 0, N)
    sizes = jnp.bincount(expert, length=N).astype(jnp.int32)[:H]
    assert int(jnp.sum(sizes)) <= M, (int(jnp.sum(sizes)), M)
    got = {"device_kind": dev.device_kind,
           "shape": dict(T=T, K=K, N=N, held=H, E=E, F=F, rows=M,
                         rows_routed=int(jnp.sum(sizes))),
           "reps": REPS, "ms": {}}

    def note(name, fn, *args):
        try:
            got["ms"][name] = _time(fn, *args)
        except Exception as e:          # a tiling the kernel refuses
            got["ms"][name] = "failed: %s" % (str(e).splitlines() or [""])[0][:200]
        print(name, got["ms"][name], flush=True)

    w = (params["we_gate_up"], params["we_down"])
    probe = jax.random.normal(ks[4], (M, E), jnp.float32).astype(jnp.bfloat16)
    hidden = jax.random.normal(ks[5], (M, F), jnp.float32).astype(jnp.bfloat16)
    got["equal"] = _equal(moe._grouped_matmul, rows, *w, sizes, probe)
    got["equal"]["rows_in_smallest_and_largest_group"] = [
        int(jnp.min(sizes)), int(jnp.max(sizes))]
    print("equal", got["equal"], flush=True)
    equal = _holds(got["equal"])
    got["calls"] = _calls(moe, rows, hidden, *w, sizes, probe, tilings)
    note("experts.ragged_dot", _experts(_ragged_dot), rows, *w, sizes)
    note("experts.as_shipped", _experts(moe._grouped_matmul), rows, *w, sizes)
    got["equal_by_tiling"] = {}
    for tiling in tilings:
        def by_tiling(a, b, s, tiling=tiling):
            # m is whole tiles of every ROW_TILES member (HELD_GRANULE)
            return megablox.gmm(a, b, s, preferred_element_type=a.dtype,
                                tiling=tiling, interpret=not moe.on_tpu())
        name = _name(tiling)
        try:
            held = got["equal_by_tiling"][name] = _equal(
                by_tiling, rows, *w, sizes, probe)
        except Exception as e:
            held = got["equal_by_tiling"][name] = {
                "failed": (str(e).splitlines() or [""])[0][:200]}
        if "failed" in held or not _holds(held):
            got["ms"]["experts.megablox_gmm." + name] = "not timed: %s" % held
            print("experts.megablox_gmm." + name, held, flush=True)
            continue
        note("experts.megablox_gmm." + name, _experts(by_tiling), rows, *w,
             sizes)

    def layer(p, x):
        return moe.dropless_moe_ffn(p, x, K)[0]

    def layer_grad():
        return jax.jit(jax.grad(lambda p, x: jnp.sum(
            layer(p, x).astype(jnp.float32)), argnums=(0, 1)))

    def remat(p, x, g):
        # the result is kept, so the first forward runs whole
        y, vjp = jax.vjp(jax.checkpoint(layer), p, x)
        return y, vjp(g)

    if not args.skip_layer:
        note("layer.gather_backward", layer_grad(), params, x)
        note("layer.remat", jax.jit(remat), params, x, x[::-1])
        note("layer.forward", jax.jit(layer), params, x)
    if not args.skip_layer and H == N:
        keep = moe._dispatch, moe._combine
        # plain gathers, no custom_vjp (the shipped sum back is a Pallas
        # kernel, which autodiff cannot transpose)
        moe._dispatch = lambda x, order, inv, k: x[order // k]
        moe._combine = lambda rows, order, inv, k: jnp.sum(
            rows[inv].reshape(-1, k, rows.shape[1]).astype(jnp.float32),
            axis=1).astype(rows.dtype)
        try:
            note("layer.scatter_backward", layer_grad(), params, x)
        finally:
            moe._dispatch, moe._combine = keep
    routed = got["shape"]["rows_routed"]
    flops = 3 * 6.0 * E * F * routed
    got["required_tflop_fwd_bwd"] = flops / 1e12
    got["least_ms_at_197_tflops"] = flops / PEAK_FLOPS * 1e3
    got["least_ms_at_819_gb_s"] = 3 * (
        H * 3.0 * E * F + 2.0 * routed * E) * 2 / PEAK_BYTES * 1e3
    print(json.dumps(got), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(got, f)
    if not equal:
        print("moe_grouped_matmul_bench: the shipped grouped matmul differs "
              "from ragged_dot: %s" % got["equal"], file=sys.stderr)
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
