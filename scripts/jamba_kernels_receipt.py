"""The selective-scan kernels compiled by Mosaic at the jamba cell's shapes
([1, 8192, 5120] channels of 16 state cells, bf16 x and z, float32 step sizes;
compared over the first 4,096 tokens) against the per-token ``lax.scan`` in
float32 (``kernels/selective_scan.py: selective_scan_reference``, whose
gradient holds the ``[S, d, N]`` state in HBM three times over: 4 GB at
4,096): the output and the seven gradients, with step sizes drawn as the
cell's weights seed them (log-uniform in [1e-3, 1e-1], rates -1..-16: a cell's
decay runs from 0.9999 to 0.2 a token) and with every step a tenth of that (a
state that still weighs ten thousand tokens on, carried over every chunk
edge). What the cell's ``correct`` cannot see (PERF.md section 7): the
backward.

    chiprun -- python3 scripts/jamba_kernels_receipt.py [out.json]
        [--against <another tree's kernels/selective_scan.py>] [chunk ...]

Each reading is ``|program - reference| / |reference|``; the limit is 2e-2
(bf16 x and z: 2^-8 a value) on every one, and a fault control (the state
dropped at chunk edges, put into the reference) has to read over it.

Then the times, by DEVICE trace (the host's clock around a call read 1.7
times the kernels' own in PR 48): the forward and its backward under
``jax.vjp`` as one program whose parameters and results are ``[1, S, d]``
arrays as the mixer hands them over, at each chunk length given (default 64
and 128), the mean of ``CALLS`` calls: the two kernels by name, and the DOOR,
a line each for x, z, dt, out and the four per-token gradients: the
instructions the compiled program runs between that array and the kernels
(``door``: followed through the entry computation's text; a view that XLA
takes as a bitcast has none and reads 0).  ``--against`` times another tree's
kernels the same way in the same process (the parent's from ``git archive``:
PR 48's door is XLA's re-tiling copies).

Then the filter in front of the scan (``kernels/mamba_filter.py``, PR 51):
the kernels on the packed projection ``[1, S, 2 d]`` against the ``jnp``
lines they replace (``mamba_filter_reference`` behind a split, the parent's
lines and fusions) in float32: the output and the gradients of the
projection, the taps and the bias, with and without rows before position 0
(limit ``FILTER_LIMIT``, and never further from float32 than the ``jnp``
lines in bf16 are); and the times of forward + backward under ``jax.vjp``
by device trace: the two kernels by name beside the whole ``jnp`` program,
with a door line for the x half (0: read in place).  ``--only filter`` or
``--only scan`` runs one of the two.  Exit 1 where a reading is off or a
kernel's name matched nothing in the trace, 2 off a TPU."""

import importlib.util
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from attn_outside_hlo import computations  # noqa: E402  (beside this file)
from paddle_tpu.kernels import mamba_filter as mf  # noqa: E402
from paddle_tpu.kernels import selective_scan as ss  # noqa: E402

S, D, N = 8192, 5120, 16
S_COMPARED = 4096       # the reference's gradient holds [S, d, N] three times
LIMIT = 2e-2
CALLS = 5
NAMES = ("x", "dt", "B", "C", "z", "a", "D")
KERNELS = ("selective_scan_fwd", "selective_scan_bwd")
# the per-token arrays at the door: (kernel, "in" | "out", its place there)
FILTER_LIMIT = 1e-2     # one bf16 rounding of the output; sums of 8,192 rows
FILTER_KERNELS = ("mamba_filter_fwd", "mamba_filter_bwd")
TAPS = 4
# the projection at the filter's door: both kernels' first operand
FILTER_DOOR = {"xz": (0, "in", 0), "xz_again": (1, "in", 0)}
DOOR = {"x": (1, "in", 0), "dt": (1, "in", 1), "z": (1, "in", 2),
        "out": (0, "out", 0), "dout": (1, "in", 8), "dx": (1, "out", 0),
        "ddt": (1, "out", 1), "dz": (1, "out", 2)}


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


def door(text, kernels=KERNELS, arrays=DOOR):
    """{array of ``DOOR``: the entry computation's instructions between it
    and the kernels}, of the compiled text of a program whose parameters and
    results are the kernels' operands and results: back from a call's
    operand to a parameter, on from a call's result to the root."""
    comps, entry = computations(text)
    by = {name: (op, operands, attrs)
          for name, _, op, operands, attrs in comps[entry]}
    users = {}
    for name, (_, operands, _) in by.items():
        for o in operands:
            users.setdefault(o, []).append(name)
    calls = [next(n for n, (op, _, _) in by.items()
                  if op == "custom-call" and kernel in n)
             for kernel in kernels]

    def back(name):
        found = []
        while by[name][0] != "parameter" and by[name][1]:
            found.append(name)
            name = by[name][1][0]
        return found

    def on(name):
        found, frontier = [], [name]
        while frontier:
            for u in users.get(frontier.pop(), ()):
                if by[u][0] not in ("tuple", "custom-call"):
                    found.append(u)
                    frontier.append(u)
        return found

    out = {}
    for array, (call, side, at) in arrays.items():
        call = calls[call]
        if side == "in":
            out[array] = back(by[call][1][at])
        else:
            out[array] = [n for u in users[call]
                          if by[u][0] == "get-tuple-element"
                          and re.search(r"\bindex=%d\b" % at, by[u][2])
                          for n in [u] + on(u)]
    return out


def device_us(fn, args):
    """{instruction: device microseconds a call of ``fn``}, from a trace."""
    from benchmark.harness import trace_reduce, tracing

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        tracing._start(tmp, 0)
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp))).devices[0]
    return {name: t / CALLS / 1e3 for name, t in dev["by_name"].items()}


def _door_us(at_door, by_name):
    """{array: its door's device microseconds and the instructions that ran}
    of ``door``'s names and a trace's ``{instruction: us}``."""
    return {array: {"us": sum(by_name.get(n.lstrip("%"), 0.0) for n in names),
                    "instructions": [n for n in names
                                     if n.lstrip("%") in by_name]}
            for array, names in at_door.items()}


def times(mod, chunk, args, g, packed=False):
    """Device microseconds a call of ``mod``'s forward + backward at the
    cell's shape: the kernels by name, the door by array, and the rest.
    ``packed``: z read in place, the second half of a ``[1, S, 2 d]`` array
    as ``in_proj`` leaves it (PR 51; its gradient's pad, which the layer's
    matmuls take as a fused operand, is a pass of its own in this program:
    the door of ``dz``)."""
    kw = {"z_at": 1} if packed else {}
    if packed:
        args = args[:4] + (jnp.concatenate([args[0], args[4]], axis=-1),) \
            + args[5:]

    def both(*a):
        out, vjp = jax.vjp(lambda *q: mod.selective_scan(*q, chunk=chunk,
                                                         **kw), *a[:-1])
        return (out,) + vjp(a[-1])

    fn = jax.jit(both)
    by_name = device_us(fn, args + (g,))
    at_door = door(fn.lower(*args, g).compile().as_text())
    took = {k: sum(t for n, t in by_name.items() if k in n) for k in KERNELS}
    took["door"] = _door_us(at_door, by_name)
    took["all"] = sum(by_name.values())
    return took


def filter_operands(seed, dtype=jnp.bfloat16, S=S, before=False):
    """The packed projection, the taps, the bias, the rows before position 0
    (or None) and the output's gradient, seeded as the cell's weights are
    (taps uniform in +-1/2, unit projections)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xz = jax.random.normal(ks[0], (1, S, 2 * D)).astype(dtype)
    conv_w = jax.random.uniform(ks[1], (TAPS, D), minval=-0.5, maxval=0.5)
    conv_b = 0.1 * jax.random.normal(ks[2], (D,))
    rows = jax.random.normal(ks[3], (1, TAPS - 1, D)) if before else None
    return (xz, conv_w, conv_b, rows), \
        jax.random.normal(ks[4], (1, S, D)).astype(dtype)


def _filter_programs():
    """{"kernel", "jnp": (xz, conv_w, conv_b, before, g) -> (out, the four
    gradients)}: the kernels on the packed projection, and the lines they
    replace behind the split."""
    def program(fn):
        def both(xz, conv_w, conv_b, before, g):
            out, vjp = jax.vjp(fn, xz, conv_w, conv_b, before)
            return (out,) + vjp(g)
        return jax.jit(both)

    return {
        "kernel": program(lambda xz, w, b, rows: mf.mamba_filter(
            xz, w, b, rows, width=D)),
        "jnp": program(lambda xz, w, b, rows: mf.mamba_filter_reference(
            jnp.split(xz, 2, axis=-1)[0], w, b, rows))}


def filter_receipt(out):
    """The filter's readings and times into ``out``; whether all held."""
    programs, ok = _filter_programs(), True
    names = ("out", "dxz", "dconv_w", "dconv_b", "dbefore")
    for label, before in (("zeros", False), ("rows", True)):
        args, g = filter_operands(13, before=before)
        exact = programs["jnp"](args[0].astype(jnp.float32), *args[1:],
                                g.astype(jnp.float32))
        got, old = programs["kernel"](*args, g), programs["jnp"](*args, g)
        for name, a, o, e in zip(names, got, old, exact):
            if e is None:
                continue
            reading, jnp_reads = _rel(a, e), _rel(o, e)
            key = "filter.%s.%s" % (label, name)
            out["readings"][key] = reading
            print(key, reading, "(the jnp lines in bf16: %g)" % jnp_reads,
                  flush=True)
            ok = ok and reading <= max(FILTER_LIMIT, 1.5 * jnp_reads)
    # a fault control: the halo dropped (the filter restarted at row 16)
    args, g = filter_operands(13)
    dropped = jnp.concatenate([mf.mamba_filter(
        args[0][:, at:at + 16], *args[1:3], width=D)
        for at in (0, 16)], axis=1)
    out["control_halo_dropped"] = _rel(
        dropped, programs["kernel"](*args, g)[0][:, :32])
    print("control (the halo dropped at row 16):",
          out["control_halo_dropped"], flush=True)
    ok = ok and out["control_halo_dropped"] > FILTER_LIMIT
    args, g = filter_operands(14)
    for tree, fn in programs.items():
        by_name = device_us(fn, args + (g,))
        took = {k: sum(t for n, t in by_name.items() if k in n)
                for k in FILTER_KERNELS}
        took["all"] = sum(by_name.values())
        took["by_name"] = dict(sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:8])
        if tree == "kernel":
            at_door = door(fn.lower(*args, g).compile().as_text(),
                           FILTER_KERNELS, FILTER_DOOR)
            took["door"] = _door_us(at_door, by_name)
            ok = ok and all(took[k] > 0 for k in FILTER_KERNELS)
        out["device_us"]["filter." + tree] = took
        print("filter, %s, device us a call: %s; all %.1f" % (
            tree, ", ".join("%s %.1f" % (k, took[k])
                            for k in FILTER_KERNELS), took["all"]),
            flush=True)
        for name, t in took["by_name"].items():
            print("    %9.1f us  %s" % (t, name), flush=True)
        for array, at in took.get("door", {}).items():
            print("    door %-8s %9.1f us  %s" % (
                array, at["us"], " ".join(at["instructions"]) or "-"),
                flush=True)
    return ok


def _other_tree(path):
    """Another tree's ``kernels/selective_scan.py`` beside this tree's
    ``_common``."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.kernels._selective_scan_against", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def scan_receipt(out, chunks, against):
    """The scan's readings and times into ``out``; whether all held."""
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
    g = w.astype(args[0].dtype)
    trees = [("this", ss, False), ("this, z in place", ss, True)] \
        + ([("against", against, False)] if against else [])
    for chunk in chunks:
        for tree, mod, packed in trees:
            took = times(mod, chunk, args, g, packed)
            out["device_us"]["%s.chunk%d" % (tree, chunk)] = took
            print("%s, chunk %d, device us a call: %s; all %.1f" % (
                tree, chunk, ", ".join("%s %.1f" % (k, took[k])
                                       for k in KERNELS), took["all"]),
                flush=True)
            for array, at in took["door"].items():
                print("    door %-5s %9.1f us  %s" % (
                    array, at["us"], " ".join(at["instructions"]) or "-"),
                    flush=True)
            ok = ok and all(took[k] > 0 for k in KERNELS)
    return ok


def main(*argv):
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU")
        return 2
    argv, against, only = list(argv), None, ("scan", "filter")
    if "--against" in argv:
        at = argv.index("--against")
        against = _other_tree(argv[at + 1])
        del argv[at:at + 2]
    if "--only" in argv:
        at = argv.index("--only")
        only = (argv[at + 1],)
        del argv[at:at + 2]
    out_path = argv[0] if argv and not argv[0].isdigit() else None
    chunks = [int(c) for c in argv[bool(out_path):]] or [64, 128]
    out = {"device_kind": jax.devices()[0].device_kind, "readings": {},
           "device_us": {}}
    ok = True
    if "scan" in only:
        ok = scan_receipt(out, chunks, against) and ok
    if "filter" in only:
        ok = filter_receipt(out) and ok
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
