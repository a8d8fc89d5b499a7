"""The Nemotron-H cell's kernels, Mosaic-compiled on the chip, against
float32 ``jax.numpy`` at the cell's shapes:

    chiprun --timeout 1500 -- python3 scripts/nemotron_kernels_receipt.py [out.json] [--only scan|norm|experts|flash]

- ``scan``: ``kernels/ssd_scan.py``'s ``ssd_scan_fwd`` / ``ssd_scan_bwd`` at
  [2, 8192, 6144] bf16, 64 heads of 64 in 8 groups of 128 state cells,
  chunks of 128, against the PER-TOKEN recurrence in float32 (a ``lax.scan``
  a token): the output at the cell's 8,192 tokens, and at 512 tokens (the
  recurrence's gradient keeps a [2, 64, 64, 128] state a token: 2.1 GB) the
  gradients of the filtered channels, the step sizes, the rates and
  the skips, with seeded and with slow decays (a state carried over every
  chunk), and a control with the state dropped at the chunk edges (read on
  the output without the skip's part), which must NOT pass; then both kernels' device microseconds a call at the
  cell's whole shape, by name off a trace, beside the least HBM's bytes
  allow.
- ``norm``: ``kernels/gated_norm.py``'s ``gated_norm_fwd`` / ``gated_norm_bwd``
  at y [2, 8192, 4096] bf16 in 8 groups of 512 with z at lane 6,144 of the
  packed projection [2, 8192, 10240], against the ``jnp`` lines they replace
  (``gated_norm_reference``) in float32: the output and the gradients of y,
  z and ``gate_norm``, each no further from float32 than the lines' own bf16
  path is; a control with the statistic over the whole row (one group),
  which must NOT pass; then both kernels' device microseconds a call by
  name off a trace, beside the lines' whole program and the least HBM's
  bytes allow (three arrays of y's size forward, five backward).
- ``experts``: the ungated grouped matmuls at the width 1,856 (no whole
  number of lane tiles), 15,360 rows in 16 groups of E = 2,688, through
  ``moe._grouped_matmul`` (``down(relu(up x)^2)``, forward and the
  gradients of rows and both weights) against ``jax.lax.ragged_dot`` in
  float32 at ``highest`` precision, and the six calls' tiles.
- ``flash``: the packed flash kernels at 32 query heads on 2 key/value heads
  of 128 (a group of 16), [2, 2048], causal, against a masked softmax in
  float32, forward and the gradients of q, k and v.

Exit 1 where a reading is off or a kernel's name matched nothing in the
trace, 2 off a TPU."""

import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.flops import nemotron_h_train  # noqa: E402
from paddle_tpu.kernels import gated_norm as gn, ssd_scan as ssd  # noqa: E402
from paddle_tpu.kernels.flash_attention import flash_attention_packed  # noqa: E402
from paddle_tpu.parallel import moe  # noqa: E402

B, S, HEADS, P, G, N, CHUNK = 2, 8192, 64, 64, 8, 128, 128
S_COMPARED = 512        # the recurrence's gradient keeps a state a token
D = HEADS * P
SHAPE = dict(heads=HEADS, groups=G, d_state=N, chunk=CHUNK)
# bf16 operands of four chained products and a bf16 output against float32;
# the rates' gradient is a sum over every token of both signs
LIMIT, RATE_LIMIT = 1e-2, 3e-2
CALLS = 5
NAMES = ("xbc", "dt", "a", "d_skip")
KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")
# one rounding to bf16 of the output and of two gradients
NORM_LIMIT, EPS = 3e-3, 1e-5
NORM_NAMES = ("out", "dy", "dz", "dgate_norm")
NORM_KERNELS = ("gated_norm_fwd", "gated_norm_bwd")


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def recurrence(xbc, dt, a, d_skip, drop=0):
    """The per-token recurrence in float32; ``drop``: the state zeroed at
    every multiple of ``drop`` tokens (the control)."""
    b, s, _ = xbc.shape
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., :D].reshape(b, s, HEADS, P)
    bm, cm = (jnp.repeat(t.reshape(b, s, G, N), HEADS // G, axis=2)
              for t in (xbc[..., D:D + G * N], xbc[..., D + G * N:]))

    def token(h, turn):
        x_t, dt_t, b_t, c_t, t = turn
        if drop:
            h = jnp.where(t % drop == 0, jnp.zeros_like(h), h)
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t) + d_skip[:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((b, HEADS, P, N), jnp.float32),
        tuple(t.swapaxes(0, 1) for t in (x, dt, bm, cm)) + (jnp.arange(s),))
    return y.swapaxes(0, 1).reshape(b, s, D)


def operands(seed, dt_scale, s):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xbc = (0.5 * jax.random.normal(ks[0], (B, s, D + 2 * G * N))).astype(
        jnp.bfloat16)
    dt = dt_scale * jnp.exp(jax.random.uniform(
        ks[1], (B, s, HEADS), minval=np.log(1e-3), maxval=np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (HEADS,), minval=1.0, maxval=16.0)
    return (xbc, dt, a, jnp.ones((HEADS,), jnp.float32)), \
        jax.random.normal(ks[3], (B, s, D))


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


def scan_receipt(out):
    ok = True
    kernels = jax.jit(lambda *o: ssd.ssd_scan(*o, **SHAPE))

    def exact(fn):
        """``fn`` jitted with float32 products (the reference alone: the
        kernels' bf16 operands take the chip's own matmul)."""
        def run(*o):
            with jax.default_matmul_precision("highest"):
                return fn(*o)
        return jax.jit(run)

    for label, scale in (("seeded", 1.0), ("slow_decay", 0.05)):
        args, w = operands(11, scale, S_COMPARED)

        def loss(fn):
            return lambda *o: jnp.sum(fn(*o).astype(jnp.float32) * w)

        want = exact(recurrence)(*args)
        want_g = exact(jax.grad(loss(recurrence), argnums=(0, 1, 2, 3)))(
            *args)
        got = kernels(*args)
        got_g = jax.jit(jax.grad(loss(kernels), argnums=(0, 1, 2, 3)))(*args)
        out["readings"]["scan.%s.out" % label] = _rel(got, want)
        for name, g, wg in zip(NAMES, got_g, want_g):
            out["readings"]["scan.%s.d%s" % (label, name)] = _rel(g, wg)
        del want_g, got_g
        # the output over the cell's whole length: 63 chunk edges
        args, _ = operands(13, scale, S)
        got = kernels(*args)
        out["readings"]["scan.%s.out_8192" % label] = _rel(
            got, exact(recurrence)(*args))
        if label == "slow_decay":
            # without the skip's part (d_skip = 1: most of the output)
            skipped = args[0][..., :D].astype(jnp.float32)
            out["control_state_dropped"] = _rel(
                got.astype(jnp.float32) - skipped, exact(
                    lambda *o: recurrence(*o, drop=CHUNK))(*args) - skipped)
    for key, reading in out["readings"].items():
        if key.startswith("scan."):
            limit = RATE_LIMIT if key.endswith(".da") else LIMIT
            print(key, reading, "limit", limit, flush=True)
            ok = ok and reading <= limit
    ok = ok and out["control_state_dropped"] > LIMIT
    print("control (state dropped at chunk edges):",
          out["control_state_dropped"], flush=True)
    args, w = operands(12, 1.0, S)
    g = w.astype(jnp.bfloat16)

    def both(*o):
        y, vjp = jax.vjp(lambda *q: ssd.ssd_scan(*q, **SHAPE), *o)
        return (y,) + vjp(g)

    by_name = device_us(jax.jit(both), args)
    took = {k: sum(us for n, us in by_name.items() if k in n)
            for k in KERNELS}
    took["all"] = sum(by_name.values())
    model = {"mamba_num_heads": HEADS, "mamba_head_dim": P, "n_groups": G,
             "ssm_state_size": N, "conv_kernel": 4, "chunk_size": CHUNK,
             "hidden_size": 2688}
    need = nemotron_h_train.ssd_scan(model, B * S)
    took["least_us_by_bytes"] = need["bytes"] / 819e9 * 1e6
    took["least_us_by_flops"] = need["flops"] / 197e12 * 1e6
    took["kept_states_mb"] = ssd.kept_state_bytes(B, S, CHUNK, D, N, 2) / 1e6
    out["device_us"]["scan"] = took
    print("scan, device us a call (forward + backward): %s" % json.dumps(
        took), flush=True)
    return ok and all(took[k] > 0 for k in KERNELS)


def norm_operands(seed, s=S, d=D, packed=D + 2 * G * N + D):
    """(y, the packed projection with z its last d lanes, the scale), the
    output's cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return ((jax.random.normal(ks[0], (B, s, d)).astype(jnp.bfloat16),
             jax.random.normal(ks[1], (B, s, packed)).astype(jnp.bfloat16),
             1.0 + 0.2 * jax.random.normal(ks[2], (d,))),
            jax.random.normal(ks[3], (B, s, d)).astype(jnp.bfloat16))


def norm_programs(groups=G):
    """{"kernel", "jnp", "float32"}: jitted ``(y, packed, scale, g) -> (out,
    dy, dz, dgate_norm)``, dz of the gate's lanes alone: the kernels on the
    packed projection, the lines they replace behind its slice, and those
    lines on float32 copies."""
    def program(norm, cast):
        def run(y, packed, scale, g):
            d = y.shape[-1]
            out, vjp = jax.vjp(norm, cast(y), cast(packed), scale)
            dy, dz, dscale = vjp(g.astype(out.dtype))
            return out, dy, dz[..., -d:], dscale
        return jax.jit(run)

    lines = lambda y, packed, scale: gn.gated_norm_reference(
        y, packed[..., -y.shape[-1]:], scale, groups, EPS)
    return {"kernel": program(lambda *o: gn.gated_norm(
                *o, groups=groups, eps=EPS), lambda t: t),
            "jnp": program(lines, lambda t: t),
            "float32": program(lines, lambda t: t.astype(jnp.float32))}


def norm_receipt(out):
    programs = norm_programs()
    args, g = norm_operands(17)
    want = programs["float32"](*args, g)
    got, old = programs["kernel"](*args, g), programs["jnp"](*args, g)
    ok = True
    for name, a, o, w in zip(NORM_NAMES, got, old, want):
        new, lines = _rel(a, w), _rel(o, w)
        out["readings"]["norm." + name] = new
        out["readings"]["norm_lines." + name] = lines
        print("norm." + name, new, "the lines'", lines, "limit", NORM_LIMIT,
              flush=True)
        ok = ok and new <= NORM_LIMIT and new <= 1.02 * lines + 1e-6
    out["control_one_group"] = _rel(
        got[0], norm_programs(groups=1)["float32"](*args, g)[0])
    print("control (the statistic over the whole row):",
          out["control_one_group"], flush=True)
    ok = ok and out["control_one_group"] > NORM_LIMIT
    del want, got, old
    by_name = device_us(programs["kernel"], args + (g,))
    took = {k: sum(us for n, us in by_name.items() if k in n)
            for k in NORM_KERNELS}
    took["all"] = sum(by_name.values())
    took["jnp_all"] = sum(device_us(programs["jnp"], args + (g,)).values())
    array = B * S * D * 2
    took["least_us_by_bytes"] = {"gated_norm_fwd": 3 * array / 819e9 * 1e6,
                                 "gated_norm_bwd": 5 * array / 819e9 * 1e6}
    rows = gn.block_rows(S, D // G, 2)
    took["blocks"] = [rows, D // G, gn.walk_rows(rows, D // G, 2)]
    out["device_us"]["norm"] = took
    print("norm, device us a call (forward + backward): %s" % json.dumps(
        took), flush=True)
    return ok and all(took[k] > 0 for k in NORM_KERNELS)


def experts_receipt(out):
    rows, groups, E, F = 15360, 16, 2688, 1856
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (rows, E)).astype(jnp.bfloat16)
    w_up = (jax.random.normal(ks[1], (groups, E, F)) * E ** -0.5).astype(
        jnp.bfloat16)
    w_down = (jax.random.normal(ks[2], (groups, F, E)) * F ** -0.5).astype(
        jnp.bfloat16)
    # uneven groups, one empty, rows past the last group
    sizes = jnp.asarray([768, 1024, 0, 512, 900, 1111, 640, 777, 768, 768,
                         1500, 333, 1024, 1200, 768, 999], jnp.int32)
    held = int(sizes.sum())
    weigh = jax.random.normal(ks[3], (rows, E))
    weigh = jnp.where(jnp.arange(rows)[:, None] < held, weigh, 0.0)

    def ffn(matmul, cast):
        """``(y, gradients)`` of the rows that lie in a group."""
        def f(x, w_up, w_down):
            hidden = jnp.square(jax.nn.relu(matmul(
                cast(x), cast(w_up), sizes).astype(jnp.float32)))
            y = matmul(cast(hidden.astype(x.dtype)), cast(w_down), sizes)
            y = jnp.where(jnp.arange(rows)[:, None] < held,
                          y.astype(jnp.float32), 0.0)
            return jnp.sum(y * weigh), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, got_y), got = ffn(moe._grouped_matmul, lambda t: t)(x, w_up, w_down)
    with jax.default_matmul_precision("highest"):
        (_, want_y), want = ffn(
            jax.lax.ragged_dot, lambda t: t.astype(jnp.float32))(
                x, w_up, w_down)
    out["readings"]["experts.out"] = _rel(got_y, want_y)
    live = np.arange(rows) < held
    for name, g, w in zip(("dx", "dw_up", "dw_down"), got, want):
        if name == "dx":
            g, w = np.asarray(g, np.float32)[live], np.asarray(w)[live]
        out["readings"]["experts." + name] = _rel(g, w)
    out["tilings"] = {
        "%s %dx%d" % (kernel, k, n): list(moe._tiling(rows, k, n, groups, 2,
                                                      dw=kernel == "tgmm"))
        for kernel, k, n in (("gmm", E, F), ("gmm", F, E), ("tgmm", E, F),
                             ("tgmm", F, E))}
    print("tilings:", json.dumps(out["tilings"]), flush=True)
    ok = True
    for key, reading in out["readings"].items():
        if key.startswith("experts."):
            print(key, reading, flush=True)
            ok = ok and reading <= LIMIT
    return ok


def flash_receipt(out):
    b, s, H, KV, dh = 2, 2048, 32, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, s, H * dh)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, KV * dh)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, KV * dh)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, s, H * dh))

    def kernel(q, k, v):
        return flash_attention_packed(q, k, v, H, causal=True, block_q=512,
                                      block_k=512, n_kv_heads=KV)

    def plain(q, k, v):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        qh = q.reshape(b, s, H, dh)
        kh, vh = (jnp.repeat(t.reshape(b, s, KV, dh), H // KV, axis=2)
                  for t in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(dh)
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(b, s, H * dh)

    def loss(fn):
        return lambda *o: jnp.sum(fn(*o).astype(jnp.float32) * w)

    got = jax.jit(kernel)(q, k, v)
    got_g = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(q, k, v)
        want_g = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2)))(q, k, v)
    out["readings"]["flash.out"] = _rel(got, want)
    for name, g, wg in zip(("dq", "dk", "dv"), got_g, want_g):
        out["readings"]["flash." + name] = _rel(g, wg)
    ok = True
    for key, reading in out["readings"].items():
        if key.startswith("flash."):
            print(key, reading, flush=True)
            ok = ok and reading <= LIMIT
    return ok


def main(*argv):
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU")
        return 2
    argv, only = list(argv), ("scan", "norm", "experts", "flash")
    if "--only" in argv:
        at = argv.index("--only")
        only = (argv[at + 1],)
        del argv[at:at + 2]
    out_path = argv[0] if argv else None
    out = {"device_kind": jax.devices()[0].device_kind, "readings": {},
           "device_us": {}}
    ok = True
    for name, receipt in (("scan", scan_receipt), ("norm", norm_receipt),
                          ("experts", experts_receipt),
                          ("flash", flash_receipt)):
        if name in only:
            ok = receipt(out) and ok
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
