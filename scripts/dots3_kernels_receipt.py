"""dots3-note-prev's kernel modes as Mosaic compiles them, each ALONE against
the float32 formula, at the cell's shape:

    chiprun -- python3 scripts/dots3_kernels_receipt.py [--seq 8192] [--block 512] [--seed 0]
        [--only rows] [--times-only]

A full layer: q and k [1, S, 32 x 256] (heads of 192, zeros behind them), v
[1, S, 32 x 128], an indexer of 64 heads of 128, the 2,048 best keys a row
(a quarter of a shorter ``--seq``).  A sliding layer: q and k [1, S, 16 x
256], v [1, S, 16 x 128], a window of 513.  Holds ``indexer_scores``
(forward, and dq / dk / dw from a random dI), the masked online sweep's
statistic (``dsa_lse``: ``flash_dsa_fwd``, a head a step, no value read),
the pass with the statistic known (``dsa_attend_kl``: o, the KL term, dq /
dk / dv from a random do by the masked backward sweep
``flash_dsa_bwd_fused``, and the KL's gradient of the scores' operands), the windowed flash mode at a value
width of its own (o, dq / dk / dv) and the rotation of a head's first 64
columns by the row kernel, to the formulas computed in float32 at
``highest`` precision on the same bf16 operands, a block of query rows at a
time (nothing [heads, S, S] stands); and, the CONTROLS, the masked output
against the formula WITHOUT the selection and the windowed output against
the formula at a window of 512, which must be far off.  Each reading is the
largest absolute difference over the largest absolute value of the
formula's result.  Then ``seconds``: the host's clock around each call
alone, jitted, the mean of five after one.  Writes
``chiprun_out/pr64/dots3_kernels_receipt.json``; off a chip (interpret
mode) give a short ``--seq``.

``--only rows`` (PR 66): the row kernel at a head of TWO lane blocks
(``kernels/qk_rope.py``, what ``_latent_qkv_lanes`` calls) ALONE, at ``ROWS_
SHAPES``: a full layer's q and k [1, S, 32 x 256] (a head ``[128 | 64
rotated | 64 zero]``), a sliding layer's [1, S, 16 x 256] (``[192 | 64
rotated]``) and Kimi-Linear's k [1, 2 S, 32 x 256] (the shared key added,
no positions).  Readings: output and gradients against the ``rope_pairs``
lines and the broadcast add in float32 on the same bf16 operands
(``rows_*``), and the largest absolute difference from the SAME lines in
bf16, the parent's path (``rows_*_vs_lines``: the forward 0, one rounding
both ways).  ``device_us``: forward + backward under ``jax.vjp`` by a
profiler trace, everything the device ran, the lines beside the kernel, the
two kernels by name (in this standalone program XLA copies x and dy, which
are its arguments, before the aliased calls: ``other``; in the step both
are temporaries), and the least the bytes allow were the whole rows moved (x
read and written each way at 819 GB/s; the kernels move a head's touched
lane blocks alone, half of it); ``--times-only`` skips the readings; then
the full layer's q behind its matmul, which says WHICH rounding the step's
witness moved by (``rows_after_matmul_*``), and Kimi-Linear's whole latent
projection, ``_latent_qkv_lanes`` without positions, as the parent's lines,
with the kernel alone and with the kernel and ``_project`` as shipped, which
says which of the two moved that cell's (``kimi_layer_*``).  Writes
``chiprun_out/pr66/dots3_rows_receipt.json``; exit 1 where a reading is
off, 2 for times off a TPU."""

import argparse
import json
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

from paddle_tpu.kernels import indexer as ix  # noqa: E402
from paddle_tpu.kernels.flash_attention import (  # noqa: E402
    flash_attention_packed)
from paddle_tpu.parallel import transformer as T  # noqa: E402

H, HS, LANES, D, DV, HI, DI, WINDOW, THETA = 32, 16, 256, 192, 128, 64, 128, \
    513, 8e7
ROWS = 128          # query rows of the formulas' blocks


def formulas(S):
    at = jnp.arange(S)

    def by_blocks(block, *operands):
        """``block(first, *operands)`` of each ROWS rows, put together; at
        ``highest`` precision (the kernels beside them keep the device's
        own: a context around both would hand Mosaic float32 passes)."""
        with jax.default_matmul_precision("highest"):
            out = jax.lax.map(jax.checkpoint(lambda first: block(
                first, *operands)), jnp.arange(0, S, ROWS))
        return jax.tree.map(lambda a: a.reshape((S,) + a.shape[2:]), out)

    def rows_of(x, first):
        return jax.lax.dynamic_slice_in_dim(x, first, ROWS, 0)

    def scores(qi, ki, w):
        """[S, S] float32, -inf above the diagonal."""
        def block(first, qi, ki, w):
            s = jnp.einsum("thd,sd->ths", rows_of(qi, first).reshape(
                ROWS, HI, DI), ki)
            out = jnp.einsum("th,ths->ts", rows_of(w, first), jax.nn.relu(s))
            return jnp.where(at[None] <= (first + jnp.arange(ROWS))[:, None],
                             out, -jnp.inf)
        return by_blocks(block, *(x.astype(jnp.float32)
                                  for x in (qi, ki, w)))

    def attend(q, k, v, keep, heads, width):
        """(o [S, heads * DV], lse [heads, S], mean probabilities [S, S])
        of the dense softmax over ``keep(first)`` [ROWS, S]."""
        def block(first, q, k, v):
            s = jnp.einsum("thd,shd->hts", rows_of(q, first).reshape(
                ROWS, heads, LANES), k.reshape(S, heads, LANES)) \
                * width ** -0.5
            s = jnp.where(keep(first)[None], s, -jnp.inf)
            a = jax.nn.softmax(s, -1)
            return (jnp.einsum("hts,shd->thd", a, v.reshape(
                S, heads, DV)).reshape(ROWS, -1),
                jax.nn.logsumexp(s, -1).T, jnp.mean(a, 0))
        return by_blocks(block, *(x.astype(jnp.float32) for x in (q, k, v)))

    return scores, attend, rows_of, at, by_blocks


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / 5


# name: (positions over --seq, heads, (plain, rotated, tail) lanes, rotary)
ROWS_SHAPES = {"full": (1, H, (128, 64, 64), True),
               "sliding": (1, HS, (192, 64, 0), True),
               "kimi": (2, H, (128, 64, 64), False)}
HBM = 819e9


def rows_lines(x, ks, heads, lanes, freqs, rotary, f32=False):
    """``_latent_qkv_lanes``' lines on the packed q (``ks`` None) or on the
    keys' matmul: the shared key rotated apart, rounded, padded and added
    through the [b, S, H, lanes] view; q turned on the flat array.  ``f32``:
    nothing rounded."""
    plain, dr, tail = lanes
    b, S, _ = x.shape
    wide = jnp.float32
    dtype = wide if f32 else x.dtype
    ang = jnp.arange(S, dtype=wide)[:, None] * jnp.asarray(freqs, wide)[None]
    if ks is None:
        return T.rope_pairs(x.astype(wide), jnp.pad(
            ang, ((0, 0), (plain // 2, tail // 2))), tiles=heads).astype(
                dtype)
    if rotary:
        ks = T.rope_pairs(ks.astype(wide), ang)
    return (x.astype(dtype).reshape(b, S, heads, sum(lanes)) + jnp.pad(
        ks.astype(dtype), ((0, 0), (0, 0), (plain, tail)))[:, :, None, :]
            ).reshape(b, S, -1)


def rows_kernel(x, ks, heads, lanes, freqs, rotary):
    from paddle_tpu.kernels import qk_rope as K

    plain, dr, tail = lanes
    tables = K.pair_tables(x.shape[1], freqs, sum(lanes), tail=tail) \
        if rotary else None
    return K.qk_rope(
        x, None, tables, head_dim=sum(lanes), pairs=True,
        plain_blocks=plain // K.LANES, shared=None if ks is None else jnp.pad(
            ks, ((0, 0), (0, 0), (plain, tail))))


def rows_device_us(fn, args, iters=10):
    """(microseconds a call of everything on the device, {name: us})."""
    from benchmark.harness import trace_reduce, tracing

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tmp:
        tracing._start(tmp, 0)
        for _ in range(iters):
            got = fn(*args)
        jax.block_until_ready(got)
        jax.profiler.stop_trace()
        dev = trace_reduce.Reduced(trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp))).devices[0]
    names = {}
    for name, t in dev["by_name"].items():
        key = re.search(r"qk_rope_(fwd|bwd)|$", name).group() or "other"
        names[key] = names.get(key, 0.0) + t / iters / 1e3
    return sum(names.values()), names


def rows_readings(out, key, got, old, exact, which, shape, plain):
    """The kernel's output and gradients ``got`` beside the bf16 lines'
    ``old``, both against the float32 lines' ``exact``; whether one is
    off."""
    # a key's dx counts in its own lanes: the others meet the zero columns
    # of the keys' matrix
    cut = (lambda a: a) if which == "q" else (
        lambda a: a.reshape(shape)[..., :plain])
    bad = False
    for part, a, o, e in zip(("o", "dx", "dks"), got, old, exact):
        if part == "dx":
            a, o, e = cut(a), cut(o), cut(e)
        a, o, e = (np.asarray(t, np.float32) for t in (a, o, e))
        top = np.max(np.abs(e))
        err, err_lines = (float(np.max(np.abs(t - e)) / top) for t in (a, o))
        out["readings"]["rows_%s_%s" % (key, part)] = err
        out["readings"]["rows_%s_%s_vs_lines" % (key, part)] = \
            float(np.max(np.abs(a - o)))
        print("rows", key, part, err, "lines", err_lines, "kernel - lines",
              float(np.max(np.abs(a - o))), flush=True)
        # one rounding of a float32 result, as the lines'
        bad |= err > max(1.001 * err_lines, 2 ** -8)
    return bad


def rows_after_a_matmul(args, out, r):
    """Which rounding moves where the step's witness is not the parent's to
    the bit: a full layer's q as the STEP makes it, ``rows [1, S, 1024] @ wq
    [1024, 32 x 256]`` in front of the rotation.  The lines in one jitted
    program (XLA may fuse the matmul's float32 result into the rotation and
    never round it to bf16: ``xla_allow_excess_precision``), the same lines
    behind an ``optimization_barrier`` on the bf16 product, and the kernel,
    which reads the rounded product as the program's text says: the largest
    absolute differences."""
    heads, lanes = H, (128, 64, 64)
    S, width = args.seq, 256
    freqs = THETA ** (-np.arange(32) / 32)
    rows = jnp.asarray(r.randn(1, S, 1024), jnp.bfloat16)
    wq = jnp.where(jnp.tile(jnp.arange(width) < D, heads), jnp.asarray(
        r.randn(1024, heads * width) / 32, jnp.bfloat16), 0)
    static = (None, heads, lanes, freqs, True)
    fused = jax.jit(lambda a, w: rows_lines(a @ w, *static))(rows, wq)
    apart = jax.jit(lambda a, w: rows_lines(jax.lax.optimization_barrier(
        a @ w), *static))(rows, wq)
    kernel = jax.jit(lambda a, w: rows_kernel(a @ w, *static))(rows, wq)
    f32 = lambda a: np.asarray(a, np.float32)
    for name, a, b in (("lines_fused_vs_apart", fused, apart),
                       ("kernel_vs_lines_apart", kernel, apart),
                       ("kernel_vs_lines_fused", kernel, fused)):
        out["readings"]["rows_after_matmul_" + name] = float(
            np.max(np.abs(f32(a) - f32(b))))
        print("rows after a matmul", name,
              out["readings"]["rows_after_matmul_" + name],
              "values that differ", float(np.mean(f32(a) != f32(b))),
              flush=True)
    # on the chip to the bit (the CPU's fused multiply-adds move a value in
    # a hundred thousand by one rounding)
    return out["platform"] == "tpu" and out["readings"][
        "rows_after_matmul_kernel_vs_lines_apart"] != 0


def kimi_layer(args, out, r):
    """Which of this PR's two changes moves Kimi-Linear's witness, whose k
    the kernel assembles to the bit: ``_latent_qkv_lanes(rotary=False)`` at
    the cell's configuration, q, k, v and the gradients of h and of every
    weight from one random cotangent, (a) the parent's lines (``supported``
    false), (b) the kernel with plain matmuls (``_project`` replaced by ``h
    @ w``) and (c) the kernel and ``_project`` as shipped, whose backward
    rounds dX and dW to bf16 behind an ``optimization_barrier`` where XLA
    may otherwise hand a matmul's float32 result on.  The largest absolute
    differences and the share of values that differ, part by part."""
    from paddle_tpu.kernels import qk_rope as K
    from paddle_tpu.models.kimi_linear import kimi_linear_48b_a3b_config

    cfg = kimi_linear_48b_a3b_config(n_layers=5, experts_held=16,
                                     first_expert=0, vocab_size=20480)
    S, H, dn, dr = 2 * args.seq, cfg.heads_here, cfg.qk_nope_dim, \
        cfg.qk_rope_dim
    assert not cfg.q_lora_rank and T._latent_head_lanes(cfg) == LANES
    bf = lambda *shape: jnp.asarray(r.randn(*shape), jnp.bfloat16)
    weight = lambda rows, cols: jnp.asarray(
        r.randn(rows, cols) / rows ** 0.5, jnp.bfloat16)
    pl = {"wq": weight(cfg.hidden, H * (dn + dr)),
          "wkv_a": weight(cfg.hidden, cfg.kv_lora_rank + dr),
          "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
          "wkv_b": weight(cfg.kv_lora_rank, H * (dn + cfg.v_head_dim))}
    h = bf(1, S, cfg.hidden)
    gs = bf(1, S, H * LANES), bf(1, S, H * LANES), bf(1, S, H * DV)

    def run():      # a trace of its own each, under what is patched then
        def f(pl, h):
            out, pull = jax.vjp(lambda pl, h: T._latent_qkv_lanes(
                pl, h, cfg), pl, h)
            dpl, dh = pull(gs)
            return dict(zip("qkv", out), dh=dh, **{
                "d" + name: g for name, g in dpl.items()})
        return {name: np.asarray(a, np.float32)
                for name, a in jax.jit(f)(pl, h).items()}

    took, project = K.supported, T._project
    try:
        shipped = run()
        T._project = lambda h, w: h @ w
        kernel = run()
        K.supported = lambda *a: False
        lines = run()
    finally:
        K.supported, T._project = took, project
    for name, a, b in (("kernel_vs_lines", kernel, lines),
                       ("shipped_vs_kernel", shipped, kernel)):
        for part in a:
            out["readings"]["kimi_layer_%s_%s" % (name, part)] = float(
                np.max(np.abs(a[part] - b[part])))
            print("kimi layer", name, part, float(
                np.max(np.abs(a[part] - b[part]))), "values that differ",
                float(np.mean(a[part] != b[part])), flush=True)
    return False


def rows_receipt(args, out):
    """The row kernel at a head of two lane blocks: the module docstring's
    ``--only rows``.  Returns whether a reading is off."""
    from paddle_tpu.kernels import qk_rope as K

    r = np.random.RandomState(args.seed)
    on_chip = out["platform"] == "tpu"
    out["device_us"], bad = {}, False
    for name, (times, heads, lanes, rotary) in ROWS_SHAPES.items():
        S, (plain, dr, tail) = times * args.seq, lanes
        width = sum(lanes)
        freqs = THETA ** (-np.arange(dr // 2) / (dr // 2))
        bf = lambda *shape: jnp.asarray(r.randn(*shape), jnp.bfloat16)
        ks, g = bf(1, S, dr), bf(1, S, heads * width)
        for which in ("q", "k") if rotary else ("k",):
            # q's own columns; of a key the zero columns of the keys' matrix
            own = plain + dr if which == "q" else plain
            x = jnp.where(jnp.tile(jnp.arange(width) < own, heads),
                          bf(1, S, heads * width), 0)
            operands = (x,) if which == "q" else (x, ks)

            def both(fn, **kw):
                def f(*a):
                    o, pull = jax.vjp(lambda *a: fn(
                        *(a + (None,) * (2 - len(a))), heads, lanes, freqs,
                        rotary, **kw), *a)
                    return (o,) + pull(g.astype(o.dtype))
                return jax.jit(f)

            lines = both(rows_lines)
            key = "%s.%s" % (name, which)
            row = {"rows": K.touched_rows(S, 2)}
            kernel = both(rows_kernel)
            if not args.times_only:
                bad |= rows_readings(
                    out, key, kernel(*operands), lines(*operands),
                    both(rows_lines, f32=True)(*operands), which,
                    (1, S, heads, width), plain)
            if on_chip:
                row["kernel_us"], row["by_name"] = rows_device_us(
                    kernel, operands)
            if on_chip:
                row["lines_us"], _ = rows_device_us(lines, operands)
                row["least_us"] = 4 * S * heads * width * 2 / HBM * 1e6
                out["device_us"][key] = row
                print("rows", key, json.dumps(row), flush=True)
    if not args.times_only:
        bad |= rows_after_a_matmul(args, out, r)
        bad |= kimi_layer(args, out, r)
    print(json.dumps(out), flush=True)
    path = os.path.join(ROOT, "chiprun_out", "pr66",
                        "dots3_rows_receipt.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", choices=["rows"])
    ap.add_argument("--times-only", action="store_true")
    args = ap.parse_args(argv)
    if args.only == "rows":
        platform = jax.devices()[0].platform
        if args.times_only and platform != "tpu":
            print("no TPU: a CPU time is not a device time", file=sys.stderr)
            return 2
        return int(rows_receipt(args, {
            "seq": args.seq, "platform": platform, "readings": {}}))
    S, topk = args.seq, min(2048, args.seq // 4)
    blocks = dict(block_q=args.block, block_k=args.block)
    masked = dict(blocks, scale=D ** -0.5, v_head_dim=DV)
    r = np.random.RandomState(args.seed)
    bf = lambda *shape: jnp.asarray(r.randn(*shape), jnp.bfloat16)
    head = jnp.tile(jnp.arange(LANES) < D, H)
    q, k = (jnp.where(head, bf(1, S, H * LANES), 0) for _ in "qk")
    v, do = bf(1, S, H * DV), bf(1, S, H * DV)
    qi, ki, w = bf(1, S, HI * DI) / 8, bf(1, S, DI), \
        jnp.asarray(r.randn(1, S, HI), jnp.float32) / 8
    qs, ks, vs, dos = bf(1, S, HS * LANES), bf(1, S, HS * LANES), \
        bf(1, S, HS * DV), bf(1, S, HS * DV)
    d_scores = jnp.asarray(r.randn(1, S, S), jnp.float32)
    scores_f, attend_f, rows_of, at, by_blocks = formulas(S)
    out = {"seq": S, "topk": topk, "platform": jax.devices()[0].platform,
           "readings": {}, "seconds": {}}

    def reading(name, got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        ok = np.isfinite(want)
        assert np.array_equal(np.isfinite(got), ok), name
        out["readings"][name] = float(np.max(np.abs(got[ok] - want[ok]))
                                      / np.max(np.abs(want[ok])))
        print(name, out["readings"][name], flush=True)

    causal = lambda first: at[None] <= (first + jnp.arange(ROWS))[:, None]
    # the indexer's scores, 64 heads of 128
    tri = at[None] <= at[:, None]
    weigh = lambda fn: lambda *a: jnp.sum(jnp.where(
        tri, fn(*a) * d_scores[0], 0.0))
    kernel = jax.jit(lambda *a: ix.indexer_scores(*a, **blocks))
    got = kernel(qi, ki, w)
    want = jax.jit(scores_f)(qi[0], ki[0], w[0])
    reading("indexer_scores", got[0], want)
    g_got = jax.jit(jax.grad(weigh(lambda *a: ix.indexer_scores(
        *a, **blocks)[0]), (0, 1, 2)))(qi, ki, w)
    g_want = jax.jit(jax.grad(weigh(scores_f), (0, 1, 2)))(
        qi[0], ki[0], w[0])
    for n, a, b in zip(("dq", "dk", "dw"), g_got, g_want):
        reading("indexer_scores_" + n, a[0], b)
    # the selection, the statistic and the pass that knows it
    tau = jax.jit(lambda s: ix.kth_largest(s, topk))(got)
    assert not np.any(np.isfinite(np.asarray(tau[0][:topk - 1])))
    reading("kth_largest", tau[0][topk - 1:], jax.lax.top_k(
        want[topk - 1:], topk)[0][:, -1])
    selected = lambda first: causal(first) & (
        rows_of(got[0], first) >= rows_of(tau[0], first)[:, None])
    statistic = jax.jit(lambda *a: ix.dsa_lse(
        *a, got, tau, H, scale=masked["scale"], **blocks))
    lse = statistic(q, k)
    lse_i = jax.jit(ix.selected_lse)(got, tau)
    fused = lambda q, k, v, *indexer: ix.dsa_attend_kl(
        q, k, v, indexer, got, tau, lse, lse_i, H, **masked)
    (o, kl), pull = jax.vjp(jax.jit(fused), q, k, v, qi, ki, w)
    o_want, lse_want, p = jax.jit(lambda *a: attend_f(
        *a, selected, H, D))(q[0], k[0], v[0])
    reading("dsa_lse", lse[0], lse_want.T)
    reading("dsa_attend_kl_o", o[0], o_want)
    from_o = pull((do, jnp.zeros(())))
    d_want = jax.jit(jax.grad(lambda *a: jnp.sum(attend_f(
        *a, selected, H, D)[0] * do[0].astype(jnp.float32)),
        (0, 1, 2)))(q[0], k[0], v[0])
    for n, a, b in zip("qkv", from_o, d_want):
        reading("dsa_attend_kl_d" + n, a[0], b)

    def kl_of(qi, ki, w):
        """The KL term of the rows' scores against the heads' mean
        probabilities ``p``, the selection the kernel's own (a constant); a
        block of rows at a time."""
        def block(first, qi, ki, w):
            s = jnp.einsum("thd,sd->ths", rows_of(qi, first).reshape(
                ROWS, HI, DI), ki)
            i = jnp.einsum("th,ths->ts", rows_of(w, first), jax.nn.relu(s))
            keep = causal(first) & (rows_of(got[0], first)
                                    >= rows_of(tau[0], first)[:, None])
            log_r = jax.nn.log_softmax(jnp.where(keep, i, -jnp.inf), -1)
            pb = rows_of(p, first)
            return jnp.sum(jnp.where(pb > 0, pb * (
                jnp.log(jnp.where(pb > 0, pb, 1.0))
                - jnp.where(keep, log_r, 0.0)), 0.0), -1)
        return jnp.sum(by_blocks(block, *(x.astype(jnp.float32)
                                          for x in (qi, ki, w)))) / S
    kl_want, kl_d = jax.jit(jax.value_and_grad(kl_of, (0, 1, 2)))(
        qi[0], ki[0], w[0])
    out["readings"]["dsa_attend_kl_kl"] = float(abs(kl - kl_want)
                                                / abs(kl_want))
    for n, a, b in zip(("dqi", "dki", "dw"),
                       pull((jnp.zeros_like(do), jnp.ones(())))[3:],
                       kl_d):
        reading("dsa_attend_kl_" + n, a[0], b)
    reading("CONTROL_no_selection", o[0], jax.jit(lambda *a: attend_f(
        *a, causal, H, D)[0])(q[0], k[0], v[0]))
    # the windowed mode at 256 / 128
    window = lambda *a: flash_attention_packed(
        *a, HS, causal=True, window=WINDOW, v_head_dim=DV, **blocks)
    band = lambda width: lambda first: causal(first) & (
        (first + jnp.arange(ROWS))[:, None] - at[None] < width)
    os_, pull = jax.vjp(jax.jit(window), qs, ks, vs)
    reading("flash_swa_o", os_[0], jax.jit(lambda *a: attend_f(
        *a, band(WINDOW), HS, LANES)[0])(qs[0], ks[0], vs[0]))
    d_want = jax.jit(jax.grad(lambda *a: jnp.sum(attend_f(
        *a, band(WINDOW), HS, LANES)[0] * dos[0].astype(jnp.float32)),
        (0, 1, 2)))(qs[0], ks[0], vs[0])
    for n, a, b in zip("qkv", pull(dos), d_want):
        reading("flash_swa_d" + n, a[0], b)
    reading("CONTROL_window_512", os_[0], jax.jit(lambda *a: attend_f(
        *a, band(WINDOW - 1), HS, LANES)[0])(qs[0], ks[0], vs[0]))
    # a head's first 64 columns through the row kernel
    turned = jax.jit(lambda x: T._rope_first_columns(x, DI, 64, THETA))(
        qi)
    heads = qi.astype(jnp.float32).reshape(1, S, HI, DI)
    reading("rope_first_columns", turned, jnp.concatenate([T.rope(
        heads[..., :64].reshape(1, S, -1), HI, THETA).reshape(
            1, S, HI, 64), heads[..., 64:]], -1).reshape(qi.shape))
    # the host's clock around each call alone
    sec = out["seconds"]
    sec["indexer_scores_fwd"] = timed(kernel, qi, ki, w)
    sec["indexer_scores_fwd_and_bwd"] = timed(jax.jit(jax.grad(
        lambda *a: jnp.sum(ix.indexer_scores(*a, **blocks) * jnp.where(
            tri, d_scores, 0.0)), (0, 1, 2))), qi, ki, w)
    sec["kth_largest"] = timed(jax.jit(lambda s: ix.kth_largest(s, topk)),
                               got)
    sec["dsa_lse"] = timed(statistic, q, k)
    sec["dsa_attend_kl_fwd"] = timed(jax.jit(fused), q, k, v, qi, ki, w)
    sec["dsa_attend_kl_fwd_and_bwd"] = timed(jax.jit(jax.grad(
        lambda *a: jnp.sum(fused(*a)[0].astype(jnp.float32)
                           * do.astype(jnp.float32)) + fused(*a)[1],
        (0, 1, 2, 3, 4, 5))), q, k, v, qi, ki, w)
    sec["flash_swa_fwd"] = timed(jax.jit(window), qs, ks, vs)
    sec["flash_swa_fwd_and_bwd"] = timed(jax.jit(jax.grad(
        lambda *a: jnp.sum(window(*a).astype(jnp.float32)
                           * dos.astype(jnp.float32)), (0, 1, 2))),
        qs, ks, vs)
    print(json.dumps(out), flush=True)
    path = os.path.join(ROOT, "chiprun_out", "pr64",
                        "dots3_kernels_receipt.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
