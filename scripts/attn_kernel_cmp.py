"""Standalone attention-kernel timings at one shape, on the chip.

    chiprun -- python3 scripts/attn_kernel_cmp.py --batch 256 --seq 128 \
        [--heads 12 --head-dim 64 --block 512 --causal] \
        [--kv-heads 4 --window 4096] [--v-head-dim 128] \
        [--tree _checkout/parent] [--two-sweeps] [--force 1x1,4x1,1x6] \
        [--heads-a-step 1,2] [--others]

Times ``flash_attention_packed`` (the entry the models call), forward and
backward, by DEVICE time per kernel name read from a profiler trace, as the
benchmark reads ``flash_roofline`` (``flash_fwd``, ``flash_bwd_fused``, ...),
beside the least time the chip could take by the benchmark's own formula.
``--kv-heads`` (grouped queries) and ``--window`` give the sparse decoders'
shapes (1 x 16384, 28 on 4 heads of 128, full and W = 4096; 2 x 8192, 32 on
8 of 64; 4 x 4096, 16 of 128); ``--tree DIR`` times the kernels of another
checkout (a parent commit unpacked beside this one) with this script, and
the ``digest`` of the outputs' bytes says whether two trees' kernels gave
the same numbers bit for bit.  ``--two-sweeps`` gives the several-block
backward no VMEM for its whole-sequence accumulators (``SWEEP_VMEM`` = 0
for this process), so that it runs ``flash_bwd_dq`` and ``flash_bwd_dkv``
as a sequence past the rule does.
``--force GxHg,...`` also times the kernels with the grid step's geometry
forced to G batch rows by Hg head-blocks (``step_geometry`` replaced for
that compile: an experiment of this script, not an option of the program)
and holds every output to the unforced one.  ``--heads-a-step n,...`` does
the same for the query head-blocks that ride one grid step of the
several-block sweeps (``heads_a_step`` replaced: the most of n or fewer
that divide the group, or, where the queries are not grouped, the row's
head-blocks, each with k and v of its own since PR 70, and whose step fits
``SWEEP_VMEM`` by the kernels' own count, so a backward whose accumulators
do not fit n heads takes fewer; 1 is the step before PR 68; grouped, dk
and dv sum the group in another order, so their last bits may differ from
the rule's; ungrouped, every output is the one-head step's bit for bit).
A several-block call's line says what a step holds (heads a step, forward
/ backward), its grid steps a layer and pass, and the microseconds a
(tile, head) forward and backward.  ``--v-head-dim`` gives the values a
width of their own (Kimi-Linear's latent layer: ``--head-dim 256
--v-head-dim 128``; the least time is then of heads as wide as the mean of
the two).  ``--others`` adds,
by host clock, the [B, S, H, D] entry, JAX's own TPU flash kernel and plain
XLA softmax attention.  Needs a TPU.
"""

import argparse
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_us(fn, args, iters, tmp):
    """{kernel name: microseconds an event} over ``iters`` traced calls."""
    import jax

    from benchmark.harness import trace_reduce, tracing

    jax.block_until_ready(fn(*args))
    tracing._start(tmp, 0)
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    dev = trace_reduce.Reduced(trace_reduce.load_xplane(
        trace_reduce.find_xplane(tmp))).devices[0]
    ns, calls = {}, {}
    for name, t in dev["by_name"].items():
        # under jax.vjp alone the instruction is `transpose_jvp_flash_..__.1`
        kernel = re.search(r"flash_[a-z_]*[a-z]|$", name).group() \
            or name.split(".")[0]
        ns[kernel] = ns.get(kernel, 0.0) + t
        calls[kernel] = calls.get(kernel, 0) + dev["count"][name]
    return {kernel: t / calls[kernel] / 1e3 for kernel, t in ns.items()}


def host_ms(name, fn, args, iters=30):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    print("  %-40s %8.3f ms (host clock)"
          % (name, (time.perf_counter() - t0) / iters * 1e3), flush=True)


def xla_attention(q, k, v, H, causal, window=None):
    """Plain softmax attention on the packed [B, S, H*D] layout (k, v at
    fewer heads: query head h reads key/value head h // group)."""
    import jax
    import jax.numpy as jnp

    B, S, E = q.shape
    q4 = q.reshape(B, S, H, E // H)
    Hkv = k.shape[-1] // (E // H)       # v's heads may have a width of their own
    k4, v4 = (jnp.repeat(t.reshape(B, S, Hkv, -1), H // Hkv, axis=2)
              for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4,
                   preferred_element_type=jnp.float32) * (E // H) ** -0.5
    if causal:
        seen = jnp.tril(jnp.ones((S, S), bool))
        if window:
            seen &= ~jnp.tril(jnp.ones((S, S), bool), -window)
        s = jnp.where(seen, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v4,
                      preferred_element_type=jnp.float32
                      ).astype(v.dtype).reshape(B, S, -1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--v-head-dim", type=int, default=0)
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--two-sweeps", action="store_true")
    ap.add_argument("--force", default="")
    ap.add_argument("--heads-a-step", default="")
    ap.add_argument("--vmem-mib", type=int, default=0)
    ap.add_argument("--others", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)                    # the benchmark's formulas
    sys.path.insert(0, os.path.abspath(args.tree))      # the kernels

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("attn_kernel_cmp: no TPU, nothing to measure", file=sys.stderr)
        return 2

    from benchmark.flops import flash_attention as need_of
    from benchmark.flops import flash_attention_gqa
    from benchmark.harness import flops, peaks
    import hashlib
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    B, S, H, D = args.batch, args.seq, args.heads, args.head_dim
    Hkv, window = args.kv_heads or H, args.window or None
    Dv = args.v_head_dim or D
    sparse = Hkv != H or window is not None      # the sparse decoders' modes
    key = jax.random.PRNGKey(0)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, n),
                                     (B, S, h * d), jnp.bfloat16)
                   for n, (h, d) in enumerate(((H, D), (Hkv, D), (Hkv, Dv),
                                               (H, Dv))))
    need = (flash_attention_gqa.required(B, S, H, Hkv, D, window) if sparse
            else need_of.required(B, S, H * (D + Dv) // 2,
                                  causal=args.causal))
    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    least = {p: flops.least_seconds(need[p]["flops"], need[p]["bytes"], peak)
             for p in ("fwd", "bwd")}
    print("%s: B=%d S=%d H=%d on %d D=%d block=%d causal=%s window=%s: least "
          "fwd %.1f us (%s), bwd %.1f us (%s)"
          % (os.path.relpath(fa.__file__, ROOT), B, S, H, Hkv, D, args.block,
             args.causal, window, least["fwd"][0] * 1e6, least["fwd"][1],
             least["bwd"][0] * 1e6, least["bwd"][1]))
    more = dict(n_kv_heads=Hkv, window=window) if sparse else {}
    if Dv != D:
        more["v_head_dim"] = Dv

    def both():
        def f(q, k, v, do):
            o, vjp = jax.vjp(lambda a, b, c: fa.flash_attention_packed(
                a, b, c, H, causal=args.causal, block_q=args.block,
                block_k=args.block, **more), q, k, v)
            return (o,) + vjp(do)
        return jax.jit(f)

    if args.two_sweeps:
        fa.SWEEP_VMEM = 0
    rule = getattr(fa, "step_geometry", None)   # a checkout before PR 28
    forced = [tuple(int(n) for n in g.split("x"))
              for g in args.force.split(",") if g] \
        + [int(n) for n in args.heads_a_step.split(",") if n]
    heads_rule = getattr(fa, "heads_a_step", None)  # a checkout before PR 68
    if args.vmem_mib:       # for a forced geometry over Mosaic's default scope
        import functools
        fa._CompilerParams = functools.partial(
            fa._CompilerParams, vmem_limit_bytes=args.vmem_mib * 2 ** 20)
    # XLA's attention holds the [S, S] scores: a few rows, and short ones
    n8 = min(B, 8) if S <= 4096 else 0
    ref = [np.asarray(x.astype(jnp.float32)) for x in jax.jit(
        lambda q, k, v, do: (lambda o, vjp: (o,) + vjp(do))(*jax.vjp(
            lambda a, b, c: xla_attention(a, b, c, H, args.causal, window),
            q, k, v))
    )(q[:n8], k[:n8], v[:n8], do[:n8])] if n8 else None
    want = None
    for geom in [None] + forced:
        if rule is None:
            pairs, steps = 1, -1
        else:
            if isinstance(geom, int):       # heads a step
                fa.heads_a_step = lambda group, need, most=None, n=geom: max(
                    d for d in range(1, min(n, group) + 1) if group % d == 0
                    and (d == 1 or need(d) <= fa.SWEEP_VMEM))
            else:
                fa.step_geometry = rule if geom is None else (
                    lambda *a, g=geom: g)
            try:
                grid = dict(n_kv_heads=Hkv, causal=args.causal, window=window)
                pairs, steps = fa.packed_grid(B, S, H, D, args.block,
                                              args.block, **grid)
            except TypeError:       # a checkout whose grids are not tables
                pairs, steps = fa.packed_grid(B, S, H, D, args.block,
                                              args.block, n_kv_heads=Hkv)
            if heads_rule is not None and S > args.block:
                back = fa.packed_grid(B, S, H, D, args.block, args.block,
                                      part="bwd", **grid)
                print("         heads a step: forward %d (%d grid steps a "
                      "layer), backward %d (%d)" % ((pairs, steps) + back))
        fn = both()
        t0 = time.perf_counter()
        got = [np.asarray(x.astype(jnp.float32)) for x in fn(q, k, v, do)]
        compiled = time.perf_counter() - t0
        with tempfile.TemporaryDirectory() as tmp:
            per = device_us(fn, (q, k, v, do), args.iters, tmp)
        if want is None:
            want = got
        worst = max(float(np.abs(a - b).max()) for a, b in zip(got, want))
        off = max(float(np.abs(a[:n8] - b).max())
                  for a, b in zip(got, ref)) if ref else float("nan")
        digest = hashlib.sha1(b"".join(a.tobytes() for a in got)).hexdigest()
        kernels = {n: per.pop(n) for n in list(per) if n.startswith("flash_")}
        fwd = sum(t for n, t in kernels.items() if n.endswith("fwd"))
        bwd = sum(kernels.values()) - fwd
        label = "rule" if geom is None else "%d heads" % geom \
            if isinstance(geom, int) else "%dx%d" % geom
        print("%-8s %2d pairs a step, %5d steps: fwd %8.1f us, bwd %8.1f us, "
              "roofline %5.1f %%; first call %.1f s; off the rule's by %.3g, "
              "off XLA's softmax attention by %.3g; digest %s"
              % (label, pairs, steps,
                 fwd, bwd,
                 100e6 * (least["fwd"][0] + least["bwd"][0]) / (fwd + bwd or 1e30),
                 compiled, worst, off, digest[:12]), flush=True)
        print("         by kernel: " + ", ".join(
            "%s %.1f us" % kv for kv in sorted(kernels.items())))
        if S > args.block and rule is not None:
            tiles = B * H * D // max(D, 128) * fa.kv_blocks(
                S, args.block, args.block, args.causal, window)
            print("         a (tile, head-block): forward %.3f us, backward "
                  "%.3f us over %d of them" % (fwd / tiles, bwd / tiles,
                                               tiles))
        print("         beside them: " + ", ".join(
            "%s %.1f" % kv for kv in sorted(per.items(), key=lambda kv: -kv[1])[:6]))
    if rule is not None:
        fa.step_geometry = rule
    if heads_rule is not None:
        fa.heads_a_step = heads_rule

    if args.others:
        others(args, q, k, v, do)
    return 0


def others(args, q, k, v, do):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as ref

    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    B, S, H, D = args.batch, args.seq, args.heads, args.head_dim
    sc = 1.0 / D ** 0.5
    blk = min(args.block, S)
    q4, k4, v4, do4 = (t.reshape(B, S, H, D) for t in (q, k, v, do))
    to_bhsd = lambda t: t.transpose(0, 2, 1, 3)

    def fwd_bwd(attn):
        def f(q, k, v, do):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o,) + vjp(do)
        return jax.jit(f)

    host_ms("ours packed fwd+bwd", fwd_bwd(
        lambda a, b, c: fa.flash_attention_packed(
            a, b, c, H, causal=args.causal, block_q=blk, block_k=blk)),
        (q, k, v, do))
    host_ms("ours [B,S,H,D] fwd+bwd", fwd_bwd(
        lambda a, b, c: fa.flash_attention(
            a, b, c, causal=args.causal, block_q=blk, block_k=blk)),
        (q4, k4, v4, do4))
    bs = ref.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
        block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
    host_ms("jax's TPU flash kernel fwd+bwd, [B,H,S,D]", fwd_bwd(
        lambda a, b, c: ref.flash_attention(
            a, b, c, causal=args.causal, sm_scale=sc, block_sizes=bs)),
        tuple(to_bhsd(t) for t in (q4, k4, v4, do4)))

    host_ms("xla softmax attention fwd+bwd", fwd_bwd(
        lambda a, b, c: xla_attention(a, b, c, H, args.causal)),
        (q, k, v, do))


if __name__ == "__main__":
    sys.exit(main())
