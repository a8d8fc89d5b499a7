"""Chip calibration: MXU Tflop/s on big matmuls, HBM GB/s, batched attention
matmul variants, and gather/scatter-add bandwidth at DeepFM table shapes.

The sparse probes are the receipts behind the DeepFM bench line's roofline
(ROADMAP item 3: the sparse path had NO measured ceiling — its autotuned
table-update variant won by timing, not by evidence it is bandwidth-bound).
Each probe reports an effective GB/s against a documented touched-bytes
model, and the ``sparse_roofline`` block derives a step-time floor and an
examples/s ceiling for the bench's DeepFM config from the MEASURED gather
and scatter bandwidths — the same honest-or-absent idiom as bench.py's
``_roofline`` (which derives the ceiling from XLA's analyzed bytes; this
script measures the bytes actually movable, so the two bound each other).

``--json out.json`` writes every probe row plus the derived roofline as a
machine-readable artifact, so derived sparse ceilings are reproducible
from a committed file instead of a transcript.  ``--probe`` selects a
subset (mxu / hbm / attn / sparse / all).
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_ROWS = []      # every probe row of this run, for --json


def timeit(name, fn, *args, iters=30, flops=None, bytes_=None):
    float(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        s = fn(*args)
    float(s)
    dt = (time.perf_counter() - t0) / iters
    extra = ""
    row = {"name": name, "ms": round(dt * 1000, 4)}
    if flops:
        extra += f"  {flops/dt/1e12:7.1f} Tflop/s"
        row["tflops"] = round(flops / dt / 1e12, 3)
    if bytes_:
        extra += f"  {bytes_/dt/1e9:7.1f} GB/s"
        row["gbps"] = round(bytes_ / dt / 1e9, 2)
        row["bytes_model"] = int(bytes_)
    print(f"{name:44s} {dt*1000:8.3f} ms{extra}", flush=True)
    _ROWS.append(row)
    return dt


def s_of(x):
    return jnp.sum(x.astype(jnp.float32))


def mxu_probes(key):
    # 1. big square matmul bf16
    for n in (4096, 8192):
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        f = jax.jit(lambda a: s_of(a @ a))
        timeit(f"matmul {n}x{n}x{n} bf16", f, a, flops=2 * n**3)

    # 2. BERT-ish matmul [12288, 768] x [768, 3072]
    a = jax.random.normal(key, (12288, 768), jnp.bfloat16)
    b = jax.random.normal(key, (768, 3072), jnp.bfloat16)
    f = jax.jit(lambda a, b: s_of(a @ b))
    timeit("matmul 12288x768x3072 bf16", f, a, b,
           flops=2 * 12288 * 768 * 3072)

    # 3. LM head matmul [12288, 768] x [768, 30528]
    b = jax.random.normal(key, (768, 30528), jnp.bfloat16)
    f = jax.jit(lambda a, b: s_of(a @ b))
    timeit("matmul 12288x768x30528 bf16", f, a, b,
           flops=2 * 12288 * 768 * 30528)


def hbm_probes(key):
    # HBM bandwidth: add two 512MB arrays
    x = jax.random.normal(key, (256, 1024, 1024), jnp.bfloat16)  # 512MB
    f = jax.jit(lambda x: s_of(x + 1.0))
    timeit("elementwise add 512MB bf16", f, x, bytes_=2 * x.size)


def attn_probes(key):
    # batched attention matmul, several layouts
    B, S, H, D = 24, 512, 12, 64
    BH = B * H
    flops_qk = 2 * BH * S * S * D
    q3 = jax.random.normal(key, (BH, S, D), jnp.bfloat16)
    k3 = jax.random.normal(key, (BH, S, D), jnp.bfloat16)

    f = jax.jit(lambda q, k: s_of(jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)))
    timeit("qk^t [288,512,64] batched f32-out", f, q3, k3, flops=flops_qk)

    f = jax.jit(lambda q, k: s_of(jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.bfloat16)))
    timeit("qk^t [288,512,64] batched bf16-out", f, q3, k3, flops=flops_qk)

    # merge heads into contraction: [B,S,HD] x [B,S,HD] is NOT attention
    # math; instead try head-outer loop layout with fewer batches:
    q4 = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    k4 = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
    f = jax.jit(lambda q, k: s_of(jax.lax.dot_general(
        q, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.bfloat16)))
    timeit("qk^t [24,12,512,64] 2-batch bf16-out", f, q4, k4, flops=flops_qk)

    # D=128 comparison (6 heads x 128): same flops, doubled contraction
    q5 = jax.random.normal(key, (B * 6, S, 128), jnp.bfloat16)
    f = jax.jit(lambda q, k: s_of(jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.bfloat16)))
    timeit("qk^t [144,512,128] batched bf16-out", f, q5, q5, flops=flops_qk)

    # pv: [288,512,512] x [288,512,64]
    p = jax.random.normal(key, (BH, S, S), jnp.bfloat16)
    v3 = jax.random.normal(key, (BH, S, D), jnp.bfloat16)
    f = jax.jit(lambda p, v: s_of(jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)))
    timeit("pv [288,512,512]x[...,64] f32-out", f, p, v3, flops=flops_qk)


def sparse_probes(vocab=1_000_000, dim=11, batch=8192, fields=39, iters=20):
    """Gather / scatter-add bandwidth at the DeepFM table shapes ([vocab,
    dim] f32 fused table, batch*fields ids per step, criteo-uniform ids)
    plus the same update deduped (sorted-unique scatter via merge_rows,
    and the Pallas segment-sum kernel end-to-end).

    Touched-bytes models (f32): gather = N rows read + N rows written =
    2*N*dim*4; scatter-add = N value rows read + up to N table rows
    read-modify-written = 3*N*dim*4 (an upper bound under duplicates —
    effective GB/s is conservative).  The derived roofline uses the
    MEASURED times, so the model only labels the GB/s scale."""
    key = jax.random.PRNGKey(0)
    N = batch * fields
    rowbytes = dim * 4
    table = jax.random.normal(key, (vocab, dim), jnp.float32)
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, N), jnp.int32)
    vals = jax.random.normal(key, (N, dim), jnp.float32)

    f = jax.jit(lambda t, i: s_of(t[i]))
    t_gather = timeit(f"gather [{vocab},{dim}] x {N} ids", f, table, ids,
                      iters=iters, bytes_=2 * N * rowbytes)

    f = jax.jit(lambda t, i, v: s_of(t.at[i].add(v)))
    t_scatter = timeit(f"scatter-add dup ids [{vocab},{dim}] x {N}", f,
                       table, ids, vals, iters=iters,
                       bytes_=3 * N * rowbytes)

    from paddle_tpu.sparse import merge_rows

    def mscat(t, i, v):
        # via="xla" pinned: the sorted-scatter hint below is only valid
        # for the compacted XLA merge layout
        r, mv = merge_rows(i, v, t.shape[0], via="xla")
        return s_of(t.at[r].add(mv, mode="drop", indices_are_sorted=True,
                                unique_indices=True))
    t_merge = timeit(f"scatter-add sorted-unique x {N}", jax.jit(mscat),
                     table, ids, vals, iters=iters,
                     bytes_=3 * N * rowbytes)

    from paddle_tpu.kernels.segment_update import apply_rows_update

    def kscat(t, i, v):
        return s_of(apply_rows_update(t, i, v, 1.0))
    t_kernel = timeit(f"segment-kernel update x {N}", jax.jit(kscat),
                      table, ids, vals, iters=iters,
                      bytes_=3 * N * rowbytes)

    # Derived sparse roofline for the bench's DeepFM step (the 'rows'-
    # family plumbing: ONE gather of N fused rows + ONE deduped update):
    # floor = measured gather time + the best measured update time; the
    # examples/s ceiling is batch / floor.  Honest by construction — every
    # term is a measurement from THIS chip at THESE shapes.
    t_update = min(t_scatter, t_merge, t_kernel)
    floor = t_gather + t_update
    roofline = {
        "vocab": vocab, "dim": dim, "batch": batch, "fields": fields,
        "gather_ms": round(t_gather * 1e3, 4),
        "best_update_ms": round(t_update * 1e3, 4),
        "best_update": ["scatter-add dup", "scatter-add sorted-unique",
                        "segment-kernel"][
            [t_scatter, t_merge, t_kernel].index(t_update)],
        "deepfm_step_floor_ms": round(floor * 1e3, 4),
        "deepfm_examples_per_sec_ceiling": round(batch / floor, 1),
    }
    print("sparse roofline: step floor %.3f ms -> ceiling %.1f examples/s "
          "(gather %.3f ms + %s %.3f ms)"
          % (floor * 1e3, batch / floor, t_gather * 1e3,
             roofline["best_update"], t_update * 1e3), flush=True)
    return roofline


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", choices=("all", "mxu", "hbm", "attn",
                                        "sparse"), default="all")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write probe rows + derived sparse roofline as "
                         "machine-readable JSON")
    ap.add_argument("--vocab", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=11)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--fields", type=int, default=39)
    ap.add_argument("--iters", type=int, default=20,
                    help="iterations per sparse probe")
    args = ap.parse_args(argv)

    del _ROWS[:]
    key = jax.random.PRNGKey(0)
    sparse_roofline = None
    if args.probe in ("all", "mxu"):
        mxu_probes(key)
    if args.probe in ("all", "hbm"):
        hbm_probes(key)
    if args.probe in ("all", "attn"):
        attn_probes(key)
    if args.probe in ("all", "sparse"):
        sparse_roofline = sparse_probes(args.vocab, args.dim, args.batch,
                                        args.fields, args.iters)

    if args.json:
        dev = jax.devices()[0]
        out = {"platform": dev.platform,
               "device": str(dev),
               "probes": _ROWS}
        if sparse_roofline is not None:
            out["sparse_roofline"] = sparse_roofline
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote %s" % args.json, flush=True)
    return 0


if __name__ == "__main__":
    from paddle_tpu import compile_cache

    compile_cache.place()
    main()
