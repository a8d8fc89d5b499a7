"""dots3-note-prev's kernel modes as Mosaic compiles them, each ALONE against
the float32 formula, at the cell's shape:

    chiprun -- python3 scripts/dots3_kernels_receipt.py [--seq 8192] [--block 512] [--seed 0]

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
mode) give a short ``--seq``."""

import argparse
import json
import os
import sys
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
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
    main()
