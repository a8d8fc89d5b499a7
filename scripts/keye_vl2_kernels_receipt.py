"""The learned-sparse layer's kernels as Mosaic compiles them, against the
float32 formulas, at the cell's shape:

    chiprun -- python3 scripts/keye_vl2_kernels_receipt.py [--seq 16384] [--block 512] [--seed 0] [--tree DIR] [--times-only]

q [1, S, 32 x 128] on 4 key/value heads, an indexer of 16 heads of 64, the
2,048 best keys a row (scaled with a shorter ``--seq``: an eighth).  Holds
``indexer_scores`` (forward, and dq / dk / dw from a random dI), the k-th
largest a row (``kth_largest`` against ``jax.lax.top_k``), the masked
sweeps (``flash_dsa_fwd``: the statistic alone, ``dsa_lse``;
``flash_dsa_bwd_fused``: dq / dk / dv from a random do, behind
``dsa_attend_kl``) and ``dsa_attend_kl``, the pass with the statistic known
(o, the KL term and the KL's gradient of the scores' operands) to the
formulas, computed in float32 at ``highest`` precision on the same bf16
operands, a block of query rows at a time (the dense [32, S, S] never
stands); and, the CONTROL, the output against the formula WITHOUT the
selection's mask, which must be far off.  Each reading is the
largest absolute difference over the largest absolute value of the formula's
result.  And the rotary pass with positions that are DATA
(``qk_rope.angle_tables(positions=)``, which the cell, at text positions,
never takes): the row kernel on q with an image grid's three streams
(temporal, height, width; sections [16, 24, 24]) against the float32
formula, the value and dx, with the control that swaps the spatial sections.
Then ``seconds``: the host's clock around each of the layer's calls alone,
jitted, the mean of five after one (the masked online sweep, the pass with
the statistic known, the masked backward with its ``delta``, the selected
keys' normaliser).  ``--times-only`` skips the formulas; with it ``--tree
DIR`` times another checkout's kernels (the parent's, from ``git archive``:
a tree whose masked sweeps are the shared flash kernels' ``mask=`` mode).
Writes ``chiprun_out/pr64/keye_vl2_kernels_receipt[_<tree>].json``; off a
chip (interpret mode) give a short ``--seq``."""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = os.path.abspath(sys.argv[sys.argv.index("--tree") + 1]) \
    if "--tree" in sys.argv else ROOT
sys.path.insert(0, TREE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.kernels import indexer as ix, qk_rope  # noqa: E402

H, HKV, D, HI, DI = 32, 4, 128, 16, 64
THETA, SECTIONS, EPS = 1e7, (16, 24, 24), 1e-6
ROWS = 128          # query rows of the formulas' blocks


def formulas(S):

    def rows_of(x, first):
        return jax.lax.dynamic_slice_in_dim(x, first, ROWS, 0)

    def scores(qi, ki, w):
        """[S, S] float32, -inf above the diagonal."""
        def block(first):
            s = jnp.einsum("qhd,kd->hqk", rows_of(qi, first).reshape(
                ROWS, HI, DI), ki)
            out = jnp.einsum("qh,hqk->qk", rows_of(w, first), jax.nn.relu(s))
            seen = jnp.arange(S)[None] <= (first + jnp.arange(ROWS))[:, None]
            return jnp.where(seen, out, -jnp.inf)
        return jax.lax.map(jax.checkpoint(block),
                           jnp.arange(0, S, ROWS)).reshape(S, S)

    def attend(q, k, v, keep_or_scores, tau, masked=True):
        """(o [S, H * D], lse [H, S], p = mean over heads [S, S])."""
        kh = jnp.repeat(k.reshape(S, HKV, D), H // HKV, 1)
        vh = jnp.repeat(v.reshape(S, HKV, D), H // HKV, 1)

        def block(first):
            at = first + jnp.arange(ROWS)
            seen = jnp.arange(S)[None] <= at[:, None]
            if masked:
                seen = seen & (rows_of(keep_or_scores, first)
                               >= rows_of(tau, first)[:, None])
            s = jnp.einsum("qhd,khd->hqk", rows_of(q, first).reshape(
                ROWS, H, D), kh) * D ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            a = jax.nn.softmax(s, -1)
            return (jnp.einsum("hqk,khd->qhd", a, vh).reshape(ROWS, H * D),
                    jax.nn.logsumexp(s, -1), jnp.mean(a, 0))
        o, lse, p = jax.lax.map(jax.checkpoint(block), jnp.arange(0, S, ROWS))
        return (o.reshape(S, H * D), jnp.moveaxis(lse, 0, 1).reshape(H, S),
                p.reshape(S, S))

    def kl(sc, tau, p):
        keep = (sc >= tau[:, None]) & jnp.tril(jnp.ones((S, S), bool))
        log_r = jax.nn.log_softmax(jnp.where(keep, sc, -jnp.inf), -1)
        held = p > 0
        return jnp.sum(jnp.where(held, p * (jnp.log(jnp.where(held, p, 1.0))
                                            - jnp.where(held, log_r, 0.0)),
                                 0.0)) / S

    return scores, attend, kl


def grid_streams(S):
    """[3, 1, S] int32: a quarter of text, half the sequence an image grid
    64 wide (one time step, row and column counted from the text's end),
    text again from the grid's largest position on."""
    text, width = S // 4, 64
    cell = np.arange(S // 2)
    grid = text + np.stack([0 * cell, cell // width, cell % width])
    rest = grid.max() + 1 + np.arange(S - text - S // 2)
    return np.concatenate([np.tile(np.arange(text), (3, 1)), grid,
                           np.tile(rest, (3, 1))], axis=1)[:, None].astype(
                               np.int32)


def f_rotary(x, weight, streams, sections):
    """The per-head RMS norm then the rotation of x [S, H, D] float32, pair
    i turned by ``streams[stream of i] * THETA^(-i / 64)``."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * weight
    of_pair = np.repeat(np.arange(3), sections)
    ang = jnp.asarray(streams, jnp.float32)[of_pair].T \
        * THETA ** (-jnp.arange(D // 2, dtype=jnp.float32) / (D // 2))
    cos, sin = (jnp.tile(f(ang), (1, 2))[:, None] for f in (jnp.cos, jnp.sin))
    return x * cos + jnp.concatenate(
        [-x[..., D // 2:], x[..., :D // 2]], -1) * sin


def seconds(fn, *args, calls=5):
    """The mean of ``calls`` calls of ``fn`` jitted, after one."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - start) / calls


def times(q, k, v, do, indexer, sc, tau, blocks):
    """``{call: seconds}`` of the layer's calls, each alone."""
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    bq, scale, interpret = blocks["block_q"], D ** -0.5, not ix._on_tpu()
    if hasattr(fa, "flash_dsa_packed"):
        # a tree before PR 64: the shared sweeps under ``mask=``
        lse_of = lambda q, k: fa.flash_dsa_packed(q, k, v, sc, tau, H, HKV,
                                                  **blocks)[1][..., 0]
        bwd = lambda q, k, v, o, lse, do: fa._bwd(
            scale, True, bq, bq, interpret, (q, k, v, o, lse[..., None]), do,
            H, HKV, mask=(sc, tau[..., None]))
    else:
        lse_of = lambda q, k: ix.dsa_lse(q, k, sc, tau, H, HKV, **blocks)
        bwd = lambda q, k, v, o, lse, do: ix._dsa_bwd_call(
            q, k, v, do, lse[..., None], fa._delta(
                o, do, fa._Geom(q, k, H, bq, bq, HKV), True, interpret),
            sc, tau[..., None], H, HKV, scale, bq, bq, interpret)
    lse, lse_i = lse_of(q, k), ix.selected_lse(sc, tau)
    attend = lambda q, k, v: ix.dsa_attend_kl(
        q, k, v, indexer, sc, tau, lse, lse_i, H, HKV, **blocks)
    return {"flash_dsa_fwd": seconds(lse_of, q, k),
            "flash_dsa_bwd": seconds(bwd, q, k, v, attend(q, k, v)[0], lse,
                                     do),
            "selected_lse": seconds(ix.selected_lse, sc, tau),
            "dsa_attend_kl_fwd": seconds(attend, q, k, v)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tree", default=None)
    ap.add_argument("--times-only", action="store_true")
    args = ap.parse_args()
    S, topk = args.seq, max(args.seq // 8, 1)
    blocks = dict(block_q=args.block, block_k=args.block)
    r = np.random.RandomState(args.seed)
    bf = lambda *shape: jnp.asarray(r.randn(*shape), jnp.bfloat16)
    qi, ki = bf(1, S, HI * DI), bf(1, S, DI)
    w = jnp.asarray(r.randn(1, S, HI) / 32, jnp.float32)
    # q and k at the seeded model's scale: scores N(0, 4)
    q, k, v = bf(1, S, H * D) * 2 ** 0.5, bf(1, S, HKV * D), bf(1, S, HKV * D)
    d_scores = jnp.asarray(np.tril(r.randn(S, S)), jnp.float32)[None]
    do = bf(1, S, H * D)
    up = lambda x: x[0].astype(jnp.float32)
    f_scores, f_attend, f_kl = formulas(S)
    out = {"platform": jax.devices()[0].platform, "S": S, "topk": topk,
           "block": args.block, "readings": {}}

    def read(name, got, want, where=None):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        ok = np.isfinite(want) if where is None else where
        out["readings"][name] = float(np.max(np.abs(got[ok] - want[ok]))
                                      / np.max(np.abs(want[ok])))
        print(name, out["readings"][name], flush=True)

    tri = np.tril(np.ones((S, S), bool))
    exact = jax.default_matmul_precision("highest")     # the formulas' alone

    def write():
        path = os.path.join(
            ROOT, "chiprun_out", "pr64", "keye_vl2_kernels_receipt%s.json"
            % ("_" + os.path.basename(TREE) if args.tree else ""))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        print(json.dumps(out))

    if args.times_only:
        sc = ix.indexer_scores(qi, ki, w, **blocks)
        out["seconds"] = times(q, k, v, do, (qi, ki, w), sc,
                               ix.kth_largest(sc, topk), blocks)
        return write()

    streams = grid_streams(S)
    assert not np.array_equal(streams[1], streams[2])
    weight = jnp.asarray(1 + r.randn(D) / 8, jnp.float32)
    turned, pull = jax.vjp(lambda x: qk_rope.qk_rope(
        x, weight, qk_rope.angle_tables(S, D, THETA, 0, jnp.asarray(streams),
                                        SECTIONS),
        head_dim=D, norm="head", eps=EPS), q)
    for name, sections in (("", SECTIONS), ("control_", (16, 0, 48))):
        # the control: every spatial pair from the width's stream
        want, pull_want = jax.vjp(lambda x: f_rotary(
            x, weight, streams[:, 0], sections), up(q).reshape(S, H, D))
        read(name + "rotary_streams", turned[0], want.reshape(S, -1))
        read(name + "rotary_streams_dx", pull(do)[0][0],
             pull_want(up(do).reshape(S, H, D))[0].reshape(S, -1))
    del turned, pull, want, pull_want

    sc, pull = jax.vjp(lambda *a: ix.indexer_scores(*a, **blocks), qi, ki, w)
    with exact:
        want, pull_want = jax.vjp(f_scores, up(qi), up(ki), w[0])
        wants = pull_want(d_scores[0])
    read("indexer_scores", sc[0], want, tri)
    assert np.all(np.isneginf(np.asarray(sc[0])[~tri]))
    for name, a, b in zip(("dq", "dk", "dw"), pull(d_scores), wants):
        read("indexer_scores_" + name, a[0], b)
    pull_scores = pull_want     # for the KL term's gradient, further down
    del want, wants, pull, d_scores
    tau = ix.kth_largest(sc, topk)
    read("kth_largest", jnp.where(jnp.isfinite(tau[0]), tau[0], 0.0),
         jnp.where(jnp.arange(S) >= topk - 1,
                   jax.lax.top_k(sc[0], topk)[0][:, -1], 0.0))
    lse = ix.dsa_lse(q, k, sc, tau, H, HKV, **blocks)
    with exact:
        (o_want, lse_want, p), pull_want = jax.vjp(
            lambda *a: f_attend(*a, sc[0], tau[0]), up(q), up(k), up(v))
        wants = pull_want((up(do), jnp.zeros_like(lse_want),
                           jnp.zeros_like(p)))
        unmasked = f_attend(up(q), up(k), up(v), None, None, masked=False)[0]
    read("flash_dsa_lse", lse[0], lse_want)
    del pull_want
    # the pass with the statistic known: o, the KL term, and from (do, 1)
    # the masked backward's dq / dk / dv and the KL's gradient of the
    # scores' operands (the formula's dKL/dI pulled through the formula's
    # scores)
    (o, value), pull = jax.vjp(lambda q, k, v, *indexer: ix.dsa_attend_kl(
        q, k, v, indexer, sc, tau, lse, ix.selected_lse(sc, tau), H, HKV,
        **blocks), q, k, v, qi, ki, w)
    *d_qkv, dqi, dki, dw = pull((do, jnp.ones((), value.dtype)))
    with exact:
        want, g_want = jax.value_and_grad(lambda x: f_kl(x, tau[0], p))(
            jnp.where(tri, sc[0], -1e30))
        d_indexer = pull_scores(jnp.where(tri, g_want, 0.0))
    read("dsa_attend_kl_o", o[0], o_want)
    read("control_dsa_attend_kl_o_against_no_mask", o[0], unmasked)
    for name, a, b in zip("qkv", d_qkv, wants):
        read("flash_dsa_d" + name, a[0], b)
    read("dsa_attend_kl", value, want)
    for name, a, b in zip(("dq", "dk", "dw"), (dqi, dki, dw), d_indexer):
        read("dsa_attend_kl_indexer_" + name, a[0], b)
    del o_want, wants, pull, o, g_want, p, d_qkv, pull_scores, d_indexer
    del unmasked
    out["seconds"] = times(q, k, v, do, (qi, ki, w), sc, tau, blocks)
    write()


if __name__ == "__main__":
    main()
