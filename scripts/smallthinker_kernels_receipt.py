"""A chip receipt for the kernels the SmallThinker cell brought, at the
cell's shapes (B = 1, S = 16,384; 28 query heads on 4 key/value heads of
128; 16 of 64 ReLU-gated experts held, top-6, E = 2560, F = 768; bf16):

    chiprun -- python3 scripts/smallthinker_kernels_receipt.py [out.json]

The CPU tests hold these paths to a reference in interpret mode at tiny
sizes, and ``tests/test_chip_compile_flash.py`` reads kernel names in the
compiled text; this holds what Mosaic compiled, at the published shapes, to
plain ``jax.numpy`` in float32 at ``highest`` precision on the same
bf16-rounded inputs, forward and every gradient against a random cotangent:

- ``flash.window`` / ``flash.full``: ``flash_attention_packed(n_kv_heads=4,
  window=4096 | None)`` in 512-blocks (``flash_swa_fwd`` /
  ``flash_swa_bwd_fused``, and the causal ``flash_fwd`` / ``flash_bwd_fused``
  with grouped queries): o, dq, dk, dv against attention by
  query blocks, one key/value head's group at a time.  The error is each
  HEAD's ``|got - want| / |want|`` and the worst head is reported, so a
  group summed into the wrong key/value head cannot hide in a mean.
- ``moe.balanced`` / ``moe.skewed``: ``moe.dropless_moe_ffn`` holding
  experts [0, 16) of 64 on the caller's logits, against every held expert
  on every token times the masked top-6 weights: y, dx, d_logits and each
  EXPERT's d_gate_up / d_down.  Balanced routing runs the first capacity
  (30,720 rows); with the held experts' logits raised more than 30,720
  pairs meet them and the step runs the branch that has a row for every
  pair (``rows_held`` says which ran).  Both sum back through the row
  kernel (``kernels/moe_rows.py``), forward and as the dispatch's backward.
- ``moe.rows_sum``: that kernel alone (``moe_rows_words`` and
  ``moe_rows_sum``) at [30,720, 2,560] rows and 98,304 pair slots of which a
  quarter hold a row, against the float32 gather and sum, by COLUMN.

Controls, so that the limit is known to stand below a fault: the same
reference with the band one block too wide (4,608), with no band, and with
query head h on key/value head h % 4; the MoE reference at top-5 and with
SiLU; the sum back's with each token's last slot left out and with every
pair's row the one after.  It exits 1 where a sound reading is over
EQUAL_TOLERANCE or a control under five times it; off a TPU it exits 2 (a CPU
run in interpret mode proves nothing about Mosaic)."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

S, H, HKV, D, WINDOW, BLOCK = 16384, 28, 4, 128, 4096, 512
T, K, N, HELD, E, F = 16384, 6, 64, 16, 2560, 768
ROWS = 256                  # the reference's query rows at a time
EQUAL_TOLERANCE = 2e-2


def _worst(got, want, axis):
    """The largest over ``axis``'s entries of |got - want| / |want|."""
    rest = tuple(i for i in range(want.ndim) if i != axis)
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.sqrt(jnp.sum((got - want) ** 2, axis=rest)
                                  / jnp.sum(want ** 2, axis=rest))))


@functools.partial(jax.jit, static_argnums=(3,))
def _group_attention(q, k, v, window):
    """q [S, G, D] on one key/value head k, v [S, D], float32, by blocks of
    ROWS queries; a block's scores are computed again in the backward."""
    @jax.checkpoint
    def block(q_rows, first, k, v):
        scores = jnp.einsum("qgd,kd->gqk", q_rows, k) / math.sqrt(D)
        at = first + jnp.arange(ROWS)[:, None]
        key = jnp.arange(S)[None, :]
        seen = key <= at
        if window is not None:
            seen = seen & (at - key < window)
        p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, v)

    o = jax.lax.map(lambda a: block(a[0], a[1], k, v),
                    (q.reshape(S // ROWS, ROWS, -1, D),
                     jnp.arange(0, S, ROWS)))
    return o.reshape(q.shape)


def _reference_attention(q, k, v, do, window, wrong_head=False):
    """o, dq [S, H, D] and dk, dv [S, HKV, D] in float32, a group at a time."""
    q, k, v, do = (a[0].astype(jnp.float32).reshape(S, -1, D)
                   for a in (q, k, v, do))
    group = H // HKV
    o, dq = jnp.zeros_like(q), jnp.zeros_like(q)
    dk, dv = [], []
    with jax.default_matmul_precision("highest"):
        for g in range(HKV):
            mine = slice(g, None, HKV) if wrong_head \
                else slice(g * group, (g + 1) * group)
            part, vjp = jax.vjp(
                lambda *a: _group_attention(*a, window), q[:, mine], k[:, g],
                v[:, g])
            dq_g, dk_g, dv_g = jax.block_until_ready(vjp(do[:, mine]))
            o, dq = o.at[:, mine].set(part), dq.at[:, mine].set(dq_g)
            dk.append(dk_g)
            dv.append(dv_g)
    return o, dq, jnp.stack(dk, 1), jnp.stack(dv, 1)


def flash_receipt(window):
    from paddle_tpu.kernels.flash_attention import flash_attention_packed

    ks = jax.random.split(jax.random.PRNGKey(31 if window else 32), 4)
    q, do = (jax.random.normal(key, (1, S, H * D), jnp.bfloat16)
             for key in ks[:2])
    k, v = (jax.random.normal(key, (1, S, HKV * D), jnp.bfloat16)
            for key in ks[2:])

    def kernel(q, k, v):
        return flash_attention_packed(q, k, v, H, causal=True, block_q=BLOCK,
                                      block_k=BLOCK, n_kv_heads=HKV,
                                      window=window)

    o, vjp = jax.vjp(jax.jit(kernel), q, k, v)
    got = [a[0].reshape(S, -1, D) for a in (o,) + jax.jit(vjp)(do)]

    def against(**fault):
        want = _reference_attention(q, k, v, do, **fault)
        return {name: _worst(g, w, 1)
                for name, g, w in zip(("o", "dq", "dk", "dv"), got, want)}

    out = {"sound": against(window=window),
           "controls": {"wrong_kv_head": against(window=window,
                                                 wrong_head=True)}}
    if window:
        out["controls"]["band_a_block_too_wide"] = against(
            window=window + BLOCK)
        out["controls"]["no_band"] = against(window=None)
    return out


def _dense_share(x, logits, w_gate_up, w_down, k=K, act=jax.nn.relu):
    """Every held expert on every token, times the top-k weights at its
    column: float32, no sort, no grouped matmul."""
    top_l, top_e = jax.lax.top_k(logits, k)
    chosen = jax.nn.one_hot(top_e, N, dtype=jnp.float32)
    weight = jnp.sum(chosen * jax.nn.softmax(top_l, -1)[..., None], 1)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(HELD):           # one expert at a time: 50 MB of hidden
        gu = x @ w_gate_up[e]
        y = y + ((act(gu[:, :F]) * gu[:, F:]) @ w_down[e]) * weight[:, e, None]
    return y


def moe_receipt(raise_held):
    from paddle_tpu.parallel import moe

    ks = jax.random.split(jax.random.PRNGKey(33), 5)
    x = jax.random.normal(ks[0], (T, E), jnp.bfloat16)
    dy = jax.random.normal(ks[1], (T, E), jnp.bfloat16)
    w_gate_up = (jax.random.normal(ks[2], (HELD, E, 2 * F)) * E ** -0.5
                 ).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[3], (HELD, F, E)) * F ** -0.5
              ).astype(jnp.bfloat16)
    logits = jax.random.normal(ks[4], (T, N), jnp.float32)
    logits = logits.at[:, :HELD].add(raise_held)
    router = jnp.zeros((E, N), jnp.float32)     # its shape alone is read

    def layer(x, logits, w_gate_up, w_down):
        y, aux = moe.dropless_moe_ffn(
            {"router": router, "we_gate_up": w_gate_up, "we_down": w_down},
            x, K, rule=moe.TOP_K_SOFTMAX, act="relu", logits=logits,
            first_held=0)
        return y, aux["rows_held"]

    args = (x, logits, w_gate_up, w_down)
    y, vjp, held = jax.vjp(jax.jit(layer), *args, has_aux=True)
    got = (y,) + jax.jit(vjp)(dy)
    f32 = [a.astype(jnp.float32) for a in args]
    names = (("y", 0), ("dx", 0), ("d_logits", 0), ("d_gate_up", 0),
             ("d_down", 0))

    def against(**fault):
        with jax.default_matmul_precision("highest"):
            want, ref_vjp = jax.vjp(
                jax.jit(functools.partial(_dense_share, **fault)), *f32)
            want = (want,) + ref_vjp(dy.astype(jnp.float32))
        # y, dx, d_logits as wholes ([1, ...]); the weights' by expert
        return {name: _worst(g if g.ndim == 3 else g[None],
                             w if w.ndim == 3 else w[None], axis)
                for (name, axis), g, w in zip(names, got, want)}

    caps = moe._held_capacities(T * K, HELD, N)
    return {"rows_held": int(held), "capacities": list(caps),
            "capacity_run": [c for c in caps if c >= int(held)][0],
            "sound": against(),
            "controls": {"top_5": against(k=K - 1),
                         "silu_gate": against(act=jax.nn.silu)}}


def rows_sum_receipt():
    """``kernels.moe_rows.moe_rows_sum`` alone at the layer's first capacity:
    30,720 rows of 2,560, a quarter of the 98,304 pair slots holding one."""
    from paddle_tpu.kernels.moe_rows import moe_rows_sum
    from paddle_tpu.parallel.moe import _held_capacities

    m, held = _held_capacities(T * K, HELD, N)[0], T * K * HELD // N
    ks = jax.random.split(jax.random.PRNGKey(34), 3)
    rows = jax.random.normal(ks[0], (m, E), jnp.bfloat16)
    at = jax.random.permutation(ks[1], T * K)[:held]
    inv = jnp.full((T * K,), m, jnp.int32).at[at].set(
        jax.random.permutation(ks[2], held).astype(jnp.int32))
    got = jax.jit(moe_rows_sum, static_argnums=(2,))(rows, inv, K)

    def against(places):
        back = rows.astype(jnp.float32).at[places.reshape(T, K)].get(
            mode="fill", fill_value=0)
        return {"sum": _worst(got, jnp.sum(back, axis=1), 1)}  # by column

    return {"rows_fetched": held, "sound": against(inv),
            "controls": {
                "last_slot_left_out": against(inv.at[K - 1::K].set(m)),
                "the_row_after": against(jnp.where(inv < m, inv + 1, m))}}


def main(out_path=None):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("smallthinker_kernels_receipt: no TPU (%s)" % dev.platform,
              file=sys.stderr)
        return 2
    out = {"device_kind": dev.device_kind, "tolerance": EQUAL_TOLERANCE}
    for name, run in (("flash.window", lambda: flash_receipt(WINDOW)),
                      ("flash.full", lambda: flash_receipt(None)),
                      ("moe.balanced", lambda: moe_receipt(0.0)),
                      ("moe.skewed", lambda: moe_receipt(1.0)),
                      ("moe.rows_sum", rows_sum_receipt)):
        out[name] = run()
        print(name, json.dumps(out[name]), flush=True)
    sound = max(max(out[n]["sound"].values()) for n in out if "." in n)
    control = min(max(c.values()) for n in out if "." in n
                  for c in out[n]["controls"].values())
    branches = {out[n]["capacity_run"] for n in ("moe.balanced", "moe.skewed")}
    out.update(worst_sound=sound, least_control=control,
               ok=bool(sound <= EQUAL_TOLERANCE
                       and control >= 5 * EQUAL_TOLERANCE
                       and len(branches) == 2))
    print(json.dumps(out), flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
