"""Plain reference for ``kanana_2_30b_a3b``: the training loss of a Kanana-2
decoder (kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``, HF
``model_type`` ``deepseek_v3``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  ONE device's view of the WHOLE
model: all ``n_routed_experts`` experts, every sequence of the global batch.
No kernels, no scan over layers, no sharding, no collective, no sort, no
capacity and no grouped matmul, nothing imported from the program: it takes
the program's weights by their names in the parameter tree (an
expert-parallel program's, gathered: expert e of the model is row e of
``we_gate_up`` / ``we_down``) and a batch (``ids``) and returns the loss.

A layer on one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x *
rsqrt(mean(x^2) + eps) * g``, eps ``rms_norm_eps``): ``x += attn(rms(x,
ln1_scale)); x += ffn(rms(x, ln2_scale))``.  Which FFN a layer has is read
off its leaves.

Latent attention (H = ``num_attention_heads``, dn / dr / dv =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``;
``q_lora_rank`` null: one query matrix), of the normed rows n: ``q = n @
wq`` [H, dn + dr]; ``[c | r] = n @ wkv_a`` (``kv_lora_rank`` columns, then
dr); ``rms(c, kv_a_norm) @ wkv_b`` head i ``[k_nope_i | v_i]``; the last dr
columns of every q head and the ONE vector r a token are rotated in ADJACENT
pairs (2 i, 2 i + 1) by ``p theta^(-2 i / dr)`` (``rope_interleave``,
``rope_theta``, no scaling); ``k_i = [k_nope_i | rot(r)]``, the SAME rotated
r in every head; ``o_i = softmax_causal((dn + dr)^(-1/2) q_i k_i^T) v_i``;
``concat(o) @ wo``.

Dense FFN (a layer that holds ``w_gate_up``; the first
``first_k_dense_replace``): ``(silu(m @ Wg) * (m @ Wu)) @ w_down``.  Sparse
FFN: ``s = sigmoid(m @ router)`` in float32 over all ``n_routed_experts``;
the ``num_experts_per_tok`` largest of ``s + router_bias`` (``n_group`` =
``topk_group`` = 1: no group limit); weights ``routed_scaling_factor * s_e /
sum of the chosen s`` (``norm_topk_prob``); EVERY expert is evaluated on every
token and combined with those weights at its column, zero elsewhere (a
different algorithm from the program's sort, exchange and grouped matmul, on
purpose); plus the shared experts (``ws_gate_up``, ``ws_down``: ONE FFN of
``n_shared_experts * moe_intermediate_size``), which every token meets with
weight 1, ONCE.  ``logits = rms(x_L, lnf_scale) @ lm_head^T``; cross entropy
of token t + 1 at positions 0..S-2, mean over the GLOBAL batch.  No
auxiliary loss.

THE CUT: ``num_hidden_layers`` layers, the published layers 0 to 4 (the
leading dense layer and four sparse ones).  Nothing else: every width, every
expert and the whole vocabulary are the published ones.

What it holds on the device at once is kept small (it runs beside 10.7 GB of
trainer state on the first of the four chips): a layer's attention weights
go up alone, attention runs ``HEAD_GROUP`` heads and ``QUERY_BLOCK`` rows at
a time, the experts ``EXPERT_GROUP`` at a time, a dense FFN ``DENSE_CHUNK``
hidden columns at a time, the head ``VOCAB_CHUNK`` columns at a time.  Every
call is waited for before the next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/kanana2_ref_sensitivity.py`` and the tests.

``witness_positions``: WITNESS_ROWS positions of a sequence, spread evenly,
the first and the last row among them; the driver reads them in EVERY
sequence of batch 0, and each of the cell's four sequences lives on another
chip, so a fault of one chip's exchange shows in its own quarter.
``logits_error`` is the third quartile over all of them of each position's
``|program - reference| / |reference|`` over the vocabulary.

The faults of the EXCHANGE are written as what they would do to the model's
result, in this file's own terms (``expert_parallel_size`` chips, chip c
holding experts c n / ep on, sequence j on chip j mod ep):
``holder_offset_dropped``: a chip computes the rows it receives with expert
``e mod (n / ep)`` of CHIP 0 (no offset by the holder); ``combine_permuted``:
the results come home to the wrong slots (token t sums token t - 1's rows);
``overflow_dropped``: the pairs past a capacity a destination are dropped, in
the program's order (by expert, then by token); at the program's own first
capacity, 1.25 times what uniform routing sends, balanced traffic overflows
nothing and the fault could not show, so it drops past ``OVERFLOW_SHARE`` of
what uniform routing sends (``tests/test_kanana2_expert_parallel.py`` holds
the program's own capacity under a routing that sends every pair to one
chip); ``shared_expert_summed_over_chips``: the shared expert counted ep
times.

TOLERANCE and LOGITS_TOLERANCE: see beneath the constants, with the chip
readings they were set from.
"""

import gc
import json
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Relative, on the scalar loss (cross entropy 12.25 to 12.27 at seeded
# weights; ln 128,256 = 11.76).  The system computes in bf16 with float32
# accumulation; the per-token error is random and the loss averages it over
# 32,764 positions.  From the chips (PR 73, my four-chip runs; the seeds are
# PERF.md section 6's): the program's relative error read 2.2e-6 to 1.4e-5
# over six seeds.  The same reference with every array and operation in
# bfloat16 (fault ``bfloat16_throughout``, the nearest precision below the
# configuration's) moves its loss by 4.7e-2 (a logsumexp over 128,256
# columns in bfloat16): not correct.  3e-4, the accepted decoder cells'
# limit, stands 21 times over the largest sound reading and 150 times under
# the precision's.  Of the nine other faults the loss catches NONE (1.7e-6
# to 6.2e-5: at seeded weights and uniform ids the loss sits near ln V
# whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the third quartile over 4 x 64 positions.  From
# the chips (PR 73): the sound program reads 5.54e-3 to 5.61e-3 at seven
# seeds (5.543, 5.608, 5.589, 5.581, 5.582, 5.603, 5.585; every SEQUENCE's own
# quartile, one a chip, 5.53e-3 to 5.69e-3; the least position 5.1e-3, the
# median 5.5e-3, the worst 8.0e-2 to 9.3e-2: a floor of bf16 rounding through
# five layers at EVERY position, and a few positions where rounding changes
# which expert is sixth of 128).  The limit's control is the precision below
# the configuration's, the reference itself in bfloat16 throughout: 7.76e-3.
# 6.5e-3 lies between the two readings with room on both sides: 16 % over the
# largest sound reading (a new seed, a compiler's or a later PR's other order
# of the bf16 sums has that much to move in) and 16 % under the control.
# The faults, at seed 31337: a softmax scale of 128^-1/2 8.66e-3, the shared
# key rotated twice 1.04e-2, rotate-half 1.28e-2, pairs past three quarters of
# uniform dropped 0.104, no 2.448 0.109, no offset by the holder 0.222, a
# permuted combine 0.242, the shared expert four times 0.464: nine of the ten
# fail it, the faintest by a third.  THE TENTH DOES NOT, and no limit on this
# statistic can make it with room: the bias inside the weights reads 5.875e-3
# (biases seeded N(0, 0.01^2) beside scores near one half move a weight by two
# per cent and the logits by 1.8e-3, a third of the rounding's 5.6e-3 and
# added to it in quadrature: every quantile of the positions' errors rises by
# 5 %).  A limit under it (5.73e-3 was tried) stands 2 % over the sound
# readings, which is no room.  That fault is held where rounding does not
# cover it: in float32 at the tiny size it fails the witness by orders of
# magnitude (``benchmark/tests/test_bench_kanana2.py`` and the tier-1
# reference tests run all ten).  ``benchmark/tools/kanana2_ref_sensitivity.py``
# still throws it and says ``caught_by_logits: false``.
LOGITS_TOLERANCE = 0.0065
WITNESS_ROWS = 64           # witnessed positions a sequence
HEAD_GROUP = 8              # attention heads at a time
QUERY_BLOCK = 256           # attention rows at a time
EXPERT_GROUP = 8            # experts on the device at a time
DENSE_CHUNK = 1024          # hidden columns of a dense FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
OVERFLOW_SHARE = 0.75       # ``overflow_dropped``: of what uniform routing sends
ROUTING_FAULTS = ("holder_offset_dropped", "overflow_dropped",
                  "route_scale_one", "bias_in_the_weights")
FAULTS = ("combine_permuted", "shared_expert_summed_over_chips",
          "rotate_half", "shared_key_rotated_twice",
          "softmax_scale_of_nope_alone") + ROUTING_FAULTS + (
              "bfloat16_throughout",)
LATENT_LEAVES = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate(x, theta, faults):
    """The last axis of x [S, ..., dr] rotated by its row's position:
    adjacent pairs (2 i, 2 i + 1) by ``p theta^(-2 i / dr)``."""
    s, dr = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * (theta ** (
        -2.0 * jnp.arange(dr // 2, dtype=jnp.float32) / dr))[None]
    cos, sin = (t.astype(x.dtype).reshape((s,) + (1,) * (x.ndim - 2)
                                          + (dr // 2,))
                for t in (jnp.cos(ang), jnp.sin(ang)))
    if "rotate_half" in faults:         # pairs (i, i + dr / 2)
        a, b = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _latent_project(n, p, dims, eps, theta, faults):
    """q, k [S, H, dn + dr] and v [S, H, dv] of one sequence's normed rows."""
    n_heads, r_kv, dn, dr, dv = dims
    s = n.shape[0]
    q = (n @ p["wq"]).reshape(s, n_heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta, faults)],
                        -1)
    kv_a = n @ p["wkv_a"]
    c, r = _rms(kv_a[:, :r_kv], p["kv_a_norm"], eps), kv_a[:, r_kv:]
    kv = (c @ p["wkv_b"]).reshape(s, n_heads, dn + dv)
    r = _rotate(r, theta, faults)
    if "shared_key_rotated_twice" in faults:    # once shared, again a head
        r = _rotate(r, theta, faults)
    r = jnp.broadcast_to(r[:, None, :], (s, n_heads, dr))
    return q, jnp.concatenate([kv[..., :dn], r], -1), kv[..., dn:]


def _attend(q, k, v, scale):
    """Causal softmax attention of a group of heads, q, k [S, G, d] and v
    [S, G, dv], ``QUERY_BLOCK`` rows at a time."""
    s = q.shape[0]
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)

    def block(args):
        q_rows, first = args
        scores = jnp.einsum("qgd,kgd->gqk", q_rows, k) * scale
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(rows)[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kgd->qgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, (q.reshape((s // rows, rows) + q.shape[1:]),
                            jnp.arange(0, s, rows)))
    return o.reshape((s,) + v.shape[1:])


def _route(m, router, bias, k, scaling, ep, fault, overflow_share):
    """``weight [S, n]``: each token's weights at its chosen experts'
    columns, zero elsewhere.  ``overflow_share``: OVERFLOW_SHARE as the
    caller reads it (an argument, so that a jitted trace is made for the
    value in force)."""
    score = jax.nn.sigmoid((m @ router).astype(jnp.float32))
    n = score.shape[-1]
    _, top_e = jax.lax.top_k(score + bias, k)
    if fault == "bias_in_the_weights":
        score = score + bias
    if fault == "route_scale_one":
        scaling = 1.0
    top_s = jnp.take_along_axis(score, top_e, axis=-1)
    top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scaling
    if fault == "overflow_dropped":
        # the program's order: pairs sorted by expert, then by (token, slot);
        # a pair's place among its DESTINATION's rows
        flat = top_e.reshape(-1)
        cap = int(overflow_share * flat.shape[0] / ep)
        dest = flat // (n // ep)
        order = jnp.argsort(flat, stable=True)
        place = jnp.argsort(order)
        first = jnp.cumsum(jnp.bincount(dest, length=ep)) \
            - jnp.bincount(dest, length=ep)
        kept = (place - first[dest]) < cap
        top_w = top_w * kept.reshape(top_w.shape)
    chosen = jax.nn.one_hot(top_e, n, dtype=m.dtype)
    return jnp.sum(chosen * top_w[..., None].astype(m.dtype), axis=1)


def _experts(acc, m, w_gate_up, w_down, weight):
    """``acc`` plus a group of experts on EVERY token of ``m``, each times
    its column of ``weight`` [S, g]: w_gate_up [g, E, 2F], w_down [g, F, E]."""
    f = w_down.shape[1]
    gu = jnp.einsum("se,gef->gsf", m, w_gate_up)
    out = jnp.einsum("gsf,gfe->gse", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                     w_down)
    return acc + jnp.sum(out * weight.T[..., None], axis=0)


def _dense_chunk(acc, m, w_gate, w_up, w_down):
    return acc + (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


_route_jit = jax.jit(_route, static_argnums=(3, 4, 5, 6, 7))
_experts_jit = jax.jit(_experts)
_dense_jit = jax.jit(_dense_chunk)
_latent_jit = jax.jit(_latent_project, static_argnums=(2, 3, 4, 5))
_attend_jit = jax.jit(_attend, static_argnums=3)
_rms_jit = jax.jit(_rms, static_argnums=2)


def moe_part(ms, router, bias, w_gate_up, w_down, k, scaling, cast, ep=1,
             fault=None):
    """The routed sums over ALL the experts on each sequence's normed rows
    ``ms`` (a list of [S, E]), ``EXPERT_GROUP`` experts at a time
    (``w_gate_up`` / ``w_down`` as the host holds them; ``cast`` puts a group
    on the device ONCE, for every sequence), each call waited for."""
    weights = [_done(_route_jit(m, router, bias, k, scaling, ep, fault,
                                OVERFLOW_SHARE)) for m in ms]
    ys = [jnp.zeros_like(m) for m in ms]
    n = w_gate_up.shape[0]
    for at in range(0, n, EXPERT_GROUP):
        to = min(at + EXPERT_GROUP, n)
        rows = slice(at, to)            # a view: nothing copied on the host
        if fault == "holder_offset_dropped":
            # chip 0's expert of that local index
            rows = np.arange(at, to) % (n // ep)
        up, down = cast(w_gate_up[rows]), cast(w_down[rows])
        ys = [_done(_experts_jit(y, m, up, down, weight[:, at:to]))
              for y, m, weight in zip(ys, ms, weights)]
        del up, down
    return ys


def dense_part(m, w_gate_up, w_down):
    """A dense gated FFN (the leading layer's, the shared experts'),
    ``DENSE_CHUNK`` hidden columns at a time."""
    f = w_down.shape[0]
    y = jnp.zeros_like(m)
    for at in range(0, f, min(f, DENSE_CHUNK)):
        to = min(at + DENSE_CHUNK, f)
        y = _done(_dense_jit(y, m, w_gate_up[:, at:to],
                             w_gate_up[:, f + at:f + to], w_down[at:to]))
    return y


def _head_chunk(x, g, w, labels, first, eps, keep):
    """Columns [first, first + C) of the head on one sequence: their
    logsumexp [S], the label's logit where the label is among them (else 0)
    and, where ``keep``, the logits [S, C]."""
    logits = _rms(x, g, eps) @ w.T
    at = labels - first
    inside = (at >= 0) & (at < w.shape[0])
    picked = jnp.take_along_axis(
        logits, jnp.clip(at, 0, w.shape[0] - 1)[:, None], axis=-1)[:, 0]
    return (jax.scipy.special.logsumexp(logits, axis=-1),
            jnp.where(inside, picked, 0.0), logits if keep else None)


_head_jit = jax.jit(_head_chunk, static_argnums=(5, 6))


def layer_trees(params):
    """Each layer's leaves, in the stack's order: the leading layers
    (``prefix_layers/l<i>``), then period by period the positions of
    ``params_layers`` (a tree a position ``p<i>`` stacked [periods, ...]);
    views, nothing copied."""
    prefix = params.get("prefix_layers", {})
    trees = [prefix["l%d" % i] for i in range(len(prefix))]
    stacked = params["params_layers"]
    names = sorted(stacked, key=lambda n: int(n[1:]))
    for period in range(np.shape(stacked[names[0]]["ln1_scale"])[0]):
        trees.extend({k: v[period] for k, v in stacked[name].items()}
                     for name in names)
    return trees


def forward(params, ids, model, faults=(), keep_logits=True, positions=None):
    """``(loss, logits)``: the training loss as a scalar (differentiable in
    ``params``) and each sequence's logits [S, V], or [P, V] at
    ``positions`` [P] alone (none kept where ``keep_logits`` is off)."""
    for fault in faults:
        assert fault in FAULTS, fault
    # the one fault that is a precision: every array and every operation in
    # bfloat16 at the device's default matmul precision
    low = "bfloat16_throughout" in faults
    dtype = jnp.bfloat16 if low else jnp.float32

    def cast(a):
        return _done(jnp.asarray(a).astype(dtype))

    assert model["q_lora_rank"] is None and model["norm_topk_prob"] \
        and model["n_group"] == model["topk_group"] == 1 \
        and model["scoring_func"] == "sigmoid" and model["rope_interleave"] \
        and model["rope_scaling"] is None
    n_heads = int(model["num_attention_heads"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dims = (n_heads, int(model["kv_lora_rank"]), dn, dr,
            int(model["v_head_dim"]))
    scale = (dn if "softmax_scale_of_nope_alone" in faults
             else dn + dr) ** -0.5
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    k = int(model["num_experts_per_tok"])
    scaling = float(model["routed_scaling_factor"])
    ep = int(model.get("expert_parallel_size", 1))
    routing = ([f for f in faults if f in ROUTING_FAULTS] or [None])[0]
    ids = np.asarray(ids)
    b, s = ids.shape
    trees = layer_trees(params)
    assert len(trees) == int(model["num_hidden_layers"]), len(trees)
    sparse = 0
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for at, tree in enumerate(trees):
            gc.collect()
            ln1 = cast(tree["ln1_scale"])
            p = {name: cast(tree[name]) for name in LATENT_LEAVES}
            hs = []
            for x in xs:
                q, kk, v = _done(_latent_jit(
                    _done(_rms_jit(x, ln1, eps)), p, dims, eps, theta,
                    tuple(faults)))
                o = jnp.concatenate([_done(_attend_jit(
                    q[:, g:g + HEAD_GROUP], kk[:, g:g + HEAD_GROUP],
                    v[:, g:g + HEAD_GROUP], scale))
                    for g in range(0, n_heads, HEAD_GROUP)], axis=1)
                hs.append(_done(x + o.reshape(s, -1) @ p["wo"]))
                del q, kk, v, o
            del p, ln1
            ln2 = cast(tree["ln2_scale"])
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            if "w_gate_up" in tree:
                assert at < int(model["first_k_dense_replace"]), at
                w_gate_up, w_down = cast(tree["w_gate_up"]), \
                    cast(tree["w_down"])
                xs = [_done(h + dense_part(m, w_gate_up, w_down))
                      for h, m in zip(hs, ms)]
                del w_gate_up, w_down, hs, ms, ln2
                continue
            assert np.shape(tree["we_gate_up"])[0] \
                == int(model["n_routed_experts"])
            router = cast(tree["router"])
            bias = jnp.asarray(params["router_bias"][sparse], jnp.float32)
            sparse += 1
            routed = moe_part(ms, router, bias, tree["we_gate_up"],
                              tree["we_down"], k, scaling, cast, ep, routing)
            if "combine_permuted" in faults:
                routed = [jnp.roll(y, 1, axis=0) for y in routed]
            del router
            ws_gate_up = cast(tree["ws_gate_up"])
            ws_down = cast(tree["ws_down"])
            times = ep if "shared_expert_summed_over_chips" in faults else 1
            xs = [_done(h + y + times * dense_part(m, ws_gate_up, ws_down))
                  for h, m, y in zip(hs, ms, routed)]
            del ws_gate_up, ws_down, hs, ms, routed, ln2
        table = params["lm_head"]
        g = cast(params["lnf_scale"])
        labels = [jnp.asarray(np.roll(ids[j], -1)) for j in range(b)]
        lse, picked = [None] * b, [0.0] * b
        logits = [[] for _ in range(b)]
        for at in range(0, table.shape[0], VOCAB_CHUNK):
            w = cast(table[at:at + VOCAB_CHUNK])
            for j in range(b):
                l, at_label, lg = _done(_head_jit(
                    xs[j], g, w, labels[j], jnp.int32(at), eps, keep_logits))
                lse[j] = l if lse[j] is None else jnp.logaddexp(lse[j], l)
                picked[j] = picked[j] + at_label
                if keep_logits:
                    logits[j].append(lg if positions is None
                                     else _done(lg[np.asarray(positions)]))
            del w
        nll = sum(jnp.sum((lse[j] - picked[j])[:-1].astype(jnp.float32))
                  for j in range(b))
        loss = nll / (b * (s - 1))
    return loss, [jnp.concatenate(lg, axis=-1) for lg in logits if lg]


def witness_positions(s):
    """The positions whose logits the witness reads in EVERY sequence of the
    batch: WITNESS_ROWS spread evenly over the sequence, rows 0 and s - 1
    among them.  The driver hands the sequence length alone."""
    return np.unique(np.round(np.linspace(
        0, s - 1, min(WITNESS_ROWS, s))).astype(int))


_last = {}      # the inputs' fingerprint and the results of the last run


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    last call's results are kept: the benchmark's driver asks for the logits
    and then the harness for the loss, of the same weights and batch."""
    ids = np.asarray(batch["ids"])
    marks = [np.asarray(a) for a in (
        params["lnf_scale"], params["router_bias"],
        params["prefix_layers"]["l0"]["wkv_a"],
        params["params_layers"]["p0"]["router"])]
    mark = (zlib.crc32(ids.tobytes()),
            tuple(zlib.crc32(a.tobytes()) for a in marks),
            json.dumps(model, sort_keys=True), tuple(faults))
    if _last.get("mark") != mark:
        total, logits = forward(params, ids, model, faults,
                                positions=witness_positions(ids.shape[1]))
        _last.update(mark=mark, loss=float(total),
                     logits=np.stack([np.asarray(lg, np.float32)
                                      for lg in logits]))
        del total, logits
        gc.collect()        # the jitted blocks' constants go with them
    return _last["loss"], _last["logits"]


def loss(params, batch, model, faults=()):
    return _run(params, batch, model, faults)[0]


def logits(params, batch, model, faults=()):
    """The logits [B, P, V] at ``witness_positions`` of each sequence."""
    return _run(params, batch, model, faults)[1]


def position_errors(got, params, batch, model, faults=()):
    """Each witnessed position's ``|got - want| / |want|`` over the
    vocabulary, [B * P], sequence by sequence: the program's logits ``got``
    [B, P, V] at ``witness_positions`` against the reference's."""
    want = logits(params, batch, model, faults)
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1)).reshape(-1)


def sequence_errors(got, params, batch, model, faults=()):
    """The third quartile of each SEQUENCE's positions, [B]: sequence j of
    the cell's batch lives on chip j."""
    each = position_errors(got, params, batch, model, faults)
    return np.quantile(each.reshape(np.asarray(got).shape[0], -1), 0.75,
                       axis=1)


def group_errors(got, params, batch, model, faults=()):
    """``{"sequence_<j>": q75}``: ``sequence_errors`` by name, as the other
    references name their groups."""
    return {"sequence_%d" % j: float(e) for j, e in enumerate(
        sequence_errors(got, params, batch, model, faults))}


def logits_error(got, params, batch, model, faults=()):
    """The third quartile over every witnessed position of every sequence:
    what LOGITS_TOLERANCE bounds."""
    return float(np.quantile(
        position_errors(got, params, batch, model, faults), 0.75))
