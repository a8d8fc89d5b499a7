"""Plain reference for ``solar_open2_250b``: the training loss of a
Solar-Open2 decoder (upstage/Solar-Open2-250B ``config.json``, HF
``model_type`` ``solar_open2``; Kimi Delta Attention as Kimi Linear,
arXiv:2510.26692, has it, with negative eigenvalues, arXiv:2411.12537) in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No
kernels, no chunks, no scan over layers, no sharding, no sort and no grouped
matmul, nothing imported from the program: it takes the program's weights by
their names in the parameter tree and a batch (``ids``) and returns the loss.

A layer on one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x *
rsqrt(mean(x^2) + eps) * g``, eps ``rms_norm_eps``): ``x += mixer(rms(x,
ln1_scale)); x += ffn(rms(x, ln2_scale))``.  Which mixer a layer has is read
off its leaves (``w_fa``: KDA; else grouped-query attention) and held to the
published ``gqa_layers``.

KDA mixer (H = ``linear_attn_config.num_heads`` heads of d =
``linear_attn_config.head_dim``), on the normed rows h:

1. ``q, k, v = silu(filter(h @ wq)), silu(filter(h @ wk)), silu(filter(h @
   wv))``, ``filter(y)_t = sum_j conv[j] * y[t - taps + 1 + j]``, zero before
   position 0, ``short_conv_kernel_size`` taps, no bias; per head ``q <- q /
   sqrt(|q|^2 + 1e-6) * d^(-1/2)``, ``k <- k / sqrt(|k|^2 + 1e-6)``.
2. ``g = -exp(a_log_head) * softplus((h @ w_fa) @ w_fb + dt_bias)`` [S, H,
   d], one log-decay a CHANNEL of the key; ``beta = 2 sigmoid(h @ w_beta)``
   [S, H] (``kda_allow_neg_eigval``: a write strength in (0, 2)).
3. A state ``S`` [d (key), d (value)] a head, from zero, a TOKEN at a time:
   ``S' = diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``;
   ``o_t = S^T q_t``.  Along ``k_t`` the transition's eigenvalue is ``1 -
   beta_t`` in (-1, 1): past 1 the write reflects what the state held.
4. ``y = rms_head(o) * o_norm * sigmoid((h @ w_ga) @ w_gb)``: an RMS norm
   over each head's d columns with ONE scale [d], THEN the gate; ``y @ wo``.

Grouped-query mixer (H = ``num_attention_heads`` query heads on KV =
``num_key_value_heads`` key/value heads of d = ``head_dim``; ``use_rope``
false: NOTHING is rotated): ``q = h @ wq`` [H, d], ``k, v = h @ wk, h @ wv``
[KV, d]; query head i reads key/value head ``i // (H / KV)``; ``o_i =
softmax_causal(d^(-1/2) q_i k^T) v``; ``o <- o * sigmoid(h @ wz)`` element by
element (``use_gqa_gate``, ``wz`` [E, H d]); ``o @ wo``.

FFN, every layer (``first_k_dense_replace`` 0): ``s = sigmoid(m @ router)``
over all ``router_width`` experts; the ``num_experts_per_tok`` largest of ``s
+ router_bias``; weights ``s_e / sum of the chosen s`` (``norm_topk_prob``)
times ``routed_scaling_factor``; the routed experts this share holds
(``we_gate_up`` [held, E, 2F], ``we_down`` [held, F, E]: ``(silu(m @ Wg) * (m
@ Wu)) @ Wd``) and the shared expert (``ws_gate_up``, ``ws_down``), which
every token meets with weight 1.  ``logits = rms(x_L, lnf_scale) @
lm_head^T``; cross entropy of token t + 1 at positions 0..S-2, mean over the
batch.  No auxiliary loss.

THE CUT: ``num_hidden_layers`` layers, the published layers 0 to 3 (one
period: grouped-query attention, then three KDA layers).  THE SHARE:
``n_routed_experts`` experts of the router's ``router_width`` from
``first_expert_held``, and ``vocab_size`` rows of the vocabulary.  The router
ranks all its experts and the weights are formed over all chosen ones; every
HELD expert is evaluated on every token and combined with those weights at
its column, zero elsewhere (a different algorithm from the program's sort,
capacities and grouped matmul, on purpose); what the absent experts would add
is left out, and that partial result goes on.  Every share computes the
shared expert.  ``tests/test_solar_open2_reference.py`` adds the program's
routed parts over all shares, and the shared expert ONCE, up to this file's
layer with every expert held.

Departures from the published description, each under ``assumed`` in the
configuration's file: the KDA layer's internals (Kimi Linear's; ``config.json``
has the heads, their width and the filters' taps alone), the gate's form, the
router's rule, the seeding, the cut and the share; no document mask (no state
reset at a document boundary).

What it holds on the device at once is kept small (it runs beside 8.5 GB of
trainer state): a layer's mixer weights go up alone, attention runs
``HEAD_GROUP`` heads and ``QUERY_BLOCK`` rows at a time, the experts
``EXPERT_GROUP`` at a time, the shared expert ``DENSE_CHUNK`` hidden columns
at a time, the head ``VOCAB_CHUNK`` columns at a time.  Every call is waited
for before the next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/solar_open2_ref_sensitivity.py``.

``witness_positions`` has two named groups: ``edge``, the first EDGE_TOKENS
tokens after the chunk edges EDGES of the program's 64-token chunks (where a
state that was not carried, or carried wrongly, shows first) and the
sequence's last EDGE_TOKENS (where the carry is longest), and ``spread``,
SPREAD_ROWS evenly over the sequence.  ``logits_error`` is the LARGER of the
two groups' third quartile of each position's ``|program - reference| /
|reference|`` over the vocabulary.

TOLERANCE and LOGITS_TOLERANCE: see beneath the constants, with the chip
readings they were set from.
"""

import gc
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Relative, on the scalar loss (cross entropy 10.57 to 10.63 at seeded
# weights; ln 24,576 = 10.11). The system computes in bf16 with float32
# accumulation; the per-token error is random and the loss averages it over
# 4,095 positions. From the chip (PR 67; the runs and seeds are PERF.md
# section 6's): the program's relative error read 4.5e-7 to 9.0e-6 over
# fourteen seeds. The same reference with every array and operation in
# bfloat16 (fault ``bfloat16_throughout``, the nearest precision below the
# configuration's) moves its loss by 8.6e-4 to 9.5e-4: not correct. 3e-4, the
# accepted decoder cells' limit, stands thirty-three times over the largest
# sound reading and 2.9 times under the precision's. Of the ten other faults
# the loss catches NONE (4.2e-6 to 2.0e-4: at seeded weights and uniform ids
# the loss sits near ln V whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 67): the sound program reads 4.91e-3 to 4.99e-3 at
# fourteen seeds (at seed 7 ``spread`` 4.95e-3 and ``edge`` 4.89e-3; the least
# position 4.5e-3, the median 4.86e-3, the worst 1.2e-2 to 1.3e-2: a floor of
# bf16 rounding through four layers at EVERY position). The limit's control is
# the precision below the configuration's, the reference itself in bfloat16
# throughout: 6.16e-3 at both seeds read (which the LOSS limit refuses, as
# above; the witness's limit lies UNDER its control, PR 54's lesson). Then, by
# both groups' larger, at seeds 7 and 4000000007, each of ISSUE 67's controls
# and the others: the grouped-query layer rotated 8.79e-3 / 8.94e-3 and its
# gate left out 9.77e-3 / 9.81e-3 (one layer of four; the ``edge`` group,
# whose first rows have few keys), query head i reading key/value head i mod 8
# 1.23e-2 / 1.22e-2, ``beta`` without its factor 2 3.26e-2 / 3.34e-2, no
# ``S'^T k`` subtraction 7.68e-2, a decay a head instead of a channel 7.71e-2
# / 7.70e-2, the gate before the norm 1.02e-1, no decay 1.38e-1, the shared
# expert dropped 1.78e-1. ONE fault the limit does not hold: 7 of 8 experts
# reads 6.94e-3 at seed 7 and 5.25e-3 at seed 4000000007 (this share's 10 held
# experts meet a thirty-second of the pairs, and dropping the eighth choice
# moves only the rows it would have met); the CPU tests hold the count at the
# tiny size. 5.5e-3 stands 10 % over the largest sound reading and 11 % under
# the control: the geometric middle of the two. Both readings are properties
# of the architecture and the precision (the sound readings are 1.6 % apart
# over fourteen seeds).
LOGITS_TOLERANCE = 0.0055
CHUNK = 64                  # the program's chunk, which the edges follow
EDGES = (1, 8, 63)          # chunk edges whose next tokens are witnessed
EDGE_TOKENS = 8             # witnessed tokens after an edge, and at the end
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
HEAD_GROUP = 8              # attention heads at a time
QUERY_BLOCK = 256           # attention rows at a time
EXPERT_GROUP = 4            # experts on the device at a time
DENSE_CHUNK = 1280          # hidden columns of the shared expert at a time
VOCAB_CHUNK = 2048          # head columns at a time
ROPE_THETA = 10000.0        # what ``gqa_rotated`` rotates by
ROUTING_FAULTS = ("seven_of_eight_experts",)
FAULTS = ("beta_unscaled", "decay_a_head", "no_decay", "no_subtraction",
          "gate_before_norm", "gqa_gate_dropped", "kv_head_mod",
          "gqa_rotated", "shared_expert_dropped"
          ) + ROUTING_FAULTS + ("bfloat16_throughout",)
KDA_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
              "dt_bias", "a_log", "w_beta", "w_ga", "w_gb", "o_norm", "wo")
GQA_LEAVES = ("wq", "wk", "wv", "wz", "wo")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _filter(y, taps):
    """``silu(sum_j taps[j] * y[t - n + 1 + j])``, y [S, P], taps [n, P]."""
    n, s = taps.shape[0], y.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, y.shape[1]), y.dtype), y])
    return jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(n)))


def _kda(h, p, heads, eps, faults):
    """The KDA mixer's steps 1 to 4 on one sequence's normed rows h [S, E]."""
    s = h.shape[0]
    dtype = h.dtype
    q, k, v = (_filter(h @ p["w" + n], p["conv_" + n]).reshape(s, heads, -1)
               for n in "qkv")
    d = q.shape[-1]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (h @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]).reshape(s, heads, d)
    beta = 2.0 * jax.nn.sigmoid(h @ p["w_beta"])
    if "beta_unscaled" in faults:           # strengths in (0, 1)
        beta = 0.5 * beta
    if "decay_a_head" in faults:            # a head's channels decay alike
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    if "no_decay" in faults:
        g = jnp.zeros_like(g)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        if "no_subtraction" in faults:
            seen = jnp.zeros_like(seen)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - seen)[:, None, :]
        return state.astype(dtype), jnp.einsum("hkv,hk->hv", state, q_t)

    o = jax.lax.scan(token, jnp.zeros((heads, d, d), dtype),
                     (q, k, v, g.astype(dtype), beta.astype(dtype)))[1]
    gate = jax.nn.sigmoid((h @ p["w_ga"]) @ p["w_gb"]).reshape(s, heads, d)
    if "gate_before_norm" in faults:
        y = _rms(o * gate, p["o_norm"], eps)
    else:
        y = _rms(o, p["o_norm"], eps) * gate
    return y.reshape(s, -1).astype(dtype) @ p["wo"]


def _gqa_project(h, p, dims, faults):
    """q [S, H, d], k and v [S, H, d] (each query head's OWN key/value head
    laid beside it) and the gate [S, H d] of one sequence's normed rows."""
    n_heads, kv_heads, d = dims
    s = h.shape[0]
    q = (h @ p["wq"]).reshape(s, n_heads, d)
    k = (h @ p["wk"]).reshape(s, kv_heads, d)
    v = (h @ p["wv"]).reshape(s, kv_heads, d)
    if "gqa_rotated" in faults:             # rotary halves at ROPE_THETA
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * (ROPE_THETA ** (
            -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d))[None]
        cos = jnp.cos(ang).astype(h.dtype)[:, None]
        sin = jnp.sin(ang).astype(h.dtype)[:, None]

        def turn(x):
            x0, x1 = x[..., :d // 2], x[..., d // 2:]
            return jnp.concatenate([x0 * cos - x1 * sin,
                                    x0 * sin + x1 * cos], -1)

        q, k = turn(q), turn(k)
    group = n_heads // kv_heads
    of = jnp.arange(n_heads) % kv_heads if "kv_head_mod" in faults \
        else jnp.arange(n_heads) // group
    gate = jax.nn.sigmoid(h @ p["wz"])
    if "gqa_gate_dropped" in faults:
        gate = jnp.ones_like(gate)
    return q, k[:, of], v[:, of], gate


def _attend(q, k, v):
    """Causal softmax attention of a group of heads, q, k [S, G, d] and v
    [S, G, dv], at scale d^(-1/2), ``QUERY_BLOCK`` rows at a time."""
    s, _, d = q.shape
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)

    def block(args):
        q_rows, first = args
        scores = jnp.einsum("qgd,kgd->gqk", q_rows, k) / math.sqrt(d)
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(rows)[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kgd->qgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, (q.reshape((s // rows, rows) + q.shape[1:]),
                            jnp.arange(0, s, rows)))
    return o.reshape((s,) + v.shape[1:])


def _route(m, router, bias, k, scaling, fault):
    """``weight [S, n]``: each token's weights at its chosen experts'
    columns, zero elsewhere."""
    score = jax.nn.sigmoid((m @ router).astype(jnp.float32))
    if fault == "seven_of_eight_experts":
        k = k - 1
    _, top_e = jax.lax.top_k(score + bias, k)
    top_s = jnp.take_along_axis(score, top_e, axis=-1)
    top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scaling
    chosen = jax.nn.one_hot(top_e, score.shape[-1], dtype=m.dtype)
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


_route_jit = jax.jit(_route, static_argnums=(3, 4, 5))
_experts_jit = jax.jit(_experts)
_dense_jit = jax.jit(_dense_chunk)
_kda_jit = jax.jit(_kda, static_argnums=(2, 3, 4))
_gqa_jit = jax.jit(_gqa_project, static_argnums=(2, 3))
_attend_jit = jax.jit(_attend)
_rms_jit = jax.jit(_rms, static_argnums=2)


def moe_part(m, router, bias, w_gate_up, w_down, first, k, scaling=1.0,
             fault=None):
    """The routed sum for the experts [first, first + held) that the weights
    hold, on one sequence's normed rows m [S, E]; the held experts
    ``EXPERT_GROUP`` at a time, each group waited for."""
    weight = _done(_route_jit(m, router, bias, k, scaling, fault))
    y = jnp.zeros_like(m)
    held = w_gate_up.shape[0]
    for at in range(0, held, EXPERT_GROUP):
        to = min(at + EXPERT_GROUP, held)
        y = _done(_experts_jit(y, m, w_gate_up[at:to], w_down[at:to],
                               weight[:, first + at:first + to]))
    return y


def dense_part(m, w_gate_up, w_down):
    """A dense gated FFN (the shared expert), ``DENSE_CHUNK`` hidden columns
    at a time."""
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
    ``params_layers`` (a tree a run ``r<i>`` stacked [periods, run length,
    ...], or a tree a position ``p<i>`` stacked [periods, ...]); numpy views,
    nothing copied."""
    prefix = params.get("prefix_layers", {})
    trees = [prefix["l%d" % i] for i in range(len(prefix))]
    stacked = params["params_layers"]
    names = sorted(stacked, key=lambda n: int(n[1:]))
    periods = np.shape(stacked[names[0]]["ln1_scale"])[0]
    for period in range(periods):
        for name in names:
            tree = stacked[name]
            if name[0] == "r":
                for at in range(np.shape(tree["ln1_scale"])[1]):
                    trees.append({k: v[period, at] for k, v in tree.items()})
            else:
                trees.append({k: v[period] for k, v in tree.items()})
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

    assert not model["use_rope"] and model["use_gqa_gate"] \
        and model["kda_allow_neg_eigval"] and not model["kda_use_full_proj"] \
        and model["norm_topk_prob"] and model["first_k_dense_replace"] == 0 \
        and model["n_shared_experts"] == 1
    linear = model["linear_attn_config"]
    kda_heads = int(linear["num_heads"])
    n_heads = int(model["num_attention_heads"])
    dims = (n_heads, int(model["num_key_value_heads"]),
            int(model["head_dim"]))
    eps = float(model["rms_norm_eps"])
    k = int(model["num_experts_per_tok"])
    scaling = float(model["routed_scaling_factor"])
    first = int(model.get("first_expert_held", 0))
    routing = ([f for f in faults if f in ROUTING_FAULTS] or [None])[0]
    ids = np.asarray(ids)
    b, s = ids.shape
    trees = layer_trees(params)
    assert len(trees) == int(model["num_hidden_layers"]), len(trees)
    assert [i for i, tree in enumerate(trees) if "w_fa" not in tree] == [
        i for i in model["gqa_layers"] if i < len(trees)]
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for layer, tree in enumerate(trees):
            gc.collect()
            ln1 = cast(tree["ln1_scale"])
            hs = []
            if "w_fa" in tree:
                p = {name: cast(tree[name]) for name in KDA_LEAVES}
                assert p["conv_q"].shape[0] == linear[
                    "short_conv_kernel_size"] and p["o_norm"].shape[0] \
                    == linear["head_dim"]
                for x in xs:
                    hs.append(_done(x + _kda_jit(
                        _done(_rms_jit(x, ln1, eps)), p, kda_heads, eps,
                        tuple(faults))))
            else:
                p = {name: cast(tree[name]) for name in GQA_LEAVES}
                for x in xs:
                    q, kk, v, gate = _done(_gqa_jit(
                        _done(_rms_jit(x, ln1, eps)), p, dims,
                        tuple(faults)))
                    o = jnp.concatenate([_done(_attend_jit(
                        q[:, g:g + HEAD_GROUP], kk[:, g:g + HEAD_GROUP],
                        v[:, g:g + HEAD_GROUP]))
                        for g in range(0, n_heads, HEAD_GROUP)], axis=1)
                    hs.append(_done(x + (o.reshape(s, -1) * gate) @ p["wo"]))
                    del q, kk, v, o, gate
            del p, ln1
            ln2 = cast(tree["ln2_scale"])
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            router = cast(tree["router"])
            bias = jnp.asarray(params["router_bias"][layer], jnp.float32)
            w_gate_up = cast(tree["we_gate_up"])
            w_down = cast(tree["we_down"])
            routed = [moe_part(m, router, bias, w_gate_up, w_down, first, k,
                               scaling, routing) for m in ms]
            del router, w_gate_up, w_down
            ws_gate_up = cast(tree["ws_gate_up"])
            ws_down = cast(tree["ws_down"])
            xs = []
            for h, m, y in zip(hs, ms, routed):
                if "shared_expert_dropped" not in faults:
                    y = y + dense_part(m, ws_gate_up, ws_down)
                xs.append(_done(h + y))
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


def witness_groups(s):
    """``{"edge": positions, "spread": positions}`` of a sequence of ``s``
    tokens: the first EDGE_TOKENS tokens after each of the chunk edges EDGES
    that lies inside the sequence (of a sequence too short for any: after
    every quarter) and the sequence's last EDGE_TOKENS; and SPREAD_ROWS
    evenly from half a stride in, those of the first group left out.  The
    driver hands the sequence length alone."""
    edges = [e * CHUNK for e in EDGES if e * CHUNK < s] or list(
        range(max(s // 4, 1), s, max(s // 4, 1)))
    n = min(EDGE_TOKENS, max(s // 8, 1))
    edge = np.unique(np.concatenate(
        [np.arange(at, min(at + n, s)) for at in edges]
        + [np.arange(s - n, s)])).astype(int)
    stride = max(s // SPREAD_ROWS, 1)
    spread = np.setdiff1d(np.arange(stride // 2, s, stride), edge)
    return {"edge": edge, "spread": spread}


def witness_positions(s):
    """The positions whose logits the witness reads: both groups, ``edge``
    first."""
    groups = witness_groups(s)
    return np.concatenate([groups["edge"], groups["spread"]])


_last = {}      # the inputs' fingerprint and the results of the last run


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    last call's results are kept: the benchmark's driver asks for the logits
    and then the harness for the loss, of the same weights and batch."""
    ids = np.asarray(batch["ids"])
    kda = next(t for t in layer_trees(params) if "a_log" in t)
    marks = [np.asarray(a) for a in (
        params["lnf_scale"], params["router_bias"], kda["a_log"],
        kda["w_beta"])]
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
    vocabulary, [B * P] (a sequence's ``edge`` group first, then its
    ``spread``): the program's logits ``got`` [B, P, V] at
    ``witness_positions`` against the reference's."""
    want = logits(params, batch, model, faults)
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1)).reshape(-1)


def group_errors(got, params, batch, model, faults=()):
    """``{"edge": q75, "spread": q75}``: the third quartile of each group's
    ``position_errors``, over all sequences of the batch."""
    each = position_errors(got, params, batch, model, faults).reshape(
        np.asarray(got).shape[0], -1)
    n_edge = len(witness_groups(np.asarray(batch["ids"]).shape[1])["edge"])
    parts = {"edge": each[:, :n_edge], "spread": each[:, n_edge:]}
    return {name: float(np.quantile(part, 0.75)) if part.size else 0.0
            for name, part in parts.items()}


def logits_error(got, params, batch, model, faults=()):
    """The LARGER of the two groups' third quartile: what LOGITS_TOLERANCE
    bounds."""
    return max(group_errors(got, params, batch, model, faults).values())
