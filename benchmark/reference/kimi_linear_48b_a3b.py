"""Plain reference for ``kimi_linear_48b_a3b``: the training loss of a
Kimi-Linear decoder (moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``,
HF ``model_type`` ``kimi_linear``; Kimi Linear, arXiv:2510.26692) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No kernels,
no chunks, no scan over layers, no sharding, no sort and no grouped matmul,
nothing imported from the program: it takes the program's weights by their
names in the parameter tree and a batch (``ids``) and returns the loss.

A layer on one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x *
rsqrt(mean(x^2) + eps) * g``, eps ``rms_norm_eps``): ``x += mixer(rms(x,
ln1_scale)); x += ffn(rms(x, ln2_scale))``.  Which mixer and which FFN a
layer has is read off its leaves.

KDA mixer (H = ``linear_attn_config.num_heads`` heads of d =
``linear_attn_config.head_dim``), on the normed rows h:

1. ``q, k, v = silu(filter(h @ wq)), silu(filter(h @ wk)), silu(filter(h @
   wv))``, ``filter(y)_t = sum_j conv[j] * y[t - taps + 1 + j]``, zero before
   position 0, ``short_conv_kernel_size`` taps, no bias; per head ``q <- q /
   sqrt(|q|^2 + 1e-6) * d^(-1/2)``, ``k <- k / sqrt(|k|^2 + 1e-6)``.
2. ``g = -exp(a_log_head) * softplus((h @ w_fa) @ w_fb + dt_bias)`` [S, H,
   d], one log-decay a CHANNEL of the key; ``beta = sigmoid(h @ w_beta)``
   [S, H].
3. A state ``S`` [d (key), d (value)] a head, from zero, a TOKEN at a time:
   ``S' = diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``;
   ``o_t = S^T q_t``.
4. ``y = rms_head(o) * o_norm * sigmoid((h @ w_ga) @ w_gb)``: an RMS norm
   over each head's d columns with ONE scale [d], THEN the gate; ``y @ wo``.

Latent mixer (H = ``num_attention_heads``, dn / dr / dv =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``; ``mla_use_nope``:
NOTHING is rotated; ``q_lora_rank`` null: one query matrix): ``q = h @ wq``
[H, dn + dr]; ``[c | ks] = h @ wkv_a`` (``kv_lora_rank`` columns, then dr);
``rms(c, kv_a_norm) @ wkv_b`` head i ``[k_nope_i | v_i]``; ``k_i = [k_nope_i
| ks]``, the SAME ``ks`` in every head; ``o_i = softmax_causal((dn +
dr)^(-1/2) q_i k_i^T) v_i``; ``concat(o) @ wo``.

Dense FFN (a layer that holds ``w_gate_up``): ``(silu(m @ Wg) * (m @ Wu))
@ w_down``.  Sparse FFN: ``s = sigmoid(m @ router)`` over all
``moe_router_width`` experts; the ``num_experts_per_token`` largest of ``s +
router_bias``; weights ``s_e / sum of the chosen s`` (``moe_renormalize``)
times ``routed_scaling_factor``; the routed experts this share holds
(``we_gate_up`` [held, E, 2F], ``we_down`` [held, F, E]) and the shared
expert (``ws_gate_up``, ``ws_down``), which every token meets with weight 1.
``logits = rms(x_L, lnf_scale) @ lm_head^T``; cross entropy of token t + 1
at positions 0..S-2, mean over the batch.  No auxiliary loss.

THE CUT: ``num_hidden_layers`` layers, the published layers 1 to 5 (KDA with
the dense FFN, then KDA, KDA, latent, KDA over experts).  THE SHARE:
``num_experts`` experts of the router's ``moe_router_width`` from
``moe_first_expert_held``, and ``vocab_size`` rows of the vocabulary.  The
router ranks all its experts and the weights are formed over all chosen
ones; every HELD expert is evaluated on every token and combined with those
weights at its column, zero elsewhere (a different algorithm from the
program's sort, capacities and grouped matmul, on purpose); what the absent
experts would add is left out, and that partial result goes on.  Every share
computes the shared expert.  ``tests/test_kimi_linear_reference.py`` adds
the program's routed parts over all shares, and the shared expert ONCE, up
to this file's layer with every expert held.

Departures from the published description, each under ``assumed`` in the
configuration's file: the KDA gates' rank and the seeding (the family's
public modelling code gives them, ``config.json`` does not), the L2 norm's
eps, the cut and the share; no document mask (no state reset at a document
boundary).

What it holds on the device at once is kept small (it runs beside 6.6 GB of
trainer state): a layer's mixer weights go up alone, attention runs
``HEAD_GROUP`` heads and ``QUERY_BLOCK`` rows at a time, the experts
``EXPERT_GROUP`` at a time, a dense FFN ``DENSE_CHUNK`` hidden columns at a
time, the head ``VOCAB_CHUNK`` columns at a time.  Every call is waited for
before the next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/kimi_linear_ref_sensitivity.py``.

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

# Relative, on the scalar loss (cross entropy 10.41 to 10.44 at seeded
# weights; ln 20,480 = 9.93).  The system computes in bf16 with float32
# accumulation; the per-token error is random and the loss averages it over
# 16,383 positions.  From the chip (PR 58; the runs and seeds are PERF.md
# section 6's): the program's relative error read 9.1e-8 to 1.1e-5 over twenty
# seeds.  The same reference with every array and operation in bfloat16
# (fault ``bfloat16_throughout``, the nearest precision below the
# configuration's) moves its loss by 1.71e-3: not correct.  3e-4, the
# accepted decoder cells' limit, stands 27 times over the largest sound
# reading and 5.7 times under the precision's.  Of the ten other faults the
# loss catches NONE (2.3e-6 to 1.3e-4: at seeded weights and uniform ids the
# loss sits near ln V whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 58): the sound program reads 5.64e-3 to 5.79e-3 at twenty
# seeds (at seed 7 ``spread`` 5.67e-3 and ``edge`` 5.65e-3; the least
# position 5.2e-3, the median 5.6e-3, the worst 3.2e-2 to 4.9e-2: a floor of
# bf16 rounding through five layers at EVERY position, and a few positions
# where rounding changes which expert is eighth of 256).  The limit's control
# is the precision below the configuration's, the reference itself in
# bfloat16 throughout: 7.33e-3 (which the LOSS limit refuses, as above; the
# witness's limit lies UNDER its control, PR 54's lesson).  Then, by both
# groups' larger: the shared key in head 0 alone 1.14e-2 and the shared key
# rotated 1.18e-2 (the ``edge`` group; one layer of five and 64 of its 192
# columns), 7 of 8 experts 2.98e-2, the value's first lanes read off the
# shared key 2.99e-2, a route scale of 1 for 2.446 3.54e-2, beta = 1 7.75e-2,
# no ``S'^T k`` subtraction 8.18e-2, the gate before the norm 1.39e-1, no
# decay 1.88e-1, the shared expert dropped 2.30e-1.  6.5e-3 stands 12 % over
# the largest sound reading and 11 % under the control: the geometric middle
# of the two.  Both readings are properties of the architecture and the
# precision (the sound readings are 2.7 % apart over twenty seeds).
LOGITS_TOLERANCE = 0.0065
CHUNK = 64                  # the program's chunk, which the edges follow
EDGES = (1, 8, 64, 255)     # chunk edges whose next tokens are witnessed
EDGE_TOKENS = 8             # witnessed tokens after an edge, and at the end
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
HEAD_GROUP = 8              # attention heads at a time
QUERY_BLOCK = 256           # attention rows at a time
EXPERT_GROUP = 4            # experts on the device at a time
DENSE_CHUNK = 1024          # hidden columns of a dense FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
ROUTING_FAULTS = ("seven_of_eight_experts", "route_scale_one")
FAULTS = ("no_decay", "beta_one", "no_subtraction", "gate_before_norm",
          "shared_key_rotated", "value_reads_shared_key",
          "shared_key_of_head_0_only", "shared_expert_dropped"
          ) + ROUTING_FAULTS + ("bfloat16_throughout",)
KDA_LEAVES = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "w_fa", "w_fb",
              "dt_bias", "a_log", "w_beta", "w_ga", "w_gb", "o_norm", "wo")
LATENT_LEAVES = ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")


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
    beta = jax.nn.sigmoid(h @ p["w_beta"])
    if "no_decay" in faults:
        g = jnp.zeros_like(g)
    if "beta_one" in faults:
        beta = jnp.ones_like(beta)

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


def _latent_project(h, p, dims, eps, faults):
    """q, k [S, H, dn + dr] and v [S, H, dv] of one sequence's normed rows."""
    n_heads, r_kv, dn, dr, dv = dims
    s = h.shape[0]
    q = (h @ p["wq"]).reshape(s, n_heads, dn + dr)
    kv_a = h @ p["wkv_a"]
    ckv, ks = _rms(kv_a[:, :r_kv], p["kv_a_norm"], eps), kv_a[:, r_kv:]
    kv = (ckv @ p["wkv_b"]).reshape(s, n_heads, dn + dv)
    if "shared_key_rotated" in faults:      # rotary pairs at theta 10,000
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * (10000.0 ** (
            -2.0 * jnp.arange(dr // 2, dtype=jnp.float32) / dr))[None]
        cos, sin = jnp.cos(ang).astype(h.dtype), jnp.sin(ang).astype(h.dtype)
        k0, k1 = ks[:, 0::2], ks[:, 1::2]
        ks = jnp.stack([k0 * cos - k1 * sin, k0 * sin + k1 * cos],
                       axis=-1).reshape(ks.shape)
    v = kv[..., dn:]
    if "value_reads_shared_key" in faults:  # lanes that are not the value's
        v = jnp.concatenate([jnp.broadcast_to(
            ks[:, None, :], (s, n_heads, dr)), v[..., dr:]], -1)
    ks = jnp.broadcast_to(ks[:, None, :], (s, n_heads, dr))
    if "shared_key_of_head_0_only" in faults:
        ks = ks * (jnp.arange(n_heads) == 0)[None, :, None].astype(ks.dtype)
    return q, jnp.concatenate([kv[..., :dn], ks], -1), v


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
    if fault == "route_scale_one":
        scaling = 1.0
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
_latent_jit = jax.jit(_latent_project, static_argnums=(2, 3, 4))
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
    """A dense gated FFN (the leading layer's, the shared expert),
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

    assert model["mla_use_nope"] and model["q_lora_rank"] is None \
        and model["moe_renormalize"] and model["num_expert_group"] == 1 \
        and model["moe_router_activation_func"] == "sigmoid"
    linear = model["linear_attn_config"]
    kda_heads = int(linear["num_heads"])
    n_heads = int(model["num_attention_heads"])
    dims = (n_heads, int(model["kv_lora_rank"]),
            int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"]),
            int(model["v_head_dim"]))
    eps = float(model["rms_norm_eps"])
    k = int(model["num_experts_per_token"])
    scaling = float(model["routed_scaling_factor"])
    first = int(model.get("moe_first_expert_held", 0))
    routing = ([f for f in faults if f in ROUTING_FAULTS] or [None])[0]
    ids = np.asarray(ids)
    b, s = ids.shape
    trees = layer_trees(params)
    assert len(trees) == int(model["num_hidden_layers"]), len(trees)
    sparse = 0
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for tree in trees:
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
                p = {name: cast(tree[name]) for name in LATENT_LEAVES}
                for x in xs:
                    q, kk, v = _done(_latent_jit(
                        _done(_rms_jit(x, ln1, eps)), p, dims, eps,
                        tuple(faults)))
                    o = jnp.concatenate([_done(_attend_jit(
                        q[:, g:g + HEAD_GROUP], kk[:, g:g + HEAD_GROUP],
                        v[:, g:g + HEAD_GROUP]))
                        for g in range(0, n_heads, HEAD_GROUP)], axis=1)
                    hs.append(_done(x + o.reshape(s, -1) @ p["wo"]))
                    del q, kk, v, o
            del p, ln1
            ln2 = cast(tree["ln2_scale"])
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            if "w_gate_up" in tree:
                w_gate_up, w_down = cast(tree["w_gate_up"]), \
                    cast(tree["w_down"])
                xs = [_done(h + dense_part(m, w_gate_up, w_down))
                      for h, m in zip(hs, ms)]
                del w_gate_up, w_down, hs, ms, ln2
                continue
            router = cast(tree["router"])
            bias = jnp.asarray(params["router_bias"][sparse], jnp.float32)
            sparse += 1
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
    marks = [np.asarray(a) for a in (
        params["lnf_scale"], params["router_bias"],
        params["prefix_layers"]["l0"]["a_log"],
        params["prefix_layers"]["l0"]["w_beta"])]
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
