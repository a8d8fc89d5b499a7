"""Plain reference for ``trinity_large_preview``: the training loss of a
Trinity decoder (arcee-ai/Trinity-Large-Preview ``config.json``, HF
``model_type`` ``afmoe``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no scan over
layers, no sharding, no sort and no grouped matmul, nothing imported from the
program: it takes the program's weights by their names in the parameter tree
and a batch (``ids``) and returns the loss.

``x_0 = tok_emb[ids] * sqrt(hidden_size)`` (``mup_enabled``).  Layer l, on
one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x * rsqrt(mean(x^2) +
eps) * g``, eps ``rms_norm_eps``; H = ``num_attention_heads``, Hkv =
``num_key_value_heads``, dh = ``head_dim``):

1. ``a = rms(x, ln1_scale)``; ``q = a @ wq`` [S, H, dh], ``k = a @ wk``,
   ``v = a @ wv`` [S, Hkv, dh], ``z = a @ wz`` [S, H * dh] (the gate).  q
   and k RMS-normed over EACH head's dh (``q_norm`` / ``k_norm`` [dh], one
   weight for all heads).  Where ``layer_types[l]`` is
   ``sliding_attention``: rotate-half rotary embedding on q and k over the
   whole head width, positions 0..S-1, theta ``rope_theta``; where it is
   ``full_attention``: NO positions.
2. Query head h reads key/value head ``h // (H // Hkv)``; scores ``q k^T /
   sqrt(dh)``; key j is visible to query i iff ``j <= i`` and, in a sliding
   layer, ``i - j < sliding_window``; softmax.  ``y = (o * sigmoid(z)) @
   wo``; ``x <- x + rms(y, ln1_post_scale)``.
3. ``b = rms(x, ln2_scale)``.  A leading layer (published index below the
   published ``num_dense_layers`` 6): ``f = (silu(b @ Wg) * (b @ Wu)) @
   w_down``, ``[Wg, Wu] = w_gate_up`` [E, 2F], F = ``intermediate_size``.
   Every other layer: ``s = sigmoid(b @ router)`` over all
   ``moe_router_width`` experts; the ``num_experts_per_tok`` experts T with
   the largest ``s_e + bias_e`` (this layer's row of ``router_bias``: it
   chooses and nothing else); weights ``w_e = s_e / (sum_{e in T} s_e +
   ROUTE_EPS) * route_scale`` (``route_norm``); ``f = sum_{e in T, held} w_e
   * down_e(silu(gate_e b) * up_e b) + down_s(silu(gate_s b) * up_s b)``:
   the routed experts this share holds (``we_gate_up`` [held, E, 2F],
   ``we_down`` [held, F, E], F = ``moe_intermediate_size``) and the shared
   expert (``ws_gate_up`` [E, 2Fs], ``ws_down`` [Fs, E], Fs =
   ``num_shared_experts * moe_intermediate_size``), which every token meets
   with weight 1.  ``x <- x + rms(f, ln2_post_scale)``: the output norm
   takes the SUM.
4. ``logits = rms(x_L, lnf_scale) @ lm_head^T``; cross entropy of token t +
   1 at positions 0..S-2, mean over the batch.  No auxiliary loss.

THE CUT.  The weights hold ``num_dense_layers`` leading layers
(``prefix_layers/l<i>``: the LAST of the published dense layers, published
indices ``first_expert_layer - num_dense_layers`` ..) and then whole periods
of ``PERIOD`` layers from published layer ``first_expert_layer`` on
(``params_layers/p<position>``, stacked by period); ``layer_types`` stands
whole and is read at those indices.  THE SHARE: ``num_experts`` experts of
the router's ``moe_router_width`` from ``moe_first_expert_held``, and
``vocab_size`` rows of the vocabulary.  The router ranks all its experts and
the weights are formed over all chosen ones; every HELD expert is evaluated
on every token and combined with those weights at its column, zero elsewhere
(a different algorithm from the program's sort, capacities and grouped
matmul, on purpose); what the absent experts would add is left out, and that
partial result goes on.  Every share computes the shared expert.
``tests/test_trinity_reference.py`` adds the program's routed parts over all
shares, and the shared expert ONCE, up to this file's ``f`` with every
expert held, BEFORE the output norm (which is not linear).

ROUTE_EPS is 1e-20, as the ``afmoe`` router has it; the program's rule
(``moe.route_top_k``) adds 1e-6 to a sum of four sigmoids that is near 2: a
weight differs by 5e-7 of itself, under float32's own rounding of the
triple product, and both limits below carry it.

Departures from the published description, each under ``assumed`` in the
configuration's file: the formulas the config names by key alone (the
gate's place, per-head q/k norm, rotary on sliding layers only, the four
norms' places, the embedding's multiplier, weights from the scores without
the bias, ``load_balance_coeff`` as the sign rule's rate); the cut and the
share; no document mask.

What it holds on the device at once is kept small (it runs beside 9.6 GB of
trainer state): a layer's attention weights go up alone, attention runs one
key/value head's group of query heads and ``QUERY_BLOCK`` rows at a time,
the dense FFN and the shared expert ``DENSE_CHUNK`` hidden columns at a
time, the experts ``EXPERT_GROUP`` at a time, the head ``VOCAB_CHUNK``
columns at a time.  Every call is waited for before the next is sent.
``faults`` puts a fault in, for
``benchmark/tools/trinity_ref_sensitivity.py``.

``witness_positions`` has two named groups: ``edge``, EDGE_TOKENS positions
on each side of position ``sliding_window`` (where the window first cuts a
key off) and the last EDGE_TOKENS of the sequence, and ``spread``,
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

# Relative, on the scalar loss (cross entropy 10.61 to 10.63 at seeded
# weights; ln 25,024 = 10.13).  The system computes in bf16 with float32
# accumulation; the per-token error is random and the loss averages it over
# 6,143 positions.  All readings beneath are the chip's, of the program as
# the configuration seeds it (output-norm gains 0.05, selection biases
# 0.01: ``assumed`` i; PR 45's last calls, the runs and seeds are PERF.md
# section 6's).  The program's relative error read 5.4e-7 to 1.4e-5 over
# fifteen runs at fifteen seeds.  The same reference with every array and
# operation in bfloat16 (fault ``bfloat16_throughout``, the nearest precision
# below the configuration's) moves its loss by 9.9e-4: not correct.  3e-4,
# the accepted decoder cells' limit, stands 21 times over the largest sound
# reading and 3.3 times under the precision's.  Of the seventeen other
# faults the loss catches three (the shared expert past the output norm
# 7.2e-4, the multiplier dropped 6.9e-4, the FFN's output norm dropped
# 4.8e-4) and not the rest (2.7e-7 to 1.7e-4: at seeded weights and uniform
# ids the loss sits near ln V whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# The sound program reads 2.62e-3 to 2.76e-3 over fifteen runs at fifteen seeds
# (at seed 1987654321 ``spread`` 2.642e-3 and ``edge`` 2.636e-3; the least
# position 1.9e-3, the median 2.5e-3, the ninth decile 2.8e-3, the worst
# 2.7e-2: a floor of bf16 rounding through five layers at EVERY position,
# and a few positions where rounding changes which expert is fourth of
# 256).  The least fault is the precision below the configuration's:
# ``bfloat16_throughout`` 6.91e-3.  Then, each put into the reference
# against the program's logits (seed 1987654321): q/k norm dropped 1.49e-2,
# the route scale 1 1.54e-2 (the median position reads 2.7e-3: a token's
# routed part is touched where it meets a held expert, one layer in eight,
# and its neighbours through attention), softmax scores 2.61e-2, weights not
# renormalised 2.94e-2, rotary on the full layer 3.98e-2, the gate dropped
# 4.47e-2, no window 4.57e-2 (the ``edge`` group: 16 of its 24 positions
# stand from 4,096 on; ``spread`` reads 2.37e-2, a third of its positions
# being past the window), the gate on the values 6.85e-2, attention's
# output norm dropped 7.92e-2, the shared expert dropped 1.00e-1, the wrong
# key/value head 1.54e-1, the shared expert past the output norm 7.91e-1,
# the FFN's output norm dropped 8.48e-1, the multiplier dropped 1.31.
# NOT seen by either limit, three: ``gate_reads_block_input`` 2.73e-3 (at
# these seeds the stream has unit scale and the input norms' weights are
# one, so the normed rows ARE the stream's to a part in a thousand: the
# fault is no fault here; with output-norm gains of one it read 1.87e-1),
# ``bias_added_to_weights`` 2.65e-3 and ``bias_ignored_in_selection``
# 2.76e-3 (its ninth decile 3.1e-3 against 2.8e-3, its worst position
# 3.9e-2): biases of 0.01 change the fourth expert of 256 at some tokens
# only, a token meets a HELD expert in one layer of eight, and the
# statistic is a third quartile: fewer than a quarter of the positions
# move.  At biases of 0.1 the choice read 2.73e-2, and the step's time
# followed the seed (``assumed`` i); what holds the bias to its place, and
# the gate to the normed rows, is ``tests/test_trinity_reference.py`` on the
# CPU (float32 against float32, 1e-5, with weights that make each matter).
# 4.3e-3 stands 56 % over the largest sound reading and 38 % under the
# least fault: the geometric middle of the two (4.37e-3) rounded down, as
# ``mistral_small_4_119b``'s.  Both readings are properties of the
# architecture, the seeding and the precision (the sound readings are 5 %
# apart over the seeds).  With output-norm gains of ONE (PR 45's first
# calls) every branch re-entered the stream at unit scale and both readings
# stood three times higher: sound 8.36e-3 to 8.60e-3, the precision
# 1.485e-2; the limit then would have been 1.12e-2.
LOGITS_TOLERANCE = 0.0043
ROUTE_EPS = 1e-20           # the renormalisation's, as the afmoe router's
EDGE_TOKENS = 8             # witnessed positions on each side of the edge
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
WINDOW = 4096               # the published sliding_window
PERIOD = 4                  # layers of one period of the published pattern
EXPERT_GROUP = 2            # experts on the device at a time
QUERY_BLOCK = 256           # attention rows at a time
DENSE_CHUNK = 1024          # hidden columns of a dense FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
ROUTING_FAULTS = ("route_scale_one", "bias_added_to_weights",
                  "bias_ignored_in_selection", "weights_not_renormalised",
                  "softmax_scores")
FAULTS = ("gate_dropped", "gate_reads_block_input", "gate_on_values",
          "attention_output_norm_dropped", "ffn_output_norm_dropped",
          "shared_expert_past_output_norm", "rotary_everywhere",
          "full_attention_everywhere", "embedding_multiplier_dropped",
          "qk_norm_dropped", "wrong_kv_head", "shared_expert_dropped",
          ) + ROUTING_FAULTS + ("bfloat16_throughout",)
ATTENTION_LEAVES = ("wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x [S, H, dh]; pair i of a head is (x[i], x[i + dh/2])."""
    s, _, dh = x.shape
    inv_freq = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * cos[:, None, :].astype(x.dtype)
            + rot * sin[:, None, :].astype(x.dtype))


def _project(a, x, p, n_heads, n_kv, eps, theta, rotary, faults):
    """Step 1 on one sequence's normed rows a [S, E] (x: the un-normed
    stream, which a fault's gate reads): q [S, H, dh], k, v [S, Hkv, dh]
    and the gate ``sigmoid(z)`` [S, H * dh]."""
    s = a.shape[0]
    q = (a @ p["wq"]).reshape(s, n_heads, -1)
    k = (a @ p["wk"]).reshape(s, n_kv, -1)
    v = (a @ p["wv"]).reshape(s, n_kv, -1)
    z = (x if "gate_reads_block_input" in faults else a) @ p["wz"]
    if "qk_norm_dropped" not in faults:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if rotary:
        q, k = _rotary(q, theta), _rotary(k, theta)
    return q, k, v, jax.nn.sigmoid(z)


def _attend(q, k, v, window):
    """Softmax attention of the query heads q [S, G, dh] that share ONE
    key/value head k, v [S, dh]; ``window`` None: every key up to the
    query's own."""
    s, _, dh = q.shape
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)

    def block(args):
        q_rows, first = args
        scores = jnp.einsum("qgd,kd->gqk", q_rows, k) / math.sqrt(dh)
        at = first + jnp.arange(rows)[:, None]
        key = jnp.arange(s)[None, :]
        seen = key <= at
        if window is not None:
            seen = seen & (at - key < window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, (q.reshape((s // rows, rows) + q.shape[1:]),
                            jnp.arange(0, s, rows)))
    return o.reshape(q.shape)


def _attention_out(x, o, gate, wo, post, eps, faults):
    """Step 2's end: ``x + rms((o * gate) @ wo, post)``."""
    if "gate_dropped" not in faults and "gate_on_values" not in faults:
        o = o * gate
    y = o @ wo
    if "attention_output_norm_dropped" not in faults:
        y = _rms(y, post, eps)
    return x + y


def _dense_chunk(acc, m, w_gate, w_up, w_down):
    return acc + (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _route(m, router, bias, k, scale, fault):
    """``weight`` [S, n]: each token's weights at its chosen experts'
    columns, zero elsewhere."""
    logits = m @ router
    scores = jax.nn.softmax(logits, axis=-1) if fault == "softmax_scores" \
        else jax.nn.sigmoid(logits)
    ranked = scores if fault == "bias_ignored_in_selection" else scores + bias
    _, top_e = jax.lax.top_k(ranked, k)
    top_w = jnp.take_along_axis(
        ranked if fault == "bias_added_to_weights" else scores, top_e, -1)
    if fault != "weights_not_renormalised":
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + ROUTE_EPS)
    if fault != "route_scale_one":
        top_w = top_w * scale
    chosen = jax.nn.one_hot(top_e, logits.shape[-1], dtype=m.dtype)
    return jnp.sum(chosen * top_w[..., None].astype(m.dtype), axis=1)


def _experts(acc, m, w_gate_up, w_down, weight):
    """``acc`` plus a group of experts on EVERY token of ``m``, each times
    its column of ``weight`` [S, g]: w_gate_up [g, E, 2F], w_down [g, F, E]."""
    f = w_down.shape[1]
    gu = jnp.einsum("se,gef->gsf", m, w_gate_up)
    out = jnp.einsum("gsf,gfe->gse", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                     w_down)
    return acc + jnp.sum(out * weight.T[..., None], axis=0)


_route_jit = jax.jit(_route, static_argnums=(3, 4, 5))
_experts_jit = jax.jit(_experts)
_dense_jit = jax.jit(_dense_chunk)
_project_jit = jax.jit(_project, static_argnums=(3, 4, 5, 6, 7, 8))
_attend_jit = jax.jit(_attend, static_argnums=3)
_attention_out_jit = jax.jit(_attention_out, static_argnums=(5, 6))
_rms_jit = jax.jit(_rms, static_argnums=2)


def moe_part(m, router, bias, w_gate_up, w_down, first, k, scale=1.0,
             fault=None):
    """Step 3's routed sum for the experts [first, first + held) that the
    weights hold, on one sequence's normed rows m [S, E]; the held experts
    ``EXPERT_GROUP`` at a time, each group waited for."""
    weight = _done(_route_jit(m, router, bias, k, scale, fault))
    y = jnp.zeros_like(m)
    for at in range(0, w_gate_up.shape[0], EXPERT_GROUP):
        y = _done(_experts_jit(
            y, m, w_gate_up[at:at + EXPERT_GROUP],
            w_down[at:at + EXPERT_GROUP],
            weight[:, first + at:first + at + EXPERT_GROUP]))
    return y


def dense_part(m, w_gate_up, w_down):
    """A dense gated FFN (a leading layer's, the shared expert),
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


def layers_of(params, model):
    """``(published index, leaves, dense, bias row or None)`` of each layer
    the weights hold, in order: a function ``leaves(name)`` gives a leaf of
    that layer."""
    n_dense = int(model["num_dense_layers"])
    first = int(model["first_expert_layer"])
    out = []
    for i in range(n_dense):
        tree = params["prefix_layers"]["l%d" % i]
        out.append((first - n_dense + i, tree.__getitem__, True, None))
    for i in range(int(model["num_hidden_layers"]) - n_dense):
        tree = params["params_layers"]["p%d" % (i % PERIOD)]
        out.append((first + i,
                    lambda name, tree=tree, at=i // PERIOD: tree[name][at],
                    False, params["router_bias"][i]))
    return out


def ffn_sum(m, leaf, bias, model, faults=(), cast=jnp.asarray):
    """Step 3's ``f`` of a sparse layer BEFORE its output norm, on one
    sequence's normed rows m [S, E]: ``(routed part of the held experts,
    shared expert)``."""
    routing = ([f for f in faults if f in ROUTING_FAULTS] or [None])[0]
    routed = moe_part(
        m, cast(leaf("router")), cast(bias), cast(leaf("we_gate_up")),
        cast(leaf("we_down")), int(model.get("moe_first_expert_held", 0)),
        int(model["num_experts_per_tok"]), float(model["route_scale"]),
        routing)
    shared = dense_part(m, cast(leaf("ws_gate_up")), cast(leaf("ws_down")))
    return routed, shared


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

    assert model["score_func"] == "sigmoid" and model["route_norm"] \
        and model["mup_enabled"] and model["num_shared_experts"] == 1 \
        and not model["tie_word_embeddings"]
    n_heads = int(model["num_attention_heads"])
    n_kv = int(model["num_key_value_heads"])
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    window = int(model["sliding_window"])
    group_heads = n_heads // n_kv
    multiplier = 1.0 if "embedding_multiplier_dropped" in faults \
        else math.sqrt(model["hidden_size"])
    static = tuple(faults)
    ids = np.asarray(ids)
    b, s = ids.shape
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(cast(params["tok_emb"][ids[j]]) * multiplier)
              for j in range(b)]
        for index, leaf, dense, bias in layers_of(params, model):
            gc.collect()
            sliding = model["layer_types"][index] == "sliding_attention"
            assert sliding or model["layer_types"][index] == "full_attention"
            rotary = sliding or "rotary_everywhere" in faults
            banded = sliding and "full_attention_everywhere" not in faults
            ln1, post = cast(leaf("ln1_scale")), cast(leaf("ln1_post_scale"))
            p = {name: cast(leaf(name)) for name in ATTENTION_LEAVES}
            hs = []
            for x in xs:
                q, kk, v, gate = _done(_project_jit(
                    _done(_rms_jit(x, ln1, eps)), x, p, n_heads, n_kv, eps,
                    theta, rotary, static))
                if "gate_on_values" in faults:      # before the softmax's sum
                    v = v * gate.reshape(s, n_heads, -1)[:, ::group_heads]
                o = jnp.zeros_like(q)
                for g in range(n_kv):
                    mine = (slice(g, None, n_kv) if "wrong_kv_head" in faults
                            else slice(g * group_heads, (g + 1) * group_heads))
                    o = o.at[:, mine].set(_done(_attend_jit(
                        q[:, mine], kk[:, g], v[:, g],
                        window if banded else None)))
                hs.append(_done(_attention_out_jit(
                    x, o.reshape(s, -1), gate, p["wo"], post, eps, static)))
                del q, kk, v, gate, o
            del p, ln1, post
            ln2, post = cast(leaf("ln2_scale")), cast(leaf("ln2_post_scale"))
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            if dense:
                w_gate_up, w_down = (cast(leaf("w_gate_up")),
                                     cast(leaf("w_down")))
                fs = [(dense_part(m, w_gate_up, w_down), 0.0) for m in ms]
                del w_gate_up, w_down
            else:
                fs = [ffn_sum(m, leaf, bias, model, faults, cast) for m in ms]
            xs = []
            for h, (routed, shared) in zip(hs, fs):
                if "shared_expert_dropped" in faults:
                    shared = 0.0
                past = "shared_expert_past_output_norm" in faults and not dense
                f = routed if past else routed + shared
                if "ffn_output_norm_dropped" not in faults:
                    f = _done(_rms_jit(f, post, eps))
                xs.append(_done(h + f + shared if past else h + f))
            del hs, ms, fs, ln2, post
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
    tokens: EDGE_TOKENS positions on each side of position WINDOW (of a
    quarter of the sequence, where it is too short to pass the window: the
    tiny configurations') and the sequence's last EDGE_TOKENS; and
    SPREAD_ROWS evenly from half a stride in, those of the first group left
    out.  The driver hands the sequence length alone."""
    at = WINDOW if s > WINDOW else max(s // 4, 1)
    n = min(EDGE_TOKENS, max(at // 4, 1))
    edge = np.unique(np.concatenate(
        [np.arange(at - n, at + n), np.arange(s - n, s)])).astype(int)
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
    tree = params["params_layers"]["p0"]
    marks = [np.asarray(params["router_bias"])] + [
        np.asarray(tree[name]) for name in ("router", "ln1_post_scale",
                                            "q_norm")]
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


def group_errors(got, params, batch, model, faults=(), quantile=0.75):
    """``{"edge": q, "spread": q}``: the ``quantile`` (the third quartile)
    of each group's ``position_errors``, over all sequences of the batch."""
    each = position_errors(got, params, batch, model, faults).reshape(
        np.asarray(got).shape[0], -1)
    n_edge = len(witness_groups(np.asarray(batch["ids"]).shape[1])["edge"])
    parts = {"edge": each[:, :n_edge], "spread": each[:, n_edge:]}
    return {name: float(np.quantile(part, quantile)) if part.size else 0.0
            for name, part in parts.items()}


def logits_error(got, params, batch, model, faults=()):
    """The LARGER of the two groups' third quartile: what LOGITS_TOLERANCE
    bounds."""
    return max(group_errors(got, params, batch, model, faults).values())
