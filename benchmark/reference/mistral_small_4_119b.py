"""Plain reference for ``mistral_small_4_119b``: the training loss of a
Mistral-Small-4 decoder (mistralai/Mistral-Small-4-119B-2603 ``config.json``,
HF ``model_type`` ``mistral4``, the language model alone) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No kernels,
no scan over layers, no sharding, no sort and no grouped matmul, nothing
imported from the program: it takes the program's weights by their names in
the parameter tree and a batch (``ids``) and returns the loss.

Layer l, on one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x *
rsqrt(mean(x^2) + eps) * g``, eps ``rms_norm_eps``; H =
``num_attention_heads``, dn / dr / dv = ``qk_nope_head_dim`` /
``qk_rope_head_dim`` / ``v_head_dim``):

1. ``h = rms(x, ln1_scale)``.
2. Queries: ``cq = rms(h @ wq_a, q_a_norm)`` [``q_lora_rank``]; ``q = cq @
   wq_b`` [H, dn + dr]; head i is ``[q_nope_i | q_rope_i]``.
3. Keys and values: ``[ckv | kr] = h @ wkv_a`` (``kv_lora_rank`` columns,
   then dr); ``ckv = rms(ckv, kv_a_norm)``; ``ckv @ wkv_b`` [H, dn + dv],
   head i is ``[k_nope_i | v_i]``.  ``kr`` is ONE vector a token.
4. Positions: ``q_rope_i`` and ``kr`` are rotated, columns (2j, 2j + 1) the
   pair j (``rope_interleave``), by the angle ``pos * f_j``, ``f_j`` YaRN's
   blend over the dr / 2 pairs: ``f_j = (1 - m_j) * theta^(-2j/dr) / factor
   + m_j * theta^(-2j/dr)``, ``m_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``,
   ``lo`` / ``hi`` the floor / ceiling of ``dr * ln(original_max / (beta * 2
   pi)) / (2 ln theta)`` at ``beta_fast`` / ``beta_slow``, inside [0, dr/2 -
   1]; cos and sin times ``mscale(factor, mscale) / mscale(factor,
   mscale_all_dim)`` (``mscale(f, a) = 0.1 a ln f + 1``), which is 1 here.
5. ``k_i = [k_nope_i | rot(kr)]``, the same rotated dr in every head;
   ``q_i = [q_nope_i | rot(q_rope_i)] * s(pos)``, ``s(pos) = 1 +
   llama_4_scaling_beta * ln(1 + floor(pos / original_max))``.
6. ``o_i = softmax_causal(c * q_i k_i^T) v_i``, ``c = (dn + dr)^(-1/2) *
   mscale(factor, mscale_all_dim)^2``; ``x <- x + concat(o) @ wo``.
7. ``h2 = rms(x, ln2_scale)``; ``g = h2 @ router`` over all
   ``moe_router_width`` experts; the ``num_experts_per_tok`` largest logits,
   weights the softmax over those (= softmax over all, the k largest,
   renormalised: ``norm_topk_prob``), times ``routed_scaling_factor``.
8. ``x <- x + sum_{e in top k, held} w_e * down_e(silu(gate_e h2) * up_e h2)
   + down_s(silu(gate_s h2) * up_s h2)``: the routed experts this share
   holds (``we_gate_up`` [held, E, 2F], ``we_down`` [held, F, E]) and the
   shared expert (``ws_gate_up`` [E, 2Fs], ``ws_down`` [Fs, E], Fs =
   ``n_shared_experts * moe_intermediate_size``), which every token meets
   with weight 1.
9. ``logits = rms(x_L, lnf_scale) @ lm_head^T``; cross entropy of token t +
   1 at positions 0..S-2, mean over the batch.  No auxiliary loss.

THE CUT: ``num_hidden_layers`` layers, every one the same (the published
stack has no dense prefix: ``first_k_dense_replace`` 0).  THE SHARE:
``n_routed_experts`` experts of the router's ``moe_router_width`` from
``moe_first_expert_held``, and ``vocab_size`` rows of the vocabulary.  The
router ranks all its experts and the weights are formed over all chosen
ones; every HELD expert is evaluated on every token and combined with those
weights at its column, zero elsewhere (a different algorithm from the
program's sort, capacities and grouped matmul, on purpose); what the absent
experts would add is left out, and that partial result goes on.  Every
share computes the shared expert.  ``tests/test_mistral4_reference.py`` adds
the program's routed parts over all shares, and the shared expert ONCE, up
to this file's layer with every expert held.

Departures from the published description, each under ``assumed`` in the
configuration's file: the config names keys and not formulas for (a) the
softmax scale's ``mscale^2`` and the unit cos / sin factor (the DeepSeek-V3
attention whose key names it carries), (b) ``s(pos)`` on the whole query
head (the Llama-4 / Ministral-3 convention), (c) softmax scoring (no
``scoring_func``; ``n_group`` = ``topk_group`` = 1 leaves no group limit);
the cut and the share; no document mask; no vision tower.

What it holds on the device at once is kept small (it runs beside 9.2 GB of
trainer state): a layer's attention weights go up alone, attention runs
``HEAD_GROUP`` heads and ``QUERY_BLOCK`` rows at a time, the experts
``EXPERT_GROUP`` at a time, the shared expert ``DENSE_CHUNK`` hidden columns
at a time, the head ``VOCAB_CHUNK`` columns at a time.  Every call is waited
for before the next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/mistral4_ref_sensitivity.py``.

``witness_positions`` has two named groups: ``edge``, EDGE_TOKENS positions
on each side of every multiple of ``original_max_position_embeddings`` inside
the sequence and the last EDGE_TOKENS of the sequence (where ``s(pos)``
steps and where the longest angles stand), and ``spread``, SPREAD_ROWS evenly
over the sequence.  ``logits_error`` is the LARGER of the two groups' third
quartile of each position's ``|program - reference| / |reference|`` over
the vocabulary.

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

# Relative, on the scalar loss (cross entropy 10.20 to 10.22 at seeded
# weights; ln 16,384 = 9.70).  The system computes in bf16 with float32
# accumulation; the per-token error is random and the loss averages it over
# 16,383 positions.  From the chip (PR 39; the runs and seeds are PERF.md
# section 6's): the program's relative error read 4.7e-7 to 1.6e-5 over
# fourteen seeds.  The same reference with every array and operation in
# bfloat16 (fault ``bfloat16_throughout``, the nearest precision below the
# configuration's) moves its loss by 8.8e-4: not correct.  3e-4, the
# accepted decoder cells' limit, stands 19 times over the largest sound
# reading and 2.9 times under the precision's.  Of the twelve other faults
# the loss catches NONE (7.5e-7 to 2.6e-4: at seeded weights and uniform
# ids the loss sits near ln V whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 39): the sound program reads 8.56e-3 to 9.05e-3 at
# fourteen seeds (at seed 2147483659 ``spread`` 8.56e-3 and ``edge`` 8.26e-3;
# the least position 7.4e-3, the median 8.3e-3, the worst 8.3e-2 to 1.6e-1: a
# floor of bf16 rounding through four layers at EVERY position, and a few
# positions where rounding changes which expert is fourth of 128).  The
# least fault is the precision below the configuration's:
# ``bfloat16_throughout`` 1.43e-2.  Then the q latent's norm dropped 2.48e-2
# (with its weight one and N(0, 1/fan_in) chains the norm divides by a root
# mean square that is near one already), the query's scale by position
# dropped 5.34e-2 (the ``edge`` group: 16 of its 24 positions stand from
# 8,192 on; the first quartile over all positions reads the sound 8.4e-3,
# the half below 8,192 being untouched), the kv latent's norm dropped
# 7.42e-2, weights not renormalised 1.33e-1, the rotated key in head 0 alone
# 2.71e-1, mscale^2 dropped 2.90e-1, plain frequencies 3.11e-1, rotate-half
# pairs 3.61e-1, the position-free half rotated 3.99e-1, the shared expert
# weighted by a router weight 6.09e-1, the shared expert dropped 8.73e-1.
# NOT seen by either limit: ``bfloat16_router`` 8.70e-3 (the sound reading):
# the program's router already reads bf16 rows (the normed stream is the
# block's bf16 activation; its weights and the matmul are float32), so a
# bf16 router differs by the rounding of 128 weights' columns, which moves a
# near-tied fourth expert at a few positions and no quartile; what holds
# the router's precision is ``tests/test_mistral4_reference.py`` on the CPU
# (float32 against float32, 1e-5).  1.14e-2 stands 26 % over the largest
# sound reading and 20 % under the least fault: the geometric middle of the
# two.  Both readings are properties of the architecture and the precision
# (the sound readings are 6 % apart over seven seeds).  With the embedding's
# rows at the fan-in scale (this PR's first chip runs) the floor read 3.1e-2
# to 3.3e-2 and the precision's 6.0e-2: the stream the head reads was then
# four layers' bf16 branch outputs and next to nothing else.
LOGITS_TOLERANCE = 0.0114
EDGE_TOKENS = 8             # witnessed positions on each side of an edge
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
HEAD_GROUP = 8              # attention heads at a time
QUERY_BLOCK = 256           # attention rows at a time
EXPERT_GROUP = 2            # experts on the device at a time
DENSE_CHUNK = 1024          # hidden columns of the shared expert at a time
VOCAB_CHUNK = 2048          # head columns at a time
ROUTING_FAULTS = ("weights_not_renormalised", "bfloat16_router")
FAULTS = ("rotate_half_pairs", "rotary_key_of_head_0_only",
          "nope_half_rotated", "plain_frequencies", "query_scale_dropped",
          "mscale_dropped", "q_latent_norm_dropped",
          "kv_latent_norm_dropped", "shared_expert_dropped",
          "shared_expert_router_weighted") + ROUTING_FAULTS + (
              "bfloat16_throughout",)
ATTENTION_LEAVES = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
                    "wkv_b", "wo")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 and a else 1.0


def yarn_frequencies(rope, dr, plain=False):
    """Step 4's ``f_j``, float64 [dr / 2] (``plain``: ``theta^(-2j/dr)``)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    pairs = dr // 2
    base = theta ** (-2.0 * np.arange(pairs, dtype=np.float64) / dr)
    if plain or factor <= 1:
        return base

    def pair_of(rotations):
        return dr * math.log(rope["original_max_position_embeddings"] / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(pair_of(rope["beta_fast"])), 0)
    hi = min(math.ceil(pair_of(rope["beta_slow"])), pairs - 1)
    m = 1.0 - np.clip((np.arange(pairs) - lo) / ((hi - lo) or 1e-3), 0, 1)
    return (1.0 - m) * base / factor + m * base


def _rotate(x, cos, sin, half):
    """x [S, ..., d] by cos, sin [S, d/2]: pair j is columns (2j, 2j + 1),
    or (``half``, a fault) columns (j, j + d/2)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    if half:
        x0, x1 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x0 * cos - x1 * sin, x0 * sin + x1 * cos], -1)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _project(h, p, cos, sin, scale, dims, eps, faults):
    """Steps 2 to 5 on one sequence's normed rows h [S, E]: q, k [S, H, dn
    + dr] and v [S, H, dv]; ``scale`` [S] is step 5's ``s(pos)`` times step
    6's ``mscale^2``, which the caller's softmax then need not carry."""
    n_heads, r_kv, dn, dr, dv = dims
    s = h.shape[0]
    cq = h @ p["wq_a"]
    if "q_latent_norm_dropped" not in faults:
        cq = _rms(cq, p["q_a_norm"], eps)
    q = (cq @ p["wq_b"]).reshape(s, n_heads, dn + dr)
    kv_a = h @ p["wkv_a"]
    ckv, kr = kv_a[:, :r_kv], kv_a[:, r_kv:]
    if "kv_latent_norm_dropped" not in faults:
        ckv = _rms(ckv, p["kv_a_norm"], eps)
    kv = (ckv @ p["wkv_b"]).reshape(s, n_heads, dn + dv)
    half = "rotate_half_pairs" in faults
    q_nope, k_nope = q[..., :dn], kv[..., :dn]
    if "nope_half_rotated" in faults:       # its first dr columns as well
        q_nope = jnp.concatenate([_rotate(q_nope[..., :dr], cos, sin, half),
                                  q_nope[..., dr:]], -1)
        k_nope = jnp.concatenate([_rotate(k_nope[..., :dr], cos, sin, half),
                                  k_nope[..., dr:]], -1)
    q = jnp.concatenate([q_nope, _rotate(q[..., dn:], cos, sin, half)], -1)
    kr = jnp.broadcast_to(_rotate(kr, cos, sin, half)[:, None, :],
                          (s, n_heads, dr))
    if "rotary_key_of_head_0_only" in faults:
        kr = kr * (jnp.arange(n_heads) == 0)[None, :, None].astype(kr.dtype)
    return (q * scale[:, None, None].astype(q.dtype),
            jnp.concatenate([k_nope, kr], -1), kv[..., dn:])


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


def _route(m, router, k, scaling, fault):
    """``(weight [S, n], largest [S])``: each token's weights at its chosen
    experts' columns, zero elsewhere, and the largest of them."""
    if fault == "bfloat16_router":
        logits = (m.astype(jnp.bfloat16) @ router.astype(jnp.bfloat16)
                  ).astype(m.dtype)
    else:
        logits = m @ router
    if fault == "weights_not_renormalised":
        top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        top_l, top_e = jax.lax.top_k(logits, k)
        top_w = jax.nn.softmax(top_l, axis=-1)
    chosen = jax.nn.one_hot(top_e, logits.shape[-1], dtype=m.dtype)
    return (jnp.sum(chosen * (top_w * scaling)[..., None].astype(m.dtype),
                    axis=1), jnp.max(top_w, axis=-1).astype(m.dtype))


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


_route_jit = jax.jit(_route, static_argnums=(2, 3, 4))
_experts_jit = jax.jit(_experts)
_dense_jit = jax.jit(_dense_chunk)
_project_jit = jax.jit(_project, static_argnums=(5, 6, 7))
_attend_jit = jax.jit(_attend)
_rms_jit = jax.jit(_rms, static_argnums=2)


def moe_part(m, router, w_gate_up, w_down, first, k, scaling=1.0, fault=None):
    """Step 8's routed sum for the experts [first, first + held) that the
    weights hold, on one sequence's normed rows m [S, E], and each token's
    largest routing weight; the held experts ``EXPERT_GROUP`` at a time,
    each group waited for."""
    weight, largest = _done(_route_jit(m, router, k, scaling, fault))
    y = jnp.zeros_like(m)
    for at in range(0, w_gate_up.shape[0], EXPERT_GROUP):
        y = _done(_experts_jit(
            y, m, w_gate_up[at:at + EXPERT_GROUP],
            w_down[at:at + EXPERT_GROUP],
            weight[:, first + at:first + at + EXPERT_GROUP]))
    return y, largest


def shared_part(m, w_gate_up, w_down):
    """Step 8's shared expert, ``DENSE_CHUNK`` hidden columns at a time."""
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

    rope = model["rope_parameters"]
    assert model["rope_interleave"] and model["norm_topk_prob"] \
        and rope["rope_type"] == "yarn" and model["n_group"] == 1
    n_heads = int(model["num_attention_heads"])
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    dims = (n_heads, int(model["kv_lora_rank"]), dn, dr,
            int(model["v_head_dim"]))
    eps = float(model["rms_norm_eps"])
    k = int(model["num_experts_per_tok"])
    scaling = float(model.get("routed_scaling_factor", 1.0))
    first = int(model.get("moe_first_expert_held", 0))
    routing = ([f for f in faults if f in ROUTING_FAULTS] or [None])[0]
    ids = np.asarray(ids)
    b, s = ids.shape
    # positions, in float64 on the host
    factor = float(rope["factor"])
    pos = np.arange(s, dtype=np.float64)
    ang = pos[:, None] * yarn_frequencies(
        rope, dr, "plain_frequencies" in faults)[None]
    rotary_factor = _mscale(factor, rope["mscale"]) / _mscale(
        factor, rope["mscale_all_dim"])
    scale = np.ones(s)
    if "query_scale_dropped" not in faults:
        scale = 1.0 + rope["llama_4_scaling_beta"] * np.log1p(np.floor(
            pos / rope["original_max_position_embeddings"]))
    if "mscale_dropped" not in faults:
        scale = scale * _mscale(factor, rope["mscale_all_dim"]) ** 2
    cos, sin, scale = (cast(np.asarray(a, np.float32)) for a in (
        rotary_factor * np.cos(ang), rotary_factor * np.sin(ang), scale))
    layers = params["params_layers"]
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for at in range(int(model["num_hidden_layers"])):
            gc.collect()
            ln1 = cast(layers["ln1_scale"][at])
            p = {name: cast(layers[name][at]) for name in ATTENTION_LEAVES}
            hs = []
            for x in xs:
                q, kk, v = _done(_project_jit(
                    _done(_rms_jit(x, ln1, eps)), p, cos, sin, scale, dims,
                    eps, tuple(faults)))
                o = jnp.concatenate([_done(_attend_jit(
                    q[:, g:g + HEAD_GROUP], kk[:, g:g + HEAD_GROUP],
                    v[:, g:g + HEAD_GROUP]))
                    for g in range(0, n_heads, HEAD_GROUP)], axis=1)
                hs.append(_done(x + o.reshape(s, -1) @ p["wo"]))
                del q, kk, v, o
            del p, ln1
            ln2 = cast(layers["ln2_scale"][at])
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            router = cast(layers["router"][at])
            w_gate_up = cast(layers["we_gate_up"][at])
            w_down = cast(layers["we_down"][at])
            routed = [moe_part(m, router, w_gate_up, w_down, first, k,
                               scaling, routing) for m in ms]
            del router, w_gate_up, w_down
            ws_gate_up = cast(layers["ws_gate_up"][at])
            ws_down = cast(layers["ws_down"][at])
            xs = []
            for h, m, (y, largest) in zip(hs, ms, routed):
                if "shared_expert_dropped" not in faults:
                    shared = shared_part(m, ws_gate_up, ws_down)
                    if "shared_expert_router_weighted" in faults:
                        shared = shared * largest[:, None]
                    y = y + shared
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


ORIGINAL_MAX = 8192     # the published original_max_position_embeddings


def witness_groups(s):
    """``{"edge": positions, "spread": positions}`` of a sequence of ``s``
    tokens: EDGE_TOKENS positions on each side of every multiple of
    ORIGINAL_MAX inside the sequence (of a quarter of the sequence, where it
    is too short to hold one: the tiny configurations', whose
    ``original_max_position_embeddings`` is that) and the sequence's last
    EDGE_TOKENS; and SPREAD_ROWS evenly from half a stride in, those of the
    first group left out.  The driver hands the sequence length alone."""
    every = ORIGINAL_MAX if s > ORIGINAL_MAX else max(s // 4, 1)
    n = min(EDGE_TOKENS, max(every // 4, 1))
    edge = np.unique(np.concatenate(
        [np.arange(at - n, at + n) for at in range(every, s, every)]
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
    tree = params["params_layers"]
    marks = [np.asarray(tree[name]) for name in ("router", "ln1_scale",
                                                 "q_a_norm", "kv_a_norm")]
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
