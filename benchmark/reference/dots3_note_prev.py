"""Plain reference for ``dots3_note_prev``: the training loss of the
dots3-note-prev language model (dots-studio/dots3-note-prev ``config.json``,
``model_type`` ``dots3_note``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no scan over
layers, no sharding, no counting passes, no grouped matmul, nothing imported
from the program: it takes the program's weights by their names in the
parameter tree and a batch (``ids``) and returns the loss.

Layer l on one sequence x [S, E] (no bias anywhere; ``rms(x, g) = x *
rsqrt(mean(x^2) + eps) * g``; ``h = rms(x, ln1_scale)``; E = hidden).  Its
kind is ``layer_types[l]`` and every size below is that KIND's (the ``swa_*``
keys for a sliding layer):

1. ``cq = rms(h wq_a, q_a_norm) (E / q_lora_rank)^(1/2)``, ``q = cq wq_b``
   [S, H, dn + dr]; ``[ckv | kr] = h wkv_a``, ``ckv = rms(ckv, kv_a_norm) (E
   / kv_lora_rank)^(1/2)``, ``[k_nope | v] = ckv wkv_b`` [S, H, dn + dv];
   ``kr`` [S, dr] is ONE vector a token.  q's last dr columns and kr are
   rotated, ADJACENT pairs: columns (2j, 2j + 1) by ``t theta^(-2j / dr)``
   (``rope_theta`` 8e7, ``swa_rope_theta`` 5e4; no scaling); ``k = [k_nope |
   kr]``, the same kr in every head.  (``apply_mla_qkv_lora_rescale`` is
   read as each normed latent times (hidden / rank)^(1/2).)
2. FULL layer, the indexer: ``qI = cq wq_idx`` [S, Hi, Di], ``kI =
   LayerNorm(h wk_idx; idx_k_norm_scale, idx_k_norm_bias)`` [S, Di], both
   rotated over their FIRST 64 columns, rotate-half (pair i is (i, i + 32),
   angle ``t theta^(-i / 32)``, the layer's theta); ``w = (h w_idx) Hi^(-1/2)
   Di^(-1/2)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, s <= t;
   ``tau_t`` the ``index_topk``-th largest of ``I[t, 0..t]`` by a real
   ``top_k`` (``-inf`` where the row has fewer), ``S_t = {s <= t : I[t, s]
   >= tau_t}``.  SLIDING layer: ``S_t = {s : 0 <= t - s < window}``.
3. ``a[n, t, .] = softmax over S_t of q[n, t] . k[n, s] (dn + dr)^(-1/2)``;
   ``o_n = a_n v_n``; ``o_n <- sigmoid((h wz)[first + n]) o_n``, ONE scalar
   a head and token, ``wz`` [E, all the layer's heads] read at the held
   heads' columns; ``h1 = x + concat(o) wo``.
4. ``m = rms(h1, ln2_scale)``.  Layer 0: ``y = (silu(m Wg) * (m Wu))
   w_down``.  Every other layer: ``s = sigmoid(m router)`` [S, n]; the k
   largest of ``s + bias``; their ``s`` over their sum (+ 1e-6) times
   ``routed_scaling_factor``; ``y = sum_e w_e down_e(silu(gate_e m) * up_e
   m)`` plus ONE shared expert of the same form on the same m with weight
   1.  ``out = h1 + y``.
5. ``logits = rms(x_L, lnf_scale) lm_head^T``; cross entropy of token t + 1
   at positions 0..S-2, mean over the batch; PLUS (coefficient 1) the mean
   over the layers THAT HAVE an indexer, sequences and tokens of ``KL(p_t ||
   softmax over S_t of I[t, .])``, ``p[t, s] = mean over the HELD heads of
   a[n, t, s]``, with a stop-gradient on p and on the indexer's inputs h and
   cq: the indexer's five leaves hear the KL alone and every other leaf the
   cross entropy alone.

THE SHARE.  The weights may hold ``num_attention_heads`` /
``swa_num_attention_heads`` of a layer's heads from ``first_head_held`` /
``swa_first_head_held`` (``wq_b``, ``wkv_b``, ``wo`` the held heads' columns
and rows alone: the branch's output is their partial sum), ``n_routed_experts``
experts of the router's ``router_width`` from ``first_expert_held`` (every
HELD expert is evaluated on every token and combined with the top-k weights
at its column) and ``vocab_size`` rows of the vocabulary.  What the absent
heads and experts would add is left out, and the partial result goes on.

Departures, each ASSUMED (``benchmark/configs/dots3_note_prev.json``): the
shares; the rescale's reading; the window's ``0 <= t - s < 513``; the
indexer's form, DeepSeek-V3.2-Exp's (the Hadamard turn of qI and kI leaves
every product as it is, the FP8 quantisation is a serving detail: both left
out); ties AT the threshold kept; the KL term, its stop-gradients and its
coefficient; no vision tower, audio encoder or multi-token prediction.

What it holds on the device at once is kept small (the reference runs beside
9 GB of trainer state): a layer's attention weights go up alone, attention
runs ``QUERY_BLOCK`` rows at a time, the dense FFN ``DENSE_CHUNK`` hidden
columns at a time, the experts ``EXPERT_GROUP`` at a time, the head
``VOCAB_CHUNK`` columns at a time.  ``faults`` puts a fault in, for
``benchmark/tools/dots3_ref_sensitivity.py``.

TOLERANCE is relative, on the scalar loss (both terms; 13.36 to 13.40 at
seeded weights).  Set from the chip (PR 63): over fourteen runs at fourteen
seeds the program's relative error lay between 1.7e-6 and 6.7e-5; this
reference with every array and operation in bfloat16 (fault
``bfloat16_throughout``) moves its loss by 6.2e-4 to 6.7e-4 at four seeds:
not correct.  3e-4, the limit of the harness's accepted decoder cells,
stands 4.5 times over the largest sound reading and 2.1 times under the
control.

LOGITS_TOLERANCE bounds ``logits_error``, of the program's logits at
``witness_positions`` (1,059 of batch 0's 8,192) against this file's, each
position's distance ``|program - reference| / |reference|`` over the
vocabulary: THE LARGER OF the MEAN over the 277 positions before 2,048 and a
quarter (``LATE_SCALE``) of the THIRD QUARTILE over the 782 from 2,048 on.
Why two regions: before 2,048 every causal key is selected, the program
(bf16 operands) and this file (float32) read the same keys, and a
position's distance is 7e-3 with little scatter; from 2,048 on the two
select other keys within rounding of a row's threshold and, with the
rescale's sharp rows (scores N(0, 35)), a position reads 1.6e-2 at the
median and 0.10 at worst: one statistic over both (the third quartile of all
1,059, this file's first build) read 1.94e-2 to 2.05e-2 over nine seeds
beside 2.12e-2 for a window of 514: no room.  Set from the chip (PR 63;
``benchmark/tools/dots3_ref_sensitivity.py`` at seeds 2147483651,
1987654321, 2718281 and 31415926, and the cell's own runs): the sound
program reads 7.02e-3 to 7.18e-3 over eleven seeds (before 2,048; a quarter
of its later region's third quartile, 2.29e-2 to 2.46e-2, is under that);
the least listed control, a window of 514, 8.30e-3 to 8.84e-3 (a window of
512: 8.84e-3 to 8.96e-3); bfloat16 throughout 1.21e-2 to 1.23e-2 before
and 3.45e-2 to 3.50e-2 after: refused by both regions and by the loss.
**7.7e-3** stands 7 % over the largest sound reading and 7 % under the
least listed control; the later region's limit, 3.08e-2, 25 % over its
largest sound reading.  The other controls (seed 31415926): no rescale
0.300, the gate dropped 0.350, the head-wise gate's weights read as an
element-wise gate's 0.192, the wrong first head 0.193, the sliding layers
at the full layers' theta 0.097; 7 of 8 experts 7.92e-3 (3 % over the
limit: the least fault of all that it refuses); and by the later region
alone, since before 2,048 they ARE the sound program: no selection 6.8e-2,
un-rotated indexer keys 6.1e-2, ``w`` dropped 7.8e-2.

What NO limit on this distance can refuse is a selection of 2,047
(``top_k_minus_one_key``): it drops ONE key of a row's 2,048, the one the
indexer ranks last, from position 2,047 on, and reads 7.08e-3 / 7.18e-3 /
7.16e-3 before 2,048 (the sound program's, but for position 2,047) and
2.37e-2 / 2.34e-2 / 2.49e-2 after, 0.3 to 3 % over the sound program at the
same seed and inside its scatter over seeds.  What holds the selection's
SIZE is a count, not a distance: the program's ``dsa_pairs_selected``
(``decoder.probe``, ``scripts/dots3_routing_watch.py``) reads 14,681,088 =
2,048 x 2,049 / 2 + 6,144 x 2,048 exactly, where 2,047 would read
14,674,944; and the CPU tests hold the selected SETS equal to this file's
(``tests/test_dots3_reference.py``) and refuse the fault at the tiny size
(``benchmark/tests/test_bench_dots3.py``).
"""

import gc
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 3e-4
LOGITS_TOLERANCE = 0.0077
LATE_SCALE = 4.0            # the later region's third quartile over this
WITNESS_ROWS = 1024         # positions spread over the sequence
GROUP_ROWS = 8              # positions of each group at an edge
EXPERT_GROUP = 2            # experts on the device at a time
QUERY_BLOCK = 128           # attention rows at a time
DENSE_CHUNK = 3456          # hidden columns of the dense FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
ROUTE_EPS = 1e-6
FAULTS = ("bfloat16_throughout", "window_minus_one", "window_plus_one",
          "top_k_minus_one_key", "no_rescale", "gate_dropped",
          "gate_elementwise", "swa_theta_of_full", "wrong_first_head",
          "no_selection", "unrotated_indexer_keys", "w_dropped",
          "top_k_minus_one")
ATTENTION_LEAVES = ("ln1_scale", "wq_a", "q_a_norm", "wq_b", "wkv_a",
                    "kv_a_norm", "wkv_b", "wo", "wz")
INDEXER_LEAVES = ("wq_idx", "wk_idx", "w_idx", "idx_k_norm_scale",
                  "idx_k_norm_bias")
INDEXER_ROPE_DIM = 64


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g + b


def _turns(x, theta):
    """(cos, sin) of ``t theta^(-i / (d / 2))`` for the d / 2 pairs of x [S,
    ..., d], shaped to multiply a half of x."""
    s, half = x.shape[0], x.shape[-1] // 2
    freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half),
                       jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    shape = (s,) + (1,) * (x.ndim - 2) + (half,)
    return tuple(f(ang).reshape(shape).astype(x.dtype)
                 for f in (jnp.cos, jnp.sin))


def _rope_pairs(x, theta):
    """x [S, ..., d]: columns (2j, 2j + 1) turned by ``t theta^(-2j / d)``."""
    cos, sin = _turns(x, theta)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _rope_half(x, theta):
    """x [S, ..., d]: pair i is (i, i + d / 2), turned by ``t theta^(-2i /
    d)`` (rotate-half)."""
    cos, sin = _turns(x, theta)
    half = x.shape[-1] // 2
    x0, x1 = x[..., :half], x[..., half:]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)


def kind_shape(model, sliding):
    """A layer kind's own sizes off the published keys: (held heads, first
    held head, dn, dr, dv, theta, window or 0)."""
    pre = "swa_" if sliding else ""
    return (int(model[pre + "num_attention_heads"]),
            int(model.get(pre + "first_head_held", 0)),
            int(model[pre + "qk_nope_head_dim"]),
            int(model[pre + "qk_rope_head_dim"]),
            int(model[pre + "v_head_dim"]),
            float(model[pre + "rope_theta"]),
            int(model["sliding_window_size"]) if sliding else 0)


def model_of(cfg):
    """The ``model`` of a program's configuration ``cfg``, read off its
    fields alone (the tests' tiny configurations have no file)."""
    kinds = cfg.prefix_kinds + cfg.layer_kinds * cfg.n_periods
    shapes = {bool(kind.window): cfg.position(kind)[0] for kind in kinds}
    model = {"num_hidden_layers": cfg.n_layers,
             "layer_types": ["sliding_attention" if kind.window
                             else "full_attention" for kind in kinds],
             "first_k_dense_replace": len(cfg.prefix_kinds),
             "rms_norm_eps": cfg.norm_eps,
             "sliding_window_size": max(kind.window for kind in kinds),
             "index_n_heads": cfg.indexer_heads,
             "index_head_dim": cfg.indexer_dim,
             "index_topk": cfg.indexer_topk,
             "num_experts_per_tok": cfg.experts_per_token,
             "first_expert_held": cfg.first_expert,
             "routed_scaling_factor": cfg.route_scale}
    for pre, at in (("", shapes[False]), ("swa_", shapes[True])):
        model.update({pre + "num_attention_heads": at.heads_here,
                      pre + "first_head_held": at.first_head,
                      pre + "qk_nope_head_dim": at.qk_nope_dim,
                      pre + "qk_rope_head_dim": at.qk_rope_dim,
                      pre + "v_head_dim": at.v_head_dim,
                      pre + "rope_theta": at.rope_theta})
    return model


def _project(x, p, shape, eps, faults):
    """One sequence's q, k [S, H, dn + dr], v [S, H, dv], the gate [S, H]
    and, of a layer with an indexer, qI [S, Hi, Di], kI [S, Di], w [S, Hi]
    (else None)."""
    heads, first, dn, dr, dv, theta, _, hi = shape
    s, hidden = x.shape
    h = _rms(x, p["ln1_scale"], eps)

    def rescaled(latent, g):
        out = _rms(latent, g, eps)
        return out if "no_rescale" in faults \
            else out * math.sqrt(hidden / latent.shape[-1])

    cq = rescaled(h @ p["wq_a"], p["q_a_norm"])
    q = (cq @ p["wq_b"]).reshape(s, heads, dn + dr)
    rkv = p["kv_a_norm"].shape[0]
    down = h @ p["wkv_a"]
    kv = (rescaled(down[:, :rkv], p["kv_a_norm"]) @ p["wkv_b"]).reshape(
        s, heads, dn + dv)
    kr = _rope_pairs(down[:, rkv:], theta)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], theta)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kr[:, None, :], (s, heads, dr))], -1)
    total = p["wz"].shape[1]
    if "wrong_first_head" in faults:
        first = (first + heads) % total
    z = h @ p["wz"]
    if "gate_elementwise" in faults:
        # the head-wise weights read as an element-wise gate's: column c of
        # the heads' output takes gate column c mod the gate's width
        gate = jax.nn.sigmoid(jnp.tile(z, (1, -(-heads * dv // total)))[
            :, :heads * dv]).reshape(s, heads, dv)
    else:
        gate = jax.nn.sigmoid(z[:, first:first + heads])[..., None]
    if "gate_dropped" in faults:
        gate = jnp.ones_like(gate)
    if not hi:
        return q, k, kv[..., dn:], gate, None, None, None
    # the indexer hears the KL term alone: its inputs are constants
    hx, cqx = jax.lax.stop_gradient(h), jax.lax.stop_gradient(cq)
    qi = (cqx @ p["wq_idx"]).reshape(s, hi, -1)
    ki = _layer_norm(hx @ p["wk_idx"], p["idx_k_norm_scale"],
                     p["idx_k_norm_bias"], eps)
    r = INDEXER_ROPE_DIM
    qi = jnp.concatenate([_rope_half(qi[..., :r], theta), qi[..., r:]], -1)
    if "unrotated_indexer_keys" not in faults:
        ki = jnp.concatenate([_rope_half(ki[..., :r], theta), ki[..., r:]],
                             -1)
    di = qi.shape[-1]
    w = (hx @ p["w_idx"]) * (hi ** -0.5 * di ** -0.5)
    if "w_dropped" in faults:
        w = jnp.full_like(w, hi ** -0.5 * di ** -0.5)
    return q, k, kv[..., dn:], gate, qi, ki, w


def _attend(q, k, v, gate, qi, ki, w, window, topk, faults):
    """Steps 2 and 3 and step 5's KL of one sequence: ``(gated o [S, H, dv],
    kl [S] (zeros without an indexer), selected [S, S] bool)``,
    ``QUERY_BLOCK`` rows at a time."""
    s, heads, dh = q.shape
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)
    key = jnp.arange(s)[None, :]
    if "window_minus_one" in faults:
        window -= 1
    if "window_plus_one" in faults:
        window += 1
    if "top_k_minus_one_key" in faults:
        topk -= 1

    def block(first):
        at = first + jnp.arange(rows)
        seen = key <= at[:, None]
        if qi is None:
            keep = seen & (at[:, None] - key < window)
        else:
            scores = jnp.einsum("qh,hqk->qk", w[at], jax.nn.relu(
                jnp.einsum("qhd,kd->hqk", qi[at], ki)))
            scores = jnp.where(seen, scores, -jnp.inf)
            # a real top-k by value; the k-th value is the threshold, and
            # ties at it are kept
            tau = jax.lax.top_k(scores, min(topk, s))[0][:, -1:]
            keep = seen if "no_selection" in faults \
                else seen & (scores >= tau)
        main = jnp.einsum("qhd,khd->hqk", q[at], k) / math.sqrt(dh)
        a = jax.nn.softmax(jnp.where(keep[None], main, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v) * gate[at]
        if qi is None:
            return o, jnp.zeros((rows,), o.dtype), keep
        p = jax.lax.stop_gradient(jnp.mean(a, axis=0))          # [rows, S]
        log_r = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        held = p > 0
        kl = jnp.sum(jnp.where(held, p * (
            jnp.log(jnp.where(held, p, 1.0)) - jnp.where(held, log_r, 0.0)),
            0.0), axis=-1)
        return o, kl, keep

    o, kl, keep = jax.lax.map(block, jnp.arange(0, s, rows))
    return o.reshape(s, heads, -1), kl.reshape(s), keep.reshape(s, s)


def _route(m, router, bias, k, scale):
    """``weight`` [S, n]: each token's weights at its chosen experts'
    columns, zero elsewhere."""
    scores = jax.nn.sigmoid(m @ router)
    _, top_e = jax.lax.top_k(scores + bias, k)
    top_w = jnp.take_along_axis(scores, top_e, -1)
    top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + ROUTE_EPS) \
        * scale
    chosen = jax.nn.one_hot(top_e, router.shape[-1], dtype=m.dtype)
    return jnp.sum(chosen * top_w[..., None].astype(m.dtype), axis=1)


def _experts(acc, m, w_gate_up, w_down, weight):
    """``acc`` plus a group of experts on EVERY token of ``m``, each times
    its column of ``weight`` [S, g]: w_gate_up [g, E, 2F], w_down [g, F, E]."""
    f = w_down.shape[1]
    gu = jnp.einsum("se,gef->gsf", m, w_gate_up)
    out = jnp.einsum("gsf,gfe->gse", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                     w_down)
    return acc + jnp.sum(out * weight.T[..., None], axis=0)


def _gated_chunk(acc, m, w_gate, w_up, w_down):
    """``acc`` plus hidden columns of a dense gated FFN: w_gate, w_up [E,
    C], w_down [C, E]."""
    return acc + (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


_project_jit = jax.jit(_project, static_argnums=(2, 3, 4))
_attend_jit = jax.jit(_attend, static_argnums=(7, 8, 9))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_experts_jit = jax.jit(_experts)
_gated_chunk_jit = jax.jit(_gated_chunk)
_rms_jit = jax.jit(_rms, static_argnums=(2,))


def gated_ffn(m, w_gate_up, w_down):
    """``(silu(m Wg) * (m Wu)) w_down``, ``[Wg | Wu] = w_gate_up`` [E, 2F],
    DENSE_CHUNK hidden columns at a time."""
    f = w_down.shape[0]
    y = jnp.zeros_like(m)
    for at in range(0, f, DENSE_CHUNK):
        y = _done(_gated_chunk_jit(
            y, m, w_gate_up[:, at:min(at + DENSE_CHUNK, f)],
            w_gate_up[:, f + at:f + min(at + DENSE_CHUNK, f)],
            w_down[at:at + DENSE_CHUNK]))
    return y


def moe_part(m, router, bias, w_gate_up, w_down, first, k, scale):
    """Step 4's routed ``y`` for the experts [first, first + held) that the
    weights hold, on one sequence's normed rows ``m``; the held experts
    ``EXPERT_GROUP`` at a time."""
    weight = _done(_route_jit(m, router, bias, k, scale))
    y = jnp.zeros_like(m)
    for at in range(0, w_gate_up.shape[0], EXPERT_GROUP):
        y = _done(_experts_jit(
            y, m, w_gate_up[at:at + EXPERT_GROUP],
            w_down[at:at + EXPERT_GROUP],
            weight[:, first + at:first + at + EXPERT_GROUP]))
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


_head_chunk_jit = jax.jit(_head_chunk, static_argnums=(5, 6))


def layer_leaves(params, i):
    """Layer ``i``'s leaves out of the program's tree: the leading layer
    unstacked under ``prefix_layers/l0``; of period p = (i - 1) // 4 the full
    layer at ``params_layers/r0[p, 0]`` and the three sliding ones at
    ``r1[p, 0..2]`` (a run's layers stacked behind the periods)."""
    if i == 0:
        return params["prefix_layers"]["l0"]
    p, at = divmod(i - 1, 4)
    run, j = ("r0", 0) if at == 0 else ("r1", at - 1)
    return {name: leaf[p, j]
            for name, leaf in params["params_layers"][run].items()}


def attention_part(x, p, model, sliding, faults=()):
    """Steps 1 to 3 of one layer on one sequence x [S, E], ``p`` that
    layer's attention leaves: ``(gated o wo [S, E], kl [S], selected [S,
    S])``."""
    if sliding and "swa_theta_of_full" in faults:
        model = dict(model, swa_rope_theta=model["rope_theta"])
    shape = kind_shape(model, sliding)
    hi = 0 if sliding else int(model["index_n_heads"])
    q, k, v, gate, qi, ki, w = _done(_project_jit(
        x, p, shape + (hi,), float(model["rms_norm_eps"]), tuple(faults)))
    o, kl, keep = _done(_attend_jit(q, k, v, gate, qi, ki, w, shape[-1],
                                    int(model["index_topk"]), tuple(faults)))
    return o.reshape(x.shape[0], -1) @ p["wo"], kl, keep


def forward_terms(params, batch, model, faults=(), keep_logits=True,
                  positions=None, selections=None):
    """``{"ce", "kl", "logits"}``: the two terms of the training loss as
    scalars (differentiable in ``params``) and each sequence's logits [S,
    V], or [P, V] at ``positions`` [P] alone (none kept where
    ``keep_logits`` is off).  ``selections``: a list that takes each
    layer's selected sets [B, S, S] bool."""
    for fault in faults:
        assert fault in FAULTS, fault
    low = "bfloat16_throughout" in faults
    dtype = jnp.bfloat16 if low else jnp.float32

    def cast(a):
        return _done(jnp.asarray(a).astype(dtype))

    eps = float(model["rms_norm_eps"])
    k = int(model["num_experts_per_tok"]) - ("top_k_minus_one" in faults)
    first = int(model.get("first_expert_held", 0))
    scale = float(model["routed_scaling_factor"])
    ids = np.asarray(batch["ids"])
    b, s = ids.shape
    n_layers = int(model["num_hidden_layers"])
    types = model["layer_types"][:n_layers]
    n_dense = int(model["first_k_dense_replace"])
    with jax.default_matmul_precision("default" if low else "highest"):
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        kl_total, n_indexers = 0.0, 0
        for i in range(n_layers):
            gc.collect()
            sliding = types[i] == "sliding_attention"
            n_indexers += not sliding
            leaves = layer_leaves(params, i)
            p = {name: cast(leaves[name]) for name in ATTENTION_LEAVES + (
                () if sliding else INDEXER_LEAVES)}
            hs, kept = [], []
            for j in range(b):
                o, kl, keep = attention_part(xs[j], p, model, sliding,
                                             faults)
                hs.append(_done(xs[j] + o))
                kl_total = kl_total + jnp.sum(kl.astype(jnp.float32))
                kept.append(keep)
            if selections is not None:
                selections.append(np.stack([np.asarray(x) for x in kept]))
            del p, kept
            ln2 = cast(leaves["ln2_scale"])
            ms = [_done(_rms_jit(h1, ln2, eps)) for h1 in hs]
            if i < n_dense:
                w_gate_up, w_down = (cast(leaves[name])
                                     for name in ("w_gate_up", "w_down"))
                ys = [gated_ffn(m, w_gate_up, w_down) for m in ms]
            else:
                router = cast(leaves["router"])
                bias = cast(params["router_bias"][i - n_dense])
                w_gate_up, w_down = (cast(leaves[name])
                                     for name in ("we_gate_up", "we_down"))
                ys = [moe_part(m, router, bias, w_gate_up, w_down, first, k,
                               scale) for m in ms]
                w_gate_up, w_down = (cast(leaves[name])
                                     for name in ("ws_gate_up", "ws_down"))
                ys = [_done(y + gated_ffn(m, w_gate_up, w_down))
                      for y, m in zip(ys, ms)]
                del router, bias
            xs = [_done(h1 + y) for h1, y in zip(hs, ys)]
            del w_gate_up, w_down, hs, ms, ys, ln2
        table = params["lm_head"]
        g = cast(params["lnf_scale"])
        labels = [jnp.asarray(np.roll(ids[j], -1)) for j in range(b)]
        lse, picked = [None] * b, [0.0] * b
        logits = [[] for _ in range(b)]
        for at in range(0, table.shape[0], VOCAB_CHUNK):
            w = cast(table[at:at + VOCAB_CHUNK])
            for j in range(b):
                l, at_label, lg = _done(_head_chunk_jit(
                    xs[j], g, w, labels[j], jnp.int32(at), eps, keep_logits))
                lse[j] = l if lse[j] is None else jnp.logaddexp(lse[j], l)
                picked[j] = picked[j] + at_label
                if keep_logits:
                    logits[j].append(lg if positions is None
                                     else _done(lg[np.asarray(positions)]))
            del w
        nll = sum(jnp.sum((lse[j] - picked[j])[:-1].astype(jnp.float32))
                  for j in range(b))
    return {"ce": nll / (b * (s - 1)),
            "kl": kl_total / (max(n_indexers, 1) * b * s),
            "logits": [jnp.concatenate(lg, axis=-1) for lg in logits if lg]}


def forward(params, batch, model, faults=(), keep_logits=True,
            positions=None):
    """``(loss, logits)``: cross entropy plus the indexer's term, and
    ``forward_terms``' logits."""
    out = forward_terms(params, batch, model, faults, keep_logits, positions)
    return out["ce"] + out["kl"], out["logits"]


def witness_groups(s):
    """The witnessed positions by group: ``spread``, WITNESS_ROWS of them
    evenly over the sequence from half a stride in; and GROUP_ROWS each
    ``before_window`` / ``past_window`` (the last positions whose window
    holds every causal key, and the first that drop one: 513 on), ``before_
    topk`` / ``past_topk`` (the same of the indexer's selection: 2,048 on)
    and ``end``.  A sequence too short for an edge (the CPU tests') takes a
    quarter of itself and one for the window's, an eighth for the
    selection's."""
    stride = max(s // WITNESS_ROWS, 1)
    window = 513 if s > 513 + GROUP_ROWS else s // 4 + 1
    topk = 2048 if s > 2048 + GROUP_ROWS else max(s // 8, 1)
    groups = {"spread": np.arange(stride // 2, s, stride),
              "end": np.arange(s - min(GROUP_ROWS, s), s)}
    for name, edge in (("window", window), ("topk", topk)):
        n = min(GROUP_ROWS, edge)
        groups["before_" + name] = np.arange(edge - n, edge)
        groups["past_" + name] = np.arange(edge, min(edge + n, s))
    return groups


def witness_positions(s):
    """Every witnessed position once, ascending."""
    return np.unique(np.concatenate(list(witness_groups(s).values())))


_last = {}      # the inputs' fingerprint and the results of the last run


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    last call's results are kept: the benchmark's driver asks for the logits
    and then the harness for the loss, of the same weights and batch."""
    ids = np.asarray(batch["ids"])
    router = np.asarray(params["params_layers"]["r0"]["router"])
    wz = np.asarray(params["prefix_layers"]["l0"]["wz"])
    mark = (zlib.crc32(ids.tobytes()), zlib.crc32(router.tobytes()),
            zlib.crc32(wz.tobytes()), json.dumps(model, sort_keys=True),
            tuple(faults))
    if _last.get("mark") != mark:
        total, logits = forward(params, batch, model, faults,
                                positions=witness_positions(ids.shape[1]))
        _last.update(mark=mark, loss=float(total), logits=np.stack(
            [np.asarray(lg, np.float32) for lg in logits]))
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
    vocabulary, [B * P]: the program's logits ``got`` [B, P, V] at
    ``witness_positions`` against the reference's."""
    want = logits(params, batch, model, faults)
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1)).reshape(-1)


def group_errors(got, params, batch, model, faults=()):
    """``{group: third quartile of its positions' errors}`` over
    ``witness_groups``: where along the sequence a fault shows."""
    s = np.asarray(batch["ids"]).shape[1]
    each = position_errors(got, params, batch, model, faults).reshape(
        len(got), -1)
    at = witness_positions(s)
    return {name: float(np.quantile(each[:, np.isin(at, rows)], 0.75))
            for name, rows in witness_groups(s).items()}


def _topk_edge(s):
    """The first position whose row drops a key: 2,048, or an eighth of a
    short sequence (``witness_groups``)."""
    return int(witness_groups(s)["past_topk"][0])


def region_errors(got, params, batch, model, faults=()):
    """``(mean over the witnessed positions before the selection's first
    drop, third quartile over those from it on)``."""
    s = np.asarray(batch["ids"]).shape[1]
    each = position_errors(got, params, batch, model, faults).reshape(
        len(got), -1)
    late = witness_positions(s) >= _topk_edge(s)
    return (float(np.mean(each[:, ~late])),
            float(np.quantile(each[:, late], 0.75)))


def logits_error(got, params, batch, model, faults=()):
    """What LOGITS_TOLERANCE bounds: the larger of ``region_errors``'
    first and its second over LATE_SCALE."""
    dense, late = region_errors(got, params, batch, model, faults)
    return max(dense, late / LATE_SCALE)
