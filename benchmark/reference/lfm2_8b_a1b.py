"""Plain reference for ``lfm2_8b_a1b``: the training loss of an LFM2-MoE
decoder (LiquidAI LFM2-8B-A1B ``config.json``, HF ``model_type``
``lfm2_moe``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no scan over
layers, no sharding, no sort and no grouped matmul, nothing imported from the
program: it takes the program's weights by their names in the parameter tree
and a batch (``ids``) and returns the loss.

Layer l, on one sequence x [S, E] (no bias anywhere;
``rms(x, g) = x * rsqrt(mean(x^2) + eps) * g``, eps ``norm_eps``):

1. ``u = rms(x, ln1_scale)`` (HF ``operator_norm``).
2. Where ``layer_types[l]`` is ``conv``: ``[B, C, z] = split3(u @ conv_in)``
   (``conv_in`` [E, 3E]); ``v = B * z``; ``c_t = sum_{j<taps} conv_w[j] *
   v[t - taps + 1 + j]`` with v zero before position 0 (``conv_L_cache``
   taps, depthwise, causal); ``op = (C * c) @ conv_out``.
   Where it is ``full_attention``: ``q = u @ wq`` [S, H, dh], ``k = u @ wk``,
   ``v = u @ wv`` [S, Hkv, dh]; q and k RMS-normed over EACH head's dh
   (``q_norm`` / ``k_norm`` [dh]), then rotate-half rotary embedding over
   the whole head width, positions 0..S-1; query head h reads key/value head
   ``h // (H // Hkv)``; causal softmax at scale 1/sqrt(dh); ``op = o @ wo``.
3. ``h = x + op``; ``m = rms(h, ln2_scale)`` (HF ``ffn_norm``).
4. A leading layer (index below ``num_dense_layers``): ``y = (silu(m @ Wg) *
   (m @ Wu)) @ w_down``, ``[Wg, Wu] = w_gate_up`` [E, 2F], F =
   ``intermediate_size``.  Every other layer: ``s = sigmoid(m @ router)``
   over all ``moe_router_width`` experts; the ``num_experts_per_tok``
   experts T with the largest ``s_e + b_e`` (``b`` = this layer's row of
   ``router_bias``); weights ``s_e / (sum_{e in T} s_e + 1e-6)`` times
   ``routed_scaling_factor``; ``y = sum_{e in T} w_e * down_e(silu(gate_e m)
   * up_e m)`` (``we_gate_up`` [held, E, 2F], ``we_down`` [held, F, E], F =
   ``moe_intermediate_size``).  ``out = h + y``.
5. ``logits = rms(x_L, lnf_scale) @ tok_emb^T`` (the head is the embedding);
   cross entropy of token t + 1 at positions 0..S-2, mean over the batch.
   No auxiliary loss.

THE CUT.  The weights hold ``num_dense_layers`` leading layers
(``prefix_layers/l<i>``: published layers 0..) and then whole periods of
``PERIOD`` layers from published layer ``first_expert_layer`` on
(``params_layers/p<position>``, stacked by period); ``layer_types`` stands
whole and is read at those indices.  THE SHARE: ``num_experts`` experts of
the router's ``moe_router_width`` from ``moe_first_expert_held``, and
``vocab_size`` rows of the vocabulary.  The router ranks all its experts and
the weights are formed over all chosen ones; every HELD expert is evaluated
on every token and combined with those weights at its column, zero elsewhere
(a different algorithm from the program's sort, capacities and grouped
matmul, on purpose); what the absent experts would add is left out, and that
partial result goes on.  With every expert held this is the whole layer
(``tests/test_lfm2_reference.py`` adds the program's four shares up to it).

Departures from the published description: the cut and the share above; no
document mask (attention and the convolution run across document
boundaries); the tied head (the catalog gives no ``tie_word_embeddings``;
the published 8.3 B parameters are met only with it); the dense width read
as ``intermediate_size``.

What it holds on the device at once is kept small (the reference runs beside
7.4 GB of trainer state, and ``peak_hbm_gb`` counts its peak): a layer's
operator weights go up alone, attention runs one key/value head's group of
query heads and ``QUERY_BLOCK`` rows at a time, the dense FFN ``DENSE_CHUNK``
hidden columns at a time, the experts ``EXPERT_GROUP`` at a time, the head
``VOCAB_CHUNK`` columns at a time.  Every call is waited for before the next
is sent (``benchmark/reference/olmoe_1b_7b.py`` has the measurements that
taught this).  ``faults`` puts a fault in, for
``benchmark/tools/lfm2_ref_sensitivity.py``.

TOLERANCE is relative, on the scalar loss (cross entropy 10.19 to 10.22 at
seeded weights; ln 16384 = 9.70 and more).  The system computes in bf16 with
f32 accumulation; the per-token error is random and the loss averages it
over 2 x 8,191 positions.  Set from the chip (PR 33; the runs and seeds are
PERF.md section 6's): the program's relative error lay between 2.3e-5 and
9.5e-5 over eight runs at eight seeds.  The same reference computed with
every array and operation in bfloat16 (fault ``bfloat16_throughout``, the
nearest precision below the configuration's) moves its loss by 9.2e-4 and
9.7e-4 at two seedings: not correct.  3e-4 stands 3.2 times over the
largest sound reading and 3.1 times under the precision's.  What else the
loss catches, measured by putting each fault into the reference at the
timed sizes (``benchmark/tools/lfm2_ref_sensitivity.py``, on the chip, one
seed at two seedings of the biases): weights not renormalised (7.7e-4,
4.9e-4) and the gate dropped (8.5e-4, 4.8e-4) both times; taps on t..t+2
(1.7e-4, 4.8e-4), the wrong kv head (2.7e-4, 3.5e-4), the dense layer as
experts (7.2e-4, 1.6e-4) and softmax scores (9e-5, 3.1e-4) once of two; the
bias added to the weights, the bias left out of the selection, rotary left
out and q/k norm over the whole projection (5e-6 to 2.2e-4) NOT: at seeded
weights and uniform ids the loss sits near ln V whatever the block does,
and a fault moves it by about what the seed does.

LOGITS_TOLERANCE is what sees those on the chip: the cell's driver
(``benchmark/drivers/train_scan_witnessed.py``) reads the program's logits
at ``witness_positions`` (256 of each of batch 0's two sequences) before
the warm-up, and ``logits_error`` is the FIRST QUARTILE over those 512
positions of each one's ``|program - reference| / |reference|`` over the
vocabulary.  Why a quartile, and why the first where
``smallthinker_21b_a3b`` takes the third: what separates the bf16 program
from this float32 file has two parts here.  A FLOOR at every position, 2.0
to 2.6 % (each short convolution multiplies three bf16 projections of the
same rounded input, so a layer hands on about 1.3 times the relative error
it was given plus its own rounding, seven times over), which the same
forward in float32 does not have (``scripts/lfm2_float32_receipt.py`` on the
chip: 1.3e-5 at the median position, 1.7e-5 at the third quartile, so the
compiled path is the reference's function).  And FLIPS: rounding changes
which expert is fourth of 32 sigmoid scores a few hundredths apart, in eight
expert layers, at 30 to 40 % of the positions, which then read 5 to 36 %.
The third quartile is the flips' (6.1e-2 to 7.6e-2 over eight readings,
where the least fault reads 8.8e-2); the first is the floor's: 2.446e-2 to
2.547e-2 over eight readings at seven seeds, 4 % apart.  A fault of the
block moves EVERY position, so the first quartile sees it as well as any;
the witness cannot see a fault that touches fewer than three quarters of
the positions.  Each fault put into the reference, against the program's
logits (first quartile; seed 2147483659 as the biases are seeded now, and
before: PERF.md has both): q/k norm over the whole projection 3.23e-2
(3.15e-2), bfloat16 throughout 4.10e-2 (4.08e-2), the bias added to the
weights 5.97e-2 (5.47e-2), rotary left out 1.24e-1, the wrong kv head
1.71e-1, the bias left out of the selection 3.55e-1, softmax scores 4.79e-1,
weights not renormalised 8.29e-1, the dense layer as experts 9.95e-1, the
gate dropped 1.40, taps on t..t+2 1.40.  2.85e-2 stands 12 % over the
largest sound reading and 10 % under the least fault, which is thin by the
ratio and wide by the spread: both readings are properties of the
architecture and the precision, not of the seed (sound readings 1.3 % apart
at one standard deviation; the limit is eleven of them above the mean).
The least fault is the least for a reason: with q_norm = k_norm = 1, as
every model is seeded, norming the whole projection differs from norming a
head by the ratio of two root mean squares, 9 % at 64 channels, inside a
softmax that is near-uniform at seeded weights.  The CPU tests
(``tests/test_lfm2_reference.py``) hold every position's logits and every
leaf's gradient to this file at 1e-5 in float32 with the norm weights moved
off one, where every fault shows by a thousand times.
"""

import gc
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 3e-4
LOGITS_TOLERANCE = 0.0285
WITNESS_ROWS = 256          # positions whose logits the witness reads
EXPERT_GROUP = 2            # experts on the device at a time
QUERY_BLOCK = 256           # attention rows at a time
DENSE_CHUNK = 1792          # hidden columns of the dense FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
PERIOD = 4                  # layers of one period of the published pattern
FAULTS = ("bias_added_to_weights", "bias_ignored_in_selection",
          "softmax_scores", "weights_not_renormalised", "acausal_taps",
          "gate_dropped", "wrong_kv_head", "qk_norm_whole_projection",
          "rotary_left_out", "dense_layer_as_experts", "bfloat16_throughout")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
CONV_LEAVES = ("conv_in", "conv_w", "conv_out")


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


def _project(u, p, n_heads, n_kv, eps, theta, whole_norm, rotary):
    """q [S, H, dh] and k, v [S, Hkv, dh] of one sequence, q and k normed
    per head (``whole_norm``, a fault: over the whole projection, the
    head's weight repeated) and rotated."""
    s = u.shape[0]
    q, k, v = u @ p["wq"], u @ p["wk"], u @ p["wv"]
    if whole_norm:
        q = _rms(q, jnp.tile(p["q_norm"], n_heads), eps)
        k = _rms(k, jnp.tile(p["k_norm"], n_kv), eps)
    q, k, v = (a.reshape(s, n, -1) for a, n in ((q, n_heads), (k, n_kv),
                                                (v, n_kv)))
    if not whole_norm:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if rotary:
        q, k = _rotary(q, theta), _rotary(k, theta)
    return q, k, v


def _attend(q, k, v):
    """Causal softmax attention of the query heads q [S, G, dh] that share
    ONE key/value head k, v [S, dh]."""
    s, _, dh = q.shape
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)

    def block(args):
        q_rows, first = args
        scores = jnp.einsum("qgd,kd->gqk", q_rows, k) / math.sqrt(dh)
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(rows)[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)

    o = jax.lax.map(block, (q.reshape((s // rows, rows) + q.shape[1:]),
                            jnp.arange(0, s, rows)))
    return o.reshape(q.shape)


def _short_conv(u, p, acausal, gate_dropped):
    """Step 2's ``op`` of a conv layer on one sequence u [S, E]."""
    s = u.shape[0]
    gate_b, gate_c, z = jnp.split(u @ p["conv_in"], 3, axis=-1)
    v = gate_b * z
    taps = p["conv_w"].shape[0]
    # row t + taps - 1 of ``padded`` is v[t]
    padded = jnp.pad(v, ((taps - 1, taps - 1), (0, 0)))
    first = taps - 1 if acausal else 0          # the fault: taps on t..t+2
    c = sum(p["conv_w"][j] * jax.lax.dynamic_slice_in_dim(
        padded, first + j, s, 0) for j in range(taps))
    return (c if gate_dropped else gate_c * c) @ p["conv_out"]


def _dense_chunk(acc, m, w_gate, w_up, w_down):
    return acc + (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _route(m, router, bias, k, scaling, fault):
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
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
    chosen = jax.nn.one_hot(top_e, logits.shape[-1], dtype=m.dtype)
    return jnp.sum(chosen * (top_w * scaling)[..., None].astype(m.dtype),
                   axis=1)


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
_conv_jit = jax.jit(_short_conv, static_argnums=(2, 3))
_project_jit = jax.jit(_project, static_argnums=(2, 3, 4, 5, 6, 7))
_attend_jit = jax.jit(_attend)
_rms_jit = jax.jit(_rms, static_argnums=2)


def moe_part(m, router, bias, w_gate_up, w_down, first, k, scaling=1.0,
             fault=None):
    """Step 4's ``y`` for the experts [first, first + held) that the weights
    hold, on one sequence's normed rows m [S, E]; the held experts
    ``EXPERT_GROUP`` at a time, each group waited for."""
    weight = _done(_route_jit(m, router, bias, k, scaling, fault))
    y = jnp.zeros_like(m)
    for at in range(0, w_gate_up.shape[0], EXPERT_GROUP):
        y = _done(_experts_jit(
            y, m, w_gate_up[at:at + EXPERT_GROUP],
            w_down[at:at + EXPERT_GROUP],
            weight[:, first + at:first + at + EXPERT_GROUP]))
    return y


def dense_part(m, w_gate_up, w_down, as_experts=False):
    """Step 4's ``y`` of a leading layer, ``DENSE_CHUNK`` hidden columns at
    a time.  ``as_experts`` (a fault): the chunks combined as experts with
    renormalised equal weights, their mean and not their sum."""
    f = w_down.shape[0]
    y = jnp.zeros_like(m)
    chunks = range(0, f, min(f, DENSE_CHUNK))
    for at in chunks:
        to = min(at + DENSE_CHUNK, f)
        y = _done(_dense_jit(y, m, w_gate_up[:, at:to],
                             w_gate_up[:, f + at:f + to], w_down[at:to]))
    return y / len(chunks) if as_experts else y


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
    out = []
    for i in range(n_dense):
        tree = params["prefix_layers"]["l%d" % i]
        out.append((i, tree.__getitem__, True, None))
    first = int(model["first_expert_layer"])
    for i in range(int(model["num_hidden_layers"]) - n_dense):
        tree = params["params_layers"]["p%d" % (i % PERIOD)]
        out.append((first + i,
                    lambda name, tree=tree, at=i // PERIOD: tree[name][at],
                    False, params["router_bias"][i]))
    return out


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

    n_heads = int(model["num_attention_heads"])
    n_kv = int(model["num_key_value_heads"])
    eps, theta = float(model["norm_eps"]), float(model["rope_theta"])
    k = int(model["num_experts_per_tok"])
    scaling = float(model.get("routed_scaling_factor", 1.0))
    first = int(model.get("moe_first_expert_held", 0))
    assert model["norm_topk_prob"] and model["use_expert_bias"]
    routing = [f for f in faults if f in FAULTS[:4]]
    routing = routing[0] if routing else None
    group_heads = n_heads // n_kv
    ids = np.asarray(ids)
    b, s = ids.shape
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for index, leaf, dense, bias in layers_of(params, model):
            gc.collect()
            kind = model["layer_types"][index]
            ln1 = cast(leaf("ln1_scale"))
            us = [_done(_rms_jit(x, ln1, eps)) for x in xs]
            if kind == "conv":
                p = {name: cast(leaf(name)) for name in CONV_LEAVES}
                ops = [_done(_conv_jit(u, p, "acausal_taps" in faults,
                                       "gate_dropped" in faults))
                       for u in us]
            else:
                assert kind == "full_attention", kind
                p = {name: cast(leaf(name)) for name in ATTENTION_LEAVES}
                ops = []
                for u in us:
                    q, kk, v = _done(_project_jit(
                        u, p, n_heads, n_kv, eps, theta,
                        "qk_norm_whole_projection" in faults,
                        "rotary_left_out" not in faults))
                    o = jnp.zeros_like(q)
                    for g in range(n_kv):
                        mine = (slice(g, None, n_kv)
                                if "wrong_kv_head" in faults else
                                slice(g * group_heads, (g + 1) * group_heads))
                        o = o.at[:, mine].set(_done(_attend_jit(
                            q[:, mine], kk[:, g], v[:, g])))
                    ops.append(_done(o.reshape(s, -1) @ p["wo"]))
                    del q, kk, v, o
            del p, us
            hs = [_done(x + op) for x, op in zip(xs, ops)]
            del ops
            ln2 = cast(leaf("ln2_scale"))
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            if dense:
                w_gate_up, w_down = cast(leaf("w_gate_up")), cast(leaf("w_down"))
                ys = [dense_part(m, w_gate_up, w_down,
                                 "dense_layer_as_experts" in faults)
                      for m in ms]
            else:
                router, bias = cast(leaf("router")), cast(bias)
                w_gate_up = cast(leaf("we_gate_up"))
                w_down = cast(leaf("we_down"))
                ys = [moe_part(m, router, bias, w_gate_up, w_down, first, k,
                               scaling, routing) for m in ms]
                del router
            xs = [_done(h + y) for h, y in zip(hs, ys)]
            del w_gate_up, w_down, hs, ms, ys, ln1, ln2
        table = params["tok_emb"]                       # the tied head
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
    """The positions whose logits the witness reads: WITNESS_ROWS of them,
    evenly over the sequence from half a stride in (at S = 8,192: 16, 48,
    ..., 8,176)."""
    stride = max(s // WITNESS_ROWS, 1)
    return np.arange(stride // 2, s, stride)


_last = {}      # the inputs' fingerprint and the results of the last run


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    last call's results are kept: the benchmark's driver asks for the logits
    and then the harness for the loss, of the same weights and batch."""
    ids = np.asarray(batch["ids"])
    marks = [np.asarray(params["router_bias"]),
             np.asarray(params["params_layers"]["p0"]["router"])]
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
    vocabulary, [B * P]: the program's logits ``got`` [B, P, V] at
    ``witness_positions`` against the reference's."""
    want = logits(params, batch, model, faults)
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    return (np.linalg.norm(got - want, axis=-1)
            / np.linalg.norm(want, axis=-1)).reshape(-1)


def logits_error(got, params, batch, model, faults=()):
    """The FIRST quartile of ``position_errors``: what LOGITS_TOLERANCE
    bounds (the module's text says why not the third)."""
    return float(np.quantile(
        position_errors(got, params, batch, model, faults), 0.25))
