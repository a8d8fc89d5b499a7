"""Plain reference for ``brumby_14b``: the training loss of a Brumby decoder
(Manifest AI Brumby-14B-Base ``config.json``, HF ``model_type`` ``brumby``;
the operator is the power retention of Buckman, Gelada et al.,
arXiv:2507.04239) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no scan over
layers, no sharding, nothing imported from the program: it takes the
program's weights by their names in the parameter tree and a batch (``ids``)
and returns the loss.

Power retention is computed in its SCORE-MATRIX form: every (query, key)
pair's weight, no feature expansion, no chunks, no state.  The program
computes the other form (a state of 8,320 x 128 numbers a key/value head,
carried chunk to chunk); that the two agree is what the comparison shows.

Layer l, on one sequence x [S, E] (no bias anywhere;
``rms(x, g) = x * rsqrt(mean(x^2) + eps) * g``, eps ``rms_norm_eps``):

1. ``u = rms(x, ln1_scale)``.
2. ``q = u @ wq`` [S, H, dh], ``k = u @ wk``, ``v = u @ wv`` [S, Hkv, dh];
   q and k RMS-normed over EACH head's dh (``q_norm`` / ``k_norm`` [dh]),
   then rotate-half rotary embedding, positions 0..S-1, theta
   ``rope_theta``; query head i reads key/value head ``i // (H // Hkv)``.
3. ``g = logsigmoid(u @ wg)`` [S, Hkv] (``wg`` [E, Hkv]), the log-decay of
   every token and key/value head; ``G_t = sum_{l <= t} g_l``.
4. ``a_tj = (q_t . k_j / sqrt(dh))^2 * exp(G_t - G_j)`` for j <= t (the
   token's own term carries no decay), ``o_t = sum_j a_tj v_j / (sum_j a_tj
   + retention_eps)``; ``op = o @ wo``.
5. ``h = x + op``; ``m = rms(h, ln2_scale)``; ``y = (silu(m @ Wg) * (m @
   Wu)) @ w_down``, ``[Wg, Wu] = w_gate_up`` [E, 2F], F =
   ``intermediate_size``; ``out = h + y``.

After the last layer ``rms(., lnf_scale)`` and the untied head ``lm_head``
[V, E]; next-token cross entropy over positions 0..S-2.

THE CUT.  The weights hold ``num_hidden_layers`` layers
(``params_layers/p0``, stacked) and ``vocab_size`` rows of the vocabulary
(ids, logits and loss are over the slice).  Departures from the published
description: the cut; no document mask (the state runs across document
boundaries); what the published config does not give and the configuration
file lists under ``assumed`` (degree 2, one gate a key/value head without
bias, the q/k norms and rotary positions of the Qwen3 skeleton, scale
dh^-1/2, eps 1e-6, the sum of the weights as normaliser).

THE RUNNING SUM ``G`` reaches -11,000 at S = 16,384 and seeded gates, where
a float32 holds it to 1e-3.  ``exp(G_t - G_j)`` is therefore never formed
from G: within a block of ``QUERY_BLOCK`` rows the sum runs from the
block's start, and between blocks the whole blocks' sums are added up
directly, so that an exponent near zero is exact to 1e-5 (far keys, whose
exponent is -100 and less, are not, and weigh nothing).

What it holds on the device at once is kept small (the reference runs beside
12.1 GB of trainer state, and ``peak_hbm_gb`` counts its peak): one layer's
leaves go up one at a time, retention runs one key/value head's group of
query heads and ``QUERY_BLOCK`` rows at a time, the FFN ``DENSE_CHUNK``
hidden columns at a time, the head ``VOCAB_CHUNK`` columns at a time.  Every
call is waited for before the next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/brumby_ref_sensitivity.py``.

THE WITNESS.  At seeded weights the gates average one half, so the state a
chunk hands on reaches some ten tokens past the chunk's edge: a fault that
lives in the carried state alone (dropped at an edge, read undecayed, its
off-diagonal products unweighted) touches those tokens and no others.
``witness_positions`` therefore has two named groups: ``edge``, the first
``EDGE_TOKENS`` tokens after each multiple of ``EDGE_EVERY`` = 2,048 (an
edge of every chunk length that divides 2,048), and ``spread``, evenly over
the sequence.  ``logits_error`` is the LARGER of the two groups' third
quartile of each position's ``|program - reference| / |reference|`` over
the vocabulary.  What the seeded model cannot show, a state carried over
many chunks, is held at the operator (``tests/test_brumby_reference.py`` on
the CPU, ``scripts/brumby_retention_receipt.py`` on the chip, both with
gates near one as well).

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

# Relative, on the scalar loss (cross entropy 10.35 to 10.36 at seeded
# weights; ln 18,992 = 9.85).  The system computes in bf16 with float32
# accumulation and a float32 state; the per-token error is random and the
# loss averages it over 16,383 positions.  From the chip (PR 37; the runs and
# seeds are PERF.md section 6's): the program's relative error read 1.2e-5
# to 4.2e-5 over thirteen seeds, and the precision hardly moves this number: the
# same reference with every array and operation in bfloat16 (fault
# ``bfloat16_throughout``) moves its loss by 1.8e-7.  So the loss carries the
# accepted decoder cells' limit, 3e-4, which leaves the largest sound reading
# seven times of room, and the PRECISION is the witness's to catch (below).
# What the loss does catch of the faults, each put into the reference at the
# timed sizes (``benchmark/tools/brumby_ref_sensitivity.py 2147483659``, on
# the chip): the wrong kv head 1.6e-3, an ungated FFN 8.3e-4, the state
# read undecayed 5.1e-4, the key's own gate counted 4.8e-4, softmax weights
# 4.0e-4; the nine others move it by 2e-7 to 1.3e-4 and pass: at seeded weights and uniform ids the loss sits
# near ln V whatever the block does.
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 37): the sound program reads 2.84e-2 to 3.07e-2 at thirteen
# seeds (at the first four the ``spread`` group 2.81e-2 to 2.91e-2, the
# ``edge`` group of 56 positions 2.70e-2 to 3.03e-2; the median position
# 2.5e-2, the worst 4.4e-2 to 6.5e-2: four layers of bf16 products through a
# quotient).  The least fault is the precision below the configuration's:
# ``bfloat16_throughout`` 4.60e-2, not correct by this limit alone.  Then q/k
# norm left out 2.24e-1, a GELU gate 2.95e-1, sqrt 2 left out of the state
# 4.39e-1 (the ``edge`` group alone: ``spread`` reads the sound 2.81e-2),
# degree one 6.31e-1, the key's own gate counted 6.32e-1, rotary left out
# 6.45e-1, the state dropped at chunk edges 9.17e-1 (``edge`` alone again),
# softmax weights 1.05, no normaliser 1.14, an ungated FFN 1.19, the wrong
# kv head 1.39, no decay 1.42, the state read undecayed 1.26.  3.7e-2
# stands 20 % over the largest sound reading and 20 % under the least fault:
# the geometric middle of the two.  Both readings are properties of the
# architecture and the precision (the sound third quartiles are 2 % apart
# in ``spread``, 5 % in ``edge``).
LOGITS_TOLERANCE = 0.037
EDGE_EVERY = 2048           # an edge of every chunk length that divides it
EDGE_TOKENS = 8             # witnessed tokens after each edge
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
QUERY_BLOCK = 256           # retention rows at a time
DENSE_CHUNK = 2176          # hidden columns of the FFN at a time
VOCAB_CHUNK = 2048          # head columns at a time
FAULTS = ("degree_one", "no_decay", "own_gate_counted", "no_normaliser",
          "softmax_weights", "state_dropped_at_chunk_edges",
          "state_read_undecayed", "sqrt2_left_out_of_state",
          "wrong_kv_head", "rotary_left_out", "qk_norm_left_out",
          "gelu_gate", "ungated_ffn", "bfloat16_throughout")
RETENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wg")


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


def _project(u, p, n_heads, n_kv, eps, theta, normed, rotary):
    """q [S, H, dh], k, v [S, Hkv, dh] and the log-decays [S, Hkv] of one
    sequence; q and k normed per head and rotated unless a fault says no."""
    s = u.shape[0]
    q, k, v = ((u @ p[w]).reshape(s, n, -1) for w, n in
               (("wq", n_heads), ("wk", n_kv), ("wv", n_kv)))
    if normed:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if rotary:
        q, k = _rotary(q, theta), _rotary(k, theta)
    return q, k, v, jax.nn.log_sigmoid(
        (u @ p["wg"].astype(u.dtype)).astype(jnp.float32))


def _exponents(g, rows):
    """Of one key/value head's log-decays g [S]: ``(rel_q [nb, rows], rel_k
    [nb, S])`` with ``rel_q[m, r] - rel_k[m, j] = G_t - G_j`` for t = m *
    rows + r and every j <= t, formed without ever holding G (the module's
    text says why)."""
    nb = g.shape[0] // rows
    inside = jnp.cumsum(g.reshape(nb, rows), axis=1)        # from block start
    whole = inside[:, -1]                                   # a block's sum
    m, at = jnp.arange(nb)[:, None, None], jnp.arange(nb)[None, :, None]
    i = jnp.arange(nb)[None, None, :]
    # between[m, m'] = -(sum of the whole blocks m' .. m-1), m' <= m
    between = -jnp.sum(jnp.where((at <= i) & (i < m), whole[None, None, :],
                                 0.0), axis=-1)
    rel_k = inside[None] + between[:, :, None]              # [nb, nb, rows]
    return inside, rel_k.reshape(nb, -1)


def _retain_rows(q_rows, first, e_q, e_k, k, v, g, eps, chunk, fault):
    """Step 4's ``o`` [rows, G, dh] of the query rows q_rows [rows, G, dh]
    at positions ``first`` on, against ALL keys k, v [S, dh]: every pair's
    weight.  ``e_q`` [rows] and ``e_k`` [S] are ``_exponents``' for this
    block of rows; g [S] is read by the faults alone."""
    rows, dh = q_rows.shape[0], q_rows.shape[-1]
    t = first + jnp.arange(rows)[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    scores = jnp.einsum("qgd,kd->gqk", q_rows, k) / math.sqrt(dh)
    if q_rows.dtype != jnp.float32:     # the one fault that is a precision
        scores = scores.astype(jnp.float32)
    decay = jnp.exp(jnp.minimum(e_q[:, None] - e_k[None, :], 0.0))
    power = jnp.abs(scores) if fault == "degree_one" else scores * scores
    earlier = (j // chunk < t // chunk)[None]       # from an earlier chunk
    if fault == "sqrt2_left_out_of_state":
        # phi without its sqrt 2: phi(q).phi(k) = ((q.k)^2 + q^2.k^2) / 2
        squares = jnp.einsum("qgd,kd->gqk", q_rows * q_rows, k * k) / dh
        power = jnp.where(earlier, 0.5 * (power + squares), power)
    if fault == "state_read_undecayed":
        # the state read as the chunk found it: the reader's running sum
        # inside its chunk left out
        inside = jnp.cumsum(g.reshape(-1, chunk), axis=1).reshape(-1)
        decay = jnp.where(earlier[0], jnp.exp(jnp.minimum(
            e_q[:, None] - e_k[None, :] - jax.lax.dynamic_slice_in_dim(
                inside, first, rows)[:, None], 0.0)), decay)
    if fault == "softmax_weights":
        weights = jax.nn.softmax(jnp.where(
            (j <= t)[None], scores + jnp.log(decay)[None], -jnp.inf), -1)
        return jnp.einsum("gqk,kd->qgd", weights.astype(v.dtype), v)
    weights = jnp.where((j <= t)[None], power * decay[None], 0.0)
    if fault == "state_dropped_at_chunk_edges":
        weights = jnp.where(earlier, 0.0, weights)
    num = jnp.einsum("gqk,kd->qgd", weights.astype(v.dtype), v)
    if fault == "no_normaliser":
        return num
    den = jnp.sum(weights, axis=-1).T[..., None] + eps
    return (num / den.astype(num.dtype)).astype(q_rows.dtype)


def _retain(q, k, v, g, eps, chunk, fault):
    """Step 4's ``o`` [S, G, dh] of the query heads q [S, G, dh] that share
    ONE key/value head k, v [S, dh] with log-decays g [S] (float32),
    ``QUERY_BLOCK`` rows at a time."""
    s = q.shape[0]
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    rel_q, rel_k = _exponents(g, rows)
    if fault == "own_gate_counted":     # the decay taken from G_{j-1}
        rel_k = rel_k - g[None]
    o = jax.lax.map(
        lambda turn: _retain_rows(*turn, k, v, g, eps, chunk, fault),
        (q.reshape((s // rows, rows) + q.shape[1:]), jnp.arange(0, s, rows),
         rel_q, rel_k))
    return o.reshape(q.shape)


def _ffn_chunk(acc, m, w_gate, w_up, w_down, fault):
    gate = m @ w_gate
    act = jax.nn.gelu(gate) if fault == "gelu_gate" else jax.nn.silu(gate)
    return acc + (act if fault == "ungated_ffn" else act * (m @ w_up)) @ w_down


_project_jit = jax.jit(_project, static_argnums=(2, 3, 4, 5, 6, 7))
_retain_jit = jax.jit(_retain, static_argnums=(4, 5, 6))
_ffn_jit = jax.jit(_ffn_chunk, static_argnums=5)
_rms_jit = jax.jit(_rms, static_argnums=2)


def ffn_part(m, w_gate_up, w_down, fault=None):
    """Step 5's ``y``, ``DENSE_CHUNK`` hidden columns at a time."""
    f = w_down.shape[0]
    y = jnp.zeros_like(m)
    for at in range(0, f, min(f, DENSE_CHUNK)):
        to = min(at + DENSE_CHUNK, f)
        y = _done(_ffn_jit(y, m, w_gate_up[:, at:to],
                           w_gate_up[:, f + at:f + to], w_down[at:to], fault))
    return y


def _head_chunk(x, g, w, labels, first, eps, keep):
    """Columns [first, first + C) of the head on one sequence: their
    logsumexp [S], the label's logit where the label is among them (else 0)
    and, where ``keep``, the logits [S, C]."""
    logits = (_rms(x, g, eps) @ w.T).astype(jnp.float32)
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

    n_heads = int(model["num_attention_heads"])
    n_kv = int(model["num_key_value_heads"])
    assert int(model["head_dim"]) * n_heads == params["params_layers"]["p0"][
        "wq"].shape[-1] and int(model["retention_degree"]) == 2
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    ret_eps = float(model["retention_eps"])
    chunk = int(model["retention_chunk"])
    group = n_heads // n_kv
    retain = [f for f in faults if f in FAULTS[:8]]
    retain = retain[0] if retain else None
    ffn = [f for f in faults if f in ("gelu_gate", "ungated_ffn")]
    ffn = ffn[0] if ffn else None
    ids = np.asarray(ids)
    b, s = ids.shape
    tree = params["params_layers"]["p0"]
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for layer in range(int(model["num_hidden_layers"])):
            gc.collect()
            ln1 = cast(tree["ln1_scale"][layer])
            us = [_done(_rms_jit(x, ln1, eps)) for x in xs]
            p = {name: cast(tree[name][layer]) for name in RETENTION_LEAVES}
            ops = []
            for u in us:
                q, k, v, g = _done(_project_jit(
                    u, p, n_heads, n_kv, eps, theta,
                    "qk_norm_left_out" not in faults,
                    "rotary_left_out" not in faults))
                o = jnp.zeros_like(q)
                for at in range(n_kv):
                    mine = (slice(at, None, n_kv)
                            if "wrong_kv_head" in faults else
                            slice(at * group, (at + 1) * group))
                    o = o.at[:, mine].set(_done(_retain_jit(
                        q[:, mine], k[:, at], v[:, at], g[:, at], ret_eps,
                        min(chunk, s), retain)))
                ops.append(_done(o.reshape(s, -1) @ p["wo"]))
                del q, k, v, g, o
            del p, us
            hs = [_done(x + op) for x, op in zip(xs, ops)]
            del ops
            ln2 = cast(tree["ln2_scale"][layer])
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            w_gate_up = cast(tree["w_gate_up"][layer])
            w_down = cast(tree["w_down"][layer])
            ys = [ffn_part(m, w_gate_up, w_down, ffn) for m in ms]
            xs = [_done(h + y) for h, y in zip(hs, ys)]
            del w_gate_up, w_down, hs, ms, ys, ln1, ln2
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
    tokens: the first EDGE_TOKENS tokens after each multiple of EDGE_EVERY
    (of a quarter of the sequence, where that is shorter), and SPREAD_ROWS
    evenly from half a stride in, those of the first group left out."""
    every = min(EDGE_EVERY, max(s // 4, 1))
    edge = np.unique(np.concatenate(
        [np.arange(at, min(at + min(EDGE_TOKENS, max(every // 2, 1)), s))
         for at in range(every, s, every)] or [np.arange(0)])).astype(int)
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
    marks = [np.asarray(tree["wg"]), np.asarray(tree["ln1_scale"]),
             np.asarray(tree["q_norm"]), np.asarray(params["lnf_scale"])]
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
