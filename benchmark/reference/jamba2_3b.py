"""Plain reference for ``jamba2_3b``: the training loss of a Jamba decoder
(AI21 Jamba2-3B ``config.json``, HF ``model_type`` ``jamba``, the published
``modeling_jamba.py`` semantics; the mixer is Mamba-1, Gu & Dao,
arXiv:2312.00752) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no chunks, no
cache, no remat, no scan over layers, no sharding, nothing imported from the
program: it takes the program's weights by their names in the parameter tree
and a batch (``ids``) and returns the loss.

The selective scan is a ``lax.scan`` a TOKEN over the state ``[d, N]``: the
recurrence as it is written down.  The program walks chunks with the state
in fast memory; that the two agree is what the comparison shows.

Layer i, on one sequence x [S, E] (``rms(x, g) = x * rsqrt(mean(x^2) + eps)
* g``, eps ``rms_norm_eps``; no bias but the filter's):

1. ``u = rms(x, ln1_scale)``; ``x <- x + mixer_i(u)``, attention where
   ``i mod attn_layer_period == attn_layer_offset``, else Mamba.
2. ``m = rms(x, ln2_scale)``; ``x <- x + (silu(m @ Wg) * (m @ Wu)) @
   w_down``, ``[Wg, Wu] = w_gate_up`` [E, 2F], F = ``intermediate_size``, in
   EVERY layer (``num_experts`` 1: the ``expert_layer_*`` keys select
   nothing).

Mamba mixer on u [S, E], d = ``mamba_expand`` * E, N = ``mamba_d_state``, R
= ``mamba_dt_rank``, taps = ``mamba_d_conv``:

a. ``[x, z] = split(u @ w_in)``.
b. ``x_t <- silu(conv_b + sum_j conv_w[j] * x_{t - taps + 1 + j})``, zero
   before position 0.
c. ``[delta, B, C] = split(x @ w_x)`` at R, R + N; ``delta <- rms(delta,
   dt_norm)``, ``B <- rms(B, b_norm)``, ``C <- rms(C, c_norm)``.
d. ``dt = softplus(delta @ w_dt + b_dt)``; ``A = -exp(a_log)`` [d, N].
e. ``h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] *
   B_t[None, :]``, ``h_{-1} = 0``; ``y_t = h_t @ C_t + d_skip * x_t``.
f. ``out = (y * silu(z)) @ w_out``.

Attention mixer: ``q = u @ wq`` (H heads of dh), ``k = u @ wk``, ``v = u @
wv`` (ONE head, read by all H), causal softmax at scale dh^-1/2, no q/k
norm, NO positions, ``wo``.

After the last layer ``rms(., lnf_scale)`` and the TIED head ``tok_emb``
[V, E]; next-token cross entropy over positions 0..S-2.

THE CUT.  The weights hold ``num_hidden_layers`` layers, whole periods of
``attn_layer_period``: with ``run_scan`` the program keeps a tree for each
run of one kind inside the period (``params_layers/r<i>``, stacked [periods,
run length, ...]), and layer i's leaves are read from there.  Departures
from the published description: the cut; no document mask (the state runs
across document boundaries); what the published config does not give and the
configuration file lists under ``assumed``.

What it holds on the device at once is kept small (the reference runs beside
12.8 GB of trainer state, and ``peak_hbm_gb`` counts its peak): one layer's
leaves go up one at a time, attention runs ``QUERY_BLOCK`` rows at a time,
the FFN ``DENSE_CHUNK`` hidden columns at a time, the head ``VOCAB_CHUNK``
columns at a time.  Every call is waited for before the next is sent.
``faults`` puts a fault in, for ``benchmark/tools/jamba_ref_sensitivity.py``.

THE WITNESS.  ``witness_positions`` has two named groups: ``edge``, the
first ``EDGE_TOKENS`` tokens after each multiple of ``EDGE_EVERY`` = 128 (an
edge of every chunk length that divides 128, and every multiple of 2,048),
and ``spread``, evenly over the sequence.  ``logits_error`` is the LARGER of
the two groups' third quartile of each position's ``|program - reference| /
|reference|`` over the vocabulary.  At seeded weights a state cell's decay
runs from 0.999 to 0.2 a token, so the slow cells carry for thousands of
tokens over every edge and the fast ones for a few: a state dropped at an
edge moves every token after the first chunk, most of all those just past
an edge.  What the seeded model cannot show, the backward, is held at the
operator (``tests/test_jamba_reference.py`` on the CPU,
``scripts/jamba_kernels_receipt.py`` on the chip).

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

# Relative, on the scalar loss (cross entropy 11.58 to 11.61 at seeded
# weights; ln 65,536 = 11.09).  The system computes in bf16 with float32
# accumulation and a float32 state; the per-token error is random and the
# loss averages it over 8,191 positions.  From the chip (PR 48; the runs and
# seeds are PERF.md section 6's): the program's relative error read 1.5e-5
# to 6.1e-5 over nine seeds, and the precision hardly moves this number: the
# same reference with every array and operation in bfloat16 (fault
# ``bfloat16_throughout``) moves its loss by 4.2e-6 to 3.9e-4 over three
# seeds.  So the loss carries the accepted decoder cells' limit, 3e-4, which
# leaves the first reading (1.8e-5) sixteen times of room and the largest
# five, and the PRECISION is the witness's to catch (below).  What the loss
# does catch of the faults, each put into the reference at the timed sizes
# (``benchmark/tools/jamba_ref_sensitivity.py 2147483659``, on the chip): no
# gate 1.6e-3, the filter a tap late 1.3e-3, the filter without its bias
# 9.0e-4, no skip 8.8e-4, no norm on the step sizes' input 5.0e-4, the step
# size left out of the input 4.7e-4, and the two that overflow (no softplus,
# rates not negated: no number); the six others move it by 1.5e-5 to 1.8e-4
# and pass: at seeded weights and uniform ids the loss sits near ln V
# whatever the block does.
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 48): the sound program reads 3.16e-2 to 3.76e-2 at
# thirteen seeds (mean 3.46e-2; the two groups within 2 % of each other; the worst
# position 4.1e-2 to 5.3e-2: fourteen layers of bf16 products under a tied
# head).  The precision below the configuration's, ``bfloat16_throughout``,
# reads 1.06e-1 to 1.84e-1 at three seeds, and the scan's state alone in
# bfloat16 1.01e-1 to 1.81e-1: not correct by this limit alone.  Then a
# second key/value head 9.6e-2 to 1.0e-1, no norm on the step sizes' input
# 6.2e-1, no norm on B and C 9.7e-1, the state dropped at chunk edges 1.04
# (``edge``; ``spread`` 0.82), the filter without its bias 1.29, no gate
# 1.38, a tap late 1.41, no skip 1.41, the step size left out of the input
# 1.42; no softplus and rates not negated give no number, which is no pass.
# THE LEAST FAULT IS NOT THE PRECISION: rotary positions put on the one
# attention layer read 4.26e-2, 4.54e-2 and 4.83e-2 at three seeds, 1.31 to
# 1.35 times the sound reading OF THE SAME SEED (at seeded weights the
# scores of one attention layer in fourteen are near zero, the softmax near
# uniform, and a rotation moves little).  The limit is held to the sound
# side: 4.2e-2 stands 12 % (3.8 standard deviations of the thirteen readings)
# over the largest sound reading, so that a new seed does not fail a sound
# program, under all three readings of the rotation (by 1.4 to 15 %) and a
# factor 2.3 under the next fault.  A seed whose rotation reads under it is
# possible; every other fault clears it by that factor or more.
LOGITS_TOLERANCE = 0.042
EDGE_EVERY = 128            # an edge of every chunk length that divides it
EDGE_TOKENS = 8             # witnessed tokens after each edge
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
QUERY_BLOCK = 512           # attention rows at a time
DENSE_CHUNK = 2048          # hidden columns of the FFN at a time
VOCAB_CHUNK = 4096          # head columns at a time
MAMBA_FAULTS = ("state_dropped_at_chunk_edges", "dt_without_softplus",
                "dt_left_out_of_input", "a_not_negated", "no_d_skip",
                "no_z_gate", "no_dt_norm", "no_bc_norm", "conv_without_bias",
                "conv_one_tap_late", "scan_state_bfloat16")
ATTENTION_FAULTS = ("rotary_on_attention", "second_kv_head")
FAULTS = MAMBA_FAULTS + ATTENTION_FAULTS + ("bfloat16_throughout",)
MAMBA_LEAVES = ("w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm",
                "c_norm", "w_dt", "b_dt", "a_log", "d_skip", "w_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mamba(u, p, n_state, rank, eps, chunk, faults):
    """Steps a to f on one sequence u [S, E]."""
    s, dtype = u.shape[0], u.dtype
    x, z = jnp.split(u @ p["w_in"], 2, axis=-1)
    taps = p["conv_w"].shape[0]
    late = 1 if "conv_one_tap_late" in faults else 0
    padded = jnp.pad(x, ((taps - 1 - late, late), (0, 0)))
    conv = sum(p["conv_w"][j] * padded[j:j + s] for j in range(taps))
    if "conv_without_bias" not in faults:
        conv = conv + p["conv_b"]
    x = jax.nn.silu(conv)
    delta, bmat, cmat = jnp.split(x @ p["w_x"], [rank, rank + n_state],
                                  axis=-1)
    if "no_dt_norm" not in faults:
        delta = _rms(delta, p["dt_norm"], eps)
    if "no_bc_norm" not in faults:
        bmat, cmat = _rms(bmat, p["b_norm"], eps), _rms(cmat, p["c_norm"], eps)
    dt = delta @ p["w_dt"] + p["b_dt"]
    if "dt_without_softplus" not in faults:
        dt = jax.nn.softplus(dt)
    a = jnp.exp(p["a_log"])
    if "a_not_negated" not in faults:
        a = -a
    low_state = "scan_state_bfloat16" in faults
    drop = "state_dropped_at_chunk_edges" in faults
    weight = jnp.ones_like(dt) if "dt_left_out_of_input" in faults else dt

    def token(h, turn):
        x_t, dt_t, w_t, b_t, c_t, t = turn
        if drop:
            h = jnp.where(t % chunk == 0, jnp.zeros_like(h), h)
        h = jnp.exp(dt_t[:, None] * a) * h + (w_t * x_t)[:, None] * b_t[None]
        if low_state:
            # bfloat16's 8 bits of mantissa (a cast there and back is one
            # the compiler may drop: it allows excess precision)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, h @ c_t

    _, y = jax.lax.scan(token, jnp.zeros(a.shape, dtype),
                        (x, dt, weight, bmat, cmat, jnp.arange(s)))
    if "no_d_skip" not in faults:
        y = y + p["d_skip"] * x
    if "no_z_gate" not in faults:
        y = y * jax.nn.silu(z)
    return y @ p["w_out"]


def _rotary(x, theta=10000.0):
    """x [S, H, dh]; pair i of a head is (x[i], x[i + dh/2]): the fault."""
    s, _, dh = x.shape
    inv_freq = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv_freq[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), x.dtype)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), x.dtype)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def _attention_rows(q_rows, first, k, v):
    """Causal softmax of the query rows q_rows [rows, H, dh] at positions
    ``first`` on against the keys k, v [S, H or 1, dh]."""
    rows, dh = q_rows.shape[0], q_rows.shape[-1]
    t = first + jnp.arange(rows)[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    scores = jnp.einsum("qhd,khd->hqk", q_rows,
                        jnp.broadcast_to(k, k.shape[:1] + q_rows.shape[1:]))
    scores = scores.astype(jnp.float32) / math.sqrt(dh)
    weights = jax.nn.softmax(jnp.where((j <= t)[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", weights.astype(v.dtype),
                      jnp.broadcast_to(v, v.shape[:1] + q_rows.shape[1:]))


def _attention(u, p, n_heads, faults):
    """The attention mixer on one sequence u [S, E]."""
    s = u.shape[0]
    q = (u @ p["wq"]).reshape(s, n_heads, -1)
    k = (u @ p["wk"]).reshape(s, 1, -1)
    v = (u @ p["wv"]).reshape(s, 1, -1)
    if "rotary_on_attention" in faults:
        q, k = _rotary(q), _rotary(k)
    if "second_kv_head" in faults:
        # the upper half of the query heads on a head of their own: the one
        # head's columns, the halves changed over
        half = n_heads // 2
        other = [jnp.roll(t, t.shape[-1] // 2, axis=-1) for t in (k, v)]
        k, v = (jnp.concatenate(
            [jnp.broadcast_to(t, (s, half, t.shape[-1])),
             jnp.broadcast_to(o, (s, n_heads - half, t.shape[-1]))], axis=1)
            for t, o in zip((k, v), other))
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)
    o = jax.lax.map(lambda turn: _attention_rows(*turn, k, v),
                    (q.reshape((s // rows, rows) + q.shape[1:]),
                     jnp.arange(0, s, rows)))
    return o.reshape(s, -1) @ p["wo"]


def _ffn_chunk(acc, m, w_gate, w_up, w_down):
    return acc + (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


_mamba_jit = jax.jit(_mamba, static_argnums=(2, 3, 4, 5, 6))
_attention_jit = jax.jit(_attention, static_argnums=(2, 3))
_ffn_jit = jax.jit(_ffn_chunk)
_rms_jit = jax.jit(_rms, static_argnums=2)


def ffn_part(m, w_gate_up, w_down):
    """Step 2's FFN, ``DENSE_CHUNK`` hidden columns at a time."""
    f = w_down.shape[0]
    y = jnp.zeros_like(m)
    for at in range(0, f, min(f, DENSE_CHUNK)):
        to = min(at + DENSE_CHUNK, f)
        y = _done(_ffn_jit(y, m, w_gate_up[:, at:to],
                           w_gate_up[:, f + at:f + to], w_down[at:to]))
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


def layer_places(model):
    """``(is attention, tree name, period, place in its run)`` of every
    layer: the period's kinds from ``attn_layer_period`` / ``_offset`` as
    ``modeling_jamba.py`` reads them, its runs of one kind as the program's
    tree names them."""
    period, offset = (int(model[k]) for k in ("attn_layer_period",
                                              "attn_layer_offset"))
    kinds = [at == offset for at in range(period)]
    runs, run_of = [], []
    for at, kind in enumerate(kinds):
        if not runs or kinds[runs[-1]] != kind:
            runs.append(at)
        run_of.append((len(runs) - 1, at - runs[-1]))
    n = int(model["num_hidden_layers"])
    assert n % period == 0, (n, period)
    return [(kinds[i % period], "r%d" % run_of[i % period][0], i // period,
             run_of[i % period][1]) for i in range(n)]


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
    assert int(model["num_key_value_heads"]) == 1 \
        and int(model["num_experts"]) == 1 and model["tie_word_embeddings"]
    eps = float(model["rms_norm_eps"])
    n_state, rank = int(model["mamba_d_state"]), int(model["mamba_dt_rank"])
    mamba_faults = tuple(f for f in faults if f in MAMBA_FAULTS)
    attention_faults = tuple(f for f in faults if f in ATTENTION_FAULTS)
    ids = np.asarray(ids)
    b, s = ids.shape
    chunk = min(int(model["scan_chunk"]), s)
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        for attention, name, period, place in layer_places(model):
            gc.collect()
            tree = params["params_layers"][name]

            def leaf(key):
                return cast(tree[key][period, place])

            ln1 = leaf("ln1_scale")
            us = [_done(_rms_jit(x, ln1, eps)) for x in xs]
            if attention:
                p = {key: leaf(key) for key in ATTENTION_LEAVES}
                assert p["wk"].shape[-1] * n_heads == p["wq"].shape[-1]
                ops = [_done(_attention_jit(u, p, n_heads, attention_faults))
                       for u in us]
            else:
                p = {key: leaf(key) for key in MAMBA_LEAVES}
                ops = [_done(_mamba_jit(u, p, n_state, rank, eps, chunk,
                                        mamba_faults)) for u in us]
            del p, us
            hs = [_done(x + op) for x, op in zip(xs, ops)]
            del ops
            ln2 = leaf("ln2_scale")
            ms = [_done(_rms_jit(h, ln2, eps)) for h in hs]
            w_gate_up, w_down = leaf("w_gate_up"), leaf("w_down")
            ys = [ffn_part(m, w_gate_up, w_down) for m in ms]
            xs = [_done(h + y) for h, y in zip(hs, ys)]
            del w_gate_up, w_down, hs, ms, ys, ln1, ln2
        table = params["tok_emb"]
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
    tree = params["params_layers"]["r0"]
    marks = [np.asarray(tree["b_dt"]), np.asarray(tree["ln1_scale"]),
             np.asarray(tree["w_dt"][0, 0]), np.asarray(params["lnf_scale"])]
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
