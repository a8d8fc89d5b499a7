"""Plain reference for ``ouro_2_6b``: the Stage-I training loss and the
weighted-exit logits of an Ouro LOOPED decoder (ByteDance/Ouro-2.6B
``config.json``, HF ``model_type`` ``ouro``; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  Written from the description:
a Python loop over passes and layers, no kernels, no scan, no remat, no
sharding, nothing imported from the program.  It takes the program's weights
by their names in the parameter tree and a batch (``ids``).

With ``rms(x; g) = g * x / sqrt(mean(x^2) + eps)``, eps ``rms_norm_eps``, on
one sequence x [S, E], E = ``hidden_size``, T = ``total_ut_steps``, L =
``num_hidden_layers``:

A LAYER (the same equations for all L; no bias anywhere):

1. ``a = x + rms(Attn(rms(x; ln1_scale)); ln1_post_scale)``.  ``Attn(h)``:
   ``q, k, v = h wq, h wk, h wv``, H = ``num_attention_heads`` heads of
   ``head_dim`` each (as many key/value heads); rotary positions on q and k
   over the whole head width, theta ``rope_theta``, the halves of a head the
   pairs (column i with column i + dh / 2, angle ``pos * theta^(-2 i /
   dh)``); causal softmax at scale ``dh^-1/2``; ``wo``.
2. ``y = a + rms(FFN(rms(a; ln2_scale)); ln2_post_scale)``, ``FFN(m) =
   (silu(m Wg) * (m Wu)) w_down``, ``[Wg, Wu] = w_gate_up`` [E, 2F], F =
   ``intermediate_size``.

THE LOOP.  ``x_0 = tok_emb[ids]``.  For pass t = 1..T: ``u_t`` = the L
layers in order on ``x_{t-1}``, the SAME leaves every pass; ``h_t = rms(u_t;
lnf_scale)``, the model's one final norm; ``x_t = h_t``: the NORMED state is
what the next pass reads.  After every pass the gate ``lam_t = sigmoid(h_t .
exit_gate_w + exit_gate_b)`` and the head ``z_t = h_t lm_head^T`` (the same
matrix at every exit, no second norm).

THE EXIT DISTRIBUTION, a token: ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for t
< T, ``p_T = prod_{j<T} (1 - lam_j)``: what is left (``lam_T`` is not read).

THE LOSS, mean over positions 0..S-2 of every sequence, label the next
token: ``sum_t p_t nll_t - beta H(p)``, ``nll_t = logsumexp(z_t) -
z_t[label]``, ``H(p) = -sum_t p_t ln p_t``, beta ``exit_entropy_coef``.
THE LOGITS: the weighted-exit ``sum_t p_t z_t``, the published forward's
second output mode, at ``witness_positions``.

THE CUT.  The weights hold ``num_hidden_layers`` layers (12 of the published
48, ``params_layers/<leaf>`` stacked [L, ...]); every pass runs those.
Departures from the published description: the cut; what ``config.json``
has no key for and the configuration file lists under ``assumed`` (the
output norms, the gate on the normed state, beta, the rotary pairing).

What it holds on the device at once is kept small (it runs beside the
trainer's state, and ``peak_hbm_gb`` counts its peak): one layer's leaves go
up at a time, a sequence at a time, attention ``QUERY_BLOCK`` rows at a
time, the FFN ``DENSE_CHUNK`` hidden columns at a time, the head
``VOCAB_CHUNK`` columns at a time.  Every call is waited for before the next
is sent.  ``faults`` puts a fault in, for
``benchmark/tools/ouro_ref_sensitivity.py``.

THE WITNESS.  ``witness_positions`` has two named groups: ``edge``, the
first ``EDGE_TOKENS`` tokens after each multiple of ``EDGE_EVERY`` = 512 (a
block edge of the program's attention kernels), and ``spread``, evenly over
the sequence.  A position's error is ``|program - reference| / |reference|``
over the vocabulary (``position_errors``).  ``logits_error`` is the LARGER
of the two groups' MEDIAN of each position's error IN UNITS OF THE PRECISION
BELOW THE CONFIGURATION'S: over the same position's ``|this file in bfloat16
- this file| / |this file|`` (``precision_unit``: the fault
``bfloat16_throughout``, every array in bfloat16 and EVERY operation's
result rounded to it, against the sound float32 forward; a second, cheaper
forward a run).  So the control, that forward put in the program's place,
reads 1 by construction at every seed, and the limit lies under 1: a program
has to be NEARER the float32 forward than bfloat16 throughout is, by a margin
(the configuration states bf16 weights and activations under float32
accumulation and float32 norms, softmax, gate and loss).  Why a unit: a
looped stack's plain error follows the SEED.  The normed state has a large
part every token shares, so the gate's logit ``h_t . w_e`` is mostly a
constant of the seed (N(0, 1) over seeds), each seed's exits weigh
differently, ``|sum_t p_t z_t|`` is as short as half a single exit's where
they weigh alike, and the error 48 layer applications amplify differs by the
seed's weights: over 21 seeds the sound program's plain third quartile read
0.0147 to 0.0241, and the control's plain median 0.0216 to 0.0269 at three
of them: no plain limit separates them at every seed, the ratio does at each
(readings beneath the constants).  At seeded weights every exit's cross
entropy sits near ln V + 1/2 whatever the stack does, so the LOSS sees what
moves ``beta H(p)`` (1 % of it) and the normalisation of ``p``; the
weighted-exit LOGITS see the passes, the norm between them, the gate and the
shared leaves.  What the seeded model cannot show, the backward through the
shared leaves, is held on the CPU (``tests/test_ouro_reference.py``: every
leaf's gradient against ``jax.grad`` of this file) and by a receipt on the
chip at the published widths (``benchmark/tools/ouro_grad_receipt.py``).

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

# Relative, on the scalar loss (11.16 to 11.21 at seeded weights: ln 49,152
# = 10.80, half a nat of the seeded head's unit-variance logits, less beta
# H(p) = 0.13).  The system computes in bf16 with float32 accumulation; a
# token's error is random and the loss averages it over 8,190 positions and
# four exits.  From the chip (PR 54; the runs and seeds are PERF.md section
# 6's): the program's relative error read 2.9e-6 to 4.7e-5 over twenty-seven
# seeds (the first reading 2.7e-5), and the precision hardly moves this
# number: the same reference with every array and operation in bfloat16
# (fault ``bfloat16_throughout``) moves its loss by 9.9e-6 to 4.6e-5 over
# six seeds.  So the loss carries the accepted decoder
# cells' limit, 3e-4, which leaves the first reading eleven times of room and
# the largest six, and the PRECISION is the witness's to catch (below).  What
# the loss does catch of the faults, each put into the reference at the timed
# sizes (``benchmark/tools/ouro_ref_sensitivity.py 2147483659``, on the
# chip): a last exit that takes its own gate 1.9e-1 (``sum p`` is no longer
# 1), the entropy term dropped 1.05e-2 (beta H(p) is 1.2 % of the loss:
# thirty-five limits), a single pass 1.03e-2, the gate on the un-normed state
# 6.3e-3, uniform exit weights 2.4e-3, fresh leaves each pass 6.6e-4, no
# output norms 6.2e-4, no norm between the passes 6.2e-4 (1.2e-4 and 4.0e-4
# at two other seeds: not at every seed); the gate without its bias 2.8e-4
# (5.0e-4 to 1.3e-3 at three others) and the precision pass: at seeded
# weights every exit's cross entropy sits near ln V + 1/2 whatever the stack
# does.
TOLERANCE = 3e-4
# On the witness's statistic: the larger of the two groups' median of each
# position's error over that position's ``precision_unit``, so 1 is what the
# precision below the configuration's, bfloat16 throughout with every
# operation's result rounded, moves the logits by.  THE TWO READINGS the
# limit lies between, from the chip (PR 54, after its review): the sound
# program reads 0.703 to 0.828 at fifteen seeds (mean 0.746, standard
# deviation 0.036; the two groups within 2 % of each other): bf16 products
# under float32 accumulation, float32 norms and softmax and one rounding a
# fused chain are nearer the float32 forward than bfloat16 throughout is, by
# a quarter.  THE CONTROL, that bfloat16 forward put in the program's place
# through this file's own comparison, reads 1.000 at every seed, by
# construction (it is the unit; its plain median 0.0212 to 0.0269 at six
# seeds where the sound program's is 0.0152 to 0.0210): NOT CORRECT by this
# limit alone.  0.91 is the geometric middle of the largest sound reading
# and 1: 9.9 % over every sound reading (4.6 of their standard deviations
# over their mean) and 9 % under the control, which does not vary (another
# program in bfloat16 throughout would read 1 give or take the median's own
# noise over 480 positions, a few hundredths).
# The same forward as XLA compiles it by default (a fused chain kept in
# float32: ``_EVERY_OP_ROUNDS`` below) is no control: it reads 0.83 to 0.84
# (three seeds), under the limit as the sound program does, a precision
# between the two.  Nor is the sound program against the bfloat16 reference,
# the fault's reading below: two independent errors added.  The faults, each
# put into the reference against the program's logits (seed 2147483659; the
# least three at four):
# fresh leaves each pass 39.7, no output norms 36.5, a single pass 27.7, no
# norm between the passes 27.3, the gate on the un-normed state 15.3, uniform
# exit weights 14.5, a last exit that takes its own gate 10.5 (5.3 to 15.9),
# the gate without its bias 9.3 (8.1 to 9.7), bfloat16 throughout 1.245
# (1.217 to 1.245); the entropy term is not in the logits (0.778, the sound
# reading) and is the loss's.  The PLAIN statistic (the third quartile of
# the positions' relative error, what the other decoder cells' limits bound)
# read 0.0147 to 0.0241 for the sound program over 21 seeds: one limit
# cannot keep a fresh seed's sound program and catch the control at every
# seed (its plain median 0.0212 at one seed, 0.0269 at another); the
# docstring says why the plain error follows the seed.
LOGITS_TOLERANCE = 0.91
EDGE_EVERY = 512            # a block edge of the program's attention kernels
EDGE_TOKENS = 16            # witnessed tokens after each edge
SPREAD_ROWS = 128           # witnessed positions spread over the sequence
QUERY_BLOCK = 512           # attention rows at a time
DENSE_CHUNK = 2048          # hidden columns of the FFN at a time
VOCAB_CHUNK = 4096          # head columns at a time
FAULTS = ("one_pass", "no_norm_between_passes", "gate_without_bias",
          "gate_on_unnormed_state", "last_exit_takes_lambda",
          "entropy_term_dropped", "uniform_exit_weights",
          "post_norms_dropped", "fresh_leaves_each_pass",
          "bfloat16_throughout")
MATRICES = ("wq", "wk", "wv", "wo", "w_gate_up", "w_down")
NORMS = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale")


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
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), x.dtype)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), x.dtype)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


def _attention(h, p, n_heads, theta):
    """``Attn`` on one sequence h [S, E], ``QUERY_BLOCK`` rows at a time
    against the keys up to the block's last row."""
    s = h.shape[0]
    q, k = (_rotary((h @ p[w]).reshape(s, n_heads, -1), theta)
            for w in ("wq", "wk"))
    v = (h @ p["wv"]).reshape(s, n_heads, -1)
    rows, out = min(s, QUERY_BLOCK), []
    for first in range(0, s, rows):
        last = min(first + rows, s)
        scores = jnp.einsum("qhd,khd->hqk", q[first:last], k[:last])
        scores = scores.astype(jnp.float32) / math.sqrt(q.shape[-1])
        causal = (jnp.arange(last)[None, :]
                  <= jnp.arange(first, last)[:, None])
        weights = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", weights.astype(v.dtype),
                              v[:last]))
    return jnp.concatenate(out).reshape(s, -1) @ p["wo"]


def _ffn(m, p):
    """``FFN`` on m [S, E], ``DENSE_CHUNK`` hidden columns at a time."""
    f = p["w_down"].shape[0]
    y = jnp.zeros_like(m)
    for at in range(0, f, DENSE_CHUNK):
        to = min(at + DENSE_CHUNK, f)
        y = y + (jax.nn.silu(m @ p["w_gate_up"][:, at:to])
                 * (m @ p["w_gate_up"][:, f + at:f + to])) @ p["w_down"][at:to]
    return y


def _layer(x, p, n_heads, theta, eps, post_norms):
    """Steps 1 and 2 on one sequence x [S, E]."""
    branch = _attention(_rms(x, p["ln1_scale"], eps), p, n_heads, theta)
    a = x + (_rms(branch, p["ln1_post_scale"], eps) if post_norms else branch)
    branch = _ffn(_rms(a, p["ln2_scale"], eps), p)
    return a + (_rms(branch, p["ln2_post_scale"], eps) if post_norms
                else branch)


def _head_chunk(h, w, labels, first, keep):
    """Columns [first, first + C) of the head on one sequence's normed state
    h [S, E]: their logsumexp [S], the label's logit where the label is
    among them (else 0) and the logits at the rows ``keep`` [P, C]."""
    logits = (h @ w.T).astype(jnp.float32)
    at = labels - first
    inside = (at >= 0) & (at < w.shape[0])
    picked = jnp.take_along_axis(
        logits, jnp.clip(at, 0, w.shape[0] - 1)[:, None], axis=-1)[:, 0]
    return (jax.scipy.special.logsumexp(logits, axis=-1),
            jnp.where(inside, picked, 0.0), logits[keep])


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4, 5))
_head_jit = jax.jit(_head_chunk)
_rms_jit = jax.jit(_rms, static_argnums=2)
# ``bfloat16_throughout`` rounds the result of EVERY operation: as XLA
# compiles a block by default it keeps a fused chain of bfloat16 operations
# in float32 and rounds once at its end (a described v5e's text of ``_rms``:
# no ``reduce-precision`` by default, nine with this), which is a precision
# between the two and not the one below
_EVERY_OP_ROUNDS = {"xla_allow_excess_precision": False}
_layer_low = jax.jit(_layer, static_argnums=(2, 3, 4, 5),
                     compiler_options=_EVERY_OP_ROUNDS)
_head_low = jax.jit(_head_chunk, compiler_options=_EVERY_OP_ROUNDS)
_rms_low = jax.jit(_rms, static_argnums=2, compiler_options=_EVERY_OP_ROUNDS)


def exit_distribution(lam, faults=()):
    """``p`` [T, ...] from the gates ``lam`` [T, ...]."""
    T = lam.shape[0]
    if "uniform_exit_weights" in faults:
        return jnp.full(lam.shape, 1.0 / T, lam.dtype)
    p, left = [], jnp.ones_like(lam[0])
    for t in range(T - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    p.append(lam[T - 1] * left if "last_exit_takes_lambda" in faults
             else left)
    return jnp.stack(p)


def _fresh(leaf, t, layer, at):
    """A leaf of the same shape and scale from a seed of its own: what pass
    ``t`` would read if the passes did NOT share their leaves."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(54), t), layer), at)
    return (jax.random.normal(key, leaf.shape, jnp.float32)
            * jnp.std(leaf.astype(jnp.float32))).astype(leaf.dtype)


def forward(params, ids, model, faults=(), positions=None):
    """``(loss, logits, exits)``: the training loss as a scalar
    (differentiable in ``params``), each sequence's weighted-exit logits [P,
    V] at ``positions`` (all of them where None) and ``{"p": [B, T, S],
    "nll": [B, T, S]}``, each position's exit distribution and each exit's
    cross entropy."""
    for fault in faults:
        assert fault in FAULTS, fault
    # the one fault that is a precision: every array and every operation in
    # bfloat16, each operation's result rounded, at the device's default
    # matmul precision
    low = "bfloat16_throughout" in faults
    layer_fn, head_fn, rms_fn = (_layer_low, _head_low, _rms_low) if low \
        else (_layer_jit, _head_jit, _rms_jit)
    dtype = jnp.bfloat16 if low else jnp.float32

    def cast(a):
        return _done(jnp.asarray(a).astype(dtype))

    n_heads, dh = int(model["num_attention_heads"]), int(model["head_dim"])
    assert int(model["num_key_value_heads"]) == n_heads \
        and not model["tie_word_embeddings"] \
        and model["rope_scaling"] is None and model["sliding_window"] is None
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    n_layers = int(model["num_hidden_layers"])
    assert all(kind == "full_attention"
               for kind in model["layer_types"][:n_layers])
    passes = 1 if "one_pass" in faults else int(model["total_ut_steps"])
    beta = 0.0 if "entropy_term_dropped" in faults \
        else float(model["exit_entropy_coef"])
    post_norms = "post_norms_dropped" not in faults
    ids = np.asarray(ids)
    b, s = ids.shape
    keep = jnp.arange(s) if positions is None else jnp.asarray(positions)
    layers = params["params_layers"]
    assert layers["wq"].shape[-1] == n_heads * dh, layers["wq"].shape
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        g_f, w_e = cast(params["lnf_scale"]), cast(params["exit_gate_w"])
        b_e = 0.0 if "gate_without_bias" in faults \
            else cast(params["exit_gate_b"])
        labels = [jnp.asarray(np.roll(ids[j], -1)) for j in range(b)]
        head = params["lm_head"]
        lam, nll, zs = [], [], []
        for t in range(passes):
            for i in range(n_layers):
                gc.collect()
                p = {name: cast(layers[name][i]) for name in MATRICES + NORMS}
                if "fresh_leaves_each_pass" in faults and t:
                    p.update({name: _fresh(p[name], t, i, at)
                              for at, name in enumerate(MATRICES)})
                xs = [_done(layer_fn(x, p, n_heads, theta, eps, post_norms))
                      for x in xs]
                del p
            hs = [_done(rms_fn(x, g_f, eps)) for x in xs]
            gated = xs if "gate_on_unnormed_state" in faults else hs
            lam.append(jnp.stack([jax.nn.sigmoid(
                (h @ w_e + b_e).astype(jnp.float32)) for h in gated]))
            lse, picked = [None] * b, [0.0] * b
            kept = [[] for _ in range(b)]
            for at in range(0, head.shape[0], VOCAB_CHUNK):
                w = cast(head[at:at + VOCAB_CHUNK])
                for j in range(b):
                    l, at_label, lg = _done(head_fn(
                        hs[j], w, labels[j], jnp.int32(at), keep))
                    lse[j] = l if lse[j] is None else jnp.logaddexp(lse[j], l)
                    picked[j] = picked[j] + at_label
                    kept[j].append(lg)
                del w
            nll.append(jnp.stack([lse[j] - picked[j] for j in range(b)]))
            zs.append([jnp.concatenate(lg, axis=-1) for lg in kept])
            if "no_norm_between_passes" not in faults:
                xs = hs
            del hs, kept
        lam, nll = jnp.stack(lam), jnp.stack(nll)               # [T, B, S]
        p = exit_distribution(lam, faults)
        entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(
            jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
        each = jnp.sum(p * nll, axis=0) - beta * entropy        # [B, S]
        loss = jnp.sum(each[:, :-1]) / (b * (s - 1))
        logits = [sum(p[t, j][keep][:, None] * zs[t][j]
                      for t in range(passes)) for j in range(b)]
    return loss, logits, {"p": jnp.swapaxes(p, 0, 1),
                          "nll": jnp.swapaxes(nll, 0, 1)}


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


PRECISION = ("bfloat16_throughout",)     # the fault that is the unit
_last = {}      # the inputs' fingerprint, and (loss, logits) by faults


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    results of the last weights and batch are kept, by faults (the sound
    forward, the unit's, and the last other one): the benchmark's driver
    asks for the logits' error and then the harness for the loss."""
    ids = np.asarray(batch["ids"])
    tree = params["params_layers"]
    marks = [np.asarray(params["exit_gate_w"]), np.asarray(tree["ln1_scale"]),
             np.asarray(tree["wq"][0]), np.asarray(params["exit_gate_b"])]
    mark = (zlib.crc32(ids.tobytes()),
            tuple(zlib.crc32(a.tobytes()) for a in marks),
            json.dumps(model, sort_keys=True))
    if _last.get("mark") != mark:
        _last.clear()
        _last["mark"] = mark
    faults = tuple(faults)
    if faults not in _last:
        for other in [f for f in _last if f not in ("mark", (), PRECISION)]:
            del _last[other]
        total, logits, _ = forward(params, ids, model, faults,
                                   positions=witness_positions(ids.shape[1]))
        _last[faults] = (float(total), np.stack(
            [np.asarray(lg, np.float32) for lg in logits]))
        del total, logits
        gc.collect()        # the jitted blocks' constants go with them
    return _last[faults]


def loss(params, batch, model, faults=()):
    return _run(params, batch, model, faults)[0]


def logits(params, batch, model, faults=()):
    """The weighted-exit logits [B, P, V] at ``witness_positions`` of each
    sequence."""
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


def precision_unit(params, batch, model):
    """Each witnessed position's error of THIS FILE computed in bfloat16
    throughout against itself in float32, [B * P]: what one rounding to
    bfloat16 of every array and operation moves that position's logits by,
    at these weights."""
    return position_errors(logits(params, batch, model, PRECISION), params,
                           batch, model)


def group_errors(got, params, batch, model, faults=()):
    """``{"edge": median, "spread": median}``: the median over each group's
    positions, all sequences of the batch, of ``position_errors`` over
    ``precision_unit``."""
    each = (position_errors(got, params, batch, model, faults)
            / precision_unit(params, batch, model)).reshape(
        np.asarray(got).shape[0], -1)
    n_edge = len(witness_groups(np.asarray(batch["ids"]).shape[1])["edge"])
    parts = {"edge": each[:, :n_edge], "spread": each[:, n_edge:]}
    return {name: float(np.median(part)) if part.size else 0.0
            for name, part in parts.items()}


def logits_error(got, params, batch, model, faults=()):
    """The LARGER of the two groups' median, in units of one bfloat16
    rounding throughout: what LOGITS_TOLERANCE bounds."""
    return max(group_errors(got, params, batch, model, faults).values())
