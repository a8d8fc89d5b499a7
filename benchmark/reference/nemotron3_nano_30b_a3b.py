"""Plain reference for ``nemotron3_nano_30b_a3b``: the training loss of a
Nemotron-H decoder (NVIDIA-Nemotron-3-Nano-30B-A3B ``config.json``, HF
``model_type`` ``nemotron_h``; the family: arXiv:2504.03624; the mixer is
Mamba-2, Dao and Gu, arXiv:2405.21060; the router's rule: DeepSeek-V3,
arXiv:2412.19437) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no chunks, no
cache, no remat, no scan over layers, no sharding, nothing imported from the
program: it takes the program's weights by their names in the parameter tree
and a batch (``ids``) and returns the loss.

The Mamba-2 scan is a ``lax.scan`` a TOKEN over the state ``[heads, P,
N]``: the recurrence as it is written down.  The program runs the chunked
DUAL form (four matrix products a chunk, the state in fast memory between
chunks); that the two agree is what the comparison shows.

Layer i is ONE pre-norm residual branch, ``x <- x + branch(rms(x, g))``
(``rms(x, g) = x * rsqrt(mean(x^2) + eps) * g``, eps ``norm_eps``; no bias
but the filter's), the branch by character ``first_layer + i`` of
``hybrid_override_pattern``:

``M``, the Mamba-2 mixer on u [S, E]; d = ``mamba_num_heads`` x
``mamba_head_dim`` (heads of P channels), G = ``n_groups``, N =
``ssm_state_size``, W = d + 2 G N, taps = ``conv_kernel``:

a. ``[xBC | z] = u @ w_in`` (W then d columns: the published ``in_proj``
   has ``z | xBC | dt``; the program keeps the ``xBC`` columns first, and
   the ``dt`` columns as a leaf of their own, ``w_dt``: a permutation of the
   published columns, nothing else); ``dt = softplus(u @ w_dt + b_dt)``
   [S, heads].
b. ``xBC_t <- silu(conv_b + sum_j conv_w[j] * xBC_{t - taps + 1 + j})``,
   zero before position 0; ``[x | B | C] = split(xBC)`` at d, d + G N: x
   [S, heads, P], B and C [S, G, N].
c. For head h of group g = h // (heads / G), ``A_h = -exp(a_log_h)``:
   ``H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t`` ([P, N], ``H_{-1} =
   0``), ``y_t = H_t C_t + d_skip_h x_t``.
d. ``y <- y * silu(z)`` (the gate BEFORE the norm), RMS-normed over each
   group's d / G channels, times ``gate_norm`` [d]; ``out = y @ w_out``.

``*``, attention: ``q = u @ wq`` (H heads of ``head_dim``), ``k = u @ wk``,
``v = u @ wv`` (``num_key_value_heads`` heads, each read by H / kv query
heads), causal softmax at scale ``head_dim^-1/2``, NO positions, ``wo``.

``E``, the sparse feed-forward part on m [S, E]: ``s = sigmoid(m @
router)`` over ALL ``router_experts`` (float32); the ``num_experts_per_tok``
experts with the largest ``s + router_bias``; weights ``s`` of the chosen
(WITHOUT the bias) over their sum (+ 1e-6), times ``routed_scaling_factor``;
``y = sum_e w_e down_e(relu(up_e m)^2)`` over the chosen experts THIS
SHARE HOLDS (``we_up`` [held, E, F], experts ``first_expert`` on: a plain
loop over them), plus the shared expert ``relu(m @ ws_up)^2 @ ws_down``.

After the last layer ``rms(., lnf_scale)`` and the UNTIED head ``lm_head``
[V, E]; next-token cross entropy over positions 0..S-2.

THE CUT.  ``num_hidden_layers`` layers from published layer ``first_layer``
(one tree a layer, ``params_layers/p<i>``, stacked [1, ...]);
``n_routed_experts`` experts held of the router's ``router_experts``, what
the absent ones would add left out here as in the program; ``vocab_size``
rows of the vocabulary.  Departures from the published description: the
cut; no document mask (the state runs across document boundaries); no
``rescale_prenorm_residual`` (a property of the published initialisation);
what the published config does not give and the configuration file lists
under ``assumed``.

What it holds on the device at once is kept small (the reference runs beside
8 GB of trainer state, and ``peak_hbm_gb`` counts its peak): one layer's
leaves go up one at a time, an expert at a time, attention runs
``QUERY_BLOCK`` rows at a time, the head ``VOCAB_CHUNK`` columns at a time.
Every call is waited for before the next is sent.  ``faults`` puts a fault
in, for ``benchmark/tools/nemotron_ref_sensitivity.py``.

THE WITNESS.  ``witness_positions`` has two named groups: ``edge``, the
first ``EDGE_TOKENS`` tokens after each multiple of ``EDGE_EVERY`` = 128
(every chunk edge the state crosses), and ``spread``, evenly over the
sequence.  ``logits_error`` is the LARGER of the two groups' third quartile
of each position's ``|program - reference| / |reference|`` over the
vocabulary.  What the seeded model cannot show, the backward, is held at the
operator (``tests/test_nemotron_h_reference.py`` on the CPU,
``scripts/nemotron_kernels_receipt.py`` on the chip).

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

# Relative, on the scalar loss (cross entropy 10.20 to 10.21 at seeded
# weights; ln 16,384 = 9.70).  The system computes in bf16 with float32
# accumulation and a float32 state; the per-token error is random and the
# loss averages it over 16,382 positions.  From the chip (PR 52; the runs and
# seeds are PERF.md section 6's): the program's relative error read 9.3e-7 to
# 1.7e-5 over eight runs at eight seeds, and the precision hardly moves this
# number: the same reference with every array and operation in bfloat16
# (fault ``bfloat16_throughout``) moves its loss by 1.6e-6 to 2.5e-5 over
# seven seeds.  So the loss carries the accepted decoder cells' limit, 3e-4,
# which leaves the first reading (1.7e-5) eighteen times of room, and the
# PRECISION is the witness's to catch (below).  Of the eight other faults
# the loss catches none (9.3e-8 to 1.4e-4: at seeded weights and uniform ids
# the loss sits near ln V whatever the block does).
TOLERANCE = 3e-4
# On the witness's statistic, the larger of the two groups' third quartile.
# From the chip (PR 52), of the program as the configuration seeds it (branch
# output projections times 52^-1/2, embedding rows N(0, 1), selection biases
# 0.01: ``assumed`` f, i): the sound program reads 5.82e-3 to 5.87e-3 at
# sixteen seeds (the two groups within 1 % of each other; the least
# position 5.3e-3, the median 5.7e-3: a floor of bf16 rounding of a stream
# that the token's own row leads, the same at EVERY position; the worst
# position 9.2e-2 to 1.2e-1, where rounding changes which expert is sixth of
# 128).  The precision below the configuration's, ``bfloat16_throughout``,
# reads 7.21e-3 to 8.13e-3 at seven seeds: not correct by this limit alone
# (program and reference round the stream the same way, so most of the floor
# is common to both, and what is left is what float32 norms, router, filter,
# step sizes and state add inside branches that enter the stream at 0.14).
# Then, each put into the reference against the program's logits: rotary
# positions on the one attention layer 8.13e-3 to 8.29e-3 (seven seeds), a
# route scale of 1 8.8e-2, B and C of the wrong group 9.4e-2, a whole-width
# norm for the grouped one 1.0e-1, the gate after the norm 1.3e-1, the state
# dropped at chunk edges 1.4e-1 (``edge``; ``spread`` 8.2e-2), ``relu`` for
# ``relu^2`` 1.9e-1 (seed 2147483659).  NOT seen by either limit, one: the
# bias inside the gathered weights 6.04e-3 against 5.84e-3 sound: biases of
# 0.01 move a weight by a part in a hundred, a token meets a HELD expert in
# half the sparse layers, and the branch enters the stream at 0.14; at
# biases of 0.1 the routing is not balanced (``assumed`` i).  What holds the
# bias to its place is ``tests/test_nemotron_h_reference.py`` on the CPU
# (float32 against float32, 1e-5).  6.5e-3 is the geometric middle of the
# largest sound reading and the least reading of the precision (5.87e-3,
# 7.21e-3): 10.8 % over the one, which is over ten times the spread of the
# sixteen, and 9.8 % under the other.  Both readings are properties of the
# architecture, the seeding and the precision.
LOGITS_TOLERANCE = 0.0065
EDGE_EVERY = 128            # the chunk edges the state crosses
EDGE_TOKENS = 8             # witnessed tokens after each edge
SPREAD_ROWS = 256           # witnessed positions spread over the sequence
QUERY_BLOCK = 256           # attention rows at a time
VOCAB_CHUNK = 4096          # head columns at a time
MAMBA_FAULTS = ("state_dropped_at_chunk_edges", "bc_of_wrong_group",
                "gate_after_norm", "whole_width_norm")
ATTENTION_FAULTS = ("rotary_on_attention",)
EXPERT_FAULTS = ("relu_for_relu2", "route_scale_one", "bias_in_weights")
FAULTS = MAMBA_FAULTS + ATTENTION_FAULTS + EXPERT_FAULTS \
    + ("bfloat16_throughout",)
MAMBA_LEAVES = ("w_in", "w_dt", "conv_w", "conv_b", "b_dt", "a_log",
                "d_skip", "gate_norm", "w_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _mamba2(u, p, heads, groups, n_state, eps, chunk, faults):
    """Steps a to d on one sequence u [S, E]."""
    s, dtype = u.shape[0], u.dtype
    d = p["w_out"].shape[0]
    width = d + 2 * groups * n_state
    packed = u @ p["w_in"]
    xbc, z = packed[:, :width], packed[:, width:]
    dt = jax.nn.softplus(u @ p["w_dt"] + p["b_dt"])             # [S, heads]
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * padded[j:j + s] for j in range(taps)))
    per = heads // groups
    x = xbc[:, :d].reshape(s, heads, d // heads)
    bmat = xbc[:, d:d + groups * n_state].reshape(s, groups, n_state)
    cmat = xbc[:, d + groups * n_state:].reshape(s, groups, n_state)
    if "bc_of_wrong_group" in faults:
        bmat, cmat = (jnp.roll(t, 1, axis=1) for t in (bmat, cmat))
    bmat, cmat = (jnp.repeat(t, per, axis=1) for t in (bmat, cmat))
    a = -jnp.exp(p["a_log"])                                    # [heads]
    drop = "state_dropped_at_chunk_edges" in faults

    def token(h, turn):
        x_t, dt_t, b_t, c_t, t = turn
        if drop:
            h = jnp.where(t % chunk == 0, jnp.zeros_like(h), h)
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, d // heads, n_state), dtype),
        (x, dt, bmat, cmat, jnp.arange(s)))
    y = (y + p["d_skip"][:, None] * x).reshape(s, d)
    gate = jax.nn.silu(z)
    if "gate_after_norm" not in faults:
        y = y * gate
    size = d if "whole_width_norm" in faults else d // groups
    y = _rms(y.reshape(s, d // size, size), 1.0, eps).reshape(s, d) \
        * p["gate_norm"]
    if "gate_after_norm" in faults:
        y = y * gate
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
    ``first`` on against the keys k, v [S, H, dh]."""
    rows, dh = q_rows.shape[0], q_rows.shape[-1]
    t = first + jnp.arange(rows)[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    scores = jnp.einsum("qhd,khd->hqk", q_rows, k).astype(jnp.float32) \
        / math.sqrt(dh)
    weights = jax.nn.softmax(jnp.where((j <= t)[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", weights.astype(v.dtype), v)


def _attention(u, p, n_heads, n_kv, faults):
    """The attention mixer on one sequence u [S, E]: query head i reads
    key/value head ``i // (n_heads / n_kv)``."""
    s = u.shape[0]
    q = (u @ p["wq"]).reshape(s, n_heads, -1)
    k = (u @ p["wk"]).reshape(s, n_kv, -1)
    v = (u @ p["wv"]).reshape(s, n_kv, -1)
    if "rotary_on_attention" in faults:
        q, k = _rotary(q), _rotary(k)
    k, v = (jnp.repeat(t, n_heads // n_kv, axis=1) for t in (k, v))
    rows = min(s, QUERY_BLOCK)
    assert s % rows == 0, (s, rows)
    o = jax.lax.map(lambda turn: _attention_rows(*turn, k, v),
                    (q.reshape((s // rows, rows) + q.shape[1:]),
                     jnp.arange(0, s, rows)))
    return o.reshape(s, -1) @ p["wo"]


def _route(m, router, bias, k, scale, faults):
    """The chosen experts [S, k] and their weights."""
    s = jax.nn.sigmoid(m.astype(jnp.float32) @ router.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(
        s + bias if "bias_in_weights" in faults else s, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    if "route_scale_one" not in faults:
        weights = weights * scale
    return chosen, weights.astype(m.dtype)


def _act(h, faults):
    h = jax.nn.relu(h)
    return h if "relu_for_relu2" in faults else h * h


def _expert(acc, m, chosen, weights, expert, w_up, w_down, faults):
    """``acc`` plus expert ``expert``'s part: its weight where a token chose
    it (else 0) times ``down(relu(up m)^2)``."""
    w = jnp.sum(jnp.where(chosen == expert, weights, 0), axis=-1)
    return acc + w[:, None] * (_act(m @ w_up, faults) @ w_down)


def _shared(m, w_up, w_down, faults):
    return _act(m @ w_up, faults) @ w_down


_mamba2_jit = jax.jit(_mamba2, static_argnums=(2, 3, 4, 5, 6, 7))
_attention_jit = jax.jit(_attention, static_argnums=(2, 3, 4))
_route_jit = jax.jit(_route, static_argnums=(3, 4, 5))
_expert_jit = jax.jit(_expert, static_argnums=(7,))
_shared_jit = jax.jit(_shared, static_argnums=(3,))
_rms_jit = jax.jit(_rms, static_argnums=2)


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


def layer_kinds(model):
    """``M``, ``E`` or ``*`` of each layer held: the published pattern's
    characters from ``first_layer`` on."""
    first, n = int(model.get("first_layer", 0)), int(
        model["num_hidden_layers"])
    kinds = model["hybrid_override_pattern"][first:first + n]
    assert len(kinds) == n and set(kinds) <= set("ME*"), kinds
    return kinds


def expert_part(ms, tree, bias, model, faults, cast):
    """The ``E`` branch's routed part on the normed sequences ``ms``: the
    held experts' share of the layer's result, a plain loop over them."""
    k = int(model["num_experts_per_tok"])
    scale = float(model["routed_scaling_factor"])
    first = int(model.get("first_expert", 0))
    router = cast(tree["router"][0])
    assert router.shape[-1] == int(model.get(
        "router_experts", model["n_routed_experts"]))
    routes = [_done(_route_jit(m, router, bias, k, scale, faults))
              for m in ms]
    ys = [jnp.zeros_like(m) for m in ms]
    for e in range(tree["we_up"].shape[1]):
        w_up, w_down = cast(tree["we_up"][0, e]), cast(tree["we_down"][0, e])
        ys = [_done(_expert_jit(y, m, chosen, weights, first + e, w_up,
                                w_down, faults))
              for y, m, (chosen, weights) in zip(ys, ms, routes)]
        del w_up, w_down
    return ys


def shared_part(ms, tree, faults, cast):
    """The shared expert on the normed sequences ``ms``."""
    w_up, w_down = cast(tree["ws_up"][0]), cast(tree["ws_down"][0])
    return [_done(_shared_jit(m, w_up, w_down, faults)) for m in ms]


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

    n_heads, n_kv = (int(model[k]) for k in ("num_attention_heads",
                                             "num_key_value_heads"))
    heads, groups, n_state = (int(model[k]) for k in (
        "mamba_num_heads", "n_groups", "ssm_state_size"))
    assert not model["tie_word_embeddings"] and int(model["n_group"]) \
        == int(model["topk_group"]) == 1 and model["norm_topk_prob"]
    eps = float(model["norm_eps"])
    faults = tuple(faults)
    mamba_faults = tuple(f for f in faults if f in MAMBA_FAULTS)
    attention_faults = tuple(f for f in faults if f in ATTENTION_FAULTS)
    expert_faults = tuple(f for f in faults if f in EXPERT_FAULTS)
    ids = np.asarray(ids)
    b, s = ids.shape
    chunk = min(int(model["chunk_size"]), s)
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][ids[j]]) for j in range(b)]
        sparse = 0
        for i, kind in enumerate(layer_kinds(model)):
            gc.collect()
            tree = params["params_layers"]["p%d" % i]

            def leaf(key):
                return cast(tree[key][0])

            g = leaf("ln2_scale" if kind == "E" else "ln1_scale")
            us = [_done(_rms_jit(x, g, eps)) for x in xs]
            if kind == "*":
                p = {key: leaf(key) for key in ATTENTION_LEAVES}
                assert p["wq"].shape[-1] == n_heads * int(model["head_dim"])
                ops = [_done(_attention_jit(u, p, n_heads, n_kv,
                                            attention_faults)) for u in us]
            elif kind == "M":
                p = {key: leaf(key) for key in MAMBA_LEAVES}
                assert p["w_out"].shape[0] == heads * int(
                    model["mamba_head_dim"])
                ops = [_done(_mamba2_jit(u, p, heads, groups, n_state, eps,
                                         chunk, mamba_faults)) for u in us]
            else:
                p = None
                bias = jnp.asarray(params["router_bias"][sparse],
                                   jnp.float32)
                sparse += 1
                routed = expert_part(us, tree, bias, model, expert_faults,
                                     cast)
                shared = shared_part(us, tree, expert_faults, cast)
                ops = [_done(r + sh) for r, sh in zip(routed, shared)]
                del routed, shared
            del p, us
            xs = [_done(x + op) for x, op in zip(xs, ops)]
            del ops, g
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
    marks = [np.asarray(params["lnf_scale"]),
             np.asarray(params["router_bias"]),
             np.asarray(params["lm_head"][:8])]
    marks += [np.asarray(tree[name]) for tree in
              params["params_layers"].values()
              for name in ("b_dt", "a_log", "router") if name in tree]
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
