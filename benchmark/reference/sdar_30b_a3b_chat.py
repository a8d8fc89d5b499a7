"""Plain reference for ``sdar_30b_a3b_chat``: the block-diffusion training
loss of SDAR-30B-A3B-Chat (JetLM ``config.json``, ``model_type``
``sdar_moe``; the training form BD3-LM's, arXiv:2503.09573, which SDAR,
arXiv:2510.06303, adapts an autoregressive model to) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No
kernels, no step table, no scan over layers, no sharding, no sort, no grouped
matmul, nothing imported from the program: it takes the program's weights by
their names in the parameter tree and a batch (``ids`` [B, S], ``t`` [B, S /
Bd], ``u`` [B, S]) and returns the loss.

One sequence ``ids`` [S], block length Bd (``model["block_length"]``), ``b(r)
= (r mod S) // Bd``:

0. Noising: ``m_i = u_i < t_{i // Bd}``; ``x_t = where(m, MASK, ids)``
   (``model["mask_token_id"]``); the rows are ``z = [x_t ; ids]`` [2 S] and
   ``x_0 = tok_emb[z]``.
1. Layer l (no bias anywhere; ``rms(x, g) = x * rsqrt(mean(x^2) + eps) *
   g``; ``h = rms(x, ln1_scale)``): ``q = h wq`` [2 S, H, dh], ``k = h wk``,
   ``v = h wv`` [2 S, Hkv, dh]; q and k RMS-normed a head by ONE weight [dh]
   each, then rotated (rotate-half: pair i of a head is (i, i + dh / 2), its
   angle ``pos * theta^(-i / (dh / 2))``) by position ``r mod S``: both
   copies carry the sequence's positions.
2. Query head n reads key/value head ``n // (H / Hkv)``: ``a[n, r, .] =
   softmax over the ALLOWED c of q[n, r] . k[n // 8, c] / sqrt(dh)``, allowed
   iff (r < S and c < S and b(r) == b(c)) or (r < S and c >= S and b(c) <
   b(r)) or (r >= S and c >= S and b(c) <= b(r)): a noised query its own
   noised block (both directions) and the EARLIER clean blocks, a clean
   query its own and the earlier clean blocks.  The dense mask is built from
   these three conditions ``QUERY_BLOCK`` query rows at a time.  ``o = a
   v``; ``h1 = x + concat(o) wo``.
3. ``m = rms(h1, ln2_scale)``; ``p = softmax(m router)`` over all n; the k
   largest; weights ``p_e`` over the chosen's sum (``norm_topk_prob``); ``y =
   sum_e w_e down_e(silu(gate_e m) * up_e m)``; ``out = h1 + y``.
4. ``logits_i = rms(x_L[i], lnf_scale) lm_head^T`` for the NOISED rows i < S
   alone; ``loss = (1 / S) sum_i m_i / t_{i // Bd} CE(logits_i, ids_i)``: no
   shift, the linear schedule's weight, the divisor the sequence's length;
   mean over the batch.  The clean rows feed keys and values and no loss.

THE SHARE.  As ``keye_vl2_30b_a3b.py``: the weights may hold ``num_experts``
experts of the router's ``router_width`` from ``first_expert_held`` and
``vocab_size`` rows of the vocabulary; the router ranks all its experts,
every HELD expert is evaluated on every row and combined with the top-k
weights at its column (zero elsewhere), what the absent experts would add is
left out and the partial result goes on.

ASSUMED, the config having no key for it (``benchmark/configs/
sdar_30b_a3b_chat.json`` gives each its source): the block length, the noise
(the batch's), the per-head q/k norm, no auxiliary router loss, the row that
stands for the mask token.

What it holds on the device at once is kept small (it runs beside 7 GB of
trainer state): a layer's attention weights go up alone, attention runs
``QUERY_BLOCK`` rows at a time (all 32 heads' [32, 128, 16384] float32 score
tile is 0.27 GB), the experts ``EXPERT_GROUP`` at a time, the head
``VOCAB_CHUNK`` columns at a time.  Every call is waited for before the
next is sent.  ``faults`` puts a fault in, for
``benchmark/tools/sdar_ref_sensitivity.py``.

LOGITS_TOLERANCE is what decides on the chip.  The cell's driver
(``benchmark/drivers/train_scan_witnessed_batch.py``) reads the program's
logits at ``witness_positions`` through the trainer's ``logits_at`` before
the warm-up: 272 rows of the NOISED copy (256 spread, and whole blocks where
the rule has an edge: block 0, which has no clean key at all, the blocks on
both sides of the 512-row tile edge, the last block), of which the batch's
noise masks about 195; ``logits_error`` is the MEDIAN over the MASKED ones of
each one's ``|program - reference| / |reference|`` over the vocabulary.  Why
the masked rows and why their median: the mask token's embedding row is
seeded small (the configuration's ``assumed`` f: at a token's size every
masked row met the same eight experts and a share's time followed the seed),
so a masked row's stream is what the layers' branches brought it, and it
carries bf16's rounding of whole branches: 2 to 6 % of float32's logits, and
where rounding flips a near-tied eighth expert (a fifth of the masked rows,
over six layers) 20 % to over 100 %, so their third quartile swung 0.06 to
0.18 with the seed, while an UNMASKED row, whose own exact embedding row
dominates its stream, stands 6e-3 from float32 under every fault of the
MASK (it reads 6.1e-3 sound and 6.6e-3 to 7.4e-3 under the three faults
that touch a noised block alone: it cannot see them) and is NEARER the
bfloat16 reference (5.0e-3 to 5.5e-3) than the float32 one.  The masked
rows are where the loss is and where the rule decides what is read.

Set from the chip (PR 71, my chip runs; PERF.md section 6 has the table):
the sound program read 0.0484 to 0.0612 over seven runs at seven seeds
(0.0484, 0.0493, 0.0494, 0.0533, 0.0550, 0.0583, 0.0612); each fault put into
the reference, against the program's logits, at seeds 1987654321 /
3000000019: a causal mask inside the noised block 0.196 / 0.197 (the least),
the clean keys of a noised query's own block let through 0.340 / 0.346, clean
queries reading noised keys 0.634 / 0.653, positions 0 .. 2 S - 1 1.39, no
q/k norm 1.36 / 1.37, key/value head ``n mod 4`` 1.40 / 1.39, nothing masked
1.41; the control, this reference in bfloat16 throughout, 0.212 / 0.215.
**0.11** stands 1.8 times over the largest sound reading and 1.8 times under
the least fault, 1.9 under the control.  The final tree at seven FRESH seeds
under that limit: 0.0536 to 0.0609, every run ``correct``.

TOLERANCE is relative, on the scalar loss (11.0 to 11.2 at seeded weights:
ln 37,984 = 10.54 and half a nat of seeded logits, the weights ``m / t``
averaging 1).  The loss is a mean over the 5,700 MASKED rows alone, the rows
whose logits are loose, so it is loose too: the program's relative error
read 2.0e-5 to 7.5e-4 over six seeds (2.0e-5, 8.4e-5, 1.06e-4, 1.55e-4,
1.80e-4 and one of 7.5e-4), where the other decoder cells read 1e-5.  The
bfloat16 control moves it 2.2e-3 / 3.3e-3 and is refused by the witness with
room to spare, so this limit is the check of GROSS faults only (nothing
masked 2.0e-3 to 4.6e-3, key/value head 2.5e-3 to 2.9e-3): **2e-3**, 2.7
times over the largest sound reading, under the control at both seeds (by
10 % and 65 %: the witness is what refuses it, not this).  Seven fresh
seeds of the final tree: 1.3e-5 to 4.4e-4.
"""

import gc
import json
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

TOLERANCE = 2e-3
LOGITS_TOLERANCE = 0.11
WITNESS_ROWS = 256          # positions spread over the noised rows
TILE = 512                  # the program's tile height: an edge of the rule
EXPERT_GROUP = 2            # experts on the device at a time
QUERY_BLOCK = 128           # attention rows at a time
VOCAB_CHUNK = 2048          # head columns at a time
FAULTS = ("causal_inside_a_noised_block", "noised_reads_its_own_clean_block",
          "clean_reads_noised", "positions_not_repeated", "no_qk_norm",
          "wrong_kv_head", "nothing_masked", "bfloat16_throughout")
ATTENTION_LEAVES = ("ln1_scale", "wq", "wk", "wv", "wo", "q_norm", "k_norm")


def _done(tree):
    """Wait for the arrays of ``tree`` (tracers, under ``jax.grad``, pass)."""
    return jax.block_until_ready(tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, pos, theta):
    """x [R, H, dh]; pair i of a head is (x[i], x[i + dh/2]), turned by
    ``pos[r] * theta^(-2i / dh)``."""
    dh = x.shape[-1]
    inv_freq = jnp.asarray(
        1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh),
        jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq           # [R, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :].astype(x.dtype)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * cos + rot * sin


def allowed(rows, s, bd, faults=()):
    """[len(rows), 2 s] bool: which keys c of ``[x_t ; x_0]`` the queries
    ``rows`` may read, from the rule's three conditions."""
    r, c = rows[:, None], jnp.arange(2 * s)[None, :]
    br, bc = (r % s) // bd, (c % s) // bd
    noised_r, noised_c = r < s, c < s
    own = br == bc
    if "causal_inside_a_noised_block" in faults:
        own = own & (c <= r)
    earlier = bc <= br if "noised_reads_its_own_clean_block" in faults \
        else bc < br
    clean = ~noised_r & ~noised_c & (bc <= br)
    if "clean_reads_noised" in faults:
        clean = ~noised_r & (bc <= br)
    return (noised_r & noised_c & own) | (noised_r & ~noised_c & earlier) \
        | clean


def _project(x, p, shape, faults):
    """The rows' q [R, H, dh] and k, v [R, Hkv, dh], normed and rotated."""
    n_heads, n_kv, eps, theta, _ = shape
    rows = x.shape[0]
    h = _rms(x, p["ln1_scale"], eps)
    q = (h @ p["wq"]).reshape(rows, n_heads, -1)
    k = (h @ p["wk"]).reshape(rows, n_kv, -1)
    v = (h @ p["wv"]).reshape(rows, n_kv, -1)
    if "no_qk_norm" not in faults:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    pos = jnp.arange(rows)
    if "positions_not_repeated" not in faults:
        pos = pos % (rows // 2)
    return _rotary(q, pos, theta), _rotary(k, pos, theta), v


def _attend(q, k, v, bd, faults):
    """Step 2 of one sequence's rows: ``o`` [R, H, dh], ``QUERY_BLOCK`` rows
    at a time under the dense mask of those rows."""
    rows, n_heads, dh = q.shape
    n_kv = k.shape[1]
    group = n_heads // n_kv
    at_a_time = min(rows, QUERY_BLOCK)
    assert rows % at_a_time == 0, (rows, at_a_time)
    kv_of = np.arange(n_heads) % n_kv if "wrong_kv_head" in faults \
        else np.arange(n_heads) // group
    k_heads, v_heads = k[:, kv_of], v[:, kv_of]                 # [R, H, dh]

    def block(first):
        at = first + jnp.arange(at_a_time)
        scores = jnp.einsum("qhd,khd->hqk", q[at], k_heads) / math.sqrt(dh)
        mask = allowed(at, rows // 2, bd, faults)
        a = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, v_heads)

    return jax.lax.map(block, jnp.arange(0, rows, at_a_time)).reshape(q.shape)


def _route(h1, ln2_scale, router, k, eps):
    """``m = rms(h1, ln2_scale)`` and ``weight`` [R, n]: each row's top-k
    probabilities over their sum, at their experts' columns."""
    m = _rms(h1, ln2_scale, eps)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(m @ router, axis=-1), k)
    chosen = jax.nn.one_hot(top_e, router.shape[-1], dtype=m.dtype)
    return m, jnp.sum(chosen * (top_p / jnp.sum(
        top_p, axis=-1, keepdims=True))[..., None], axis=1)


def _experts(acc, m, w_gate_up, w_down, weight):
    """``acc`` plus a group of experts on EVERY row of ``m``, each times its
    column of ``weight`` [R, g]: w_gate_up [g, E, 2F], w_down [g, F, E]."""
    f = w_down.shape[1]
    gu = jnp.einsum("se,gef->gsf", m, w_gate_up)
    out = jnp.einsum("gsf,gfe->gse", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                     w_down)
    return acc + jnp.sum(out * weight.T[..., None], axis=0)


_project_jit = jax.jit(_project, static_argnums=(2, 3))
_attend_jit = jax.jit(_attend, static_argnums=(3, 4))
_route_jit = jax.jit(_route, static_argnums=(3, 4))
_experts_jit = jax.jit(_experts)


def moe_part(h1, ln2_scale, router, w_gate_up, w_down, first, k, eps):
    """Step 3's ``y`` for the experts [first, first + held) that the weights
    hold, on one sequence's rows; the held experts ``EXPERT_GROUP`` at a
    time."""
    m, weight = _done(_route_jit(h1, ln2_scale, router, k, eps))
    y = jnp.zeros_like(h1)
    for at in range(0, w_gate_up.shape[0], EXPERT_GROUP):
        y = _done(_experts_jit(
            y, m, w_gate_up[at:at + EXPERT_GROUP],
            w_down[at:at + EXPERT_GROUP],
            weight[:, first + at:first + at + EXPERT_GROUP]))
    return y


def _head_chunk(x, g, w, labels, first, eps, keep):
    """Columns [first, first + C) of the head on one sequence's noised rows:
    their logsumexp [S], the label's logit where the label is among them
    (else 0) and, where ``keep``, the logits [S, C]."""
    logits = _rms(x, g, eps) @ w.T
    at = labels - first
    inside = (at >= 0) & (at < w.shape[0])
    picked = jnp.take_along_axis(
        logits, jnp.clip(at, 0, w.shape[0] - 1)[:, None], axis=-1)[:, 0]
    return (jax.scipy.special.logsumexp(logits, axis=-1),
            jnp.where(inside, picked, 0.0), logits if keep else None)


_head_chunk_jit = jax.jit(_head_chunk, static_argnums=(5, 6))


def _shape(model):
    return (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), float(model["rms_norm_eps"]),
            float(model["rope_theta"]), int(model["block_length"]))


def attention_part(x, p, model, faults=()):
    """Steps 1 and 2 of one layer on one sequence's rows x [2 S, E], ``p``
    that layer's attention leaves: ``o wo`` [2 S, E]."""
    shape = _shape(model)
    q, k, v = _done(_project_jit(x, p, shape, tuple(faults)))
    o = _done(_attend_jit(q, k, v, shape[-1], tuple(faults)))
    return o.reshape(x.shape[0], -1) @ p["wo"]


def noise_of(batch, model, faults=()):
    """``(rows [B, 2 S], masked [B, S] bool, level [B, S])`` of a batch:
    step 0."""
    ids = np.asarray(batch["ids"])
    level = np.repeat(np.asarray(batch["t"], np.float32),
                      int(model["block_length"]), axis=-1)
    masked = np.asarray(batch["u"], np.float32) < level
    noised = ids if "nothing_masked" in faults else np.where(
        masked, np.asarray(model["mask_token_id"], ids.dtype), ids)
    return np.concatenate([noised, ids], axis=-1), masked, level


def forward(params, batch, model, faults=(), keep_logits=True,
            positions=None):
    """``(loss, logits)``: the denoising loss as a scalar (differentiable in
    ``params``) and each sequence's NOISED rows' logits [S, V], or [P, V] at
    ``positions`` [P] alone (none kept where ``keep_logits`` is off)."""
    for fault in faults:
        assert fault in FAULTS, fault
    # the fault that is a precision: every array and every operation in
    # bfloat16 at the device's default matmul precision
    low = "bfloat16_throughout" in faults
    dtype = jnp.bfloat16 if low else jnp.float32

    def cast(a):
        return _done(jnp.asarray(a).astype(dtype))

    eps = float(model["rms_norm_eps"])
    k = int(model["num_experts_per_tok"])
    first = int(model.get("first_expert_held", 0))
    rows, masked, level = noise_of(batch, model, faults)
    ids = np.asarray(batch["ids"])
    b, s = ids.shape
    n_layers = int(model["num_hidden_layers"])
    with jax.default_matmul_precision("default" if low else "highest"):
        # rows gathered where the table is: a host table stays on the host
        xs = [cast(params["tok_emb"][rows[j]]) for j in range(b)]
        layers = params["params_layers"]
        for i in range(n_layers):
            gc.collect()
            p = {name: cast(layers[name][i]) for name in ATTENTION_LEAVES}
            hs = [_done(xs[j] + attention_part(xs[j], p, model, faults))
                  for j in range(b)]
            del p
            router = cast(layers["router"][i])
            ln2 = cast(layers["ln2_scale"][i])
            w_gate_up = cast(layers["we_gate_up"][i])
            w_down = cast(layers["we_down"][i])
            xs = [_done(hs[j] + moe_part(hs[j], ln2, router, w_gate_up,
                                         w_down, first, k, eps))
                  for j in range(b)]
            del w_gate_up, w_down, hs, ln2, router
        xs = [x[:s] for x in xs]            # the noised rows alone
        table = params["lm_head"]
        g = cast(params["lnf_scale"])
        labels = [jnp.asarray(ids[j]) for j in range(b)]
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
        weight = jnp.asarray(np.where(masked, 1.0 / level, 0.0), jnp.float32)
        total = sum(jnp.sum((lse[j] - picked[j]).astype(jnp.float32)
                            * weight[j]) for j in range(b))
    return total / (b * s), [jnp.concatenate(lg, axis=-1)
                             for lg in logits if lg]


def witness_groups(s, bd=4):
    """The witnessed positions (rows of the NOISED copy) by group:
    ``spread``, WITNESS_ROWS of them evenly over the sequence from half a
    stride in; and whole blocks where the rule has an edge: ``block_0`` (no
    clean key at all), ``tile_edge`` (the blocks on both sides of a tile
    edge: row TILE, or a quarter of a shorter sequence) and ``last``."""
    stride = max(s // WITNESS_ROWS, 1)
    edge = TILE if s > TILE else max(s // 4 // bd * bd, bd)
    return {"spread": np.arange(stride // 2, s, stride),
            "block_0": np.arange(0, bd),
            "tile_edge": np.arange(edge - bd, edge + bd),
            "last": np.arange(s - bd, s)}


def witness_positions(s, bd=4):
    """Every witnessed position once, ascending."""
    return np.unique(np.concatenate(list(witness_groups(s, bd).values())))


_last = {}      # the inputs' fingerprint and the results of the last run


def _run(params, batch, model, faults):
    """``(loss, logits [B, P, V] at witness_positions)`` as numpy.  The
    last call's results are kept: the benchmark's driver asks for the logits
    and then the harness for the loss, of the same weights and batch."""
    ids = np.asarray(batch["ids"])
    router = np.asarray(params["params_layers"]["router"])
    mark = (zlib.crc32(ids.tobytes()),
            zlib.crc32(np.asarray(batch["u"]).tobytes()),
            zlib.crc32(np.asarray(batch["t"]).tobytes()),
            zlib.crc32(router.tobytes()),
            json.dumps(model, sort_keys=True), tuple(faults))
    if _last.get("mark") != mark:
        total, logits = forward(
            params, batch, model, faults, positions=witness_positions(
                ids.shape[1], int(model["block_length"])))
        _last.update(mark=mark, loss=float(total),
                     logits=np.stack([np.asarray(lg) for lg in logits]))
        del total, logits
        gc.collect()        # the jitted blocks' constants go with them
    return _last["loss"], _last["logits"]


def loss(params, batch, model, faults=()):
    return _run(params, batch, model, faults)[0]


def logits(params, batch, model, faults=()):
    """The noised rows' logits [B, P, V] at ``witness_positions`` of each
    sequence."""
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
    bd = int(model["block_length"])
    each = position_errors(got, params, batch, model, faults).reshape(
        len(got), -1)
    at = witness_positions(s, bd)
    masked = witnessed_mask(batch, model)
    out = {name: float(np.quantile(each[:, np.isin(at, rows)], 0.75))
           for name, rows in witness_groups(s, bd).items()}
    out["unmasked"] = float(np.quantile(each[~masked], 0.75))
    out["masked"] = float(np.quantile(each[masked], 0.75))
    return out


def witnessed_mask(batch, model):
    """[B, P] bool: which of the witnessed rows the batch's noise masks."""
    s = np.asarray(batch["ids"]).shape[1]
    return noise_of(batch, model)[1][
        :, witness_positions(s, int(model["block_length"]))]


def logits_error(got, params, batch, model, faults=()):
    """The MEDIAN of ``position_errors`` over the MASKED witnessed rows: what
    LOGITS_TOLERANCE bounds (the docstring's last part says why those rows
    and why their median)."""
    each = position_errors(got, params, batch, model, faults)
    return float(np.median(each[witnessed_mask(batch, model).reshape(-1)]))
