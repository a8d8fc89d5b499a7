"""FLOPs the JOB requires to train a Kimi-Linear-class decoder on one token
(``"flops": "kimi_linear_train"`` in a configuration file): Kimi Delta
Attention layers (three projections through short filters, a delta rule on a
[d, d] state a head with a decay a channel, low-rank gates) beside latent
attention WITHOUT positions whose q and k heads (dn + dr) are wider than its
values, a leading dense layer, then a top-k mixture of gated experts of
which this chip holds a share beside a shared expert every token meets, an
untied head over the vocabulary's slice; and what one layer's delta rule
(``kda_chunk_roofline``), latent attention's pairs at the two widths
(``flash_qk192_v128_roofline``) and routed expert matmuls
(``moe_held16of256_roofline``) alone require.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding (a head of 192 carried in 256 lanes),
masked halves of a diagonal block, the chunked form's solve and rows beyond
the held pairs do not count, and an expert counts only for the tokens routed
to it.  One multiply-accumulate is two FLOPs, as in the chip's published
peak."""

CHUNK = 64      # the published kernels' chunk, which the requirement follows
GATE_RANK = 128     # the decay's and the output gate's rank (assumed)


def layer_counts(model):
    """(KDA layers, latent layers, dense layers, sparse layers) of the
    published layers 1 .. ``num_hidden_layers``."""
    n = model["num_hidden_layers"]
    linear = model["linear_attn_config"]
    kda = sum(1 for i in linear["kda_layers"] if i <= n)
    full = sum(1 for i in linear["full_attn_layers"] if i <= n)
    dense = min(model["first_k_dense_replace"], n)
    assert kda + full == n, (kda, full, n)
    return kda, full, dense, n - dense


def kda_width(model):
    linear = model["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def kda_projection_flops_per_token(model):
    """Forward, one KDA layer: ``wq`` / ``wk`` / ``wv`` / ``wo`` [E, P], both
    low-rank gates (E x R + R x P each) and ``w_beta`` [E, heads]."""
    E, P = model["hidden_size"], kda_width(model)
    return 2.0 * (4 * E * P + 2 * (E * GATE_RANK + GATE_RANK * P)
                  + E * model["linear_attn_config"]["num_heads"])


def delta_rule_flops_per_token(model):
    """Forward, one KDA layer, the recurrence a token and head: the decay of
    the state (d^2 multiplies), the read ``S'^T k`` (2 d^2), the rank-one
    write (2 d^2) and the read ``S^T q`` (2 d^2)."""
    linear = model["linear_attn_config"]
    return 7.0 * linear["num_heads"] * linear["head_dim"] ** 2


def kda_flops_per_token(model):
    """Forward, one KDA layer: projections, gates and the delta rule (the
    filters' 2 x taps a channel are under 0.1 % and left out)."""
    return kda_projection_flops_per_token(model) \
        + delta_rule_flops_per_token(model)


def head_dim(model):
    """The width of a latent query or key head."""
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def latent_chain_flops_per_token(model):
    """Forward, one latent layer: ``wq`` [E, H (dn + dr)], ``wkv_a`` [E, rkv
    + dr], ``wkv_b`` [rkv, H (dn + dv)], ``wo`` [H dv, E]."""
    E, H, rkv = (model["hidden_size"], model["num_attention_heads"],
                 model["kv_lora_rank"])
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return 2.0 * (E * H * (dn + dr) + E * (rkv + dr) + rkv * H * (dn + dv)
                  + H * dv * E)


def pair_flops_per_token(model, seq):
    """Forward, one latent layer: QK^T at the head's width (192) and PV at
    the value's (128) over the keys a query sees, mean over a causal
    sequence of ``seq``."""
    return (2.0 * model["num_attention_heads"]
            * (head_dim(model) + model["v_head_dim"]) * (seq + 1) / 2)


def held_experts_per_token(model):
    """Routed experts a token meets HERE at uniform routing: k times the
    share of the router's experts that this chip holds (8 x 16 / 256 =
    0.5)."""
    return (model["num_experts_per_token"] * model["num_experts"]
            / model["moe_router_width"])


def expert_flops_per_token(model):
    """Forward, one sparse layer: the held routed experts a token meets,
    each three E x F matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def shared_flops_per_token(model):
    return (6.0 * model["hidden_size"] * model["num_shared_experts"]
            * model["moe_intermediate_size"])


def parts_per_token(model, seq):
    """Forward FLOPs a token by part: the KDA mixers, latent attention, the
    feed-forward parts (dense, shared, held routed, routers) and the
    head."""
    kda, full, dense, sparse = layer_counts(model)
    E = model["hidden_size"]
    return {
        "kda": kda * kda_flops_per_token(model),
        "latent": full * (latent_chain_flops_per_token(model)
                          + pair_flops_per_token(model, seq)),
        "ffn": dense * 6.0 * E * model["intermediate_size"] + sparse * (
            2.0 * E * model["moe_router_width"]
            + expert_flops_per_token(model) + shared_flops_per_token(model)),
        "head": 2.0 * E * model["vocab_size"]}


def per_unit(model, dims):
    """Training = 3 x forward.  Embedding lookups, norms, filters, softmax,
    the sort and the optimizer are not counted."""
    return 3.0 * sum(parts_per_token(model, dims["S"]).values())


def delta_rule(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE KDA layer's delta rule in one training
    step over ``tokens`` tokens, counted the same whatever implements it:
    three passes of the recurrence's FLOPs (forward, and two for the
    backward's gradients of the state's reads and writes); q, k, v in and o
    out at the operands' width, the log-decays in float32 and the write
    strengths, read in the forward and again in the backward, which also
    reads do and writes the five gradients; a float32 state [d, d] a head
    and chunk of CHUNK tokens written by the forward and read by the
    backward (the recurrence cannot be run backwards through a decay that
    underflows).  The solve of the chunked form, the recomputed forward and
    whatever an implementation keeps besides are not in it."""
    linear = model["linear_attn_config"]
    H, d = linear["num_heads"], linear["head_dim"]
    row = H * d
    operands = tokens * (4 * row * itemsize + row * 4 + H * 4)
    grads = tokens * (4 * row * itemsize + row * 4 + H * 4)
    states = tokens / CHUNK * H * d * d * 4
    return {"flops": 3.0 * delta_rule_flops_per_token(model) * tokens,
            "bytes": 2.0 * operands + grads + 2.0 * states}


def flash_two_widths(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE latent layer's causal attention over
    ``batch`` sequences at the PUBLISHED widths, forward and backward apart:
    QK^T (and dQ, dK) at dn + dr, PV (and dV, dP) at dv, a pair the mask
    lets through and head; q and k (dq, dk) at dn + dr, v and o (do, dv) at
    dv, every head a key and a value of its own.  The lanes a head is padded
    to are not in it."""
    H, dq, dv = model["num_attention_heads"], head_dim(model), \
        model["v_head_dim"]
    pairs = batch * seq * (seq + 1) / 2 * H
    qk_tile = batch * seq * H * dq * itemsize
    v_tile = batch * seq * H * dv * itemsize
    return {"fwd": {"flops": 2.0 * pairs * (dq + dv),
                    "bytes": 2.0 * qk_tile + 2.0 * v_tile},
            "bwd": {"flops": 4.0 * pairs * (dq + dv),
                    "bytes": 4.0 * qk_tile + 4.0 * v_tile}}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE sparse layer's ROUTED expert matmuls in
    one training step over ``tokens`` tokens, for the rows that meet a held
    expert at uniform routing (T * 8 * 16 / 256 = 8,192, 512 an expert).
    Three passes (forward, the backward's dX, the backward's dW), each 6EF
    FLOPs a row; a pass reads (or, for dW, writes) every held expert's
    weights once, held * 3EF values, and reads and writes the rows once, E
    values each.  What passes between the gate/up and the down matmul need
    not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
