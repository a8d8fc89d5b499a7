"""FLOPs the JOB requires to train a Solar-Open2-class decoder on one token
(``"flops": "solar_open2_train"`` in a configuration file): Kimi Delta
Attention layers (three projections through short filters, a delta rule on a
[d, d] state a head with a decay a channel and a write strength in (0, 2),
low-rank gates) three to one beside gated grouped-query softmax attention
WITHOUT positions, every layer over a top-k mixture of gated experts of
which this chip holds a share beside a shared expert every token meets, an
untied head over the vocabulary's slice; and what one layer's delta rule
(``kda64_chunk_roofline``: ``kimi_linear_train.delta_rule``, the same count
at this model's 64 heads; a strength past 1 costs what one under it does, so
the KDA layer's counts are Kimi-Linear's, by the same keys of
``linear_attn_config``), the grouped-query layer's causal pairs
(``flash_gqa64q8_roofline``) and routed expert matmuls
(``moe_held10of320_roofline``) alone require.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, masked halves of a diagonal block, the
chunked form's solve and rows beyond the held pairs do not count, and an
expert counts only for the tokens routed to it.  One multiply-accumulate is
two FLOPs, as in the chip's published peak."""

from .kimi_linear_train import (  # noqa: F401  (the KDA layer's counts)
    CHUNK,
    GATE_RANK,
    delta_rule,
    delta_rule_flops_per_token,
    kda_flops_per_token,
    kda_projection_flops_per_token,
)


def layer_counts(model):
    """(KDA layers, grouped-query layers) of the published layers 0 ..
    ``num_hidden_layers`` - 1; every one is sparse."""
    n = model["num_hidden_layers"]
    assert model["first_k_dense_replace"] == 0
    full = sum(1 for i in model["gqa_layers"] if i < n)
    return n - full, full


def gqa_projection_flops_per_token(model):
    """Forward, the grouped-query layer: q, the gate and the output
    projection at the query heads' width, k and v at the key/value
    heads'."""
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    return 2.0 * model["hidden_size"] * (3 * q + 2 * kv)


def pair_flops_per_token(model, seq):
    """Forward, the grouped-query layer: QK^T and PV over the keys a query
    sees, mean over a causal sequence of ``seq``."""
    return (4.0 * model["num_attention_heads"] * model["head_dim"]
            * (seq + 1) / 2)


def held_experts_per_token(model):
    """Routed experts a token meets HERE at uniform routing: k times the
    share of the router's experts that this chip holds (8 x 10 / 320 =
    0.25)."""
    return (model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["router_width"])


def expert_flops_per_token(model):
    """Forward, one layer: the held routed experts a token meets, each
    three E x F matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def shared_flops_per_token(model):
    return (6.0 * model["hidden_size"] * model["n_shared_experts"]
            * model["moe_intermediate_size"])


def parts_per_token(model, seq):
    """Forward FLOPs a token by part: the KDA mixers, the grouped-query
    mixer, the feed-forward parts (shared, held routed, routers) and the
    head."""
    kda, full = layer_counts(model)
    E = model["hidden_size"]
    return {
        "kda": kda * kda_flops_per_token(model),
        "gqa": full * (gqa_projection_flops_per_token(model)
                       + pair_flops_per_token(model, seq)),
        "ffn": (kda + full) * (
            2.0 * E * model["router_width"]
            + expert_flops_per_token(model) + shared_flops_per_token(model)),
        "head": 2.0 * E * model["vocab_size"]}


def per_unit(model, dims):
    """Training = 3 x forward.  Embedding lookups, norms, filters, the
    gate's sigmoid and product, softmax, the sort and the optimizer are not
    counted."""
    return 3.0 * sum(parts_per_token(model, dims["S"]).values())


def flash_gqa(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of the grouped-query layer's causal attention
    over ``batch`` sequences, forward and backward apart: QK^T and PV (and
    the backward's dQ, dK, dV, dP) a pair the mask lets through and QUERY
    head; q and o (dq, do) at the query heads, k and v (dk, dv) at the
    key/value heads: a K or V repeated in HBM would not be counted, and
    would show."""
    H, KV, d = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    pairs = batch * seq * (seq + 1) / 2 * H
    q_tile = batch * seq * H * d * itemsize
    kv_tile = batch * seq * KV * d * itemsize
    return {"fwd": {"flops": 4.0 * pairs * d,
                    "bytes": 2.0 * q_tile + 2.0 * kv_tile},
            "bwd": {"flops": 8.0 * pairs * d,
                    "bytes": 4.0 * q_tile + 4.0 * kv_tile}}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's ROUTED expert matmuls in one
    training step over ``tokens`` tokens, for the rows that meet a held
    expert at uniform routing (T * 8 * 10 / 320 = 1,024, 102 an expert).
    Three passes (forward, the backward's dX, the backward's dW), each 6EF
    FLOPs a row; a pass reads (or, for dW, writes) every held expert's
    weights once, held * 3EF values, and reads and writes the rows once, E
    values each.  What passes between the gate/up and the down matmul need
    not touch HBM and is not counted.  At 102 rows an expert the weights'
    bytes bind, not the MXU."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["n_routed_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
