"""FLOPs the JOB requires to train a Kanana-2-class decoder on one token
(``"flops": "kanana2_train"`` in a configuration file): latent attention at
full-rank queries in every layer (q and k heads of dn + dr against values of
dv, keys and values off one latent), a leading dense layer, then a top-k
mixture of gated experts beside shared experts every token meets, an untied
head over the whole vocabulary; and what one layer's latent attention
(``ep_mla_flash_roofline``), routed expert matmuls
(``moe_ep32of128_roofline``) and expert-parallel exchange
(``ep_all_to_all_roofline``) alone require.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding (a head of 192 carried in 256 lanes; the
exchange's unused slots), masked halves of a diagonal block do not count,
and an expert counts only for the tokens routed to it.  EVERY pair a token
is routed to counts: the experts are all on the host, whichever chip
computes them.  One multiply-accumulate is two FLOPs, as in the chip's
published peak."""


def head_dim(model):
    """The width of a latent query or key head."""
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def chain_flops_per_token(model):
    """Forward, one layer: ``wq`` [E, H (dn + dr)], ``wkv_a`` [E, rkv + dr],
    ``wkv_b`` [rkv, H (dn + dv)], ``wo`` [H dv, E]."""
    E, H, rkv = (model["hidden_size"], model["num_attention_heads"],
                 model["kv_lora_rank"])
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return 2.0 * (E * H * (dn + dr) + E * (rkv + dr) + rkv * H * (dn + dv)
                  + H * dv * E)


def pair_flops_per_token(model, seq):
    """Forward, one layer: QK^T at the head's width (192) and PV at the
    value's (128) over the keys a query sees, mean over a causal sequence of
    ``seq``."""
    return (2.0 * model["num_attention_heads"]
            * (head_dim(model) + model["v_head_dim"]) * (seq + 1) / 2)


def expert_flops_per_token(model):
    """Forward, one sparse layer: the k routed experts a token meets, each
    three E x F matmuls (gate, up, down)."""
    return (model["num_experts_per_tok"] * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def shared_flops_per_token(model):
    return (6.0 * model["hidden_size"] * model["n_shared_experts"]
            * model["moe_intermediate_size"])


def parts_per_token(model, seq):
    """Forward FLOPs a token by part: latent attention, the dense FFN, the
    routed experts, the shared ones, the routers and the head."""
    n = model["num_hidden_layers"]
    dense = min(model["first_k_dense_replace"], n)
    sparse = n - dense
    E = model["hidden_size"]
    return {
        "latent": n * (chain_flops_per_token(model)
                       + pair_flops_per_token(model, seq)),
        "dense": dense * 6.0 * E * model["intermediate_size"],
        "routed": sparse * expert_flops_per_token(model),
        "shared": sparse * shared_flops_per_token(model),
        "router": sparse * 2.0 * E * model["n_routed_experts"],
        "head": 2.0 * E * model["vocab_size"]}


def per_unit(model, dims):
    """Training = 3 x forward.  Embedding lookups, norms, rotation, softmax,
    the sort, the exchange and the optimizer are not counted."""
    return 3.0 * sum(parts_per_token(model, dims["S"]).values())


def latent_attention(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's causal attention over ``batch``
    sequences at the PUBLISHED widths, forward and backward apart: QK^T (and
    dQ, dK) at dn + dr, PV (and dV, dP) at dv, a pair the mask lets through
    and head; q and k (dq, dk) at dn + dr, v and o (do, dv) at dv, every
    head a key and a value of its own.  The lanes a head is padded to are
    not in it."""
    H, dq, dv = model["num_attention_heads"], head_dim(model), \
        model["v_head_dim"]
    pairs = batch * seq * (seq + 1) / 2 * H
    qk_tile = batch * seq * H * dq * itemsize
    v_tile = batch * seq * H * dv * itemsize
    return {"fwd": {"flops": 2.0 * pairs * (dq + dv),
                    "bytes": 2.0 * qk_tile + 2.0 * v_tile},
            "bwd": {"flops": 4.0 * pairs * (dq + dv),
                    "bytes": 4.0 * qk_tile + 4.0 * v_tile}}


def experts_a_chip(model):
    return model["n_routed_experts"] // model["expert_parallel_size"]


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE sparse layer's ROUTED expert matmuls ON ONE
    CHIP in one training step in which EVERY chip of the group runs
    ``tokens`` tokens: under uniform routing the chip's n / ep experts meet
    ``tokens * k`` rows (each chip sends it 1 / ep of its own ``tokens *
    k``), ``tokens * k * ep / n`` an expert.  Three passes (forward, the
    backward's dX, the backward's dW), each 6EF FLOPs a row; a pass reads
    (or, for dW, writes) every one of the chip's experts' weights once and
    reads and writes the rows once, E values each.  What passes between the
    gate/up and the down matmul need not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = experts_a_chip(model) * 3.0 * E * F * itemsize
    rows = tokens * model["num_experts_per_tok"] * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}


def exchange_bytes(model, tokens, chips, itemsize=2):
    """Bytes ONE chip has to SEND in one training step's exchange of ONE
    sparse layer, counted the same whatever implements it: a row a (token,
    expert) pair that meets another chip's expert, ``(chips - 1) / chips``
    of ``tokens * k`` under uniform routing, E values each, four times a
    layer and step (out and back, forward and backward; a recomputed
    forward's exchange is the program's choice and is not in it).  The
    router weights that travel beside the rows (4 bytes a pair) and the
    counts are under 0.1 % and left out."""
    pairs = tokens * model["num_experts_per_tok"]
    return 4.0 * pairs * (chips - 1) / chips * model["hidden_size"] * itemsize
