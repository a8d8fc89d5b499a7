"""FLOPs the JOB requires to train a Mistral-Small-4-class decoder on one
token (``"flops": "mistral4_train"`` in a configuration file): latent
attention (two low-rank chains, heads whose key and value come off one
latent, a rotary key all heads share), a top-k mixture of gated experts of
which this chip holds a share beside a shared expert every token meets, an
untied head over the vocabulary's slice; and what one layer's expert
matmuls alone require (``moe_held8_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, masked halves of a diagonal block and
rows beyond the held pairs do not count, and an expert counts only for the
tokens routed to it.  One multiply-accumulate is two FLOPs, as in the
chip's published peak."""


def head_dim(model):
    """The width of a query or key head: its position-free columns and its
    rotated ones."""
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"]


def chain_flops_per_token(model):
    """Forward, one layer: both low-rank chains and the output projection:
    ``wq_a`` [E, rq], ``wq_b`` [rq, H (dn + dr)], ``wkv_a`` [E, rkv + dr],
    ``wkv_b`` [rkv, H (dn + dv)], ``wo`` [H dv, E]."""
    E, H = model["hidden_size"], model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return 2.0 * (E * rq + rq * H * (dn + dr) + E * (rkv + dr)
                  + rkv * H * (dn + dv) + H * dv * E)


def pair_flops_per_token(model, seq):
    """Forward, one layer: QK^T at the head's width and PV at the value's
    over the keys a query sees, mean over a causal sequence of ``seq``."""
    return (2.0 * model["num_attention_heads"]
            * (head_dim(model) + model["v_head_dim"]) * (seq + 1) / 2)


def shared_flops_per_token(model):
    """Forward, one layer: the shared expert's three E x Fs matmuls."""
    return (6.0 * model["hidden_size"] * model["n_shared_experts"]
            * model["moe_intermediate_size"])


def held_experts_per_token(model):
    """Routed experts a token meets HERE at uniform routing: k times the
    share of the router's experts that this chip holds (4 x 8 / 128 =
    0.25)."""
    return (model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["moe_router_width"])


def expert_flops_per_token(model):
    """Forward, one layer: the held routed experts a token meets, each
    three E x F matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def per_unit(model, dims):
    """Forward per token: per layer the chains and ``wo``, the pairs, the
    router over all its experts (2E n), the held routed experts and the
    shared expert; the head 2EV over the slice on every position.  Training
    = 3 x forward.  Embedding lookups, norms, rotation, the query scale,
    softmax, the sort and the optimizer are not counted."""
    E = model["hidden_size"]
    layer = (chain_flops_per_token(model)
             + pair_flops_per_token(model, dims["S"])
             + 2.0 * E * model["moe_router_width"]
             + expert_flops_per_token(model) + shared_flops_per_token(model))
    return 3.0 * (model["num_hidden_layers"] * layer
                  + 2.0 * E * model["vocab_size"])


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's ROUTED expert matmuls in one
    training step over ``tokens`` tokens, for the rows that meet a held
    expert at uniform routing.  Three passes (forward, the backward's dX,
    the backward's dW), each 6EF FLOPs a row; a pass reads (or, for dW,
    writes) every held expert's weights once, held*3EF values, and reads
    and writes the rows once, E values each.  What passes between the
    gate/up and the down matmul need not touch HBM and is not counted.  At
    512 rows an expert the weights' bytes bind, not the MXU."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["n_routed_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
