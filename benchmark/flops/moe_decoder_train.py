"""FLOPs the JOB requires to train a causal decoder with a top-k
mixture-of-experts FFN and an untied LM head on one token (``"flops":
"moe_decoder_train"`` in a configuration file), and what its expert matmuls
alone require (``moe_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat and padding do not count, and an expert counts
only for the tokens routed to it.  One multiply-accumulate is two FLOPs, as
in the chip's published peak."""


def expert_flops_per_token(model):
    """Forward, one layer: k experts, each three E x F matmuls (gate, up,
    down)."""
    return (model["num_experts_per_tok"] * 6.0 * model["hidden_size"]
            * model["intermediate_size"])


def per_unit(model, dims):
    """Forward per token: per layer 8E^2 (q, k, v, output projections) +
    2SE (QK^T and PV over S keys, halved: causal) + 2E*n (the router) +
    k*6EF (the k routed experts); the head 2EV on every position.
    Training = 3 x forward.  Embedding lookups, norms, rotary embedding,
    softmax, the sort and the optimizer are not counted."""
    E, L = model["hidden_size"], model["num_hidden_layers"]
    V, n, S = model["vocab_size"], model["num_experts"], dims["S"]
    per_layer = (8 * E * E + 2 * S * E + 2 * E * n
                 + expert_flops_per_token(model))
    return 3.0 * (L * per_layer + 2 * E * V)


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's expert matmuls in one training
    step over ``tokens`` tokens.  Three passes (forward, the backward's
    dX, the backward's dW), each k*6EF FLOPs a token; a pass reads (or, for
    dW, writes) every expert's weights once, n*3EF values, and reads and
    writes the T*k sorted rows once, E values each.  What passes between
    the gate/up and the down matmul need not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["intermediate_size"]
    n, k = model["num_experts"], model["num_experts_per_tok"]
    weights = n * 3.0 * E * F * itemsize
    rows = tokens * k * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
