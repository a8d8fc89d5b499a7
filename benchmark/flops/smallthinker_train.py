"""FLOPs the JOB requires to train a SmallThinker-class decoder on one token
(``"flops": "smallthinker_train"`` in a configuration file): grouped-query
attention whose layers are full or windowed by ``sliding_window_layout``, a
top-k mixture of gated experts of which this chip holds a share, an untied
head over the vocabulary's slice; and what its expert matmuls alone require
(``moe_held_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, masked halves of a diagonal block and
rows beyond the held pairs do not count, and an expert counts only for the
tokens routed to it.  One multiply-accumulate is two FLOPs, as in the
chip's published peak."""


def seen_pairs(seq, window=None):
    """(query, key) pairs one sequence's causal attention holds: all
    ``j <= i``, or with a window those with ``i - window < j`` too."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_windows(model):
    """Each layer's window, None for a full layer."""
    return [model["sliding_window_size"] if banded else None
            for banded in model["sliding_window_layout"][
                :model["num_hidden_layers"]]]


def held_experts_per_token(model):
    """Experts a token meets HERE at uniform routing: k times the share of
    the router's experts that this chip holds (6 x 16 / 64 = 1.5)."""
    return (model["moe_num_active_primary_experts"]
            * model["moe_num_primary_experts"] / model["moe_router_width"])


def expert_flops_per_token(model):
    """Forward, one layer: the held experts a token meets, each three E x F
    matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_ffn_hidden_size"])


def per_unit(model, dims):
    """Forward per token: per layer 2E(2*H*dh + 2*Hkv*dh) (q, output, k, v
    projections) + 4*H*dh*pairs/S (QK^T and PV over the keys a query sees,
    mean over the sequence) + 2E*n (the router, all n experts) + the held
    experts; the head 2EV over the slice on every position.  Training = 3 x
    forward.  Embedding lookups, norms, rotary embedding, softmax, the sort
    and the optimizer are not counted."""
    E, S = model["hidden_size"], dims["S"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    flat = (2 * E * (2 * q + 2 * kv) + 2 * E * model["moe_router_width"]
            + expert_flops_per_token(model))
    attention = sum(4.0 * q * seen_pairs(S, w) / S
                    for w in layer_windows(model))
    return 3.0 * (model["num_hidden_layers"] * flat + attention
                  + 2 * E * model["vocab_size"])


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's expert matmuls in one training
    step over ``tokens`` tokens, for the rows that meet a held expert at
    uniform routing.  Three passes (forward, the backward's dX, the
    backward's dW), each 6EF FLOPs a row; a pass reads (or, for dW, writes)
    every held expert's weights once, held*3EF values, and reads and writes
    the rows once, E values each.  What passes between the gate/up and the
    down matmul need not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["moe_ffn_hidden_size"]
    weights = model["moe_num_primary_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
