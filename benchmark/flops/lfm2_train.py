"""FLOPs the JOB requires to train an LFM2-MoE-class hybrid decoder on one
token (``"flops": "lfm2_train"`` in a configuration file): layers whose
operator is a gated short convolution or grouped-query attention by
``layer_types``, leading layers with a dense gated FFN, the others a top-k
mixture of gated experts of which this chip holds a share, a tied head over
the vocabulary's slice; and what one layer's short convolution
(``short_conv_roofline``) and expert matmuls (``moe_biased_roofline``)
alone require.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, masked halves of a diagonal block and
rows beyond the held pairs do not count, and an expert counts only for the
tokens routed to it.  One multiply-accumulate is two FLOPs, as in the
chip's published peak."""


def layer_types(model):
    """The published type of each layer the cut holds: the leading
    ``num_dense_layers``, then ``first_expert_layer`` onward."""
    dense, first = model["num_dense_layers"], model["first_expert_layer"]
    at = list(range(dense)) + list(
        range(first, first + model["num_hidden_layers"] - dense))
    return [model["layer_types"][i] for i in at]


def held_experts_per_token(model):
    """Experts a token meets HERE at uniform routing: k times the share of
    the router's experts that this chip holds (4 x 8 / 32 = 1)."""
    return (model["num_experts_per_tok"] * model["num_experts"]
            / model["moe_router_width"])


def expert_flops_per_token(model):
    """Forward, one layer: the held experts a token meets, each three E x F
    matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def short_conv_flops_per_token(model):
    """Forward, one layer: ``in_proj`` [E, 3E] and ``out_proj`` [E, E].  The
    taps and the two gates are 2 * taps + 2 operations a channel, a
    thousandth of the matmuls', and not counted."""
    return 8.0 * model["hidden_size"] ** 2


def per_unit(model, dims):
    """Forward per token, by layer: a conv operator 8E^2; attention
    2E(2*H*dh + 2*Hkv*dh) (q, output, k, v projections) + 4*H*dh*(S+1)/2
    (QK^T and PV over the keys a query sees, mean over the sequence); a
    dense FFN 6E*F_dense; an expert layer 2E*n (the router, all n experts)
    + the held experts; the head 2EV over the slice on every position.
    Training = 3 x forward.  Embedding lookups, norms, rotary embedding,
    softmax, the convolution's taps, the sort and the optimizer are not
    counted."""
    E, S = model["hidden_size"], dims["S"]
    dh = E // model["num_attention_heads"]
    q, kv = E, model["num_key_value_heads"] * dh
    total = 2.0 * E * model["vocab_size"]
    for i, kind in enumerate(layer_types(model)):
        if kind == "conv":
            total += short_conv_flops_per_token(model)
        else:
            total += 2.0 * E * (2 * q + 2 * kv) + 4.0 * q * (S + 1) / 2
        if i < model["num_dense_layers"]:
            total += 6.0 * E * model["intermediate_size"]
        else:
            total += (2.0 * E * model["moe_router_width"]
                      + expert_flops_per_token(model))
    return 3.0 * total


def short_conv(model, tokens, itemsize=2):
    """FLOPs and least HBM bytes of ONE layer's short convolution in one
    training step over ``tokens`` tokens.  Three passes (forward, the
    backward's dX, the backward's dW), each 8E^2 FLOPs a token; a pass reads
    (or, for dW, writes) both matrices once, 4E^2 values, and reads and
    writes the rows once, E values each.  What passes between the two
    matmuls (the gates, the taps) need not touch HBM and is not counted."""
    E = model["hidden_size"]
    return {"flops": 3.0 * short_conv_flops_per_token(model) * tokens,
            "bytes": 3.0 * (4.0 * E * E + 2.0 * tokens * E) * itemsize}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's expert matmuls in one training
    step over ``tokens`` tokens, for the rows that meet a held expert at
    uniform routing.  Three passes (forward, the backward's dX, the
    backward's dW), each 6EF FLOPs a row; a pass reads (or, for dW, writes)
    every held expert's weights once, held*3EF values, and reads and writes
    the rows once, E values each.  What passes between the gate/up and the
    down matmul need not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
