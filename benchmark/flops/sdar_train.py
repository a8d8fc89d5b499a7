"""FLOPs the JOB requires to train an SDAR-class block-diffusion decoder on
one token of the CLEAN sequence (``"flops": "sdar_train"`` in a configuration
file): a noised and a clean copy of the sequence run through the stack
together, 2 rows a token in every projection, the router and the experts;
grouped-query attention over the pairs the block-diffusion rule lets through,
S (S + Bd) a head and sequence (a noised query its own block's Bd noised keys
and the earlier clean ones, a clean query its own and the earlier clean
ones: TWICE a causal sequence's, half a causal 2 S's); a top-k mixture of
gated experts of which this chip holds a share; an untied head over the
vocabulary's slice on the MASKED rows alone (the noise's expected share of
the tokens); and what the attention under the rule and the expert matmuls
alone require, for their rooflines.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, the pairs a masked tile computes and
drops (a noised diagonal tile of 512 x 512 holds 4 x 4 squares: 0.8 % of
it), the head's rows up to a whole block and rows beyond the held pairs do
not count; an expert counts only for the rows routed to it.  The same
whatever implements it, one sweep over both copies or two.  One
multiply-accumulate is two FLOPs, as in the chip's published peak."""

COPIES = 2      # rows of the stack a token of the clean sequence


def blockdiff_pairs(seq, block):
    """(query, key) pairs of one sequence of ``seq`` clean tokens that the
    rule lets through, a head: noised on noised ``seq * block``, noised on
    the earlier clean blocks ``seq (seq - block) / 2``, clean on clean ``seq
    (seq + block) / 2``."""
    return seq * (seq + block)


def masked_share(model):
    """The expected share of a sequence's tokens that are masked: the mean
    of the noise levels' interval."""
    return (model["noise_low"] + model["noise_high"]) / 2.0


def held_experts_per_row(model):
    """Experts a row meets HERE at uniform routing: k times the share of the
    router's experts that this chip holds (8 x 32 / 128 = 2)."""
    return (model["num_experts_per_tok"] * model["num_experts"]
            / model["router_width"])


def expert_flops_per_row(model):
    """Forward, one layer: the held experts a row meets, each three E x F
    matmuls (gate, up, down)."""
    return (held_experts_per_row(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def layer_forward(model, seq):
    """Forward FLOPs of ONE layer on one sequence of ``seq`` clean tokens,
    by part."""
    E = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    rows = COPIES * seq
    return {
        "projections": rows * 2.0 * E * (2 * q + 2 * kv),
        # QK^T and PV over the rule's pairs, every query head
        "attention": 4.0 * blockdiff_pairs(seq, model["block_length"]) * q,
        "experts": rows * expert_flops_per_row(model),
        "router": rows * 2.0 * E * model["router_width"],
    }


def head_forward(model, seq):
    """The head on the masked rows of one sequence: 2EV over the slice."""
    return (masked_share(model) * seq * 2.0 * model["hidden_size"]
            * model["vocab_size"])


def shares(model, seq):
    """Each part's share of a step's required FLOPs (every part is taken
    three times: the shares are the forward's)."""
    parts = {name: model["num_hidden_layers"] * flops
             for name, flops in layer_forward(model, seq).items()}
    parts["head"] = head_forward(model, seq)
    total = sum(parts.values())
    return {name: flops / total for name, flops in parts.items()}


def per_unit(model, dims):
    """A trained step per token of the clean sequence: the layers' parts and
    the head, each forward and twice in the backward.  Embedding lookups,
    the noising, norms, rotary embedding, softmax, the sort and the optimizer
    are not counted."""
    S = dims["S"]
    layer = sum(layer_forward(model, S).values())
    return 3.0 * (model["num_hidden_layers"] * layer
                  + head_forward(model, S)) / S


def blockdiff_attention(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's attention under the rule over
    ``batch`` sequences of ``seq`` clean tokens, forward and backward apart,
    as ``flash_attention_gqa.required`` counts a mask's: 4 * dh a pair and
    query head forward, 8 * dh backward; q and o (backward: q, o, do, dq) of
    both copies' rows at the query heads, k and v (and dk, dv) at the
    key/value heads, each once."""
    heads, dh = model["num_attention_heads"], model["head_dim"]
    pairs = batch * blockdiff_pairs(seq, model["block_length"]) * heads * dh
    q_tile = batch * COPIES * seq * heads * dh * itemsize
    kv_tile = batch * COPIES * seq * model["num_key_value_heads"] * dh \
        * itemsize
    return {"fwd": {"flops": 4.0 * pairs,
                    "bytes": 2.0 * q_tile + 2.0 * kv_tile},
            "bwd": {"flops": 8.0 * pairs,
                    "bytes": 4.0 * q_tile + 4.0 * kv_tile}}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's expert matmuls in one training
    step over ``tokens`` clean tokens (both copies' rows routed), for the
    rows that meet a held expert at uniform routing: three passes, each 6EF
    FLOPs a row, each reads (or writes) every held expert's weights once and
    reads and writes the rows once."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * 3.0 * E * F * itemsize
    rows = COPIES * tokens * held_experts_per_row(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_row(model) * COPIES * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
