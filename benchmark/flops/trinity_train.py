"""FLOPs the JOB requires to train a Trinity-class decoder on one token
(``"flops": "trinity_train"`` in a configuration file): gated grouped-query
attention whose layers are sliding or full by ``layer_types``, leading
layers with a dense gated FFN, then a top-k mixture of gated experts of
which this chip holds a share beside a shared expert every token meets, an
untied head over the vocabulary's slice; and what one layer's routed expert
matmuls alone require (``moe_held8of256_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, masked halves of a diagonal block and
rows beyond the held pairs do not count, and an expert counts only for the
tokens routed to it.  One multiply-accumulate is two FLOPs, as in the
chip's published peak."""

from .smallthinker_train import seen_pairs


def layer_indices(model):
    """The published index of each layer held: the LAST ``num_dense_layers``
    of the leading dense layers, then the layers from
    ``first_expert_layer`` on."""
    first = model["first_expert_layer"]
    dense = model["num_dense_layers"]
    return list(range(first - dense, first - dense
                      + model["num_hidden_layers"]))


def layer_windows(model):
    """Each held layer's window, None for a full layer."""
    return [model["sliding_window"]
            if model["layer_types"][i] == "sliding_attention" else None
            for i in layer_indices(model)]


def projection_flops_per_token(model):
    """Forward, one layer: q, the gate and the output projection at the
    query heads' width, k and v at the key/value heads'."""
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    return 2.0 * model["hidden_size"] * (3 * q + 2 * kv)


def pair_flops_per_token(model, seq, window):
    """Forward, one layer: QK^T and PV over the keys a query sees, mean over
    a causal sequence of ``seq``."""
    return (4.0 * model["num_attention_heads"] * model["head_dim"]
            * seen_pairs(seq, window) / seq)


def dense_flops_per_token(model):
    """Forward, one leading layer: the dense FFN's three E x F matmuls."""
    return 6.0 * model["hidden_size"] * model["intermediate_size"]


def shared_flops_per_token(model):
    """Forward, one sparse layer: the shared expert's three E x Fs matmuls."""
    return (6.0 * model["hidden_size"] * model["num_shared_experts"]
            * model["moe_intermediate_size"])


def held_experts_per_token(model):
    """Routed experts a token meets HERE at uniform routing: k times the
    share of the router's experts that this chip holds (4 x 8 / 256 =
    0.125)."""
    return (model["num_experts_per_tok"] * model["num_experts"]
            / model["moe_router_width"])


def expert_flops_per_token(model):
    """Forward, one sparse layer: the held routed experts a token meets,
    each three E x F matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def parts(model, dims):
    """Forward FLOPs per token by part, over all held layers."""
    dense = model["num_dense_layers"]
    sparse = model["num_hidden_layers"] - dense
    E = model["hidden_size"]
    return {
        "projections": model["num_hidden_layers"]
        * projection_flops_per_token(model),
        "pairs": sum(pair_flops_per_token(model, dims["S"], w)
                     for w in layer_windows(model)),
        "dense_ffn": dense * dense_flops_per_token(model),
        "shared_experts": sparse * shared_flops_per_token(model),
        "routed_experts": sparse * expert_flops_per_token(model),
        "routers": sparse * 2.0 * E * model["moe_router_width"],
        "head": 2.0 * E * model["vocab_size"]}


def per_unit(model, dims):
    """Training = 3 x forward (``parts``).  Embedding lookups, norms,
    rotation, the gate's sigmoid and product, softmax, the sort and the
    optimizer are not counted."""
    return 3.0 * sum(parts(model, dims).values())


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's ROUTED expert matmuls in one
    training step over ``tokens`` tokens, for the rows that meet a held
    expert at uniform routing.  Three passes (forward, the backward's dX,
    the backward's dW), each 6EF FLOPs a row; a pass reads (or, for dW,
    writes) every held expert's weights once, held*3EF values, and reads
    and writes the rows once, E values each.  What passes between the
    gate/up and the down matmul need not touch HBM and is not counted.  At
    96 rows an expert the weights' bytes bind, not the MXU."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
