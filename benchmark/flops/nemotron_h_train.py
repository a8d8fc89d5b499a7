"""FLOPs the JOB requires to train a Nemotron-H-class decoder on one token
(``"flops": "nemotron_h_train"`` in a configuration file): layers that are
ONE branch each by ``hybrid_override_pattern`` (a Mamba-2 mixer, grouped-query
attention without positions, or a sparse feed-forward part of ungated
experts of which this chip holds a share beside a shared one), an untied
head over the vocabulary's slice; and what one layer's chunked scan
(``ssd_scan_roofline``) and one layer's routed expert matmuls
(``moe_relu2_roofline``) alone require.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, the masked half of a chunk's own block
and rows beyond the held pairs do not count, and an expert counts only for
the tokens routed to it.  One multiply-accumulate is two FLOPs, as in the
chip's published peak."""


def layer_counts(model):
    """(Mamba-2, sparse feed-forward, attention) layers held: the published
    pattern's characters from ``first_layer`` on."""
    first = model.get("first_layer", 0)
    kinds = model["hybrid_override_pattern"][
        first:first + model["num_hidden_layers"]]
    return kinds.count("M"), kinds.count("E"), kinds.count("*")


def mamba_widths(model):
    """(E, d, heads, P, G, N, taps, Q) of the mixer."""
    heads, P = model["mamba_num_heads"], model["mamba_head_dim"]
    return (model["hidden_size"], heads * P, heads, P, model["n_groups"],
            model["ssm_state_size"], model["conv_kernel"],
            model["chunk_size"])


def scan_flops_per_token(model):
    """Forward, one layer, a token: the chunked dual form's four products at
    the published chunk Q: ``C B^T`` once a GROUP and its product with ``dt
    x`` once a head, both over the (Q + 1) / 2 tokens of the chunk at or
    before this one; the state's read ``C H^T`` and its fold ``x (x) B``,
    2 P N each a head."""
    _, _, heads, P, G, N, _, Q = mamba_widths(model)
    seen = (Q + 1) / 2.0
    return 2.0 * G * N * seen + heads * (2.0 * P * seen + 4.0 * P * N)


def mixer_flops_per_token(model):
    """Forward, one Mamba-2 layer, a token: in_proj 2E(2d + 2GN + heads),
    out_proj 2dE, the filter two FLOPs a tap and channel, the scan."""
    E, d, heads, _, G, N, taps, _ = mamba_widths(model)
    return (2.0 * E * (2 * d + 2 * G * N + heads) + 2.0 * d * E
            + 2.0 * taps * (d + 2 * G * N) + scan_flops_per_token(model))


def attention_flops_per_token(model, seq):
    """Forward, the attention layer, a token: q and o at H heads, k and v at
    the key/value heads, and the causal pairs' QK^T and PV."""
    E, H, dh = (model[k] for k in ("hidden_size", "num_attention_heads",
                                   "head_dim"))
    projections = 2.0 * E * dh * (2 * H + 2 * model["num_key_value_heads"])
    return projections + 4.0 * dh * H * (seq + 1) / 2


def held_experts_per_token(model):
    """Routed experts a token meets HERE at uniform routing: k times the
    share of the router's experts that this chip holds (6 x 16 / 128 =
    0.75)."""
    return (model["num_experts_per_tok"] * model["n_routed_experts"]
            / model.get("router_experts", model["n_routed_experts"]))


def expert_flops_per_token(model):
    """Forward, one sparse layer: the held routed experts a token meets,
    each TWO E x F matmuls (up and down: no gate)."""
    return (held_experts_per_token(model) * 4.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def parts(model, dims):
    """Forward FLOPs per token by part, over all held layers."""
    mamba, sparse, attention = layer_counts(model)
    E = model["hidden_size"]
    return {
        "mamba2": mamba * mixer_flops_per_token(model),
        "attention": attention * attention_flops_per_token(model, dims["S"]),
        "shared_experts": sparse * 4.0 * E * model["n_shared_experts"]
        * model["moe_shared_expert_intermediate_size"],
        "routed_experts": sparse * expert_flops_per_token(model),
        "routers": sparse * 2.0 * E * model.get(
            "router_experts", model["n_routed_experts"]),
        "head": 2.0 * E * model["vocab_size"]}


def per_unit(model, dims):
    """Training = 3 x forward (``parts``).  Embedding lookups, norms,
    softplus, the gates' sigmoids, softmax, the sort and the optimizer are
    not counted."""
    return 3.0 * sum(parts(model, dims).values())


def ssd_scan(model, tokens, itemsize=2):
    """FLOPs and least HBM bytes of ONE layer's chunked scan (what the two
    kernels compute: the four products, the skip) in one training step over
    ``tokens`` tokens.  Three passes' worth of FLOPs (the forward, and a
    backward that is twice it).  Bytes at the operands' stored widths: the
    forward reads x, B and C (``itemsize``) and the float32 per-head
    scalars (the running log-decay, twice, and the step size) and writes
    the output; the backward reads x, B, C, the output's gradient and the
    scalars and writes the gradients of x, B, C and the scalars.  The
    states need not touch HBM (they can be made again) and are not
    counted."""
    _, d, heads, _, G, N, _, _ = mamba_widths(model)
    scalars = 3 * heads * 4
    forward = (2 * d + 2 * G * N) * itemsize + scalars
    backward = (3 * d + 4 * G * N) * itemsize + 2 * scalars
    return {"flops": 3.0 * scan_flops_per_token(model) * tokens,
            "bytes": float((forward + backward) * tokens)}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's ROUTED expert matmuls in one
    training step over ``tokens`` tokens, for the rows that meet a held
    expert at uniform routing.  Three passes (forward, the backward's dX,
    the backward's dW), each 4EF FLOPs a row at the published F = 1,856
    (whatever the program's tiles pad it to); a pass reads (or, for dW,
    writes) every held expert's weights once, held * 2EF values, and reads
    and writes the rows once, E values each.  What passes between the up
    and the down matmul need not touch HBM and is not counted."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["n_routed_experts"] * 2.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
