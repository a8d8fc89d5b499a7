"""FLOPs the JOB requires to train a Jamba-class decoder on one token
(``"flops": "jamba_train"`` in a configuration file): Mamba-1 mixers beside
one multi-query attention layer a period, a dense gated FFN in every layer,
a tied head over the vocabulary; and what one layer's selective scan alone
requires (``selective_scan_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat, the states a chunk's backward makes again and the
forward's second run do not count.  The selective scan is counted as its
authors count it (``9 d N`` a token: the step size's product with the rate,
the exponential, the decay's and the input's products and their sum, the
read by C and its sum over the cells); the filter two FLOPs a tap and
channel.  One multiply-accumulate is two FLOPs, as in the chip's published
peak."""

SCAN_FLOPS_PER_CELL = 9.0


def widths(model):
    """(E, d, N, R, taps) of the mixer."""
    E = model["hidden_size"]
    return (E, model["mamba_expand"] * E, model["mamba_d_state"],
            model["mamba_dt_rank"], model["mamba_d_conv"])


def layer_counts(model):
    """(Mamba layers, attention layers) among ``num_hidden_layers``."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    attention = sum(i % period == offset
                    for i in range(model["num_hidden_layers"]))
    return model["num_hidden_layers"] - attention, attention


def scan_flops_per_token(model):
    """Forward, one layer, a token: the recurrence and the filter."""
    _, d, N, _, taps = widths(model)
    return SCAN_FLOPS_PER_CELL * d * N + 2.0 * taps * d


def mixer_flops_per_token(model):
    """Forward, one Mamba layer's mixer, a token: in_proj 2E(2d), out_proj
    2dE, x_proj 2d(R + 2N), dt_proj 2Rd, the scan and the filter."""
    E, d, N, R, _ = widths(model)
    return (2.0 * E * 2 * d + 2.0 * d * E + 2.0 * d * (R + 2 * N)
            + 2.0 * R * d + scan_flops_per_token(model))


def attention_flops_per_token(model, seq):
    """Forward, the attention layer's mixer, a token: q and o at H heads, k
    and v at the key/value heads, and the causal pairs' QK^T and PV."""
    E, H = model["hidden_size"], model["num_attention_heads"]
    dh = E // H
    projections = 2.0 * E * (2 * H * dh + 2 * model["num_key_value_heads"]
                             * dh)
    return projections + 4.0 * dh * H * (seq + 1) / 2


def per_unit(model, dims):
    """Forward per token: the Mamba layers' mixers, the attention layers',
    the FFN 6EF in EVERY layer, the tied head 2EV on every position.
    Training = 3 x forward.  Embedding lookups, norms, softplus, the gates'
    sigmoids and the optimizer are not counted."""
    E = model["hidden_size"]
    mamba, attention = layer_counts(model)
    forward = (mamba * mixer_flops_per_token(model)
               + attention * attention_flops_per_token(model, dims["S"])
               + model["num_hidden_layers"] * 6.0 * E
               * model["intermediate_size"]
               + 2.0 * E * model["vocab_size"])
    return 3.0 * forward


def selective_scan(model, tokens, itemsize=2):
    """FLOPs and least HBM bytes of ONE layer's selective scan (the
    recurrence and its gate: what the two kernels compute) in one training
    step over ``tokens`` tokens.  Three passes' worth of FLOPs (the forward,
    and a backward that is twice it).  Bytes: the forward reads x and z
    (``itemsize``), the float32 step sizes, B and C, and writes the output;
    the backward reads x, z, the step sizes, B, C and the output's
    gradient and writes the gradients of x, z, the step sizes, B and C.
    The states need not touch HBM (they can be made again) and are not
    counted."""
    _, d, N, _, _ = widths(model)
    forward = (3 * itemsize + 4) * d + 2 * 4 * N
    backward = (5 * itemsize + 2 * 4) * d + 4 * 4 * N
    return {"flops": 3.0 * SCAN_FLOPS_PER_CELL * d * N * tokens,
            "bytes": float((forward + backward) * tokens)}
