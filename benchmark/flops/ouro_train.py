"""FLOPs the JOB requires to train an Ouro-class LOOPED decoder on one token
(``"flops": "ouro_train"`` in a configuration file): the stack of
``num_hidden_layers`` layers applied ``total_ut_steps`` times to the stream,
every application attention's four projections, causal softmax attention
and a dense gated FFN; at the end of EVERY pass the untied head over the
whole vocabulary and a one-column exit gate.

Counts what the algorithm needs, not what the program computes: the second
forward under remat, the masked halves of attention's blocks and the head's
last position (which has no label) do not count.  A leaf that is used four
times a step is multiplied four times: required FLOPs a PARAMETER are
``total_ut_steps`` times a plain stack's.  One multiply-accumulate is two
FLOPs, as in the chip's published peak."""


def layer_flops_per_token(model, seq):
    """Forward, ONE application of one layer, a token: the projections 2E(2
    H dh + 2 Hkv dh), causal scores and values 4 dh H (S + 1) / 2 (QK^T and
    PV over the keys up to the token's own, the mean over positions), the
    gated FFN 6EF."""
    E, dh = model["hidden_size"], model["head_dim"]
    q = model["num_attention_heads"] * dh
    kv = model["num_key_value_heads"] * dh
    return (2.0 * E * (2 * q + 2 * kv) + 2.0 * dh
            * model["num_attention_heads"] * (seq + 1)
            + 6.0 * E * model["intermediate_size"])


def exit_flops_per_token(model):
    """Forward, ONE exit, a token: the head 2EV and the gate 2E."""
    E = model["hidden_size"]
    return 2.0 * E * model["vocab_size"] + 2.0 * E


def per_unit(model, dims):
    """Forward per token: ``total_ut_steps`` passes, each every layer's
    application and one exit.  Training = 3 x forward.  Embedding lookups,
    norms, rotary embedding, softmax, the exit distribution and the
    optimizer are not counted."""
    return 3.0 * model["total_ut_steps"] * (
        model["num_hidden_layers"] * layer_flops_per_token(model, dims["S"])
        + exit_flops_per_token(model))
