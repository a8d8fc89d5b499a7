"""FLOPs the JOB requires to train an encoder with a tied LM head on one
token (``"flops": "transformer_mlm_train"`` in a configuration file).

Counts what the algorithm needs, not what the program computes:
recomputation, the LM head on positions nobody predicts and padding do not
count.  One multiply-accumulate is two FLOPs, as in the chip's published
peak."""


def per_unit(model, dims):
    """Forward per token: per layer 8E^2 (q, k, v, output projections) + 4EF
    (the two FFN matmuls) + 4SE (QK^T and PV over S keys); the head 2EV on
    the predicted share P/S.  Training = 3 x forward (backward is two
    matmuls for each forward one).  Embedding lookups, layer norms, softmax
    and the optimizer are not counted."""
    E, F = model["hidden_size"], model["intermediate_size"]
    L, V = model["num_hidden_layers"], model["vocab_size"]
    S, P = dims["S"], dims.get("P", dims["S"])
    per_layer = 8 * E * E + 4 * E * F + 4 * S * E
    return 3.0 * (L * per_layer + 2 * E * V * P / S)
