"""FLOPs the JOB requires to train a Brumby-class decoder on one token
(``"flops": "brumby_train"`` in a configuration file): in every layer
attention's four projections and a gate projection, power retention of
degree 2, a dense gated FFN; an untied head over the vocabulary's slice; and
what one layer's retention alone requires (``retention_roofline``).

Counts what the algorithm needs, not what the program computes:
recomputation under remat, the 64 spare columns of the program's state
layout (8,320 for 8,256), its in-chunk block and the masked halves of that
block do not count.  Power retention has two exact forms: the score matrix
(every pair ``j <= t``: QK^T and PV, 4 * dh a pair and query head) and the
carried state (``phi`` of 8,256 products a head and token: a query head
reads the state, 2 * 8,256 * dh, a key/value head updates it, the same, and
the normaliser costs 2 * 8,256 a head).  The job requires the CHEAPER of
the two at the cell's sequence length: the state form from S = 12,3xx on at
40 / 8 heads.  One multiply-accumulate is two FLOPs, as in the chip's
published peak."""


def state_columns(model):
    """Distinct products ``x_a x_b``, a <= b, of one head: 8,256 at 128."""
    dh = model["head_dim"]
    assert model["retention_degree"] == 2
    return dh * (dh + 1) // 2


def retention_flops_per_token(model, seq):
    """Forward, one layer, a token: the cheaper of the two exact forms."""
    dh, hq = model["head_dim"], model["num_attention_heads"]
    heads = hq + model["num_key_value_heads"]
    state = heads * 2.0 * state_columns(model) * (dh + 1)
    scores = 4.0 * dh * hq * (seq + 1) / 2
    return min(state, scores)


def per_unit(model, dims):
    """Forward per token, by layer: the projections 2E(2*H*dh + 2*Hkv*dh)
    and the gate 2E*Hkv; retention (above); the FFN 6EF; the head 2EV over
    the slice on every position.  Training = 3 x forward.  Embedding
    lookups, norms, rotary embedding, the decays' exponentials and the
    optimizer are not counted."""
    E, dh = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * dh, model["num_key_value_heads"] * dh
    layer = (2.0 * E * (2 * q + 2 * kv)
             + 2.0 * E * model["num_key_value_heads"]
             + retention_flops_per_token(model, dims["S"])
             + 6.0 * E * model["intermediate_size"])
    return 3.0 * (model["num_hidden_layers"] * layer
                  + 2.0 * E * model["vocab_size"])


def retention(model, tokens, seq, itemsize=2):
    """FLOPs and least HBM bytes of ONE layer's power retention in one
    training step over ``tokens`` tokens in sequences of ``seq``.  Three
    passes' worth of FLOPs (the forward, and a backward that is twice it:
    every product has two factors to differentiate).  Bytes: the forward
    reads q, k, v and writes o; the backward reads q, k, v and do and writes
    dq, dk, dv; rows of dh values a head.  The chunk states need not touch
    HBM (they can be made again) and are not counted."""
    dh = model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    rows = (2 * hq + 2 * hkv) + (2 * hq + 2 * hkv) + (hq + 2 * hkv)
    return {"flops": 3.0 * retention_flops_per_token(model, seq) * tokens,
            "bytes": float(rows * dh * tokens * itemsize)}
