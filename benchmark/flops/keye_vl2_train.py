"""FLOPs the JOB requires to train a Keye-VL-2.0-class decoder on one token
(``"flops": "keye_vl2_train"`` in a configuration file): grouped-query
attention over the keys an INDEXER selects (the ``topk`` best of a query's
causal keys), the indexer's own scores over every causal key and its KL
target, a top-k mixture of gated experts of which this chip holds a share, an
untied head over the vocabulary's slice; and what the indexer's scores, the
sparse attention and the expert matmuls alone require, for their rooflines.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding, the pairs a masked kernel computes and
drops, and rows beyond the held pairs do not count; an expert counts only
for the tokens routed to it.  What S and ``topk`` fix (the selected share of
the causal pairs, 23.4 % at S = 16,384 and 2,048) is arithmetic here, not a
gauge.  One multiply-accumulate is two FLOPs, as in the chip's published
peak."""


def causal_pairs(seq):
    """(query, key) pairs of one sequence with key <= query."""
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """Those the selection keeps: every causal key of the first ``topk``
    queries, ``topk`` keys of each later one (ties at the threshold, a set of
    measure zero, not counted)."""
    if topk >= seq:
        return causal_pairs(seq)
    return causal_pairs(topk) + (seq - topk) * topk


def selected_share(seq, topk):
    return selected_pairs(seq, topk) / causal_pairs(seq)


def _indexer(model):
    sa = model["sa_config"]
    return sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]


def held_experts_per_token(model):
    """Experts a token meets HERE at uniform routing: k times the share of
    the router's experts that this chip holds (8 x 16 / 128 = 1)."""
    return (model["num_experts_per_tok"] * model["num_experts"]
            / model["router_width"])


def expert_flops_per_token(model):
    """Forward, one layer: the held experts a token meets, each three E x F
    matmuls (gate, up, down)."""
    return (held_experts_per_token(model) * 6.0 * model["hidden_size"]
            * model["moe_intermediate_size"])


def layer_forward(model, seq):
    """Forward FLOPs of ONE layer on one sequence of ``seq`` tokens, by
    part."""
    E = model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    hi, di, topk = _indexer(model)
    chosen = selected_pairs(seq, topk)
    return {
        "projections": seq * 2.0 * E * (2 * q + 2 * kv),
        "indexer_projections": seq * 2.0 * E * (hi * di + di + hi),
        # every causal pair, every indexer head: q . k
        "indexer_scores": 2.0 * causal_pairs(seq) * hi * di,
        # QK^T and PV over the selected pairs, every query head
        "attention": 4.0 * chosen * q,
        # the KL's target: the heads' probabilities on the selected pairs
        "kl_target": 2.0 * chosen * q,
        "experts": seq * expert_flops_per_token(model),
        "router": seq * 2.0 * E * model["router_width"],
    }


def mechanism_share(model, seq):
    """The share of a layer's required FLOPs that is the learned-sparse
    mechanism (indexer, selection, sparse attention, KL)."""
    parts = layer_forward(model, seq)
    own = sum(parts[name] for name in ("indexer_projections",
                                       "indexer_scores", "attention",
                                       "kl_target"))
    return own / sum(parts.values())


# What a trained step requires of each part, in forwards: 3 (the forward,
# and in the backward a gradient to the input and one to the weight or the
# other operand) but for the two parts the loss stops a gradient at.  The
# indexer's INPUT carries a stop-gradient, so its projections need their
# weights' gradient alone: 2.  The KL's TARGET carries one too, so it needs
# no backward at all: 1.  (The target is a second QK^T over the selected
# pairs, which ``attention`` counts once already; it stays at 1 and not 0
# because the heads' probabilities a pair, [32, S, topk] = 4.1 GB a layer in
# float32, are what no implementation at this size keeps, and ISSUE 61
# lists it as required.  ISSUE 61's own 38 T a step used a flat 3.)
PASSES = {"indexer_projections": 2.0, "kl_target": 1.0}


def layer_trained(model, seq):
    """FLOPs ONE layer requires in a trained step on one sequence, by part:
    ``layer_forward`` times each part's ``PASSES`` (3 where not listed)."""
    return {name: PASSES.get(name, 3.0) * flops
            for name, flops in layer_forward(model, seq).items()}


def per_unit(model, dims):
    """A trained step per token: the layers' parts (``layer_trained``) and
    the head, 2EV over the slice on every position, forward and twice in the
    backward.  Embedding lookups, norms, rotary embedding, softmax, the
    selection's counting passes, the sort and the optimizer are not
    counted."""
    S = dims["S"]
    layer = sum(layer_trained(model, S).values()) / S
    return (model["num_hidden_layers"] * layer
            + 3.0 * 2.0 * model["hidden_size"] * model["vocab_size"])


def indexer_scores(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's indexer scores over ``batch``
    sequences, forward and backward apart.  Forward: q . k of every causal
    pair and indexer head; reads q [S, Hi * Di], the one key head and the
    weights, writes the causal scores in float32.  Backward (dq, dk from
    dI; the recomputed product does not count): reads dI and the three
    operands, writes their gradients."""
    hi, di, _ = _indexer(model)
    pairs = batch * causal_pairs(seq)
    rows = batch * seq * ((hi * di + di) * itemsize + hi * 4)
    return {"fwd": {"flops": 2.0 * pairs * hi * di,
                    "bytes": rows + 4.0 * pairs},
            "bwd": {"flops": 4.0 * pairs * hi * di,
                    "bytes": 2.0 * rows + 4.0 * pairs}}


def sparse_attention(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's attention over the SELECTED pairs,
    forward and backward apart, as ``flash_attention_gqa.required`` counts a
    mask's: 4 * dh a pair and query head forward, 8 * dh backward; q and o
    (backward: q, o, do, dq) at the query heads, k and v (and dk, dv) at the
    key/value heads.  The mask's own bytes are no requirement."""
    heads, dh = model["num_attention_heads"], model["head_dim"]
    pairs = batch * selected_pairs(seq, _indexer(model)[2]) * heads * dh
    q_tile = batch * seq * heads * dh * itemsize
    kv_tile = batch * seq * model["num_key_value_heads"] * dh * itemsize
    return {"fwd": {"flops": 4.0 * pairs,
                    "bytes": 2.0 * q_tile + 2.0 * kv_tile},
            "bwd": {"flops": 8.0 * pairs,
                    "bytes": 4.0 * q_tile + 4.0 * kv_tile}}


def expert_matmuls(model, tokens, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's expert matmuls in one training
    step over ``tokens`` tokens, for the rows that meet a held expert at
    uniform routing (``smallthinker_train.expert_matmuls``' count): three
    passes, each 6EF FLOPs a row, each reads (or writes) every held expert's
    weights once and reads and writes the rows once."""
    E, F = model["hidden_size"], model["moe_intermediate_size"]
    weights = model["num_experts"] * 3.0 * E * F * itemsize
    rows = tokens * held_experts_per_token(model) * E * itemsize
    return {"flops": 3.0 * expert_flops_per_token(model) * tokens,
            "bytes": 3.0 * (weights + 2.0 * rows)}
