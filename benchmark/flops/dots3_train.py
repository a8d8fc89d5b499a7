"""FLOPs the JOB requires to train a dots3-note-prev-class decoder on one
token (``"flops": "dots3_train"`` in a configuration file): latent attention
of TWO shapes (a full layer's heads over the keys an INDEXER selects, a
sliding layer's over its window), of each the heads this chip HOLDS; the
indexer's own scores over every causal key and its KL target; a dense gated
FFN in the leading layer, elsewhere a top-k mixture of gated experts of which
this chip holds a share beside one shared expert; an untied head over the
vocabulary's slice; and what the indexer's scores and the two kinds' flash
calls alone require, for their rooflines.

Counts what the algorithm needs, not what the program computes:
recomputation under remat, padding (the 64 zero lanes a head of 192 is
carried with), the pairs a masked or banded kernel computes and drops, and
rows beyond the held pairs do not count; an expert counts only for the
tokens routed to it.  One multiply-accumulate is two FLOPs, as in the chip's
published peak."""

from .keye_vl2_train import causal_pairs, selected_pairs
from .smallthinker_train import seen_pairs


def kind(model, sliding):
    """A layer kind's sizes off the published keys: (held heads, q latent,
    kv latent, nope, rope, value width)."""
    pre = "swa_" if sliding else ""
    return (model[pre + "num_attention_heads"], model[pre + "q_lora_rank"],
            model[pre + "kv_lora_rank"], model[pre + "qk_nope_head_dim"],
            model[pre + "qk_rope_head_dim"], model[pre + "v_head_dim"])


def projection_weights(model, sliding):
    """The elements of a layer's attention matrices that this chip holds and
    multiplies by: both latents' down-projections whole, their
    up-projections and ``wo`` for the held heads, the gate's held columns."""
    E = model["hidden_size"]
    heads, rq, rkv, dn, dr, dv = kind(model, sliding)
    return (E * rq + rq * heads * (dn + dr) + E * (rkv + dr)
            + rkv * heads * (dn + dv) + heads * dv * E + E * heads)


def indexer_weights(model):
    hi, di = model["index_n_heads"], model["index_head_dim"]
    return model["q_lora_rank"] * hi * di + model["hidden_size"] * (di + hi)


def held_experts_per_token(model):
    """Experts a token meets HERE at uniform routing: k times the share of
    the router's experts that this chip holds (8 x 8 / 256 = 1/4)."""
    return (model["num_experts_per_tok"] * model["n_routed_experts"]
            / model["router_width"])


def pair_flops(model, sliding):
    """Forward FLOPs a (query, key) pair and held head: QK^T at nope + rope,
    PV at the value's width."""
    _, _, _, dn, dr, dv = kind(model, sliding)
    return 2.0 * (dn + dr) + 2.0 * dv


def attention_forward(model, seq, sliding):
    """Forward FLOPs of one layer's attention branch on one sequence, by
    part."""
    heads, _, _, dn, dr, _ = kind(model, sliding)
    parts = {"projections": seq * 2.0 * projection_weights(model, sliding)}
    if sliding:
        parts["attention"] = seen_pairs(seq, model["sliding_window_size"]) \
            * heads * pair_flops(model, True)
        return parts
    hi, di = model["index_n_heads"], model["index_head_dim"]
    chosen = selected_pairs(seq, model["index_topk"])
    parts.update(
        indexer_projections=seq * 2.0 * indexer_weights(model),
        # every causal pair, every indexer head: q . k
        indexer_scores=2.0 * causal_pairs(seq) * hi * di,
        attention=chosen * heads * pair_flops(model, False),
        # the KL's target: the held heads' probabilities on the selected
        # pairs, a second QK^T
        kl_target=2.0 * chosen * heads * (dn + dr))
    return parts


def ffn_forward(model, seq, dense):
    E = model["hidden_size"]
    if dense:
        return {"dense_ffn": seq * 6.0 * E * model["intermediate_size"]}
    F = model["moe_intermediate_size"]
    return {"shared_expert": seq * 6.0 * E * F * model["n_shared_experts"],
            "experts": seq * held_experts_per_token(model) * 6.0 * E * F,
            "router": seq * 2.0 * E * model["router_width"]}


def layers(model):
    """(sliding, dense FFN) of each layer that is run."""
    n = model["num_hidden_layers"]
    return [(t == "sliding_attention", i < model["first_k_dense_replace"])
            for i, t in enumerate(model["layer_types"][:n])]


def forward(model, seq):
    """Forward FLOPs of the whole stack and the head on one sequence, by
    part, the two attention kinds apart (``full.*`` / ``sliding.*``)."""
    total = {"head": seq * 2.0 * model["hidden_size"] * model["vocab_size"]}
    for sliding, dense in layers(model):
        pre = "sliding." if sliding else "full."
        for name, flops in attention_forward(model, seq, sliding).items():
            total[pre + name] = total.get(pre + name, 0.0) + flops
        for name, flops in ffn_forward(model, seq, dense).items():
            total[name] = total.get(name, 0.0) + flops
    return total


# What a trained step requires of each part, in forwards: 3 (the forward,
# and in the backward a gradient to the input and one to the weight or the
# other operand) but for the two parts the loss stops a gradient at
# (``keye_vl2_train.PASSES``): the indexer's inputs are constants, so its
# projections need their weights' gradient alone, and the KL's target needs
# no backward at all.
PASSES = {"full.indexer_projections": 2.0, "full.kl_target": 1.0}


def trained(model, seq):
    return {name: PASSES.get(name, 3.0) * flops
            for name, flops in forward(model, seq).items()}


def share(model, seq, names):
    """The share of the forward's required FLOPs in the parts whose name
    starts with one of ``names``."""
    parts = forward(model, seq)
    return sum(f for n, f in parts.items() if n.startswith(tuple(names))) \
        / sum(parts.values())


def per_unit(model, dims):
    """A trained step per token.  Embedding lookups, norms, rotary
    embedding, softmax, the gate's sigmoid, the selection's counting passes
    and the optimizer are not counted."""
    S = dims["S"]
    return sum(trained(model, S).values()) / S


def indexer_scores(model, batch, seq, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's indexer scores over ``batch``
    sequences, forward and backward apart
    (``keye_vl2_train.indexer_scores``' count at 64 heads of 128)."""
    hi, di = model["index_n_heads"], model["index_head_dim"]
    pairs = batch * causal_pairs(seq)
    rows = batch * seq * ((hi * di + di) * itemsize + hi * 4)
    return {"fwd": {"flops": 2.0 * pairs * hi * di,
                    "bytes": rows + 4.0 * pairs},
            "bwd": {"flops": 4.0 * pairs * hi * di,
                    "bytes": 2.0 * rows + 4.0 * pairs}}


def flash(model, batch, seq, sliding, itemsize=2):
    """FLOPs and HBM bytes of ONE layer's attention over the pairs its mask
    keeps (the SELECTED pairs of a full layer, the window's of a sliding
    one), for the held heads at the PUBLISHED widths, forward and backward
    apart: QK^T and PV forward; dV, dP, dQ and dK backward (the recomputed
    QK^T does not count); q, k at nope + rope and v, o at the value's
    width, each read or written once, their gradients too."""
    heads, _, _, dn, dr, dv = kind(model, sliding)
    pairs = batch * (seen_pairs(seq, model["sliding_window_size"]) if sliding
                     else selected_pairs(seq, model["index_topk"])) * heads
    qk = batch * seq * heads * (dn + dr) * itemsize
    vo = batch * seq * heads * dv * itemsize
    return {"fwd": {"flops": pairs * pair_flops(model, sliding),
                    "bytes": 2.0 * qk + 2.0 * vo},
            "bwd": {"flops": 2.0 * pairs * pair_flops(model, sliding),
                    "bytes": 4.0 * qk + 4.0 * vo}}
