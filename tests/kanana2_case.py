"""What the two Kanana-2 test files share (``test_kanana2_reference.py``: one
device against the reference on PR 69's harness; ``test_kanana2_expert_
parallel.py``: four and two devices that exchange rows): the tiny model's
published keys, its leaves and how ``moved`` seeds them.  Not collected."""

import decoder_reference as H
from benchmark.reference import kanana_2_30b_a3b as reference

# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
         "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
         "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_interleave": True,
         "rope_scaling": None, "norm_topk_prob": True, "n_group": 1,
         "topk_group": 1, "scoring_func": "sigmoid", "num_experts_per_tok": 2,
         "n_routed_experts": 8, "n_shared_experts": 2,
         "routed_scaling_factor": 2.448, "first_k_dense_replace": 1,
         "num_hidden_layers": 3, "expert_parallel_size": 4}
LATENT = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")
SPARSE = ("router", "we_gate_up", "we_down", "ws_gate_up", "ws_down")
LEAVES = tuple(["tok_emb", "lm_head", "lnf_scale"]
               + ["prefix_layers/l0/" + n
                  for n in LATENT + ("w_gate_up", "w_down")]
               + ["params_layers/p0/" + n for n in LATENT + SPARSE])


def gain(name):
    """A router steep enough that the weights are not all alike, and branch
    outputs at the fan-in scale again (the seeded 48^-1/2 would hide a wrong
    branch behind the embedding)."""
    if "router_bias" in name:
        return 1.0
    if name.endswith("['wo']") or "down" in name:
        return 48 ** 0.5
    return 3.0 if "router" in name else 1.0


def case(**more):
    return H.Case("kanana2", reference, MODEL, LEAVES, aux=True, biased=True,
                  gain=gain, **more)
