"""Tensor/sequence-parallel transformer building blocks (explicit SPMD).

These functions are per-device code run inside a shard_map body over the mesh
of parallel/mesh.py.  They implement the Megatron-SP layout — which the
reference does NOT have (SURVEY.md §2.9: tensor parallel "Absent", only the
DistFCConfig stub incubate/fleet/collective/__init__.py:36) — as well as a
ring-attention context-parallel mode for long sequences (net-new, SURVEY.md
§5 long-context note):

- attn_mode="heads" (Megatron-SP): activations live sequence-sharded over the
  `tp` axis between blocks; each block all_gathers the sequence, computes with
  heads/ffn sharded over tp (column-parallel in, row-parallel out), and
  reduce_scatters back to the sequence shard.  Per block: 2 all_gather +
  2 reduce_scatter on the fast axis.
- attn_mode="ring" (context parallel): activations stay sequence-sharded
  through attention; K/V rotate around the ring (ring_attention.py); weights
  are replicated over tp (grads psum'd by the train step).

Embedding is vocab-parallel (the TP generalization of the reference's
row-sharded distributed_lookup_table_op.cc), and the LM loss is a
vocab-parallel softmax cross-entropy that never materializes gathered logits.
"""

import copy
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import collectives as col
from .mesh import DP, PP, TP
from ..monitor import devscope
from ..monitor.recompile import compile_ledger
from .ring_attention import ring_attention

__all__ = ["TransformerConfig", "AttentionShape", "CONV", "RETENTION", "MAMBA", "MAMBA2", "FFN",
           "KDA",
           "init_transformer_params", "transformer_param_specs",
           "grad_sync_axes", "embed", "transformer_layer", "run_layers",
           "mamba_mixer", "mamba_operands", "mamba2_mixer", "mamba2_operands",
           "kda_mixer", "kda_log_decay", "kda_write_strength",
           "rms_norm", "rope", "rope_pairs", "yarn_blend_range",
           "yarn_frequencies",
           "yarn_softmax_scale", "yarn_rotary_factor", "final_logits_loss",
           "head_logits", "head_row_block", "head_rows_computed",
           "run_passes", "exit_log_probs", "exit_weighted_loss"]


CONV = "conv"       # a layer kind: the gated short convolution, no attention
# a layer kind: power retention on attention's projections, a learned
# per-token decay and a state carried along the sequence; no softmax
RETENTION = "retention"
# a layer kind: the Mamba-1 mixer where attention stands: a causal depthwise
# convolution, step sizes and rates of its own for every channel and state
# cell, a selective scan carried along the sequence, an output gate
MAMBA = "mamba"
# a layer kind: the Mamba-2 mixer (the state-space dual form): ONE scalar
# decay a head, B and C shared by the heads of a group, a [head width, state
# cells] state a head carried from chunk to chunk, a gated group norm
MAMBA2 = "mamba2"
# a layer kind of a ``single_branch`` stack: the feed-forward part alone
FFN = "ffn"
# a layer kind: Kimi Delta Attention where attention stands: q, k and v each
# through a causal depthwise filter, a log-decay for every CHANNEL of the key
# and a write strength a head off low-rank gates, a [key, value] state a head
# corrected by the delta rule and carried along the sequence, a head-wise
# RMS norm and THEN a sigmoid gate on the output
KDA = "kda"
# kinds whose position owns other leaves
_OWN_LEAVES = (CONV, RETENTION, MAMBA, MAMBA2, FFN, KDA)


@dataclasses.dataclass(frozen=True)
class AttentionShape:
    """An attention position that carries its OWN shape: where a pattern
    holds one of these in place of ``(window, rotary)``, that position reads
    every field given here in place of the configuration's field of the same
    name (None: the configuration's), in its leaves and in its layer alike
    (``TransformerConfig.position``).  ``indexer`` off: this position has no
    indexer where the configuration gives one.  ``scope``: the
    ``monitor.devscope`` word the position's attention goes under."""
    window: int = 0
    rotary: bool = True
    n_heads: int = None
    heads_held: int = None
    first_head: int = None
    head_width: int = None
    q_lora_rank: int = None
    kv_lora_rank: int = None
    qk_nope_dim: int = None
    qk_rope_dim: int = None
    v_head_dim: int = None
    rope_theta: float = None
    indexer: bool = True
    scope: str = None

    def own(self):
        """The fields this position reads in the configuration's place."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if f.name not in ("window", "rotary", "indexer", "scope")
                and getattr(self, f.name) is not None}


def _is_attention(kind):
    return kind not in _OWN_LEAVES


def _named(k):
    """Whether a pattern's entry is more than ``(window, rotary)``."""
    return k in _OWN_LEAVES or isinstance(k, AttentionShape)


def _kind(k):
    """A pattern's entry as the configuration keeps it."""
    return k if _named(k) else (int(k[0]), bool(k[1]))


def _kinds(pattern):
    return tuple(k if _named(k) else (k[0] or None, k[1]) for k in pattern)


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 32000
    hidden: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_hidden: int = 3072
    max_seq: int = 512
    dtype: str = "bfloat16"          # compute/param dtype (MXU-native bf16)
    causal: bool = False             # False = BERT (bidirectional), True = GPT
    attn_mode: str = "heads"         # "heads" (Megatron-SP) | "ring" (context parallel)
    remat: bool = False              # jax.checkpoint per layer (RecomputeOptimizer parity)
    tp: int = 1                      # tensor-parallel degree (mesh tp axis size)
    pp: int = 1                      # pipeline stages (mesh pp axis size)
    # Pallas kernel q/kv block sizes, clamped to S.  S = 512 is one block;
    # S = 4096 is 8 x 8 blocks, the causal sweeps' steps the 36 under the
    # diagonal.  Either way the backward is one kernel (flash_bwd_fused),
    # over several blocks with dk and dv of the whole sequence in VMEM; two
    # sweeps (flash_bwd_dq, flash_bwd_dkv) only where those do not fit
    flash_block_q: int = 512
    flash_block_k: int = 512
    scan_unroll: int = 1             # lax.scan unroll over layers (1 = rolled;
    # full unroll turns the per-layer dynamic slices into static ones)
    # The block's shape.  The defaults are the BERT block (LayerNorm, learned
    # positions, biases, GELU FFN, head tied to tok_emb); a decoder of the
    # 2024 kind sets them all (models/olmoe.py).
    norm: str = "layer"              # "layer" | "rms" (scale only, no bias leaf)
    norm_eps: float = 1e-6
    # "learned" (pos_emb) | "rotary" (no pos_emb) | None (a stack of several
    # layer kinds none of which adds or rotates positions)
    positions: object = "learned"
    rope_theta: float = 10000.0
    # False | True: RMS norm of the whole q / k projection, before the heads
    # | "head": of each head on its own, one weight [head_dim] for all heads
    qk_norm: object = False
    bias: bool = True                # biases on the attention and FFN matmuls
    tie_head: bool = True            # False: the head is its own [V, E] leaf, lm_head
    # n_experts > 0 replaces the GELU FFN by the dropless top-k MoE of
    # parallel/moe.py: n_experts gated experts of width ffn_hidden
    n_experts: int = 0
    experts_per_token: int = 0
    router_aux_coef: float = 0.0     # x load-balance loss, mean over layers
    router_z_coef: float = 0.0       # x router z-loss, mean over layers
    routing: str = "softmax_top_k"   # moe.RULES: how the k weights are formed
    # moe.SIGMOID_BIASED alone: what a step moves each expert's selection
    # bias by, against its load (``moe.balance_bias``)
    router_bias_rate: float = 0.0
    expert_act: str = "silu"         # the gate's activation (moe.ACTIVATIONS)
    # False: UNGATED experts, ``down(act(up(x)))``, one up matrix (leaves
    # ``we_up`` [held, E, F], ``ws_up`` [E, Fs]) where the gated ones hold
    # gate and up side by side
    expert_gated: bool = True
    router_input: str = "ffn"        # "ffn": the normed FFN input | "block":
    # the block's input, before its first norm and before attention
    # the experts this device holds, of the router's n_experts (0: all):
    # experts [first_expert, first_expert + experts_held)
    experts_held: int = 0
    first_expert: int = 0
    # EXPERT-PARALLEL: the routed experts ride the mesh's ``dp`` axis ("EP
    # rides DP").  Device c of the dp devices holds experts c n / dp .. (c +
    # 1) n / dp - 1 of every layer (``parallel/rules.py`` splits the experts'
    # leaves and their moments over ``dp``; their gradients are not summed
    # over it) and the layer exchanges rows with ``lax.all_to_all``
    # (``moe.dropless_moe_ffn(ep_axis=)``): the result is what one device
    # holding all n would give.  Everything else stays replicated and
    # data-parallel.  At dp 1 it is the all-held layer.  It and
    # ``experts_held`` (a share WITHOUT the exchange) exclude each other
    expert_parallel: bool = False
    # Attention's shape.  head_width 0: hidden // n_heads; n_kv_heads 0:
    # n_heads (else grouped queries: wk, wv [E, n_kv_heads * head_dim])
    head_width: int = 0
    n_kv_heads: int = 0
    # the heads this device holds, of the latent form's n_heads (0: all):
    # heads [first_head, first_head + heads_held).  ``wq_b``, ``wkv_b`` and
    # ``wo`` hold the held heads' columns and rows alone and the branch's
    # output is that partial sum; a head-wise gate's ``wz`` is whole [E,
    # n_heads] (as a router is whole beside held experts) and the share reads
    # its own columns of it
    heads_held: int = 0
    first_head: int = 0
    # One period of the stack's layer kinds, a position either (window,
    # rotary), an attention layer: window 0 is full attention, rotary off is
    # NO positional encoding in that layer; or an ``AttentionShape``, an
    # attention layer with a shape of its own (heads, latent ranks, widths,
    # ``rope_theta``, an indexer or none) beside its window; or CONV, a layer whose operator
    # is the gated short convolution (``short_conv``) and which has no
    # attention leaves; or RETENTION, a layer that keeps attention's
    # projections (and ``qk_norm``, rotary positions, the grouping on
    # ``n_kv_heads``) and replaces the softmax by power retention
    # (``power_retention``), with a gate projection ``wg`` of its own; or
    # MAMBA, a layer whose operator is the Mamba-1 mixer (``mamba_mixer``)
    # and which has no attention leaves; or MAMBA2, the same of the Mamba-2
    # mixer (``mamba2_mixer``); or, in a ``single_branch`` stack, FFN.
    # Empty: one kind, full attention, rotary as ``positions`` says.
    # n_layers is ``prefix_pattern`` and whole periods.
    layer_pattern: tuple = ()
    # The kinds of the LEADING layers, which run before the scan over
    # periods and whose FFN, where the others' is the MoE, is one dense
    # gated FFN (``expert_act``) of width ``dense_ffn_hidden``.  A stack
    # WITHOUT experts that gives this width carries that FFN in every layer
    # (``dense_stack``), whichever way its tree is built
    prefix_pattern: tuple = ()
    dense_ffn_hidden: int = 0
    conv_taps: int = 3               # CONV: taps of the causal depthwise filter
    retention_chunk: int = 1024      # RETENTION: tokens between two states
    # MAMBA: the mixer's inner width (channels), the state cells a channel,
    # the taps of its causal depthwise filter, the rank the step sizes are
    # projected through, and the tokens between two states the scan keeps
    d_inner: int = 0
    d_state: int = 0
    d_conv: int = 0
    dt_rank: int = 0
    scan_chunk: int = 128
    # MAMBA2 (with d_inner, d_state, d_conv and scan_chunk): the heads
    # (``d_inner / ssm_heads`` channels and ONE decay each) and the groups
    # of heads that share B and C
    ssm_heads: int = 0
    ssm_groups: int = 0
    # KDA (with d_conv, the taps of its three filters): the heads and a
    # head's width (keys and values alike), the rank the decay's and the
    # output gate's projections go through, and the tokens of a chunk
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    kda_chunk: int = 64
    # what the sigmoid of a head's write strength is multiplied by: 1, beta
    # in (0, 1); 2, beta in (0, 2) (the transition along the key, ``1 -
    # beta``, in (-1, 1): negative eigenvalues, arXiv:2411.12537)
    kda_beta_scale: float = 1.0
    # every layer is ONE pre-norm residual branch, ``x + branch(norm(x))``:
    # a mixer position (attention, MAMBA2, ...) has no FFN and no second
    # norm, and the feed-forward part is a position of its own, FFN
    single_branch: bool = False
    # a RUN, consecutive positions of one kind inside a period, is one tree
    # ``params_layers["r<i>"]`` stacked [n_periods, run length, ...] and one
    # inner scan of the period's body (``run_layers``), where each position
    # would else be a tree ``p<i>`` and a copy of its layer in the body
    run_scan: bool = False
    # Latent attention (kv_lora_rank > 0; every ATTENTION position, in place
    # of wq / wk / wv; full or under a window, whichever kinds stand beside
    # it in a pattern): queries off a latent of q_lora_rank, RMS-normed (0: ONE
    # matrix ``wq``, no latent and no norm); keys and values off ONE latent
    # of kv_lora_rank, RMS-normed, beside which the same projection gives
    # qk_rope_dim columns that stand, the same for every head, as the last
    # columns of each head's key, rotated where ``positions`` is "rotary"
    # and as they are where it is None.  A head is [qk_nope_dim |
    # qk_rope_dim] = head_dim wide, its first part without positions; rotary
    # pairs are ADJACENT columns (``rope_pairs``).  A value is v_head_dim
    # wide, which need not be head_dim (192 / 128: the packed flash kernels'
    # value width).  A latent position reads these five, ``n_heads`` and
    # ``head_width`` (its own where its kind is an ``AttentionShape``);
    # ``n_kv_heads`` and ``qk_norm`` are unused by it
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # each normed latent times (hidden / its rank)^(1/2)
    latent_rescale: bool = False
    # YaRN positions of the latent form (rope_factor > 1; ``yarn_frequencies``,
    # ``yarn_softmax_scale``): the factor, the positions the extension starts
    # from, the rotations that bound the blend, the two mscales
    rope_factor: float = 0.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the query of position p times 1 + beta * ln(1 + p // rope_original_max)
    q_scale_beta: float = 0.0
    # a dense gated FFN of this width (``expert_act``) beside the routed
    # experts in every MoE layer, on the same input, for every token
    shared_ffn_hidden: int = 0
    # a sigmoid OUTPUT GATE on attention: a fifth projection ``wz`` [E,
    # n_heads * head_dim] off the same normed input as q, k and v, whose
    # sigmoid multiplies the heads' output before ``wo``; "head": ONE scalar
    # a head and token, ``wz`` [E, n_heads]
    attn_gate: object = False
    # sandwich norms: an RMS norm on each branch's OUTPUT beside the one on
    # its input (``ln1_post_scale``, ``ln2_post_scale``); in a layer with a
    # shared expert the FFN's takes the SUM of the routed part and the shared
    post_norm: bool = False
    # what the output norms' scales are SEEDED at (the input norms' at one).
    # At one every branch re-enters the stream at unit scale whatever it
    # computed, attention's near-constant mean among them, and a router
    # downstream ranks the experts alike for every token
    post_norm_gain: float = 1.0
    # moe.SIGMOID_BIASED alone: the standard deviation the selection biases
    # are SEEDED with.  A bias is added to a SCORE, and the k-th of n sigmoid
    # scores lies where the sigmoid is flat: 0.1 is half a unit of the logit
    # at 4 of 32 and a whole one at 4 of 256, where it empties some experts
    # and sends others eight times the mean
    router_bias_std: float = 0.1
    # what every branch's OUTPUT projection (attention's ``wo``, the Mamba-2
    # mixer's ``w_out``, the experts' and the shared expert's down matrices)
    # is SEEDED times, in a stack whose layers own their leaves; off 1 the
    # embedding's rows are seeded N(0, 1), so that the stream a router reads
    # is the token's own row and small branch outputs.  At 1 a branch re-enters
    # the stream at unit scale with what it computes for EVERY token alike
    # (the positive mean of ``relu^2`` hidden rows, a slow state's running
    # mean), and a router downstream ranks the experts alike for every token
    residual_out_gain: float = 1.0
    # what the k routing weights are multiplied by, once formed
    route_scale: float = 1.0
    # what the embedding's rows are multiplied by as they enter the stream
    embed_scale: float = 1.0
    # how many times a step applies the WHOLE stack to the stream, every
    # pass over the same leaves (a looped model): the final norm stands at
    # the end of every pass, its output is the next pass's input, and an
    # exit gate ``exit_gate_w`` [E] / ``exit_gate_b`` (float32) and the head
    # read it after every pass (``exit_weighted_loss``).  1: a plain stack
    loop_passes: int = 1
    # what the entropy of a token's distribution over the exits is rewarded
    # by in the loss (a uniform prior over the exits)
    exit_entropy_coef: float = 0.0
    # LEARNED-SPARSE attention (indexer_heads > 0; every attention position
    # but an ``AttentionShape`` that says otherwise; grouped queries at a
    # head of whole lane blocks, or the latent form at heads and values of
    # whole lane blocks):
    # an indexer of ``indexer_heads`` heads of ``indexer_dim`` on ONE key
    # head scores every causal key, ``I[t, s] = sum_j w[t, j] relu(qI[t, j]
    # . kI[s])`` (``kernels/indexer.py``), a query reads the ``indexer_topk``
    # best of them (ties at the threshold kept; fewer causal keys than that:
    # all), and the indexer's own loss term ``mean over layers and tokens of
    # KL(mean over heads of attention's probabilities || softmax over the
    # selected keys of I)`` (coefficient 1) trains its leaves (``wq_idx``,
    # ``wk_idx``, ``w_idx``, ``idx_k_norm_scale`` / ``_bias``) alone: the
    # target and the indexer's input carry a stop-gradient and the selection
    # passes none, so no other leaf hears of it and the indexer's hear of
    # nothing else
    indexer_heads: int = 0
    indexer_dim: int = 0
    indexer_topk: int = 0
    # how many of an indexer head's FIRST columns are rotated (its queries'
    # and its key's alike; 0: all of them)
    indexer_rope_dim: int = 0
    # "latent": the indexer's queries come off the normed QUERY latent
    # (``wq_idx`` [q_lora_rank, Hi * Di]) where they else come off the
    # layer's normed input (``wq_idx`` [E, Hi * Di])
    indexer_query: str = "input"
    # rotary positions from THREE streams (temporal, height, width; a batch's
    # ``positions`` [3, b, S], the token index three times where it carries
    # none): how many of a head's frequency pairs take their angle from each,
    # in order.  Empty: one stream
    mrope_sections: tuple = ()
    # what ``q_norm`` / ``k_norm`` (and an indexer's key norm's scale) are
    # SEEDED at: at 1 a head's scores are N(0, 1) over the keys at seeded
    # weights, at 2^(1/2) N(0, 4) and a row's softmax visibly uneven
    qk_norm_gain: float = 1.0
    # BLOCK DIFFUSION (BD3-LM's vectorised training form): the block length
    # Bd.  The stack then runs on the rows ``[x_t ; x_0]`` of a sequence, a
    # noised copy over the clean one (2 S rows; ``decoder.make_loss_fn``
    # makes them from the batch's noise and holds the noised rows to the
    # clean ids): attention's mask is the rule's three parts over blocks of
    # Bd positions (``kernels/flash_attention.blockdiff_seen``: a noised
    # query its own noised block and the earlier clean ones, a clean query
    # its own and the earlier clean ones; ``causal`` off), and a rotation's
    # positions repeat, row r's is ``r mod S``.  ``mask_token_id``: what a
    # masked token reads (-1: the vocabulary's last row)
    block_diffusion: int = 0
    mask_token_id: int = -1
    # what the mask token's embedding row is SEEDED at, times a token's.
    # Every masked token reads that ONE row, and where a branch's output is
    # small beside the stream (``residual_out_gain``) all of a sequence's
    # masked rows, a third of the stack's rows, meet the same k experts in
    # every layer: a share's rows, and its step's time, then follow which of
    # them it holds, by the seed (PERF.md section 6, PR 71).  Seeded small, a
    # masked row's stream is what attention brings it, which follows its
    # position: the routing is balanced at seeded weights and stays so as
    # the routers train.  The price: such a row's stream, hence its logits,
    # carry bf16's rounding of a whole branch (a few per cent of float32's,
    # where a token's own row keeps the others to half a per cent)
    mask_embed_gain: float = 1.0

    def __post_init__(self):
        assert self.norm in ("layer", "rms") and \
            self.positions in ("learned", "rotary", None)
        assert self.router_input in ("ffn", "block")
        assert self.qk_norm in (False, True, "head"), self.qk_norm
        if self.qk_norm or self.positions == "rotary" or self.n_experts:
            # the norm spans the whole projection, rotary positions start at
            # 0 and the MoE routes the tokens it holds: none is sharded yet
            assert self.tp == 1 and self.attn_mode == "heads", \
                "qk_norm, rotary positions and the MoE FFN need tp == 1"
        if self.n_experts:
            assert 0 < self.experts_per_token <= self.n_experts
            assert self.first_expert + self.experts_here <= self.n_experts
            assert not (self.expert_parallel and self.experts_held), \
                "expert_parallel and experts_held exclude each other"
        if self.kv_heads != self.n_heads or self.head_width:
            assert self.tp == 1 and self.attn_mode == "heads" \
                and not self.bias and self.n_heads % self.kv_heads == 0
        self.layer_pattern, self.prefix_pattern = (
            tuple(_kind(k) for k in pattern)
            for pattern in (self.layer_pattern, self.prefix_pattern))
        if self.positions is None:
            # no table and no rotation: every attention position says so
            assert self.layer_pattern and not any(
                rotary for _, rotary in self.attention_kinds)
        if self.layer_pattern:
            assert self.positions != "learned" and self.causal \
                and self.tp == self.pp == 1 \
                and (self.n_layers - len(self.prefix_pattern)) \
                % len(self.layer_pattern) == 0
        if self.prefix_pattern:
            assert self.layer_pattern and self.n_experts \
                and self.dense_ffn_hidden and not self.bias
        if self.per_position and not self.n_experts:
            assert self.dense_ffn_hidden
        if self.dense_stack:
            assert not self.bias
        if MAMBA in self.layer_pattern + self.prefix_pattern:
            assert self.d_inner and self.d_state and self.d_conv \
                and self.dt_rank
        if MAMBA2 in self.layer_pattern + self.prefix_pattern:
            assert self.d_state and self.d_conv and self.ssm_groups \
                and self.ssm_heads % self.ssm_groups == 0 \
                and self.d_inner % self.ssm_heads == 0
        # a feed-forward position exists in a single-branch stack alone,
        # which has one and whose layers are the period's (no leading ones,
        # no output norms, every run of length one)
        assert (FFN in self.layer_pattern) == self.single_branch \
            and FFN not in self.prefix_pattern
        assert not self.single_branch or not (
            self.prefix_pattern or self.post_norm or self.run_scan)
        assert self.per_position or not self.run_scan
        if KDA in self.layer_pattern + self.prefix_pattern:
            assert self.kda_heads and self.kda_head_dim and self.d_conv \
                and self.kda_gate_rank and self.kda_chunk > 0 \
                and self.kda_beta_scale in (1.0, 2.0)
        if self.latent:
            assert self.tp == 1 and not (self.bias or self.qk_norm)
            if self.positions == "rotary":
                # every attention position of a pattern rotates
                assert all(rotary for _, rotary in self.attention_kinds)
                assert self.rope_original_max or not (
                    self.rope_factor or self.q_scale_beta)
            else:
                # no rotation at all: nothing that shapes one, no window
                assert self.positions is None and not (
                    self.rope_factor or self.q_scale_beta) \
                    and all(k == (None, False) for k in self.attention_kinds)
            for kind in set(self.layer_kinds + self.prefix_kinds):
                if _is_attention(kind):
                    self.position(kind)[0]._check_latent()
        else:
            assert not (self.heads_held or self.latent_rescale) and not any(
                isinstance(k, AttentionShape)
                for k in self.layer_pattern + self.prefix_pattern)
        if self.shared_ffn_hidden:
            assert self.n_experts and not self.bias
        assert self.attn_gate in (False, True, "head"), self.attn_gate
        if self.attn_gate:
            # RETENTION owns a gate of its own
            assert self.tp == 1 and self.attn_mode == "heads" \
                and not self.bias and RETENTION not in self.layer_pattern
        if self.post_norm:
            # the norm of a branch's output needs the whole row: no tp yet
            assert self.norm == "rms" and self.tp == 1 and not self.bias
        assert self.route_scale == 1.0 or self.n_experts
        if self.loop_passes != 1:
            # the router's values are one pass's, the gate reads an RMS norm
            assert self.loop_passes > 1 and self.causal \
                and self.norm == "rms" and self.tp == self.pp == 1 \
                and not self.n_experts
        assert not self.exit_entropy_coef or self.loop_passes > 1
        if self.block_diffusion:
            # the rule is the packed flash kernels' alone, rows whole tiles
            assert not self.causal and self.tp == self.pp == 1 \
                and self.attn_mode == "heads" and not self.latent \
                and not self.layer_pattern and not self.indexer_heads \
                and self.loop_passes == 1 and self.positions != "learned" \
                and not self.mrope_sections
            if self.mask_token_id < 0:
                self.mask_token_id = self.vocab_size - 1
            assert self.mask_token_id < self.vocab_size
        assert self.mask_embed_gain == 1.0 or self.block_diffusion
        self.mrope_sections = tuple(int(n) for n in self.mrope_sections)
        if self.mrope_sections:
            assert self.positions == "rotary" and not self.latent \
                and len(self.mrope_sections) == 3 \
                and 2 * sum(self.mrope_sections) == self.head_dim
        if self.indexer_heads:
            # the masked flash mode takes a lane block a head, several
            # blocks (the latent form: ``_check_latent``); a stack of
            # several kinds owns its leaves by position
            assert self.causal and self.tp == self.pp == 1 \
                and self.attn_mode == "heads" and not self.bias \
                and (self.latent or self.head_dim % 128 == 0) \
                and (self.per_position or not self.layer_pattern) \
                and self.indexer_dim % 2 == 0 \
                and self.indexer_rope_dim % 2 == 0 \
                and self.indexer_topk > 0 and self.n_experts
            assert self.indexer_query == "input" or (
                self.indexer_query == "latent" and self.q_lora_rank)

    @property
    def head_dim(self):
        return self.head_width or self.hidden // self.n_heads

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def latent(self):
        """Whether attention is the latent form."""
        return self.kv_lora_rank > 0

    def _check_latent(self):
        """A latent position's shape, as the position reads it: every head
        its own key/value head, [nope | shared] wide; rotation of whole
        pairs; a share of the heads inside them; under an indexer heads and
        values of whole lane blocks."""
        assert self.kv_heads == self.n_heads and self.v_head_dim \
            and self.head_dim == self.qk_nope_dim + self.qk_rope_dim \
            and self.qk_rope_dim % 2 == 0 \
            and 0 <= self.first_head \
            and self.first_head + self.heads_here <= self.n_heads, self
        if self.v_head_dim != self.head_dim:
            # the value mode of the flash kernels: no YaRN on that path
            assert not (self.rope_factor or self.q_scale_beta)
        if self.indexer_heads:
            assert self.v_head_dim % 128 == 0

    @property
    def heads_here(self):
        """The heads this device holds of a latent position's."""
        return self.heads_held or self.n_heads

    def position(self, kind):
        """``(the configuration as the attention position ``kind`` reads it,
        (window or None, rotary))``: itself for a plain ``(window, rotary)``,
        a copy with the position's own fields for an ``AttentionShape``; the
        copy's ``indexer_heads`` is 0 where the position has no indexer and
        its ``scope`` the position's ``monitor.devscope`` word (None: by
        the form)."""
        if not isinstance(kind, AttentionShape):
            return self, kind
        at = copy.copy(self)
        vars(at).update(kind.own())
        if not kind.indexer:
            at.indexer_heads = 0
        at.scope = kind.scope
        return at, (kind.window or None, kind.rotary)

    scope = None

    @property
    def attention_kinds(self):
        """``(window or None, rotary)`` of every attention position, the
        leading layers' and one period's."""
        return tuple(self.position(k)[1]
                     for k in self.prefix_kinds + self.layer_kinds
                     if _is_attention(k))

    @property
    def indexer_layers(self):
        """How many of the stack's layers have an indexer."""
        if not self.indexer_heads:
            return 0
        has = [int(bool(self.position(k)[0].indexer_heads))
               if _is_attention(k) else 0
               for k in self.prefix_kinds + self.layer_kinds]
        n = len(self.prefix_kinds)
        return sum(has[:n]) + self.n_periods * sum(has[n:])

    @property
    def experts_here(self):
        return self.experts_held or self.n_experts

    @property
    def layer_kinds(self):
        """(window or None, rotary), or CONV, RETENTION, MAMBA, MAMBA2, KDA
        or FFN, of each layer of one period."""
        if not self.layer_pattern:
            return ((None, self.positions == "rotary"),)
        return _kinds(self.layer_pattern)

    @property
    def prefix_kinds(self):
        """The same of each leading layer."""
        return _kinds(self.prefix_pattern)

    @property
    def per_position(self):
        """Whether the layers own different leaves, so that the tree holds
        them by position of the period (``init_transformer_params``)."""
        return bool(self.prefix_pattern) or any(
            k in _OWN_LEAVES for k in self.layer_pattern)

    @property
    def dense_stack(self):
        """Whether every layer's FFN is the dense gated one
        (``gated_ffn``): a stack without experts that gives its width."""
        return bool(self.dense_ffn_hidden) and not self.n_experts

    @property
    def runs(self):
        """``(first position, kind, length)`` of each run of one period:
        with ``run_scan`` the consecutive positions of one kind, else every
        position a run of its own."""
        runs = []
        for at, kind in enumerate(self.layer_kinds):
            if self.run_scan and runs and runs[-1][1] == kind:
                runs[-1][2] += 1
            else:
                runs.append([at, kind, 1])
        return tuple(tuple(r) for r in runs)

    @property
    def n_periods(self):
        return (self.n_layers - len(self.prefix_pattern)) \
            // len(self.layer_kinds)

    @property
    def ffn_positions(self):
        """Which positions of one period have a feed-forward part: all of
        them, or in a ``single_branch`` stack the FFN positions."""
        return tuple(k == FFN or not self.single_branch
                     for k in self.layer_kinds)

    @property
    def moe_layers(self):
        """Layers whose FFN is the MoE: every feed-forward part but the
        leading layers'."""
        return self.n_periods * sum(self.ffn_positions) if self.n_experts \
            else 0

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layers_per_stage(self):
        assert self.n_layers % self.pp == 0, "n_layers must divide pp"
        return self.n_layers // self.pp


# ---------------------------------------------------------------------------
# Parameter init + sharding specs.  Layer params are stacked with a leading
# [n_layers] dim; under pipeline parallelism that dim is reshaped to
# [pp, layers_per_stage] and sharded over the pp axis.
# ---------------------------------------------------------------------------

def _dense_init(key, fan_in, shape, dtype):
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_transformer_params(key, cfg: TransformerConfig, shardings=None):
    """The parameter tree of ``cfg``'s block.  ``shardings`` (a tree of
    ``NamedSharding`` to match): the leaves are made ON THE MESH by one
    program, each device its own part (an expert-parallel stack, whose
    experts no one device has room to seed whole beside anything else).
    Which leaves exist follows the
    configuration: ``*_bias`` of the norms only with LayerNorm, ``bqkv`` /
    ``bo`` / ``b1`` / ``b2`` only with ``bias``, ``pos_emb`` only with
    learned positions, ``q_norm`` / ``k_norm`` with ``qk_norm``, ``lm_head``
    with an untied head, and the FFN's leaves are either ``w1`` / ``w2``,
    the dense gated FFN's ``w_gate_up`` / ``w_down`` (``dense_stack``) or
    the MoE's ``router`` / ``we_gate_up`` / ``we_down`` (parallel/moe.py;
    the experts' leaves hold ``experts_here`` of them).  ``wq`` / ``wo``
    are n_heads * head_dim wide, ``wk`` / ``wv`` kv_heads * head_dim;
    ``q_norm`` / ``k_norm`` are as wide as their projection, or one head
    wide with ``qk_norm="head"``.  The latent form (``cfg.latent``) has
    ``wq_a`` / ``q_a_norm`` / ``wq_b`` and ``wkv_a`` / ``kv_a_norm`` /
    ``wkv_b`` where the others have ``wq`` / ``wk`` / ``wv``
    (``_latent_qkv``); ``ws_gate_up`` [E, 2Fs] / ``ws_down`` [Fs, E] are the
    shared expert's (``shared_ffn_hidden``); ``wz`` [E, n_heads * head_dim]
    is attention's output gate (``attn_gate``), ``ln1_post_scale`` /
    ``ln2_post_scale`` the output norms' (``post_norm``); ``exit_gate_w`` [E]
    / ``exit_gate_b`` (a scalar), float32, are a looped stack's exit gate
    (``loop_passes``), beside ``lnf_scale``.

    Where every layer has the same leaves (attention layers that differ in
    window and rotary alone) ``params_layers`` is ONE tree stacked [L, ...].
    Where they do not (``cfg.per_position``: a CONV position, leading layers
    with a dense FFN) it holds a tree for each position of the period,
    ``params_layers["p<i>"]`` stacked [n_periods, ...] with the leaves of
    that position's kind (``_position_leaves``), ``prefix_layers["l<i>"]``
    holds each leading layer's, unstacked.  Either way a layer's FFN leaves
    come from ONE rule (``_ffn_leaves``), and the selection biases of the
    MoE layers, where the routing rule has them, are ONE top-level leaf
    ``router_bias`` [moe_layers, n_experts] float32, which takes no
    gradient and which a step moves itself (``moe.balance_bias``)."""
    # the leaves are made eagerly, one small program each: the ledger shows
    # them beneath this phase, which waits for their device time as well
    with compile_ledger().phase("init_params") as labels:
        init = functools.partial(_init_params, cfg=cfg)
        if shardings is not None:
            init = jax.jit(init, out_shardings=shardings)
        params = jax.block_until_ready(init(key))
        labels["leaves"] = len(jax.tree.leaves(params))
    return params


def _init_params(key, cfg):
    E, V, dt = cfg.hidden, cfg.vocab_size, cfg.jdtype
    ks = jax.random.split(key, 12)
    layers = _per_position_layers(ks, cfg) if cfg.per_position \
        else {"params_layers": _stacked_layers(ks, cfg)}
    params = {
        # a lookup averages nothing: where a block reads the un-normed stream
        # (router_input "block": a router before the first norm) the rows
        # are seeded N(0, 1), the scale of the branches' outputs, so that a
        # token's own row and not attention's mean ranks its experts; and
        # where a chip holds a SHARE of the experts that no selection bias
        # balances, whatever its router reads: at the fan-in scale the rows
        # are a sixtieth of attention's output, neighbouring tokens rank the
        # experts alike and a share's rows, and its step's time, follow the
        # seed (PERF.md section 6, PRs 31 and 39)
        "tok_emb": _dense_init(
            ks[1], 1 if cfg.n_experts and (
                cfg.router_input == "block" or _unbalanced_share(cfg)
                or cfg.residual_out_gain != 1.0) else E,
            (V, E), dt),
        "lnf_scale": jnp.ones((E,), jnp.float32),
        **layers,
        **_router_bias(ks[5], cfg),
    }
    if cfg.mask_embed_gain != 1.0:
        params["tok_emb"] = params["tok_emb"].at[cfg.mask_token_id].multiply(
            cfg.mask_embed_gain)
    if cfg.positions == "learned":
        params["pos_emb"] = _dense_init(ks[2], E, (cfg.max_seq, E), dt)
    if cfg.norm == "layer":
        params["lnf_bias"] = jnp.zeros((E,), jnp.float32)
    if not cfg.tie_head:
        params["lm_head"] = _dense_init(ks[3], E, (V, E), dt)
    if cfg.loop_passes > 1:
        # the bias is seeded OFF zero (-1/2) so that a gate that lost its
        # bias computes other numbers: at 0 no comparison could tell.  The
        # logit is -1/2 plus ``h . w``, N(0, 1) over seeds and nearly one
        # value a seed (the normed state is mostly a part every token
        # shares): every exit weighs in, the later ones the more
        params["exit_gate_w"] = _dense_init(ks[6], E, (E,), jnp.float32)
        params["exit_gate_b"] = jnp.full((), -0.5, jnp.float32)
    return params


def _unbalanced_share(cfg):
    """Whether this device holds a share of the router's experts and the
    routing rule has no selection bias that a step moves against the load."""
    from .moe import SIGMOID_BIASED

    return cfg.experts_here < cfg.n_experts and cfg.routing != SIGMOID_BIASED


def _stacked_layers(ks, cfg):
    """``params_layers`` where every layer has the same leaves: ONE tree,
    stacked [n_layers, ...] ([pp, layers_per_stage, ...] under pipeline
    parallelism)."""
    E, F, L = cfg.hidden, cfg.ffn_hidden, cfg.n_layers
    Q, KV = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    O = _heads_out_width(cfg)
    dt = cfg.jdtype

    def stack(fold, fan_in, shape, dtype=dt):
        return jax.vmap(lambda k: _dense_init(
            jax.random.fold_in(k, fold) if fold else k, fan_in, shape, dtype)
        )(jax.random.split(ks[0], L))

    layer = {
        "ln1_scale": jnp.ones((L, E), jnp.float32),
        "wq": stack(0, E, (E, Q)),
        "wk": stack(1, E, (E, KV)),
        "wv": stack(2, E, (E, KV)),
        "wo": stack(3, O, (O, E)),
        "ln2_scale": jnp.ones((L, E), jnp.float32),
    }
    if cfg.norm == "layer":
        layer["ln1_bias"] = jnp.zeros((L, E), jnp.float32)
        layer["ln2_bias"] = jnp.zeros((L, E), jnp.float32)
    if cfg.bias:
        layer["bqkv"] = jnp.zeros((L, 3, E), dt)
        layer["bo"] = jnp.zeros((L, E), dt)
    layer.update(_qk_norm_leaves(cfg, L))
    if cfg.latent:
        for name in ("wq", "wk", "wv")[not cfg.q_lora_rank:]:
            del layer[name]
        layer.update(_latent_leaves(stack, cfg, L))
    layer.update(_branch_leaves(stack, cfg, L, 15, attention=True))
    layer.update(_indexer_leaves(stack, cfg, L))
    if cfg.n_experts:
        layer.update(_ffn_leaves(stack, cfg, 6, dense=False))
    elif cfg.dense_stack:
        layer.update(_ffn_leaves(stack, cfg, 4, dense=True))
    else:
        layer["w1"] = stack(4, E, (E, F))
        layer["w2"] = stack(5, F, (F, E))
        if cfg.bias:
            layer["b1"] = jnp.zeros((L, F), dt)
            layer["b2"] = jnp.zeros((L, E), dt)
    _scale_branch_outputs(layer, cfg)
    if cfg.pp > 1:
        layer = jax.tree.map(
            lambda x: x.reshape((cfg.pp, cfg.layers_per_stage) + x.shape[1:]), layer
        )
    return layer


def _scale_branch_outputs(leaves, cfg):
    """Every branch's OUTPUT projection of ``leaves`` times
    ``cfg.residual_out_gain``, in place."""
    if cfg.residual_out_gain == 1.0:
        return
    for name in ("wo", "w_out", "w_down", "we_down", "ws_down"):
        if name in leaves:
            leaves[name] = (leaves[name].astype(jnp.float32)
                            * cfg.residual_out_gain).astype(cfg.jdtype)


def _heads_out_width(cfg):
    """Rows of attention's ``wo``: the (held) heads' values side by side."""
    if cfg.latent:
        return cfg.heads_here * cfg.v_head_dim
    return cfg.n_heads * cfg.head_dim


def _indexer_leaves(stack, cfg, n):
    """An indexer's five leaves of ``n`` stacked layers (none without one):
    ``wq_idx`` [E or q_lora_rank, Hi * Di] (``indexer_query``), ``wk_idx``
    [E, Di], ``w_idx`` [E, Hi] and the key norm's scale and bias [Di]."""
    if not cfg.indexer_heads:
        return {}
    E, Hi, Di = cfg.hidden, cfg.indexer_heads, cfg.indexer_dim
    rows = cfg.q_lora_rank if cfg.indexer_query == "latent" else E
    return dict(
        wq_idx=stack(16, rows, (rows, Hi * Di)),
        wk_idx=stack(17, E, (E, Di)), w_idx=stack(18, E, (E, Hi)),
        idx_k_norm_scale=jnp.full((n, Di), cfg.qk_norm_gain, jnp.float32),
        idx_k_norm_bias=jnp.zeros((n, Di), jnp.float32))


def _latent_leaves(stack, cfg, n):
    """What the latent form holds where the others have ``wk`` / ``wv``
    (and, with a query latent, ``wq``), of ``n`` stacked layers
    (``stack(fold, fan_in, shape[, dtype])`` seeds a stacked leaf)."""
    E, rq, rkv = cfg.hidden, cfg.q_lora_rank, cfg.kv_lora_rank
    leaves = dict(
        # the latent's columns, then the shared key's
        wkv_a=stack(11, E, (E, rkv + cfg.qk_rope_dim)),
        kv_a_norm=jnp.ones((n, rkv), jnp.float32),
        # head by head [k_nope | v], the held heads'
        wkv_b=stack(12, rkv, (rkv, cfg.heads_here * (cfg.qk_nope_dim
                                                     + cfg.v_head_dim))))
    if rq:
        leaves.update(wq_a=stack(9, E, (E, rq)),
                      q_a_norm=jnp.ones((n, rq), jnp.float32),
                      wq_b=stack(10, rq, (rq, cfg.heads_here * cfg.head_dim)))
    return leaves


def _qk_norm_leaves(cfg, n):
    """``q_norm`` / ``k_norm`` of ``n`` stacked attention layers: as wide as
    the projection, or one head wide with ``qk_norm="head"``; none without
    ``qk_norm``."""
    if not cfg.qk_norm:
        return {}
    widths = (1, 1) if cfg.qk_norm == "head" \
        else (cfg.n_heads, cfg.kv_heads)
    return {name: jnp.full((n, heads * cfg.head_dim), cfg.qk_norm_gain,
                           jnp.float32)
            for name, heads in zip(("q_norm", "k_norm"), widths)}


def _ffn_leaves(stack, cfg, fold, dense):
    """A layer's FFN leaves, the ONE rule of both ways of building the tree
    (``stack(fold, fan_in, shape[, dtype])`` seeds a stacked leaf; ``fold``
    the first of this call's): where ``dense`` the gated FFN's ``w_gate_up``
    [E, 2F] (gate in columns [0, F)) and ``w_down`` [F, E] at F =
    ``dense_ffn_hidden``; else the MoE's ``router`` [E, n_experts] float32
    (it ranks all of them), ``we_gate_up`` / ``we_down`` of the
    ``experts_here`` held and, with ``shared_ffn_hidden``, the shared
    expert's ``ws_gate_up`` [E, 2Fs] / ``ws_down`` [Fs, E]."""
    E = cfg.hidden
    if dense:
        F = cfg.dense_ffn_hidden
        return dict(w_gate_up=stack(fold, E, (E, 2 * F)),
                    w_down=stack(fold + 1, F, (F, E)))
    F, held = cfg.ffn_hidden, cfg.experts_here
    # ungated experts (``expert_gated`` off) hold ONE up matrix, ``we_up``
    # [held, E, F] / ``ws_up`` [E, Fs], where the gated hold two side by side
    up, columns = ("gate_up", 2) if cfg.expert_gated else ("up", 1)
    leaves = {"router": stack(fold, E, (E, cfg.n_experts), jnp.float32),
              "we_" + up: stack(fold + 1, E, (held, E, columns * F)),
              "we_down": stack(fold + 2, F, (held, F, E))}
    if cfg.shared_ffn_hidden:
        Fs = cfg.shared_ffn_hidden
        leaves.update({"ws_" + up: stack(13, E, (E, columns * Fs)),
                       "ws_down": stack(14, Fs, (Fs, E))})
    return leaves


def _branch_leaves(stack, cfg, n, fold, attention):
    """What the configuration adds to the two branches of ``n`` stacked
    layers: the output gate's projection ``wz`` [E, n_heads * head_dim], or
    [E, n_heads] head-wise
    (``attn_gate``; an ``attention`` layer's alone) and the output norms'
    scales ``ln1_post_scale`` / ``ln2_post_scale`` (``post_norm``)."""
    leaves = {}
    if cfg.attn_gate and attention:
        leaves["wz"] = stack(fold, cfg.hidden, (
            cfg.hidden, cfg.n_heads * (1 if cfg.attn_gate == "head"
                                       else cfg.head_dim)))
    if cfg.post_norm:
        # a buffer each: a step donates its state's leaves
        leaves.update({name: jnp.full((n, cfg.hidden), cfg.post_norm_gain,
                                      jnp.float32)
                       for name in ("ln1_post_scale", "ln2_post_scale")})
    return leaves


def _position_leaves(key, cfg, kind, n, dense):
    """The leaves of ``n`` layers of one ``kind``, stacked [n, ...]: the two
    norms' scales; attention's ``wq`` / ``wk`` / ``wv`` / ``wo`` (and
    ``q_norm`` / ``k_norm``), or for CONV ``conv_in`` [E, 3E] (the gates B
    and C and the value, side by side), ``conv_w`` [taps, E] (tap j meets
    position t - taps + 1 + j) and ``conv_out`` [E, E], for RETENTION
    attention's and the gate projection ``wg`` [E, kv_heads] float32 (one
    log-decay a key/value head and token), for MAMBA ``_mamba_leaves``', for
    MAMBA2 ``_mamba2_leaves``', for KDA ``_kda_leaves``'; an attention
    position of the latent form ``_latent_leaves``' where the others have
    ``wk`` / ``wv`` and, where it has one, its indexer's
    (``_indexer_leaves``), all at the position's OWN shape
    (``cfg.position``); what ``_branch_leaves`` adds; then the FFN's
    (``_ffn_leaves``), dense or the MoE's.  In a ``single_branch`` stack a
    position owns its ONE branch's leaves: the mixer's behind ``ln1_scale``,
    or (FFN) the feed-forward part's behind ``ln2_scale``."""
    assert cfg.norm == "rms" and not cfg.bias
    if _is_attention(kind):
        cfg = cfg.position(kind)[0]     # as this position reads it
    E, dt = cfg.hidden, cfg.jdtype
    Q, KV = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    keys = jax.random.split(key, n)

    def stack(fold, fan_in, shape, dtype=dt):
        return jax.vmap(lambda k: _dense_init(
            jax.random.fold_in(k, fold), fan_in, shape, dtype))(keys)

    leaves = {}
    if kind != FFN:
        leaves["ln1_scale"] = jnp.ones((n, E), jnp.float32)
    if kind == CONV:
        leaves.update(conv_in=stack(1, E, (E, 3 * E)),
                      conv_w=stack(2, cfg.conv_taps, (cfg.conv_taps, E)),
                      conv_out=stack(3, E, (E, E)))
    elif kind == MAMBA:
        leaves.update(_mamba_leaves(stack, keys, cfg))
    elif kind == MAMBA2:
        leaves.update(_mamba2_leaves(stack, keys, cfg))
    elif kind == KDA:
        leaves.update(_kda_leaves(stack, keys, cfg))
    elif kind != FFN:
        O = _heads_out_width(cfg)
        projections = dict(wq=(1, Q), wk=(2, KV), wv=(3, KV))
        if cfg.latent and kind != RETENTION:
            # the latent form's own chains stand where these would
            for name in ("wq", "wk", "wv")[not cfg.q_lora_rank:]:
                del projections[name]
        leaves.update({name: stack(fold, E, (E, width))
                       for name, (fold, width) in projections.items()})
        leaves["wo"] = stack(4, O, (O, E))
        leaves.update(_qk_norm_leaves(cfg, n))
        if kind == RETENTION:
            leaves["wg"] = stack(10, E, (E, cfg.kv_heads), jnp.float32)
        else:
            if cfg.latent:
                leaves.update(_latent_leaves(stack, cfg, n))
            leaves.update(_indexer_leaves(stack, cfg, n))
    leaves.update(_branch_leaves(
        stack, cfg, n, 11,
        attention=kind not in (CONV, MAMBA, MAMBA2, FFN, KDA)))
    if kind == FFN or not cfg.single_branch:
        leaves["ln2_scale"] = jnp.ones((n, E), jnp.float32)
        leaves.update(_ffn_leaves(stack, cfg, 5 if dense else 7, dense))
    _scale_branch_outputs(leaves, cfg)
    return leaves


def _mamba_leaves(stack, keys, cfg):
    """The Mamba-1 mixer's leaves of ``len(keys)`` stacked layers: ``w_in``
    [E, 2d] (the scan's input x and the gate z, side by side), ``conv_w``
    [taps, d] (tap j meets position t - taps + 1 + j) and ``conv_b`` [d],
    ``w_x`` [d, R + 2N] (the step sizes' low-rank input, then B, then C),
    the three inner norms' weights ``dt_norm`` [R], ``b_norm`` / ``c_norm``
    [N], ``w_dt`` [R, d] and ``b_dt`` [d], ``a_log`` [d, N], ``d_skip`` [d],
    ``w_out`` [d, E]; the norms, ``b_dt``, ``a_log`` and ``d_skip`` float32.

    Matrices, the filter and its bias at their fan-in's scale; ``a_log`` =
    log(1..N) in every channel and ``d_skip`` = 1 (the published mixer's own
    constructor); ``b_dt`` the inverse softplus of a step size drawn
    log-uniform in [1e-3, 1e-1] (arXiv:2312.00752, section 3.6): a cell's
    decay ``exp(-dt n)`` then runs from 0.999 to 0.2 a token."""
    E, d, N, R = cfg.hidden, cfg.d_inner, cfg.d_state, cfg.dt_rank
    n, f32 = len(keys), jnp.float32
    dt0 = jnp.exp(jax.vmap(lambda k: jax.random.uniform(
        jax.random.fold_in(k, 26), (d,), f32, math.log(1e-3),
        math.log(1e-1)))(keys))
    return dict(
        w_in=stack(20, E, (E, 2 * d)),
        conv_w=stack(21, cfg.d_conv, (cfg.d_conv, d)),
        conv_b=stack(22, cfg.d_conv, (d,)),
        w_x=stack(23, d, (d, R + 2 * N)),
        dt_norm=jnp.ones((n, R), f32), b_norm=jnp.ones((n, N), f32),
        c_norm=jnp.ones((n, N), f32),
        w_dt=stack(24, R, (R, d)),
        b_dt=dt0 + jnp.log(-jnp.expm1(-dt0)),       # softplus^-1(dt0)
        a_log=jnp.tile(jnp.log(jnp.arange(1, N + 1, dtype=f32)), (n, d, 1)),
        d_skip=jnp.ones((n, d), f32),
        w_out=stack(25, d, (d, E)))


def _mamba2_leaves(stack, keys, cfg):
    """The Mamba-2 mixer's leaves of ``len(keys)`` stacked layers, W = d + 2
    G N the filter's channels (x, then each group's B, then each group's C):
    ``w_in`` [E, W + d], the published ``in_proj``'s ``xBC`` columns and then
    its ``z`` columns (the filter's kernel reads a packed projection's FIRST
    lanes in place), ``w_dt`` [E, heads], its step-size columns (a
    projection of its own: 64 columns behind 10,240 would leave the packed
    width no whole lane block); ``conv_w`` [taps, W] (tap j meets position t
    - taps + 1 + j) and ``conv_b`` [W]; ``b_dt``, ``a_log`` and ``d_skip``
    [heads] and ``gate_norm`` [d], float32; ``w_out`` [d, E].

    Matrices, the filter and its bias at their fan-in's scale; ``a_log`` the
    log of a rate drawn uniform in [1, 16] a head and ``d_skip`` = 1 (the
    published mixer's own constructor); ``b_dt`` the inverse softplus of a
    step size drawn log-uniform in [1e-3, 1e-1] and floored at 1e-4 (the
    published ``time_step_min`` / ``_max`` / ``_floor``): a head's decay
    ``exp(-dt rate)`` then runs from 0.999 to 0.2 a token."""
    E, d, nh = cfg.hidden, cfg.d_inner, cfg.ssm_heads
    W = d + 2 * cfg.ssm_groups * cfg.d_state
    n, f32 = len(keys), jnp.float32

    def drawn(fold, low, high):
        return jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, fold), (nh,), f32, low, high))(keys)

    dt0 = jnp.maximum(jnp.exp(drawn(36, math.log(1e-3), math.log(1e-1))),
                      1e-4)
    return dict(
        w_in=stack(30, E, (E, W + d)),
        w_dt=stack(31, E, (E, nh)),
        conv_w=stack(32, cfg.d_conv, (cfg.d_conv, W)),
        conv_b=stack(33, cfg.d_conv, (W,)),
        b_dt=dt0 + jnp.log(-jnp.expm1(-dt0)),       # softplus^-1(dt0)
        a_log=jnp.log(drawn(37, 1.0, 16.0)),
        d_skip=jnp.ones((n, nh), f32),
        gate_norm=jnp.ones((n, d), f32),
        w_out=stack(34, d, (d, E)))


def _kda_leaves(stack, keys, cfg):
    """The KDA mixer's leaves of ``len(keys)`` stacked layers, P = heads x
    head width: ``wq`` / ``wk`` / ``wv`` [E, P] and their filters ``conv_q``
    / ``conv_k`` / ``conv_v`` [taps, P] (tap j meets position t - taps + 1 +
    j; no bias); the decay's gate ``w_fa`` [E, R] / ``w_fb`` [R, P] with
    ``dt_bias`` [P] and ``a_log`` [heads]; ``w_beta`` [E, heads]; the output
    gate ``w_ga`` [E, R] / ``w_gb`` [R, P]; ``o_norm`` [head width], ONE
    scale for every head; ``wo`` [P, E].  ``dt_bias``, ``a_log`` and
    ``o_norm`` float32.

    Matrices and filters at their fan-in's scale; ``a_log`` the log of a
    rate drawn uniform in [1, 16] a head and ``dt_bias`` the inverse softplus
    of a step drawn log-uniform in [1e-3, 1e-1] a channel (the family's
    public constructor, as Mamba-2's): a channel's decay ``exp(-rate step)``
    then runs from 0.999 to 0.2 a token, and in most channels a state
    outlives many chunks."""
    E, nh, R = cfg.hidden, cfg.kda_heads, cfg.kda_gate_rank
    Pw = nh * cfg.kda_head_dim
    n, f32 = len(keys), jnp.float32

    def drawn(fold, shape, low, high):
        return jax.vmap(lambda k: jax.random.uniform(
            jax.random.fold_in(k, fold), shape, f32, low, high))(keys)

    step = jnp.exp(drawn(52, (Pw,), math.log(1e-3), math.log(1e-1)))
    return dict(
        wq=stack(40, E, (E, Pw)), wk=stack(41, E, (E, Pw)),
        wv=stack(42, E, (E, Pw)),
        conv_q=stack(43, cfg.d_conv, (cfg.d_conv, Pw)),
        conv_k=stack(44, cfg.d_conv, (cfg.d_conv, Pw)),
        conv_v=stack(45, cfg.d_conv, (cfg.d_conv, Pw)),
        w_fa=stack(46, E, (E, R)), w_fb=stack(47, R, (R, Pw)),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),      # softplus^-1(step)
        a_log=jnp.log(drawn(53, (nh,), 1.0, 16.0)),
        w_beta=stack(48, E, (E, nh)),
        w_ga=stack(49, E, (E, R)), w_gb=stack(50, R, (R, Pw)),
        o_norm=jnp.ones((n, cfg.kda_head_dim), f32),
        wo=stack(51, Pw, (Pw, E)))


def _router_bias(key, cfg):
    """``{"router_bias": [moe_layers, n_experts] float32}`` where the
    routing rule has selection biases, else nothing."""
    from .moe import SIGMOID_BIASED

    if not cfg.n_experts or cfg.routing != SIGMOID_BIASED:
        return {}
    # seeded off zero, where a trained model's stand: a rule that weighted
    # by them, or left them out of the choice, gives other numbers than the
    # rule.  Each share of ``experts_here`` experts seeds its own from the
    # same key, as the chips of an expert-parallel layer would: every share
    # then holds the same biases and, at seeded weights, draws the same load
    held = cfg.experts_here
    assert cfg.n_experts % held == 0, (cfg.n_experts, held)
    return {"router_bias": jnp.tile(
        cfg.router_bias_std * jax.random.normal(
            key, (cfg.moe_layers, held), jnp.float32),
        (1, cfg.n_experts // held))}


def _per_position_layers(ks, cfg):
    """``prefix_layers`` and ``params_layers`` of a stack whose layers own
    different leaves: a tree ``p<i>`` each position of the period, stacked
    [n_periods, ...], or with ``cfg.run_scan`` a tree ``r<i>`` each run,
    its positions' trees (seeded as they would be alone) stacked behind the
    periods: [n_periods, run length, ...]."""
    # a table of learned positions is no position's leaf; rotary positions,
    # or none at all, are each attention position's own
    assert cfg.positions != "learned"

    def position(i, kind):
        return _position_leaves(jax.random.fold_in(ks[0], i), cfg, kind,
                                cfg.n_periods, dense=not cfg.n_experts)

    if cfg.run_scan:
        stacked = {"r%d" % at: jax.tree.map(
            lambda *a: jnp.stack(a, axis=1),
            *[position(first + i, kind) for i in range(length)])
            for at, (first, kind, length) in enumerate(cfg.runs)}
    else:
        stacked = {"p%d" % i: position(i, kind)
                   for i, kind in enumerate(cfg.layer_kinds)}
    layers = {"params_layers": stacked}
    if cfg.prefix_pattern:
        layers["prefix_layers"] = {
            "l%d" % i: jax.tree.map(lambda a: a[0], _position_leaves(
                jax.random.fold_in(ks[4], i), cfg, kind, 1, dense=True))
            for i, kind in enumerate(cfg.prefix_kinds)}
    return layers


def _param_skeleton(cfg: TransformerConfig):
    """The init_transformer_params tree STRUCTURE without arrays — what the
    sharding rules resolve against when no live params exist yet."""
    from .rules import SkeletonLeaf

    shapes = jax.eval_shape(lambda: init_transformer_params(
        jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda _: SkeletonLeaf(), shapes)


def transformer_param_specs(cfg: TransformerConfig, params=None):
    """PartitionSpec pytree matching init_transformer_params' structure —
    derived from the rule tree (parallel/rules.py transformer_rules), not
    spec literals: the same rules serve the compiler, the checkpoint
    re-sharder, and this builder."""
    from . import rules as shard_rules

    return shard_rules.match_partition_rules(
        shard_rules.transformer_rules(cfg),
        _param_skeleton(cfg) if params is None else params)


def grad_sync_axes(cfg: TransformerConfig):
    """Per-leaf list of mesh axes whose gradient contributions must be summed
    (the explicit-SPMD analogue of the AllReduceOpHandle placement decision,
    details/all_reduce_op_handle.cc:48).  dp for every leaf that is not
    split over it; tp for leaves whose
    params are replicated over tp but fed tp-varying activations (sequence
    parallel shards / ring mode); pp for leaves replicated over pp."""
    specs = transformer_param_specs(cfg)

    def axes(spec_leaf):
        used = {a for part in spec_leaf if part for a in
                ((part,) if isinstance(part, str) else tuple(part))}
        # a leaf split over dp (expert-parallel experts) holds its own
        # experts' whole gradient: the exchange's backward brought it the
        # rows of every device's tokens
        sync = [] if DP in used else [DP]
        if TP not in used:
            sync.append(TP)   # replicated over tp -> partial grads per seq shard
        if PP not in used:
            sync.append(PP)
        return tuple(sync)

    return jax.tree.map(axes, specs, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Per-device forward pieces (inside shard_map)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _gelu_r(x):
    return jax.nn.gelu(x)


def _gelu_r_fwd(x):
    # save only the input; the bwd recomputes the tanh instead of XLA
    # saving ~2x [B,S,F] intermediates — measured -5.5ms/step at bench
    # shapes with bit-identical numerics
    return jax.nn.gelu(x), (x,)


def _gelu_r_bwd(res, dy):
    (x,) = res
    _, vjp = jax.vjp(jax.nn.gelu, x)
    return (vjp(dy)[0],)


_gelu_r.defvjp(_gelu_r_fwd, _gelu_r_bwd)


@devscope.scoped(devscope.LAYER_NORM)
def layer_norm(x, scale, bias, eps=1e-6, fused=True):
    """fused=True dispatches to the one-pass Pallas kernel (fwd + fused bwd);
    XLA's decomposition costs several full HBM passes per direction at bench
    shapes.  Callers whose LN feeds a matmul XLA would otherwise fuse it into
    (e.g. the pre-head final LN, whose bwd fuses with the vocab-chunk
    recompute) pass fused=False — the pallas_call is a fusion barrier."""
    from ..kernels.layer_norm import _pick_bn, fused_layer_norm

    n = 1
    for d in x.shape[:-1]:
        n *= d
    if fused and x.ndim >= 2 and _pick_bn(n) is not None:
        return fused_layer_norm(x, scale, bias, eps=eps)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


@devscope.scoped(devscope.LAYER_NORM)
def rms_norm(x, scale, eps=1e-5):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, in float32.
    Plain XLA: a layer's input norm fuses into the matmuls that read it.  A
    q/k norm does not (a reduction over a reshaped minor dimension fuses
    into neither the matmul that made it nor the kernel that reads it: 9 GB
    a layer with the rotation behind it, PERF.md section 6, PR 47), which is
    why ``_qkv`` takes ``kernels/qk_rope.py`` where the shapes allow."""
    return _rms(x, scale, eps)


def _norm(x, pl, name, cfg, fused=True):
    """The configuration's norm with the leaves ``<name>_scale`` (and
    ``<name>_bias``, LayerNorm only) of ``pl``."""
    if cfg.norm == "rms":
        return rms_norm(x, pl[name + "_scale"], cfg.norm_eps)
    return layer_norm(x, pl[name + "_scale"], pl[name + "_bias"],
                      eps=cfg.norm_eps, fused=fused)


def rope(x, n_heads, theta=10000.0, first=0, positions=None, sections=(),
         period=None):
    """Rotary position embedding on a packed projection x [b, S, H*dh],
    positions ``first``..``first`` + S - 1 (``first`` may be traced: a block
    of rows of a longer sequence), rotate-half convention (the halves of a
    head are the pairs): ``x * cos + rotate_half(x) * sin`` with angle
    ``pos * theta^(-2i/dh)`` for both members of pair i.  float32 inside.
    ``positions`` [streams, b, S]: the angles' positions as DATA, pair i
    from the stream ``sections`` gives it (``qk_rope.stream_angles``).
    ``period``: the positions repeat, row r's is ``r mod period`` (the
    copies of a sequence one under the other)."""
    b, S, W = x.shape
    dh = W // n_heads
    half = dh // 2
    if positions is not None:
        from ..kernels.qk_rope import stream_angles

        ang = stream_angles(positions, half, theta, sections)   # [b,S,half]
        cos = jnp.tile(jnp.cos(ang), (1, 1, 2))[:, :, None, :]
        sin = jnp.tile(jnp.sin(ang), (1, 1, 2))[:, :, None, :]
    else:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        pos = jnp.arange(S, dtype=jnp.float32)
        if not (isinstance(first, int) and first == 0):
            pos = pos + first
        if period:
            pos = pos % period
        ang = pos[:, None] * inv_freq[None]
        cos = jnp.tile(jnp.cos(ang), (1, 2))[None, :, None, :]  # [1,S,1,dh]
        sin = jnp.tile(jnp.sin(ang), (1, 2))[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(b, S, n_heads, dh)
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos + rot * sin).reshape(b, S, W).astype(x.dtype)


def yarn_blend_range(cfg):
    """``(lo, hi)`` of YaRN's blend over the rotated pairs of the latent
    form: the floor / ceiling of the pair that turns ``rope_beta_fast`` /
    ``rope_beta_slow`` times over the ``rope_original_max`` positions,
    inside [0, pairs - 1].  Pairs up to ``lo`` keep their frequency, pairs
    from ``hi`` on are wholly interpolated, a linear ramp between."""
    dim = cfg.qk_rope_dim

    def pair_of(rotations):
        return dim * math.log(cfg.rope_original_max / (
            rotations * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    return (max(math.floor(pair_of(cfg.rope_beta_fast)), 0),
            min(math.ceil(pair_of(cfg.rope_beta_slow)), dim // 2 - 1))


def yarn_frequencies(cfg):
    """The angular frequency of each rotated pair of the latent form,
    float64 [qk_rope_dim / 2].  Plain rotary positions: ``theta^(-j /
    pairs)``.  With ``rope_factor`` > 1 YaRN's blend ``(1 - m_j) * plain_j /
    factor + m_j * plain_j``, ``m_j = 1 - clip((j - lo) / (hi - lo), 0, 1)``
    over ``yarn_blend_range``: a pair that turns often keeps its frequency,
    one that turns less than once is interpolated by the factor."""
    pairs = cfg.qk_rope_dim // 2
    plain = cfg.rope_theta ** (-np.arange(pairs, dtype=np.float64) / pairs)
    if not cfg.rope_factor > 1:
        return plain
    lo, hi = yarn_blend_range(cfg)
    m = 1 - np.clip((np.arange(pairs) - lo) / ((hi - lo) or 1e-3), 0, 1)
    return (1 - m) * plain / cfg.rope_factor + m * plain


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 and mscale \
        else 1.0


def yarn_softmax_scale(cfg):
    """What the latent form's scores are multiplied by beside
    ``head_dim^(-1/2)``: ``mscale(factor, rope_mscale_all_dim)^2``
    (``mscale(f, m) = 0.1 m ln f + 1``), 1 without YaRN."""
    return _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def yarn_rotary_factor(cfg):
    """What YaRN multiplies cos and sin by: ``mscale(factor, rope_mscale)
    / mscale(factor, rope_mscale_all_dim)``."""
    return _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)


def rope_pairs(x, ang, factor=1.0, tiles=1):
    """Rotary positions on x [b, S, ..., d] float32 in the ADJACENT-pair
    convention: columns (2j, 2j + 1) are pair j, rotated by ``ang`` [S, d/2]:
    ``(x0 cos - x1 sin, x0 sin + x1 cos)``, cos and sin times ``factor``.
    In place, by a swap of neighbours: no column leaves its lane.  ``tiles``:
    the last axis is that many heads side by side and ``ang`` [S, d / 2 /
    tiles] one head's (cosine and sine are made once, not once a head)."""
    d = x.shape[-1]
    shape = (1, ang.shape[0]) + (1,) * (x.ndim - 3) + (d,)
    cos = factor * jnp.repeat(jnp.cos(ang), 2, axis=-1)
    sin = factor * jnp.repeat(jnp.sin(ang), 2, axis=-1)
    if tiles > 1:
        cos, sin = (jnp.tile(t, (1, tiles)) for t in (cos, sin))
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even = jnp.arange(d) % 2 == 0
    # the other member of a column's pair, its sign as the rotation has it
    other = jnp.where(even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return x * cos + other * sin


@devscope.scoped(devscope.EMBED)
def embed(params, ids, cfg: TransformerConfig, seq_offset=None):
    """Vocab-parallel embedding lookup + position embedding (learned
    positions; rotary ones are applied to q and k in the block); returns
    the sequence-sharded (SP) activation [b, S/tp, E].

    TP generalization of distributed_lookup_table_op.cc (row-sharded embedding
    over pservers): each tp rank holds a vocab slice, masks out-of-range ids,
    and the psum+sequence-scatter is fused into one reduce_scatter.
    """
    V = cfg.vocab_size
    ntp = col.axis_size_in(TP)
    vshard = V // ntp if ntp > 1 else V
    lo = col.axis_index(TP) * vshard
    local = jnp.clip(ids - lo, 0, vshard - 1)
    hit = (ids >= lo) & (ids < lo + vshard)
    emb = params["tok_emb"][local] * hit[..., None].astype(params["tok_emb"].dtype)
    if cfg.embed_scale != 1.0:
        emb = (emb.astype(jnp.float32) * cfg.embed_scale).astype(emb.dtype)
    if cfg.positions != "learned":
        return col.reduce_scatter(emb, TP, dim=1) if ntp > 1 else emb
    S = ids.shape[1]
    pos = params["pos_emb"][:S][None]
    if ntp > 1:
        # sum the vocab partials and scatter the sequence in one collective
        emb = col.reduce_scatter(emb + pos / ntp, TP, dim=1)
    else:
        emb = emb + pos
    return emb


def _local_attention_dispatch(q, k, v, cfg):
    """Pick the Pallas flash kernel (multihead_matmul_op.cu parity, trained)
    when the shapes satisfy TPU tiling; otherwise the XLA blockwise path.
    The blocks are clamped to S, so S = 4096 causal runs the kernel on an
    8 x 8 grid of 512-blocks (those above the diagonal skipped) and S = 100
    takes the XLA path."""
    S = q.shape[1]
    bq = min(cfg.flash_block_q, S)
    bk = min(cfg.flash_block_k, S)
    if S % bq == 0 and k.shape[1] % bk == 0:
        from ..kernels.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=cfg.causal,
                               block_q=bq, block_k=bk)
    return ring_attention(q, k, v, axis=None, causal=cfg.causal)


def _packed_flash_blocks(cfg, hl, S, kvl=None, widths=None):
    """(block_q, block_k) where attention over ``hl`` local heads (on
    ``kvl`` key/value heads) of S positions goes to the packed flash kernel,
    else None.  ``widths``: the lanes a head of q and k and a head of v
    stand in, where they are not ``cfg.head_dim`` both."""
    from ..kernels.flash_attention import packed_layout_supported

    bq = min(cfg.flash_block_q, S)
    bk = min(cfg.flash_block_k, S)
    qk, v = widths or (cfg.head_dim, None)
    if (S % bq == 0 and S % bk == 0
            and packed_layout_supported(hl, qk, kvl, v)):
        return bq, bk
    return None


def _local_heads(cfg):
    """(query heads, key/value heads) this device holds."""
    if cfg.latent:      # tp == 1: every held head its own key/value head
        return cfg.heads_here, cfg.heads_here
    ntp = col.axis_size_in(TP)
    return (cfg.n_heads // ntp, cfg.kv_heads // ntp) if ntp > 1 \
        else (cfg.n_heads, cfg.kv_heads)


def _qkv(pl, h_full, cfg, rotary, first=0, positions=None):
    """The packed projections q [b, S, hl*dh] and k, v [b, S, kvl*dh] of the
    full sequence ``h_full`` [b, S, E] (or of its rows from position
    ``first`` on), with their biases, the configured q/k norm and, where
    ``rotary``, rotary positions: what attention and power retention both
    start from.  ``positions`` [3, b, S]: the rotation's position streams
    where they are data (``cfg.mrope_sections``).  The latent form:
    ``_latent_qkv``; where its ``rope_pairs``
    lines run, a block of positions at a time where the sequence is long (no
    whole-sequence float32 q stands)."""
    if cfg.latent and (not rotary or cfg.v_head_dim != cfg.head_dim):
        return _latent_qkv_lanes(pl, h_full, cfg, rotary)
    if cfg.latent:
        # ``wkv_b``'s columns are taken apart ONCE a layer, not a block
        columns = _latent_columns(pl["wkv_b"], cfg)
        # the lines' rotation and scale work in float32 (two bf16s a value)
        # on q and k at once; the row kernel's passes leave nothing float32
        # and their backward holds the latents alone, so a long sequence
        # goes whole: no third forward of the chain under the blocks' own
        # checkpoint, no block cut out of a stacked array
        width = cfg.q_lora_rank + cfg.kv_lora_rank + cfg.qk_rope_dim \
            if _latent_fused(cfg, h_full.shape[:2], h_full.dtype.itemsize) \
            else 2 * 3 * cfg.heads_here * cfg.head_dim
        return _by_row_blocks(
            lambda rows, first: _latent_qkv(pl, rows, cfg, first, columns),
            h_full, width)
    b, S, E = h_full.shape
    hl, kvl = _local_heads(cfg)
    dh = cfg.head_dim
    from ..kernels import qk_rope

    # which of q and k the row kernel takes; where it takes either, the
    # three weights' gradients are made beside their inputs' (``_project``)
    fused = [(cfg.qk_norm or rotary) and qk_rope.supported(
        (b, S, n * dh), dh, h_full.dtype.itemsize) for n in (hl, kvl)]
    # params arrive pre-sharded inside shard_map: wq/bqkv are [E, E/tp]/[3, E/tp]
    q2, k2, v2 = ((_project if any(fused) else jnp.matmul)(h_full, pl[w])
                  for w in ("wq", "wk", "wv"))              # [b, S, hl*dh]
    if cfg.bias:
        q2, k2, v2 = (y + pl["bqkv"][i] for i, y in enumerate((q2, k2, v2)))
    if cfg.qk_norm or rotary:
        q2, k2 = _norm_and_rotate(
            (q2, k2), (pl.get("q_norm"), pl.get("k_norm")), (hl, kvl), fused,
            cfg, rotary, first, positions)
    return q2, k2, v2


@jax.custom_vjp
def _project(h, w):
    """``h @ w``, h [b, S, E], whose backward makes the weight's gradient
    WHERE the projection's own gradient is live: left to itself the
    scheduler puts every layer's dW at the end of the step, and a row
    kernel's output (``qk_rope_bwd``'s dx), which no fusion can recompute,
    waits there for it, 75 MB a layer and projection at Trinity's shape."""
    return h @ w


def _project_bwd(res, g):
    h, w = res
    return jax.lax.optimization_barrier(
        (g @ w.T, jnp.einsum("bse,bsf->ef", h, g).astype(w.dtype)))


_project.defvjp(lambda h, w: (h @ w, (h, w)), _project_bwd)


def _norm_and_rotate(xs, weights, heads, fused, cfg, rotary, first,
                     positions=None):
    """The configured q/k norm and, where ``rotary``, rotary positions on
    the packed projections ``xs`` ([b, S, heads * dh] each): ONE pass of the
    row kernel (``kernels/qk_rope.py``) over each that ``fused`` says it
    takes, the ``rms_norm`` / ``rope`` lines, which the tests hold that
    kernel to, over the others.  Under a monitor session every projection
    of a traced call counts in ``monitor.kernels.qk_rope_calls`` (``fused``
    1 for the kernel)."""
    from ..kernels import qk_rope
    from ..kernels._common import count_call

    dh = cfg.head_dim
    norm = cfg.qk_norm and ("head" if cfg.qk_norm == "head" else "whole")
    for took in fused:
        count_call("qk_rope", dh=dh, norm=norm or "none", rotary=int(rotary),
                   convention="half", fused=int(took))
    # under block diffusion both copies of a sequence carry its positions
    period = {"period": xs[0].shape[1] // 2} if cfg.block_diffusion else {}
    tables = qk_rope.angle_tables(
        xs[0].shape[1], dh, cfg.rope_theta, first, positions,
        cfg.mrope_sections, **period) if rotary and any(fused) else None

    def normed(x, weight, n):
        if norm == "head":      # each head on its own, one weight for all
            return rms_norm(x.reshape(x.shape[:2] + (n, dh)), weight,
                            cfg.norm_eps).reshape(x.shape)
        # over the whole projection, before the heads
        return rms_norm(x, weight, cfg.norm_eps) if norm else x

    def kernel(x, w):
        # positions that are data: a table row a (batch row, position), the
        # batch folded into the rows
        rows = x.reshape((1, -1, x.shape[-1])) if positions is not None else x
        return qk_rope.qk_rope(rows, w, tables, head_dim=dh, norm=norm,
                               eps=cfg.norm_eps).reshape(x.shape)

    xs = [kernel(x, w) if took else normed(x, w, n)
          for x, w, n, took in zip(xs, weights, heads, fused)]
    return [rope(x, n, cfg.rope_theta, first, positions, cfg.mrope_sections,
                 **period)
            if rotary and not took else x
            for x, n, took in zip(xs, heads, fused)]


def _latent_columns(wkv_b, cfg):
    """``wkv_b`` [rank, H * (dn + dv)], head i ``[k_nope_i | v_i]`` as
    published, as the two matrices whose products ARE the packed arrays the
    flash kernel reads: the keys' [rank, H * head_dim], head i ``[k_nope_i |
    0]`` (the row kernel adds the rotary key into the zero lanes), and the
    values' [rank, H * dv].  No activation is cut across lanes."""
    H, dn, dr = cfg.heads_here, cfg.qk_nope_dim, cfg.qk_rope_dim
    w = wkv_b.reshape(wkv_b.shape[0], H, dn + cfg.v_head_dim)
    return (jnp.pad(w[..., :dn], ((0, 0), (0, 0), (0, dr))).reshape(
        wkv_b.shape[0], -1), w[..., dn:].reshape(wkv_b.shape[0], -1))


def _latent_head_lanes(cfg):
    """The lanes a head of q and k stands in on its way to the flash
    kernels: ``head_dim``, or, where a value is not that wide, ``head_dim``
    up to whole lane blocks (192 in 256, the last 64 zero: the packed
    kernels' value mode addresses q and k, and v, by lane block)."""
    from ..kernels.flash_attention import LANES

    if cfg.v_head_dim == cfg.head_dim:
        return cfg.head_dim
    return -(-cfg.head_dim // LANES) * LANES


def _latent_norm_scale(pl, name, cfg, rank):
    """The weight of a latent's norm, float32 [rank]: ``pl[name]``, times
    ``(hidden / rank)^(1/2)`` with ``cfg.latent_rescale``."""
    if not cfg.latent_rescale:
        return pl[name]
    return pl[name] * (cfg.hidden / rank) ** 0.5


def _latent_qkv_lanes(pl, h, cfg, rotary=False):
    """The latent form of the whole sequence ``h`` [b, S, E] with a head of
    q and k in WHOLE LANE BLOCKS (``_latent_head_lanes``: what the flash
    kernels' value mode addresses), without positions or with a value
    narrower than the head: ``q = h @ wq`` (off the query latent where there
    is one), head i ``[q_nope_i | q_s_i]``; ``[ckv | ks] = h @ wkv_a``,
    ``rms(ckv) @ wkv_b`` head i ``[k_nope_i | v_i]``; ``k_i = [k_nope_i |
    ks]``, the SAME ``ks`` in every head.  Where ``rotary``, ``q_s_i`` and
    ``ks`` are rotated (``rope_pairs`` at ``yarn_frequencies``, plain: no
    YaRN rides this path), q on the flat array by angles that are zero in
    every lane but a head's ``dr`` rotated ones; nothing is scaled (the
    caller's softmax scale is ``head_dim^(-1/2)``).  The heads are the HELD
    ones (``cfg.heads_here``).  Packed q and k [b, S, H * lanes], zeros
    behind a head's ``head_dim`` columns (the zero columns of ``wq`` and of
    the keys' matrix: no activation is padded or cut across lanes), and v
    [b, S, H * dv].

    Where the row kernel takes the shape (``kernels/qk_rope.py`` at a head of
    whole lane blocks, its ``pairs`` convention), ONE pass over q, where
    ``rotary``, and one over k: the kernel adds ``ks`` into its lanes of
    every head and rotates them as it reads the keys' matmul, a head's
    leading lane blocks that hold neither left where they are, and the
    projections' dW are made beside their dX (``_project``).  Elsewhere the ``rope_pairs``
    lines and the broadcast add, which the tests hold that kernel to.  Under
    a monitor session k, and q where ``rotary``, of a traced call count in
    ``monitor.kernels.qk_rope_calls`` (``fused`` 1 for the kernel)."""
    from ..kernels import qk_rope
    from ..kernels._common import count_call

    b, S, _ = h.shape
    H, dn, dr = cfg.heads_here, cfg.qk_nope_dim, cfg.qk_rope_dim
    lanes = _latent_head_lanes(cfg)
    tail = lanes - dn - dr
    fused = dr % 2 == 0 and lanes % qk_rope.LANES == 0 and qk_rope.supported(
        (b, S, H * lanes), lanes, h.dtype.itemsize)
    for _ in "qk" if rotary else "k":
        count_call("qk_rope", dh=lanes, norm="none", rotary=int(rotary),
                   convention="pairs", fused=int(fused))
    rows, wq = (_rms(h @ pl["wq_a"],
                     _latent_norm_scale(pl, "q_a_norm", cfg, cfg.q_lora_rank),
                     cfg.norm_eps),
                pl["wq_b"]) if cfg.q_lora_rank else (h, pl["wq"])
    if tail:
        wq = jnp.pad(wq.reshape(wq.shape[0], H, dn + dr),
                     ((0, 0), (0, 0), (0, tail))).reshape(wq.shape[0], -1)
    ckv, ks = jnp.split(h @ pl["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    ckv = _rms(ckv, _latent_norm_scale(pl, "kv_a_norm", cfg,
                                       cfg.kv_lora_rank), cfg.norm_eps)
    w = pl["wkv_b"].reshape(-1, H, dn + cfg.v_head_dim)
    k_columns = jnp.pad(w[..., :dn], ((0, 0), (0, 0), (0, dr + tail)))
    v = ckv @ w[..., dn:].reshape(w.shape[0], -1)
    if rotary:
        assert dn % 2 == 0 and tail % 2 == 0, (dn, tail)
        f32 = jnp.float32
        freqs = yarn_frequencies(cfg)
    if fused:
        rotate = functools.partial(qk_rope.qk_rope, head_dim=lanes,
                                   pairs=True,
                                   plain_blocks=dn // qk_rope.LANES)
        tables = qk_rope.pair_tables(S, freqs, lanes, tail=tail) \
            if rotary else None
        # the ONE shared key at its lanes of a head: added into the zeros
        # the keys' matrix leaves there and rotated in the pass that reads k
        k = rotate(_project(ckv, k_columns.reshape(w.shape[0], -1)), None,
                   tables, shared=jnp.pad(ks, ((0, 0), (0, 0), (dn, tail))))
        q = _project(rows, wq)
        return rotate(q, None, tables) if rotary else q, k, v
    if rotary:
        ang = jnp.arange(S, dtype=f32)[:, None] \
            * jnp.asarray(freqs, f32)[None]                     # [S, dr / 2]
        ks = rope_pairs(ks.astype(f32), ang).astype(h.dtype)
    # the ONE shared key in lanes [dn, dn + dr) of every head: added into
    # the zeros the keys' matrix leaves there
    k = (ckv @ k_columns.reshape(w.shape[0], -1)).reshape(b, S, H, lanes) \
        + jnp.pad(ks, ((0, 0), (0, 0), (dn, tail)))[:, :, None, :]
    q = rows @ wq
    if rotary:
        # angle 0 turns nothing: the lanes before and behind the rotated
        q = rope_pairs(q.astype(f32), jnp.pad(
            ang, ((0, 0), (dn // 2, tail // 2))), tiles=H).astype(h.dtype)
    return q, k.reshape(b, S, -1), v


def _latent_fused(cfg, rows, itemsize):
    """Whether the row kernel (``kernels/qk_rope.py``, ``pairs``) takes the
    latent form's q and k of ``rows`` = (b, S): heads of ``dn + dr`` in whole
    lane blocks, ``dr`` whole pairs, values a lane block wide."""
    from ..kernels import qk_rope

    width = cfg.qk_nope_dim + cfg.qk_rope_dim
    return cfg.qk_rope_dim % 2 == 0 and cfg.v_head_dim % qk_rope.LANES == 0 \
        and qk_rope.supported(tuple(rows) + (cfg.heads_here * width,),
                              width, itemsize)


def _latent_qkv(pl, h, cfg, first=0, columns=None):
    """The latent form's packed q, k and v, [b, S, H * head_dim] each, of
    the rows ``h`` [b, S, E] at positions ``first``..: ``q = rms(h @ wq_a)
    @ wq_b``, head i ``[q_nope_i | q_rope_i]``; ``[ckv | kr] = h @ wkv_a``,
    ``rms(ckv) @ wkv_b`` head i ``[k_nope_i | v_i]``; ``kr`` is ONE rotary
    key a token.  ``q_rope_i`` and ``kr`` are rotated (``rope_pairs`` at
    ``yarn_frequencies``), ``k_i = [k_nope_i | rot(kr)]`` with the same
    ``rot(kr)`` in every head, and the whole query head is scaled by what
    the kernel's ``head_dim^(-1/2)`` lacks: ``yarn_softmax_scale`` and the
    position's ``1 + q_scale_beta * ln(1 + pos // rope_original_max)``.
    Rotation and scale in float32.

    Where the row kernel takes the shape (``kernels/qk_rope.py``, its
    ``pairs`` convention: heads of ``dn + dr`` in whole lane blocks), ONE
    pass over q and one over k: the rotation, the factor and the scale are
    in the kernel's tables, k and v come packed out of ``columns``
    (``_latent_columns(wkv_b)``) and the kernel adds ``kr`` into every
    key's last ``dr`` lanes as it rotates them.  Elsewhere the ``rope_pairs``
    lines below, which the tests hold that kernel to.  Under a monitor
    session q and k of a traced call count in ``monitor.kernels.
    qk_rope_calls`` (``convention`` "pairs", ``fused`` 1 for the kernel)."""
    from ..kernels import qk_rope
    from ..kernels._common import count_call

    b, S, _ = h.shape
    H, dn, dr = cfg.heads_here, cfg.qk_nope_dim, cfg.qk_rope_dim
    f32 = jnp.float32
    fused = _latent_fused(cfg, (b, S), h.dtype.itemsize)
    for _ in "qk":
        count_call("qk_rope", dh=dn + dr, norm="none", rotary=1,
                   convention="pairs", fused=int(fused))
    # the latents' norms stay under the caller's scope (``_rms``)
    q = _rms(h @ pl["wq_a"],
             _latent_norm_scale(pl, "q_a_norm", cfg, cfg.q_lora_rank),
             cfg.norm_eps) @ pl["wq_b"]
    ckv, kr = jnp.split(h @ pl["wkv_a"], [cfg.kv_lora_rank], axis=-1)
    ckv = _rms(ckv, _latent_norm_scale(pl, "kv_a_norm", cfg,
                                       cfg.kv_lora_rank), cfg.norm_eps)
    pos = jnp.arange(S, dtype=f32) + first
    freqs = yarn_frequencies(cfg)
    factor = yarn_rotary_factor(cfg)
    scale = jnp.full((S,), yarn_softmax_scale(cfg), f32)
    if cfg.q_scale_beta:
        scale = scale * (1.0 + cfg.q_scale_beta * jnp.log1p(
            jnp.floor(pos / cfg.rope_original_max)))
    if fused:
        k_columns, v_columns = columns or _latent_columns(pl["wkv_b"], cfg)
        rotate = functools.partial(qk_rope.qk_rope, head_dim=dn + dr,
                                   pairs=True)
        q = rotate(q, None, qk_rope.pair_tables(S, freqs, dn + dr, first,
                                                factor, scale))
        # the ONE rotary key in a head's last lanes, every head of a block
        kr = jnp.tile(jnp.pad(kr, ((0, 0), (0, 0), (dn, 0))),
                      qk_rope.LANES // (dn + dr))
        k = rotate(ckv @ k_columns, None,
                   qk_rope.pair_tables(S, freqs, dn + dr, first, factor),
                   shared=kr)
        return q, k, ckv @ v_columns
    q = q.reshape(b, S, H, dn + dr)
    kv = (ckv @ pl["wkv_b"]).reshape(b, S, H, dn + cfg.v_head_dim)
    ang = pos[:, None] * jnp.asarray(freqs, f32)[None]
    q = q.astype(f32)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], ang, factor)],
                        axis=-1) * scale[None, :, None, None]
    kr = rope_pairs(kr.astype(f32)[:, :, None, :], ang, factor)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kr.astype(h.dtype), (b, S, H, dr))], axis=-1)
    return (q.astype(h.dtype).reshape(b, S, -1), k.reshape(b, S, -1),
            kv[..., dn:].reshape(b, S, -1))


@devscope.scoped(devscope.ATTN_GATE)
def _gate_heads(o, z):
    """The heads' output ``o`` [b, S, H * dh] times ``sigmoid(z)``, ``z`` the
    gate's projection of the same shape, or [b, S, H], one scalar a head;
    in float32, rounded once."""
    wide = o.astype(jnp.float32)
    gate = jax.nn.sigmoid(z.astype(jnp.float32))
    if z.shape[-1] != o.shape[-1]:
        gate = jnp.repeat(gate, o.shape[-1] // z.shape[-1], axis=-1)
    return (wide * gate).astype(o.dtype)


def _gated(pl, h, o, cfg):
    """``o`` through the configuration's output gate off the normed rows
    ``h`` (none: ``o``).  Head-wise, the columns of ``wz`` that are this
    share's heads'."""
    if not cfg.attn_gate:
        return o
    if cfg.attn_gate == "head":
        wz = pl["wz"]
        if cfg.latent and cfg.heads_here != cfg.n_heads:
            wz = jax.lax.dynamic_slice_in_dim(wz, cfg.first_head,
                                              cfg.heads_here, axis=1)
        return _gate_heads(o, h @ wz)
    # the gate's projection is as wide as q: its dW beside its dx too
    return _gate_heads(o, _project(h, pl["wz"]))


def indexer_operands(pl, h, cfg, positions=None):
    """What the indexer's scores are made of, from the normed rows ``h`` [b,
    S, E]: its queries [b, S, Hi * Di] and its ONE key head [b, S, Di]
    (LayerNorm, scale and bias), both rotated over all Di columns by the
    temporal stream (the token index where ``positions`` [3, b, S] is None)
    or over their first ``cfg.indexer_rope_dim``, and the heads' weights [b,
    S, Hi] float32, ``(h w_idx) Hi^(-1/2) Di^(-1/2)``.  With
    ``cfg.indexer_query`` "latent" the queries come off the normed query
    latent ``rms(h wq_a)``."""
    Hi, Di = cfg.indexer_heads, cfg.indexer_dim
    k = (h @ pl["wk_idx"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    k = (k * pl["idx_k_norm_scale"] + pl["idx_k_norm_bias"]).astype(h.dtype)
    temporal = None if positions is None else positions[:1]
    rows = h
    if cfg.indexer_query == "latent":
        # the normed query latent, made again from the constant rows: the
        # indexer's loss term reaches neither ``wq_a`` nor its norm
        rows = jax.lax.stop_gradient(_rms(
            h @ pl["wq_a"],
            _latent_norm_scale(pl, "q_a_norm", cfg, cfg.q_lora_rank),
            cfg.norm_eps))
    q = rows @ pl["wq_idx"]
    if cfg.indexer_rope_dim in (0, Di):
        q, k = (rope(x, n, cfg.rope_theta, positions=temporal)
                for x, n in ((q, Hi), (k, 1)))
    else:
        assert temporal is None, "a partly rotated indexer: the token index"
        q, k = (_rope_first_columns(x, Di, cfg.indexer_rope_dim,
                                    cfg.rope_theta) for x in (q, k))
    w = (h @ pl["w_idx"]).astype(jnp.float32) * (Hi ** -0.5 * Di ** -0.5)
    return q, k, w


def _rope_first_columns(x, dh, dr, theta):
    """``rope`` (rotate-half, pair i of a head is columns (i, i + dr / 2),
    angle ``pos * theta^(-2i / dr)``) over the FIRST ``dr`` columns of each
    head of the packed x [b, S, heads * dh]; the others as they are.  Where
    a head is one lane block and ``dr`` half of it, ONE pass of the row
    kernel (``kernels/qk_rope.py`` at heads of ``dr``, whose tables turn
    nothing in a block's second half); elsewhere the ``rope`` line on the
    heads' first columns, which the tests hold that kernel to."""
    from ..kernels import qk_rope
    from ..kernels._common import count_call

    b, S, W = x.shape
    fused = dh == qk_rope.LANES == 2 * dr and qk_rope.supported(
        x.shape, dr, x.dtype.itemsize)
    count_call("qk_rope", dh=dh, norm="none", rotary=1, convention="half",
               fused=int(fused))
    if fused:
        cos, sin = qk_rope.angle_tables(S, dr, theta)
        still = jnp.arange(qk_rope.LANES) >= dr
        return qk_rope.qk_rope(
            x, None, (jnp.where(still, 1.0, cos), jnp.where(still, 0.0, sin)),
            head_dim=dr)
    heads = x.reshape(b, S, W // dh, dh)
    turned = rope(heads[..., :dr].reshape(b, S, -1), W // dh, theta)
    return jnp.concatenate([turned.reshape(b, S, W // dh, dr),
                            heads[..., dr:]], axis=-1).reshape(b, S, W)


# a learned-sparse layer's residuals under remat, by name: the selection's
# thresholds [b, S], the selected keys' normaliser [b, S] and the masked
# attention's statistic [b, H, S]
DSA_TAU, DSA_LSE_I, DSA_LSE = "dsa_tau", "dsa_lse_i", "dsa_lse"
DSA_KEPT = (DSA_TAU, DSA_LSE_I, DSA_LSE)


def _selection(pl, h, cfg, positions=None):
    """``indexer_selection`` and two more: the selected keys' normaliser [b,
    S], ``log sum over S_t of exp(scores)``, which the indexer's loss term
    reads (as the thresholds a constant of the step and a layer's residual),
    and the scores' operands (``indexer_operands``), where the loss term's
    gradient goes."""
    from jax.ad_checkpoint import checkpoint_name

    from ..kernels import indexer as ix

    bq, bk = _clamped_blocks(cfg, h.shape[1])
    with jax.named_scope(devscope.INDEXER):
        operands = indexer_operands(pl, h, cfg, positions)
        scores = jax.lax.stop_gradient(ix.indexer_scores(
            *operands, block_q=bq, block_k=bk))
    with jax.named_scope(devscope.INDEXER_SELECT):
        tau = checkpoint_name(ix.kth_largest(scores, cfg.indexer_topk),
                              DSA_TAU)
    with jax.named_scope(devscope.INDEXER_KL):
        lse_i = checkpoint_name(ix.selected_lse(scores, tau), DSA_LSE_I)
    return scores, tau, lse_i, operands


def indexer_selection(pl, h, cfg, positions=None):
    """(scores [b, S, S] float32, thresholds [b, S]) of a layer's indexer on
    the normed rows ``h``: query t reads the causal keys whose score is at
    or above its threshold, the ``indexer_topk``-th largest of its row.
    Both are constants of the step (no gradient: the scores' only way on is
    ``dsa_attend_kl``'s own) and the thresholds, under a layer's remat, its
    residual: the second forward does not select again."""
    return _selection(pl, h, cfg, positions)[:2]


def _clamped_blocks(cfg, S):
    """The flash kernels' (q block, kv block), clamped to S."""
    return min(cfg.flash_block_q, S), min(cfg.flash_block_k, S)


def _sparse_attention(pl, h, cfg, rotary, positions):
    """Learned-sparse attention's branch [b, S, E] and the indexer's loss
    term (a scalar), then ``wo``.  The masked online sweep (``dsa_lse``)
    gives each head's statistic and nothing else, a constant and the
    layer's residual; ONE pass with that statistic known
    (``dsa_attend_kl``) makes the attention's output and the loss term, so
    a layer's second forward under remat is that pass and no online
    softmax.  The cross entropy reaches q, k and v alone (the mask passes
    no gradient); the KL term reaches the indexer's leaves alone (its
    target, the heads' mean probabilities, and the indexer's input are
    constants)."""
    from jax.ad_checkpoint import checkpoint_name

    from ..kernels import indexer as ix
    from ..kernels._common import count_call

    hl, kvl = _local_heads(cfg)
    bq, bk = _clamped_blocks(cfg, h.shape[1])
    shape, values = dict(block_q=bq, block_k=bk), {}
    if cfg.latent:
        # a head of q and k in whole lane blocks (zeros behind its own
        # columns), a value of its own width
        shape.update(scale=cfg.head_dim ** -0.5)
        values.update(v_head_dim=cfg.v_head_dim)
    q2, k2, v2 = _qkv(pl, h, cfg, rotary, positions=positions)
    scores, tau, lse_i, indexer = _selection(pl, jax.lax.stop_gradient(h),
                                             cfg, positions)
    with jax.named_scope(devscope.SPARSE_ATTN):
        # named as the dense [b, H, S]: a minor dimension of 1 may stand
        # padded to a lane tile (268 MB a layer where this is 2)
        lse = checkpoint_name(ix.dsa_lse(q2, k2, scores, tau, hl, kvl,
                                         **shape), DSA_LSE)
        o, kl = ix.dsa_attend_kl(q2, k2, v2, indexer, scores, tau, lse,
                                 lse_i, hl, kvl, **shape, **values)
    count_call("dsa_attend_kl")
    return _gated(pl, h, o, cfg) @ pl["wo"], kl


def _attention_heads_mode(pl, h_full, cfg, kind, positions=None):
    """Megatron attention: input full-sequence [b,S,E], heads sharded over tp.
    ``kind`` = (window or None, rotary) of this layer.  ``positions`` [3, b,
    S]: the rotation's position streams where they are data."""
    b, S, E = h_full.shape
    hl, kvl = _local_heads(cfg)
    dh = cfg.head_dim
    window, rotary = kind
    q2, k2, v2 = _qkv(pl, h_full, cfg, rotary, positions=positions)
    two_widths = cfg.latent and cfg.v_head_dim != dh
    blocks = not two_widths and _packed_flash_blocks(cfg, hl, S, kvl)
    if two_widths:
        o = _attend_two_widths(q2, k2, v2, cfg, window)
    elif blocks:
        # packed layout: the kernel reads each head's column slice in place —
        # no [b, hl, S, dh] transpose round-trips (flash_attention_packed)
        from ..kernels.flash_attention import flash_attention_packed

        # the block-diffusion rule, where the rows are the two copies
        rule = {"block_diffusion": cfg.block_diffusion} \
            if cfg.block_diffusion else {}
        o = flash_attention_packed(q2, k2, v2, hl, causal=cfg.causal,
                                   block_q=blocks[0], block_k=blocks[1],
                                   n_kv_heads=kvl, window=window, **rule)
    else:
        q = q2.reshape(b, S, hl, dh)
        k = k2.reshape(b, S, kvl, dh)
        v = v2.reshape(b, S, kvl, dh)
        assert kvl == hl and window is None and not cfg.block_diffusion, \
            "grouped queries, a window and the block-diffusion rule run on " \
            "the packed flash kernel only (flash_attention." \
            "packed_layout_supported: a lane block of whole heads that " \
            "share one key/value head; S whole blocks)"
        o = _local_attention_dispatch(q, k, v, cfg).reshape(b, S, hl * dh)
    o = _gated(pl, h_full, o, cfg)
    out = o @ pl["wo"]                                          # row-parallel partial
    out = col.reduce_scatter(out, TP, dim=1)                    # sum + seq scatter
    return out + pl["bo"] if cfg.bias else out


def _attend_two_widths(q2, k2, v2, cfg, window=None):
    """Causal attention (under ``window``, where given) of the latent form
    where a value is not as wide as a head of q and k: q2, k2 [b, S, H * lanes] (``_latent_head_lanes``,
    zeros behind ``head_dim``), v2 [b, S, H * dv]; the heads' outputs [b, S,
    H * dv].  The packed flash kernels' value mode (scores over the lanes,
    whose zeros add nothing, at ``head_dim^(-1/2)``; ``P V``, ``dP`` and
    ``dV`` at dv) where it takes the shapes, else plain blockwise
    attention."""
    b, S, _ = q2.shape
    H, dv = cfg.heads_here, cfg.v_head_dim
    lanes = q2.shape[-1] // H
    scale = cfg.head_dim ** -0.5
    blocks = _packed_flash_blocks(cfg, H, S, widths=(lanes, dv))
    if blocks:
        from ..kernels.flash_attention import flash_attention_packed

        return flash_attention_packed(
            q2, k2, v2, H, causal=cfg.causal, scale=scale, block_q=blocks[0],
            block_k=blocks[1], v_head_dim=dv, **(
                {} if window is None else {"window": window}))
    assert window is None, "a window runs on the packed flash kernel only"
    o = ring_attention(q2.reshape(b, S, H, lanes), k2.reshape(b, S, H, lanes),
                       v2.reshape(b, S, H, dv), axis=None, causal=cfg.causal,
                       scale=scale)
    return o.reshape(b, S, H * dv)


def _attention_ring_mode(pl, h_sp, cfg):
    """Context-parallel attention: sequence stays sharded; K/V ring-rotate."""
    b, Sl, E = h_sp.shape
    dh = cfg.head_dim
    H = cfg.n_heads

    def proj(w, i):
        y = h_sp @ pl[w]
        return (y + pl["bqkv"][i] if cfg.bias else y).reshape(b, Sl, H, dh)

    q, k, v = proj("wq", 0), proj("wk", 1), proj("wv", 2)
    o = ring_attention(q, k, v, axis=TP, causal=cfg.causal)
    o = o.reshape(b, Sl, H * dh) @ pl["wo"]
    return o + pl["bo"] if cfg.bias else o


@devscope.scoped(devscope.SHORT_CONV)
def short_conv(pl, h):
    """The gated short convolution on ``h`` [b, S, E], the whole sequence:
    ``(C * conv(B * z)) @ conv_out`` with ``[B, C, z] = split3(h @
    conv_in)`` and ``conv(v)_t = sum_j conv_w[j] * v[t - taps + 1 + j]``, a
    causal depthwise filter whose input is zero before position 0.  The
    filter and the two gates in float32, rounded once before ``conv_out``.

    Plain ``jnp``: the taps are shifts of the sequence axis, and XLA fuses
    them with the gates into the elementwise pass between the two matmuls
    (PERF.md section 6, PR 33, has the trace that left it so)."""
    taps = pl["conv_w"].astype(jnp.float32)
    gate_b, gate_c, z = jnp.split(h @ pl["conv_in"], 3, axis=-1)
    v = gate_b.astype(jnp.float32) * z.astype(jnp.float32)
    n, S = taps.shape[0], h.shape[1]
    conv = taps[n - 1] * v
    for back in range(1, n):        # tap n-1-back meets position t - back
        conv = conv + taps[n - 1 - back] * jnp.pad(
            v, ((0, 0), (back, 0), (0, 0)))[:, :S]
    return (gate_c.astype(jnp.float32) * conv).astype(h.dtype) \
        @ pl["conv_out"]


def retention_log_decay(pl, h):
    """The log-decay of every token and key/value head, [b, S, kv_heads]
    float32: ``logsigmoid(h @ wg)``, in (-inf, 0)."""
    return jax.nn.log_sigmoid(
        h.astype(jnp.float32) @ pl["wg"].astype(jnp.float32))


@devscope.scoped(devscope.RETENTION)
def power_retention(pl, h, cfg):
    """Power retention of degree 2 on ``h`` [b, S, E], the whole sequence,
    by the carried-state algorithm (``kernels/power_retention.py``: chunks
    of ``cfg.retention_chunk`` tokens, clamped to S): attention's
    projections, q/k norm, rotary positions and grouping, weights
    ``(q.k / sqrt(dh))^2`` decayed by the gate's running sum in place of
    the softmax, the sum of the weights (+ eps) as normaliser; then
    ``wo``."""
    from ..kernels.power_retention import power_retention as kernel

    def operands(rows, first):
        return _qkv(pl, rows, cfg, True, first) \
            + (retention_log_decay(pl, rows),)

    # the norm and the rotation work in float32 (two bf16s a value) on all
    # three projections at once
    width = (cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim
    q2, k2, v2, log_decay = _by_row_blocks(operands, h, 2 * width)
    o = kernel(q2, k2, v2, log_decay,
               chunk=min(cfg.retention_chunk, h.shape[1]))
    return o @ pl["wo"]


def _mamba_step_sizes(pl, x, cfg):
    """Of the convolved rows ``x`` [b, S, d]: the step sizes ``dt`` [b, S,
    d] and the scan's ``B`` and ``C`` [b, S, N], float32: ``[delta, B, C] =
    split(x @ w_x)``, each RMS-normed by its own weight, ``dt =
    softplus(delta @ w_dt + b_dt)``."""
    R, N, f32 = cfg.dt_rank, cfg.d_state, jnp.float32
    # 192 columns: kept in float32 on their way to the norms
    delta, bmat, cmat = jnp.split(
        jnp.matmul(x, pl["w_x"], preferred_element_type=f32), [R, R + N],
        axis=-1)
    delta, bmat, cmat = (_rms(t, pl[w], cfg.norm_eps) for t, w in (
        (delta, "dt_norm"), (bmat, "b_norm"), (cmat, "c_norm")))
    dt = jax.nn.softplus(jnp.matmul(
        delta.astype(x.dtype), pl["w_dt"], preferred_element_type=f32)
        + pl["b_dt"])
    return dt, bmat, cmat


def mamba_operands(pl, h, cfg, rows, first):
    """What the selective scan reads of the positions ``first``.. of ``h``
    [b, S, E] (``rows``, a block of them or all): the convolved x, the gate
    z (or the packed projection ``[x | z]`` that holds it), ``_mamba_step_
    sizes``.  The filter reaches ``d_conv - 1`` tokens back: a block past
    the first projects those rows of ``h`` again, its halo.  The filter, its
    bias and ``silu`` are ONE kernel each way on the packed projection's own
    x half where ``kernels/mamba_filter.py`` takes the shapes, and the
    ``jnp`` lines (its reference) elsewhere."""
    from ..kernels import mamba_filter as mf
    from ..kernels._common import count_call

    halo, d = cfg.d_conv - 1, cfg.d_inner
    whole = isinstance(first, int) and first == 0
    before = None                                   # the whole sequence
    if not whole:
        back = jax.lax.dynamic_slice_in_dim(
            h, jnp.maximum(first - halo, 0), halo, axis=1)
        # the first block's halo lies before position 0: zeros
        before = jnp.where(first > 0, (back @ pl["w_in"][:, :d])
                           .astype(jnp.float32), 0.0)
    fused = mf.supported(rows.shape[:2] + (d,), cfg.d_conv,
                         rows.dtype.itemsize)
    count_call("mamba_filter", fused=int(fused),
               halo="zeros" if whole else "rows")
    xz = rows @ pl["w_in"]
    if fused:
        x = mf.mamba_filter(xz, pl["conv_w"], pl["conv_b"], before, width=d)
        # the whole sequence's gate stays where it is: the scan's kernels
        # read z's lanes of the packed projection (no copy of them)
        z = xz if whole else xz[..., d:]
    else:
        x, z = jnp.split(xz, 2, axis=-1)
        x = mf.mamba_filter_reference(x, pl["conv_w"], pl["conv_b"], before)
    return (x, z) + _mamba_step_sizes(pl, x, cfg)


@devscope.scoped(devscope.MAMBA)
def mamba_mixer(pl, h, cfg):
    """The Mamba-1 mixer on ``h`` [b, S, E], the whole sequence: ``[x, z] =
    split(h @ w_in)``; ``x = silu(conv(x) + conv_b)``, a causal depthwise
    filter of ``d_conv`` taps whose input is zero before position 0; step
    sizes, B and C off x (``_mamba_step_sizes``); the selective scan at the
    rates ``-exp(a_log)`` with the skip ``d_skip`` and the gate ``silu(z)``
    (``kernels/selective_scan.py``, chunks of ``cfg.scan_chunk`` tokens
    clamped to S; the per-token scan in ``jnp`` where the kernels do not
    take the shapes); then ``w_out``.  The filter, the norms, the step sizes
    and the scan's state in float32.  What lies between the projections and
    the scan runs a block of positions at a time where the activations are
    large (``_by_row_blocks``)."""
    from ..kernels import selective_scan as scan
    from ..kernels._common import count_call

    # the stage's widest activation is the projection [x | z]
    x, z, dt, bmat, cmat = _by_row_blocks(
        lambda rows, first: mamba_operands(pl, h, cfg, rows, first), h,
        2 * cfg.d_inner)
    chunk = min(cfg.scan_chunk, h.shape[1])
    kernel = scan.supported(x.shape, cfg.d_state, chunk)
    # door: how the per-token operands reach the scan: the kernels read the
    # projections' own tiles; the per-token scan transposes copies
    count_call("selective_scan", fused=int(kernel),
               door="tiles" if kernel else "copied")
    z_at = z.shape[-1] // cfg.d_inner - 1       # behind x where packed
    if not kernel:
        z = z[..., z_at * cfg.d_inner:]
    with jax.named_scope(devscope.SELECTIVE_SCAN):
        operands = (x, dt, bmat, cmat, z, -jnp.exp(pl["a_log"]), pl["d_skip"])
        y = scan.selective_scan(*operands, chunk=chunk, z_at=z_at) if kernel \
            else scan.selective_scan_reference(*operands)
    return y @ pl["w_out"]


def mamba2_operands(pl, h, cfg):
    """What the Mamba-2 scan reads of ``h`` [b, S, E], the whole sequence:
    the filtered channels ``xBC = silu(conv(.) + conv_b)`` [b, S, W] (x in
    the first ``d_inner`` lanes, then each group's B, then each group's C),
    the packed projection ``[xBC | z]`` that holds the gate behind them, and
    the step sizes ``softplus(h @ w_dt + b_dt)`` [b, S, heads] float32.  The
    filter, its bias and ``silu`` are ``kernels/mamba_filter.py``'s one pass
    each way on the packed projection's own first lanes where it takes the
    shapes, and the ``jnp`` lines (its reference) elsewhere."""
    from ..kernels import mamba_filter as mf
    from ..kernels._common import count_call

    W = cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state
    packed = h @ pl["w_in"]
    fused = mf.supported(h.shape[:2] + (W,), cfg.d_conv,
                         h.dtype.itemsize) \
        and packed.shape[-1] % mf.block_lanes(W) == 0
    count_call("mamba_filter", fused=int(fused), halo="zeros")
    if fused:
        xbc = mf.mamba_filter(packed, pl["conv_w"], pl["conv_b"], width=W)
    else:
        xbc = mf.mamba_filter_reference(packed[..., :W], pl["conv_w"],
                                        pl["conv_b"])
    dt = jax.nn.softplus(jnp.matmul(
        h, pl["w_dt"], preferred_element_type=jnp.float32) + pl["b_dt"])
    return xbc, packed, dt


@devscope.scoped(devscope.MAMBA2)
def mamba2_mixer(pl, h, cfg):
    """The Mamba-2 mixer (Dao and Gu, arXiv:2405.21060) on ``h`` [b, S, E],
    the whole sequence: ``[xBC | z] = h @ w_in`` and the step sizes off
    ``w_dt`` (``mamba2_operands``); for head i of group g, at the rate ``A_i
    = -exp(a_log_i)``, the state ``H_t = exp(dt_t A_i) H_{t-1} + dt_t x_t (x)
    B_t`` [head width, d_state] float32 and ``y_t = H_t C_t + d_skip_i x_t``
    (``kernels/ssd_scan.py``, the chunked dual form in chunks of
    ``cfg.scan_chunk`` tokens clamped to S; the same form in ``jnp`` where
    the kernels do not take the shapes); the gate BEFORE the norm, ``y *
    silu(z)``, RMS-normed over each group's ``d_inner / ssm_groups``
    channels by ``gate_norm`` (``kernels/gated_norm.py``'s one pass each way
    on y and the packed projection's own z lanes where it takes the shapes,
    and the ``jnp`` lines, its reference, elsewhere); then ``w_out``.
    Filter, step sizes, state and norm in float32."""
    from ..kernels import gated_norm as gn, ssd_scan as ssd
    from ..kernels._common import count_call

    d, G, N, nh = cfg.d_inner, cfg.ssm_groups, cfg.d_state, cfg.ssm_heads
    xbc, packed, dt = mamba2_operands(pl, h, cfg)
    chunk = min(cfg.scan_chunk, h.shape[1])
    kernel = ssd.supported(xbc.shape, nh, G, N, chunk)
    count_call("ssd_scan", fused=int(kernel))
    with jax.named_scope(devscope.SSD_SCAN):
        y = (ssd.ssd_scan if kernel else ssd.ssd_scan_chunked)(
            xbc, dt, -jnp.exp(pl["a_log"]), pl["d_skip"], heads=nh,
            groups=G, d_state=N, chunk=chunk)
    fused = gn.supported(y.shape, G, packed.shape[-1], y.dtype.itemsize)
    count_call("gated_norm", fused=int(fused))
    if fused:
        normed = gn.gated_norm(y, packed, pl["gate_norm"], groups=G,
                               eps=cfg.norm_eps)
    else:
        normed = gn.gated_norm_reference(y, packed[..., -d:],
                                         pl["gate_norm"], G, cfg.norm_eps)
    return normed @ pl["w_out"]


def _kda_filtered(pl, h, cfg, name):
    """``silu(filter(h @ w<name>))`` [b, S, P]: one of the KDA mixer's three
    projections through its causal depthwise filter (``d_conv`` taps, no
    bias, zero before position 0) and ``silu``: ``kernels/mamba_filter.py``'s
    one pass each way where it takes the shapes (a zero bias), its ``jnp``
    reference elsewhere."""
    from ..kernels import mamba_filter as mf
    from ..kernels._common import count_call

    x = h @ pl["w" + name]
    taps = pl["conv_" + name]
    fused = mf.supported(x.shape, cfg.d_conv, x.dtype.itemsize)
    count_call("mamba_filter", fused=int(fused), halo="zeros")
    bias = jnp.zeros((x.shape[-1],), jnp.float32)
    return (mf.mamba_filter if fused else mf.mamba_filter_reference)(
        x, taps, bias)


def kda_log_decay(pl, h, cfg):
    """The log-decay of every token, head and key channel, [b, S, heads,
    head width] float32: ``-exp(a_log) * softplus((h @ w_fa) @ w_fb +
    dt_bias)``, in (-inf, 0)."""
    from ..kernels import kda_rows

    return kda_rows.log_decay_reference(jnp.matmul(
        h @ pl["w_fa"], pl["w_fb"], preferred_element_type=jnp.float32),
        pl["dt_bias"], pl["a_log"])


def kda_write_strength(pl, h, cfg):
    """The write strength of every token and head, [b, S, heads] float32:
    ``sigmoid(h @ w_beta)`` in (0, 1), times ``cfg.kda_beta_scale`` where
    that is not 1 (2: in (0, 2), the transition along the key ``1 - beta``
    in (-1, 1))."""
    beta = jax.nn.sigmoid(jnp.matmul(
        h, pl["w_beta"], preferred_element_type=jnp.float32))
    return beta if cfg.kda_beta_scale == 1.0 else cfg.kda_beta_scale * beta


def _kda_flat(pl, h, cfg, q, k, v):
    """``kda_mixer`` behind its filters on the FLAT arrays [b, S, heads x
    128], a head a lane tile from the filters' outputs to ``wo``'s input:
    ``kernels/kda_rows.py``'s one pass each way for the two L2 norms, the
    log-decays and the norm-then-gate, ``kernels/kda_chunk.py``'s kernels
    between them (which keep the operands, as the layer's remat does anyway,
    and a state and a solve a STACK of 128 rows: 268 + 134 MB at [16384, 32 x
    128], where a state a chunk was 537).  No [b, S, heads, d]
    view of a sequence-sized array: on the chip that view is a copy."""
    from ..kernels import kda_chunk, kda_rows

    nh, d = cfg.kda_heads, cfg.kda_head_dim
    q = kda_rows.l2_heads(q, scale=d ** -0.5)
    k = kda_rows.l2_heads(k, scale=1.0)
    beta = kda_write_strength(pl, h, cfg)
    with jax.named_scope(devscope.KDA_CHUNK):
        # the decays stand under the delta rule's scope, as
        # ``kda_log_decay`` does on the other path
        g = kda_rows.log_decay(jnp.matmul(
            h @ pl["w_fa"], pl["w_fb"], preferred_element_type=jnp.float32),
            pl["dt_bias"], pl["a_log"])
        o = kda_chunk.kda_chunk(q, k, v, g, beta, heads=nh,
                                chunk=cfg.kda_chunk,
                                over_one=cfg.kda_beta_scale > 1.0)
    return kda_rows.norm_gate(o, jnp.matmul(
        h @ pl["w_ga"], pl["w_gb"], preferred_element_type=jnp.float32),
        pl["o_norm"], eps=cfg.norm_eps)


@devscope.scoped(devscope.KDA)
def kda_mixer(pl, h, cfg):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) on ``h`` [b, S,
    E], the whole sequence: q, k and v each ``silu(filter(h @ w))``
    (``_kda_filtered``), per head ``q / |q| * d^(-1/2)`` and ``k / |k|``; the
    log-decays ``kda_log_decay`` (one a CHANNEL of the key) and the write
    strengths ``kda_write_strength`` (one a head; ``sigmoid(h @ w_beta)``
    times ``kda_beta_scale``), float32; the delta rule
    on a [d, d] state a head (``kernels/kda_chunk.py``, chunks of
    ``cfg.kda_chunk`` tokens); each head's output RMS-normed by the ONE
    scale ``o_norm`` and THEN gated by ``sigmoid((h @ w_ga) @ w_gb)`` (the
    order Mamba-2's gated norm does not have); then ``wo``.  Norms, decays,
    gates and the state in float32.  Where a head is one lane tile and the
    delta rule takes its kernels, all of it between the filters and ``wo``
    runs on the flat arrays (``_kda_flat``); elsewhere the ``jnp`` lines
    below feed ``kda_chunked``."""
    from ..kernels import kda_chunk, kda_rows
    from ..kernels._common import count_call

    nh, d = cfg.kda_heads, cfg.kda_head_dim
    b, S, _ = h.shape
    q, k, v = (_kda_filtered(pl, h, cfg, name) for name in "qkv")
    fused = kda_chunk.supported((b, S, nh, d), d, cfg.kda_chunk, k.dtype) \
        and kda_rows.supported(k.shape, d, k.dtype.itemsize)
    # ``kept``: what the delta rule's backward reads beside the operands
    count_call("kda_chunk", fused=int(fused),
               kept=kda_chunk.KEPT if fused else "chunk")
    for part in kda_rows.PARTS:
        count_call("kda_rows", part=part, fused=int(fused))
    if fused:
        return _kda_flat(pl, h, cfg, q, k, v) @ pl["wo"]
    q = kda_rows.l2_heads_reference(q, nh, d ** -0.5).astype(h.dtype)
    k = kda_rows.l2_heads_reference(k, nh, 1.0).astype(h.dtype)
    beta = kda_write_strength(pl, h, cfg)
    with jax.named_scope(devscope.KDA_CHUNK):
        # under a checkpoint of its own: what the ``jnp`` form keeps for
        # its backward (the chunks' own parts, a state a chunk: 1.7 GB
        # at [16384, 32 x 128]) then stands only while that backward
        # runs, not beside the FFN's residuals through the layer's, at
        # the price of a third forward (PERF.md section 6, PR 58)
        o = jax.checkpoint(functools.partial(
            kda_chunk.kda_chunked, chunk=cfg.kda_chunk,
            over_one=cfg.kda_beta_scale > 1.0))(
                q, k, v.reshape(b, S, nh, d), kda_log_decay(pl, h, cfg),
                beta)
    y = kda_rows.norm_gate_reference(o, jnp.matmul(
        h @ pl["w_ga"], pl["w_gb"], preferred_element_type=jnp.float32),
        pl["o_norm"], cfg.norm_eps)
    return y.astype(h.dtype).reshape(b, S, nh * d) @ pl["wo"]


# rows x width of a pointwise stage's widest activation (an FFN's hidden
# rows, the projections between their matmuls and the kernel) past which it
# runs a block of positions at a time, each block's forward run again in its
# backward: 2^27 elements are 256 MB in bf16, and the gate, the up
# projection, their product and the three gradients would each be that large
ROW_BLOCK_ELEMENTS = 1 << 27


def row_block(rows, width):
    """Rows of one block of ``rows`` rows at activation width ``width``,
    from the shapes alone: all of them up to ROW_BLOCK_ELEMENTS elements,
    else the largest divisor of ``rows`` (whole sublane tiles) that keeps a
    block under a quarter of that."""
    if rows * width <= ROW_BLOCK_ELEMENTS:
        return rows
    fits = [r for r in range(8, rows, 8)
            if rows % r == 0 and 4 * r * width <= ROW_BLOCK_ELEMENTS]
    return max(fits, default=rows)


def _by_row_blocks(fn, h, width):
    """``fn(rows, first)`` on ``h`` [b, S, E] whole (``first`` = 0), or,
    where b * S * ``width`` passes ROW_BLOCK_ELEMENTS, on ``row_block``
    positions at a time (``first`` the block's first position) under a
    ``jax.checkpoint`` of their own, so that a backward holds one block's
    wide activations and never the sequence's; the blocks' results [b,
    block, ...] put together along the positions."""
    b, S, E = h.shape
    block = row_block(S, b * width)
    if block == S:
        return fn(h, 0)
    out = jax.lax.map(jax.checkpoint(lambda turn: fn(*turn)),
                      (h.reshape(b, -1, block, E).swapaxes(0, 1),
                       jnp.arange(0, S, block)))
    return jax.tree.map(
        lambda a: a.swapaxes(0, 1).reshape((b, S) + a.shape[3:]), out)


def gated_ffn(pl, h, cfg):
    """The dense gated FFN ``(act(h @ Wg) * (h @ Wu)) @ w_down`` on ``h``
    [b, S, E], ``[Wg, Wu] = w_gate_up`` [E, 2F]; gate and product in
    float32; in row blocks where the hidden activation is large
    (``_by_row_blocks``).  Where ``pl`` holds ``w_up`` [E, F] instead, the
    UNGATED ``act(h @ w_up) @ w_down``."""
    from .moe import ACTIVATIONS

    act = ACTIVATIONS[cfg.expert_act]

    def rows_ffn(rows, first):
        if "w_up" in pl:
            hidden = act((rows @ pl["w_up"]).astype(jnp.float32))
        else:
            gate, up = jnp.split(rows @ pl["w_gate_up"], 2, axis=-1)
            hidden = act(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        return hidden.astype(rows.dtype) @ pl["w_down"]

    return _by_row_blocks(rows_ffn, h, pl["w_down"].shape[0])


def _add_branch(x, branch, pl, name, cfg):
    """``x + branch``; with sandwich norms ``x + rms(branch)`` by the leaf
    ``<name>_post_scale``, under the caller's scope and ``post_norm``."""
    if cfg.post_norm:
        with jax.named_scope(devscope.POST_NORM):
            branch = _rms(branch, pl[name + "_post_scale"], cfg.norm_eps)
    return x + branch


def transformer_layer(pl, x_sp, cfg: TransformerConfig, kind=None,
                      dense=False, router_bias=None, positions=None):
    """One pre-norm block on the SP activation [b, S/tp, E]: the new
    activation and the FFN's auxiliary values (the MoE's, ``moe.route_top_k``;
    None for a dense FFN).  ``kind`` = (window or None, rotary), or CONV,
    RETENTION, MAMBA, MAMBA2 or KDA: which of ``cfg.layer_kinds`` this layer is
    (None: the first).  In a ``single_branch`` stack the layer is ONE of the
    two branches: the mixer's alone (auxiliary values None), or where
    ``kind`` is FFN the feed-forward part's alone;
    ``dense``: a layer whose FFN is the dense gated one (a leading layer, or
    any layer of a stack without experts); ``router_bias`` [n]:
    this layer's selection biases, where the routing rule has them;
    ``positions`` [3, b, S]: the rotation's position streams, where the
    batch carries them.  A layer with an indexer adds its loss term to the
    auxiliary values (``dsa_kl``)."""
    heads_mode = cfg.attn_mode == "heads"
    logits, extras = None, {}
    if cfg.n_experts and cfg.router_input == "block" and not dense \
            and (kind == FFN or not cfg.single_branch):
        from .moe import router_logits

        # the router reads the residual stream as it ENTERS the block
        logits = router_logits(pl["router"], x_sp.reshape(-1, x_sp.shape[-1]))
    if kind == FFN:
        pass                            # the feed-forward part alone
    elif kind == CONV:
        with jax.named_scope(devscope.SHORT_CONV):
            x_sp = _add_branch(x_sp, short_conv(
                pl, _norm(x_sp, pl, "ln1", cfg)), pl, "ln1", cfg)
    elif kind == RETENTION:
        with jax.named_scope(devscope.RETENTION):
            x_sp = _add_branch(x_sp, power_retention(
                pl, _norm(x_sp, pl, "ln1", cfg), cfg), pl, "ln1", cfg)
    elif kind == MAMBA:
        with jax.named_scope(devscope.MAMBA):
            x_sp = _add_branch(x_sp, mamba_mixer(
                pl, _norm(x_sp, pl, "ln1", cfg), cfg), pl, "ln1", cfg)
    elif kind == MAMBA2:
        with jax.named_scope(devscope.MAMBA2):
            x_sp = _add_branch(x_sp, mamba2_mixer(
                pl, _norm(x_sp, pl, "ln1", cfg), cfg), pl, "ln1", cfg)
    elif kind == KDA:
        with jax.named_scope(devscope.KDA):
            x_sp = _add_branch(x_sp, kda_mixer(
                pl, _norm(x_sp, pl, "ln1", cfg), cfg), pl, "ln1", cfg)
    else:
        # the configuration as THIS position reads it (its own heads, ranks,
        # widths, theta, indexer or none) and its (window or None, rotary)
        at, kind = cfg.position(kind or cfg.layer_kinds[0])
        with jax.named_scope(at.scope or (
                devscope.LATENT_ATTENTION if cfg.latent
                else devscope.ATTENTION)):
            h = _norm(x_sp, pl, "ln1", cfg)
            if at.indexer_heads:
                attn, extras["dsa_kl"] = _sparse_attention(
                    pl, h, at, kind[1], positions)
            elif heads_mode:
                h = col.all_gather(h, TP, dim=1)
                attn = _attention_heads_mode(pl, h, at, kind, positions)
            else:
                attn = _attention_ring_mode(pl, h, cfg)
            x_sp = _add_branch(x_sp, attn, pl, "ln1", cfg)
    if cfg.indexer_heads and kind != FFN:
        # a position without an indexer adds nothing to the loss term
        extras.setdefault("dsa_kl", jnp.zeros((), jnp.float32))

    if cfg.single_branch and kind != FFN:
        return x_sp, None
    if dense:
        with jax.named_scope(devscope.MLP):
            return _add_branch(x_sp, gated_ffn(
                pl, _norm(x_sp, pl, "ln2", cfg), cfg), pl, "ln2",
                cfg), extras or None

    if cfg.n_experts:
        with jax.named_scope(devscope.MOE):
            from .moe import dropless_moe_ffn

            h = _norm(x_sp, pl, "ln2", cfg)
            y, aux = dropless_moe_ffn(
                pl, h.reshape(-1, h.shape[-1]), cfg.experts_per_token,
                rule=cfg.routing, act=cfg.expert_act, logits=logits,
                first_held=cfg.first_expert, bias=router_bias,
                scale=cfg.route_scale,
                ep_axis=DP if cfg.expert_parallel else None)
            y = y.reshape(h.shape)
            aux = dict(aux, **extras)
            if not cfg.shared_ffn_hidden:
                return _add_branch(x_sp, y, pl, "ln2", cfg), aux
            if not cfg.post_norm:
                x_sp = x_sp + y
        # every share of the experts computes it, and a sum over the
        # shares counts it once: no 129th group of the grouped matmul
        with jax.named_scope(devscope.SHARED_EXPERT):
            shared = gated_ffn(
                {name.replace("ws_", "w_"): pl[name]
                 for name in ("ws_gate_up", "ws_up", "ws_down")
                 if name in pl},
                h, cfg)
            if not cfg.post_norm:
                return x_sp + shared, aux
        with jax.named_scope(devscope.MOE):
            # the output norm is not linear: it takes the branch's SUM
            return _add_branch(x_sp, y + shared, pl, "ln2", cfg), aux

    with jax.named_scope(devscope.MLP):
        h = _norm(x_sp, pl, "ln2", cfg)
        if heads_mode:
            h = col.all_gather(h, TP, dim=1)
        y = h @ pl["w1"]
        y = _gelu_r(y + pl["b1"] if cfg.bias else y)
        y = y @ pl["w2"]                                        # partial if heads_mode
        if heads_mode:
            y = col.reduce_scatter(y, TP, dim=1)
        x_sp = _add_branch(x_sp, y, pl, "ln2", cfg)
        if cfg.bias:
            x_sp = x_sp + pl["b2"]
    return x_sp, None


def run_layers(layer_params, x_sp, cfg: TransformerConfig, with_aux=False,
               prefix=None, router_bias=None, positions=None):
    """scan over the (local) stacked layers; remat per layer if configured.
    ``with_aux`` also returns the layers' auxiliary values, stacked [L]
    (``moe.route_top_k``'s of each MoE layer; None for a dense stack).

    A pattern of several layer kinds is scanned a PERIOD at a time and the
    body runs the period's layers in turn, each with its static kind
    (``scan_unroll`` then counts periods).  Where the kinds have the same
    leaves, the stacked leaves [L, ...] are read as [L / period, period,
    ...]; where they do not (``cfg.per_position``) ``layer_params`` holds a
    tree for each position, stacked [L / period, ...], the leading layers
    (``prefix``, a tree each) run before the scan under the same remat, and
    ``router_bias`` [moe_layers, n] is read a period's rows a turn.  One
    kind is the scan over layers it always was.  A ``single_branch`` stack
    is the same per-position scan: each position one branch, the auxiliary
    values and the biases' rows those of its FFN positions.

    A RUN is consecutive positions of one kind inside a period.  With
    ``cfg.run_scan`` ``layer_params`` holds a tree for each run, stacked
    [L / period, run length, ...], and the period's body scans each run's
    layers under the same remat: the traced program holds ONE layer of each
    run, not one of each position (a period of 13 layers of one kind around
    one of another is three bodies, not fourteen).  The scan's own work (its
    slices of the stacked leaves, what it keeps for the backward pass, the
    gradients it stacks: 4 % of a step where a layer's leaves are 0.5 GB)
    goes under the scope ``layer_scan``; a layer's under the layer's.

    ``positions`` [3, b, S]: the rotation's position streams where the batch
    carries them (a stack of one attention kind)."""
    kinds = cfg.layer_kinds
    assert positions is None or (len(kinds) == 1 and not cfg.per_position)
    body = transformer_layer
    if cfg.remat:
        # an indexer's thresholds and both statistics are kept: the second
        # forward makes the scores again (the backward reads them), selects
        # nothing and runs no online softmax
        body = jax.checkpoint(
            body, static_argnums=(2, 3, 4),
            policy=jax.checkpoint_policies.save_only_these_names(*DSA_KEPT)
            if cfg.indexer_heads else None)
    unroll = max(int(cfg.scan_unroll), 1)
    if len(kinds) == 1 and not cfg.per_position:
        with jax.named_scope(devscope.LAYER_SCAN):
            x_sp, aux = jax.lax.scan(
                lambda x, turn: body(turn[0], x, cfg, kinds[0],
                                     cfg.dense_stack, turn[1], positions),
                x_sp, (layer_params, router_bias), unroll=unroll)
        return (x_sp, aux) if with_aux else x_sp

    leading = []        # the leading layers' auxiliary values
    if cfg.per_position:
        for i, kind in enumerate(cfg.prefix_kinds):
            x_sp, aux = body(prefix["l%d" % i], x_sp, cfg, kind, True)
            leading.append(aux)
        at_position = layer_params if cfg.run_scan \
            else [layer_params["p%d" % i] for i in range(len(kinds))]
    else:
        at_position = jax.tree.map(
            lambda a: a.reshape((-1, len(kinds)) + a.shape[1:]), layer_params)
    # a period's feed-forward parts, which the biases' rows follow: every
    # position's, or a single-branch stack's FFN positions'
    ffn_at = np.cumsum(cfg.ffn_positions) - 1
    if router_bias is not None:
        router_bias = router_bias.reshape((-1, sum(cfg.ffn_positions))
                                          + router_bias.shape[1:])

    def period(x, turn):
        pls, biases = turn
        auxes = []
        for at, kind in enumerate(kinds):
            pl = pls[at] if cfg.per_position \
                else jax.tree.map(lambda a: a[at], pls)
            own = biases is not None and cfg.ffn_positions[at]
            x, aux = body(pl, x, cfg, kind, cfg.dense_stack,
                          biases[int(ffn_at[at])] if own else None)
            if cfg.ffn_positions[at]:
                auxes.append(aux)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *auxes)

    def period_of_runs(x, turn):
        """The same over a tree for each run, stacked [run length, ...]: a
        scan over each run's layers."""
        pls, biases = turn
        dense, auxes = cfg.dense_stack, []
        for at, (first, kind, length) in enumerate(cfg.runs):
            own = None if biases is None else biases[first:first + length]
            x, aux = jax.lax.scan(
                lambda x, layer: body(layer[0], x, cfg, kind, dense,
                                      layer[1]),
                x, (pls["r%d" % at], own))
            auxes.append(aux)
        return x, None if dense else jax.tree.map(
            lambda *a: jnp.concatenate(a), *auxes)

    with jax.named_scope(devscope.LAYER_SCAN):
        x_sp, aux = jax.lax.scan(period_of_runs if cfg.run_scan else period,
                                 x_sp, (at_position, router_bias),
                                 unroll=unroll)
    aux = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), aux)
    if cfg.indexer_heads and leading:
        # a leading layer's FFN is dense: its indexer's term is all it adds
        aux = dict(aux, dsa_kl=jnp.concatenate([
            jnp.stack([a["dsa_kl"] for a in leading]), aux["dsa_kl"]]))
    return (x_sp, aux) if with_aux else x_sp


def run_passes(params, x_sp, cfg: TransformerConfig):
    """A looped stack on the stream ``x_sp`` [b, S, E]: ``cfg.loop_passes``
    times ``run_layers`` over the SAME ``params["params_layers"]``, the
    model's one final norm at the end of every pass, whose output the next
    pass reads.  Returns ``(exits, gates)``: every pass's last activation
    BEFORE that norm, [T, b, S, E] (the head norms the rows it reads with
    the same weight: ``_weighted_vocab_nll``, ``head_logits``), and the exit
    gate's logit on the normed state, ``h_t . exit_gate_w + exit_gate_b``,
    float32 [T, b, S].

    The passes are an outer ``lax.scan`` with the leaves closed over: the
    traced program holds the stack once.  Its backward runs the passes in
    reverse, each the layers' scan with its own per-layer remat (T * L
    activations kept), and SUMS the T contributions to a leaf's gradient in
    the carry, in the leaf's own type: bf16 leaves take the stacked [L, ...]
    gradient of each pass rounded to bf16 and one more rounding at each of
    the T additions.  The loop's own work (the carried stream, the exits
    kept, that running sum) goes under the scope ``loop_scan``; the gate's
    under ``exit_gate``; the layers keep theirs further in."""
    def one_pass(x, _):
        u = run_layers(params["params_layers"], x, cfg)
        h = rms_norm(u, params["lnf_scale"], cfg.norm_eps)
        with jax.named_scope(devscope.EXIT_GATE):
            gate = h.astype(jnp.float32) @ params["exit_gate_w"] \
                + params["exit_gate_b"]
        return h, (u, gate)

    with jax.named_scope(devscope.LOOP_SCAN):
        _, (exits, gates) = jax.lax.scan(one_pass, x_sp, None,
                                         length=cfg.loop_passes)
    return exits, gates


def exit_log_probs(gates):
    """``ln p`` [T, ...] of the exit distribution from the gates' logits
    [T, ...], float32: with ``lam_t = sigmoid(gate_t)``, ``p_t = lam_t *
    prod_{j<t} (1 - lam_j)`` for t < T and ``p_T = prod_{j<T} (1 - lam_j)``,
    what is left (the last gate is not read).  In logarithms, so that a
    saturated gate gives no ``0 * ln 0``.  ``sum_t p_t = 1``."""
    gates = gates.astype(jnp.float32)[:-1]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gates), axis=0)   # ln prod_{j<=t}
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([jax.nn.log_sigmoid(gates) + before, stay[-1:]])


_VOCAB_CHUNKS = 4


def head_row_block(n_rows):
    """Rows per block of the masked-rows head, from the shape alone: 1024
    at training sizes (a block's [R, E] x [E, V/4] matmuls fill the MXU as
    the dense head's did), a sixteenth of the rows below that."""
    return max(8, min(1024, n_rows // 128 * 8))


def head_rows_computed(count, n_rows):
    """Rows the tp=1 head computes when ``count`` of ``n_rows`` rows have a
    non-zero mask: whole blocks of ``head_row_block(n_rows)``.  The device
    code's trip count and the trainer's ``monitor.train.lm_head_rows``
    counter both come from here (``count`` a traced or a host integer)."""
    block = head_row_block(n_rows)
    return (count + block - 1) // block * block


def _vocab_chunks(emb):
    """The head's partition of the vocabulary axis, ``(offset, rows)`` a
    chunk, from the head matrix's shape alone: about ``_VOCAB_CHUNKS``
    chunks, every one but the last a whole multiple of ``head_row_block(V)``
    rows (1024 at training sizes), the last the remainder.

    The TPU compiler walks a chunk's float32 ``[rows, E]`` gradient in
    windows of whole 8-row tiles, as many tiles a window as divide the
    chunk.  A quarter of the vocabulary need not have a divisor: 37,984 / 4
    is 8 x 1,187, a prime, and ran that matmul in 1,187 windows of one
    tile, at seven times its time.  The remainder is the shortest chunk, so
    one that tiles badly costs little."""
    V = emb.shape[0]
    granule = head_row_block(V)
    rows = -(-V // (_VOCAB_CHUNKS * granule)) * granule
    return [(lo, min(rows, V - lo)) for lo in range(0, V, rows)]


def _live_first(mask):
    """Stable partition of the rows by ``mask != 0``: ``order`` lists the
    live rows first (padded with row 0 to whole blocks), ``inv`` is its
    inverse on the real rows, ``count`` the number of live rows."""
    n = mask.shape[0]
    live = mask != 0
    count = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    return jnp.pad(order, (0, -n % head_row_block(n))), inv, count


def _block_rows(i, block, order, count):
    """Block ``i`` of the compacted order: its rows' indices into the batch,
    and which of them are live (the last block's tail is not)."""
    idx = jax.lax.dynamic_slice_in_dim(order, i * block, block)
    return idx, i * block + jnp.arange(block, dtype=jnp.int32) < count


def _vocab_chunk(h, emb, labels, lo, sz):
    """Vocab rows [lo, lo+sz): their weights [sz, E], the block's logits
    against them [R, sz] in float32, each row's label as an index into the
    chunk, and whether it falls inside."""
    w = jax.lax.dynamic_slice_in_dim(emb, lo, sz, 0)
    logits = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return (w, logits, jnp.clip(labels - lo, 0, sz - 1),
            (labels >= lo) & (labels < lo + sz))


def _head_norm(norm, x, scale, bias):
    """The final norm on a block of rows; ``norm`` = (kind, eps), ``bias``
    None for RMS norm."""
    kind, eps = norm
    if kind == "rms":
        return rms_norm(x, scale, eps)
    # unfused: XLA fuses the norm into the chunk matmuls around it
    return layer_norm(x, scale, bias, eps=eps, fused=False)


def _lse_start(block):
    """A row block's running max, running sum and picked logit before its
    first vocabulary chunk."""
    return (jnp.full((block,), -jnp.inf, jnp.float32),
            jnp.zeros((block,), jnp.float32), jnp.zeros((block,), jnp.float32))


def _lse_step(stat, logits, local, hit):
    """One vocabulary chunk's logits [R, sz] into the block's running max,
    sum and picked logit (the online softmax's statistic)."""
    m_run, s_run, picked = stat
    m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
    s_run = s_run * jnp.exp(m_run - m_new) + jnp.sum(
        jnp.exp(logits - m_new[:, None]), axis=-1)
    pc = jnp.take_along_axis(logits, local[:, None], axis=-1)[:, 0]
    return m_new, s_run, picked + jnp.where(hit, pc, 0.0)


def _chunk_grads(dh, demb, h, w, logits, local, hit, lse_b, gb, lo):
    """One vocabulary chunk's part of a row block's gradient from its
    logits [R, sz]: ``d = (softmax - onehot) * gb`` in the head matrix's
    dtype, ``dh += d @ w`` and rows [lo, lo + sz) of the float32 ``demb``
    ``+= d.T @ h``."""
    sz = w.shape[0]
    p = jnp.exp(logits - lse_b[:, None])                        # softmax chunk
    onehot = (jax.nn.one_hot(local, sz, dtype=jnp.float32)
              * hit[:, None].astype(jnp.float32))
    d = ((p - onehot) * gb[:, None]).astype(w.dtype)               # [R, sz]
    dh = dh + jax.lax.dot_general(
        d, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(
        d, h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # [sz, E]
    return dh, jax.lax.dynamic_update_slice_in_dim(
        demb, jax.lax.dynamic_slice_in_dim(demb, lo, sz, 0) + dw, lo, 0)


def _weighted_vocab_nll(x, scale, bias, emb, labels, wgt,
                        norm=("layer", 1e-6)):
    """``(sum_r wgt_r * nll_r, nll)``: per row ``nll = logsumexp(norm(x) @
    emb.T) - picked`` for the rows whose ``wgt`` is non-zero, exactly 0 for
    the others (single-device vocab, tp=1), and their weighted sum, the only
    differentiable result: its gradient reaches x, scale, bias, emb and
    ``wgt`` (``nll_r``; a row of weight 0 hands its weight none).  x [N, E],
    labels and wgt [N]; ``emb`` [V, E] is the head matrix, tied or not;
    ``norm`` = (kind, eps) of the final norm, whose ``bias`` is None for RMS
    norm.

    Only the live rows are computed.  They are moved to the front (a stable
    partition by ``wgt != 0``) and the head runs over row blocks of
    ``head_row_block(N)``, ``ceil(count / R)`` of them: a ``fori_loop`` whose
    trip count is the weights' own count.  An MLM batch predicts 80
    positions of 512, so the head does a sixth of the dense work; a
    causal-LM mask of ones runs every block.

    Inside a block the vocab axis is processed in chunks with a running
    max/sum.  Asked for no gradient (evaluation, a witness's loss) that is
    all: a chunk's [R, sz] float32 logits are dropped once read.  Asked for
    one, the forward rule makes a block's logits ONCE: every chunk's is kept
    for the block while the running max and sum make ``lse``, then each
    chunk's gradient is formed from the kept logits and fed to the MXU in
    the head matrix's dtype (bf16 at training sizes), so a live block costs
    three [R, E] x [E, V] matmul passes and ``R * V * 4`` bytes of
    temporaries; the [N, V] logits never materialize.  The gradients leave
    the forward rule as residuals and the backward rule multiplies them by
    the sum's cotangent: hand the loss's normaliser in with ``wgt`` and that
    is 1."""
    return _weighted_nll(norm, x, scale, bias, emb, labels, wgt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
@devscope.scoped(devscope.LM_HEAD)
def _weighted_nll(norm, x, scale, bias, emb, labels, wgt):
    n = x.shape[0]
    block = head_row_block(n)
    order, inv, count = _live_first(wgt)

    def body(i, nll):
        idx, live = _block_rows(i, block, order, count)
        lb = labels[idx]
        h = _head_norm(norm, x[idx], scale, bias)
        stat = _lse_start(block)
        for lo, sz in _vocab_chunks(emb):
            stat = _lse_step(stat, *_vocab_chunk(h, emb, lb, lo, sz)[1:])
        m_run, s_run, picked = stat
        nll_b = jnp.where(live, m_run + jnp.log(s_run) - picked, 0.0)
        return jax.lax.dynamic_update_slice_in_dim(nll, nll_b, i * block, 0)

    n_blocks = head_rows_computed(count, n) // block
    # rows past the last block are never touched: their nll is the zero the
    # carry starts from
    nll = jax.lax.fori_loop(0, n_blocks, body,
                            jnp.zeros(order.shape, jnp.float32))[inv]
    return jnp.sum(wgt * nll), nll


# a custom_vjp's rules are traced on their own: each names its scope itself
@devscope.scoped(devscope.LM_HEAD)
def _weighted_nll_fwd(norm, x, scale, bias, emb, labels, wgt):
    n = x.shape[0]
    block = head_row_block(n)
    order, inv, count = _live_first(wgt)
    chunks = _vocab_chunks(emb)

    def body(i, carry):
        nll, dx, dnorm, demb = carry
        idx, live = _block_rows(i, block, order, count)
        lb = labels[idx]
        gb = jnp.where(live, wgt[idx], 0.0)
        h, ln_vjp = jax.vjp(functools.partial(_head_norm, norm), x[idx],
                            scale, bias)
        kept, stat = [], _lse_start(block)
        for lo, sz in chunks:
            kept.append(_vocab_chunk(h, emb, lb, lo, sz))
            stat = _lse_step(stat, *kept[-1][1:])
        m_run, s_run, picked = stat
        lse_b = m_run + jnp.log(s_run)
        nll_b = jnp.where(live, lse_b - picked, 0.0)
        dh = jnp.zeros(h.shape, jnp.float32)
        for (lo, _), chunk in zip(chunks, kept):
            dh, demb = _chunk_grads(dh, demb, h, *chunk, lse_b, gb, lo)
        dxb, *dnb = ln_vjp(dh.astype(h.dtype))
        # (scale, bias); an RMS norm's bias is None, an empty pytree
        return (jax.lax.dynamic_update_slice_in_dim(nll, nll_b, i * block, 0),
                jax.lax.dynamic_update_slice_in_dim(dx, dxb, i * block, 0),
                jax.tree.map(jnp.add, dnorm, tuple(dnb)), demb)

    n_blocks = head_rows_computed(count, n) // block
    nll, dx, dnorm, demb = jax.lax.fori_loop(0, n_blocks, body, (
        jnp.zeros(order.shape, jnp.float32),
        jnp.zeros((order.shape[0], x.shape[1]), x.dtype),
        jax.tree.map(jnp.zeros_like, (scale, bias)),
        jnp.zeros(emb.shape, jnp.float32)))
    nll = nll[inv]
    # a dead row's place in the compacted order holds the zero it started
    # with, so the way back is a gather through the inverse permutation;
    # ``like``: the head matrix's dtype, which the backward rule casts to
    like = jnp.zeros((0,), emb.dtype)
    return (jnp.sum(wgt * nll), nll), (dx[inv], dnorm, demb, like, nll)


@devscope.scoped(devscope.LM_HEAD)
def _weighted_nll_bwd(norm, res, cts):
    (dx, dnorm, demb, like, nll), g = res, cts[0]

    def times_g(a):
        return (g * a).astype(a.dtype)

    dscale, dbias = jax.tree.map(times_g, dnorm)
    return (times_g(dx), dscale, dbias, (g * demb).astype(like.dtype), None,
            g * nll)


_weighted_nll.defvjp(_weighted_nll_fwd, _weighted_nll_bwd)


@devscope.scoped(devscope.LM_HEAD)
def head_logits(params, x, cfg: TransformerConfig):
    """The LM head's float32 logits [..., V] on ``x`` [..., E]: the final
    norm and the head's matmul as ``final_logits_loss`` computes them, kept.
    For a few rows (a check against a reference), at tp == 1."""
    emb = params["tok_emb" if cfg.tie_head else "lm_head"]
    h = _head_norm((cfg.norm, cfg.norm_eps), x, params["lnf_scale"],
                   params.get("lnf_bias"))
    return jax.lax.dot_general(h, emb, (((x.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


@devscope.scoped(devscope.LM_HEAD)
def final_logits_loss(params, x_sp, labels, mask, cfg: TransformerConfig,
                      divisor=None):
    """Softmax cross-entropy with the LM head (``tok_emb``, or the head's
    own ``lm_head`` where ``cfg.tie_head`` is off) on the configuration's
    final norm, averaged over the positions ``mask`` weights:
    ``sum(nll * mask) / max(sum(mask), 1)`` over the dp-sharded global batch;
    ``divisor``: over that number a dp shard instead of the weights' sum
    (a denoising loss divides by the tokens, not by the masked ones'
    weights).

    x_sp is sequence-sharded over tp; labels/mask are FULL [b, S].  ``mask``
    alone says which positions count (MLM: the predicted positions, causal
    LM: all ones; a weight other than 0/1 is exact).  With one vocab shard
    (tp=1) the head computes only the rows whose mask is non-zero, makes a
    row block's logits once and takes the mask over the normaliser as the
    rows' weights (``_weighted_vocab_nll``).  With tp>1 it gathers the
    sequence (transpose: the gradient reduce-scatters it back), runs on
    every row unchunked and keeps logits vocab-sharded [b, S, V/tp] — the
    [*, V] logits never materialize (the vocab-parallel loss the
    reference's softmax_with_cross_entropy op cannot express).
    """
    emb = params["tok_emb" if cfg.tie_head else "lm_head"]      # [V/tp, E] local
    if col.axis_size_in(TP) == 1:
        head = (x_sp.reshape(-1, x_sp.shape[-1]), params["lnf_scale"],
                params.get("lnf_bias"), emb, labels.reshape(-1))
        # the normaliser goes in with the weights: the sum's cotangent is 1
        # and the head's forward rule makes the gradient
        over = divisor * col.axis_size_in(DP) if divisor is not None \
            else jnp.maximum(
                col.psum(jnp.sum(mask.astype(jnp.float32)), DP), 1.0)
        total, _ = _weighted_vocab_nll(*head, mask.reshape(-1) / over,
                                       norm=(cfg.norm, cfg.norm_eps))
        return col.psum_forward(total, DP)
    assert divisor is None
    x = _norm(x_sp, params, "lnf", cfg, fused=False)
    x = col.all_gather(x, TP, dim=1)                            # [b, S, E]
    logits = (x @ emb.T).astype(jnp.float32)                    # [b, S, V/tp]
    vshard = logits.shape[-1]
    lo = col.axis_index(TP) * vshard

    # the running max is numerics-only (cancels in logsumexp): stop_gradient
    # lets us use pmax, which has no AD rule
    mx = col.pmax(jax.lax.stop_gradient(jnp.max(logits, axis=-1)), TP)
    lse = jnp.log(col.psum(jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1), TP)) + mx
    local_lab = jnp.clip(labels - lo, 0, vshard - 1)
    hit = (labels >= lo) & (labels < lo + vshard)
    picked = jnp.take_along_axis(logits, local_lab[..., None], axis=-1)[..., 0]
    picked = col.psum(jnp.where(hit, picked, 0.0), TP)
    nll = (lse - picked) * mask
    # token-mean over the dp-sharded global batch (nll is tp-replicated)
    total = col.psum_forward(jnp.sum(nll), DP)
    count = col.psum(jnp.sum(mask.astype(jnp.float32)), DP)
    return total / jnp.maximum(count, 1.0)


def exit_weighted_loss(params, exits, gates, labels, mask,
                       cfg: TransformerConfig):
    """A looped stack's training loss from ``run_passes``' ``exits`` [T, b,
    S, E] and ``gates`` [T, b, S]: per position ``sum_t p_t * nll_t -
    exit_entropy_coef * H(p)``, ``nll_t`` the cross entropy of exit t's
    logits (the final norm and the ONE head on that pass's state), ``p``
    the position's exit distribution (``exit_log_probs``), ``H(p) = -sum_t
    p_t ln p_t``; averaged over the positions ``mask`` weights as
    ``final_logits_loss`` does.  The T exits' rows go through the head in
    ONE call (one float32 [V, E] gradient buffer, one loop over row blocks):
    a row's weight ``p_t * mask / count`` goes INTO the head and the head's
    gradient with respect to it, the row's ``nll``, comes back, so the
    gradient reaches the gate and, through the state it reads, the stack.
    tp == 1."""
    T = cfg.loop_passes
    emb = params["tok_emb" if cfg.tie_head else "lm_head"]
    mask = mask.reshape(-1)
    head = (exits.reshape(-1, exits.shape[-1]), params["lnf_scale"],
            params.get("lnf_bias"), emb, jnp.tile(labels.reshape(-1), T))
    count = col.psum(jnp.sum(mask.astype(jnp.float32)), DP)
    with jax.named_scope(devscope.EXIT_GATE):
        log_p = exit_log_probs(gates.reshape(T, -1))
        wgt = jnp.exp(log_p) * (mask / jnp.maximum(count, 1.0))
        entropy = cfg.exit_entropy_coef * jnp.sum(wgt * log_p)
    ce, _ = _weighted_vocab_nll(*head, wgt.reshape(-1),
                                norm=(cfg.norm, cfg.norm_eps))
    return col.psum_forward(ce + entropy, DP)
