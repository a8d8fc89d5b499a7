"""Rule-based sharding core: ONE authority for how every leaf shards.

The problem this solves (ROADMAP item 4): sharding decisions used to be
scattered per model and per subsystem — transformer/deepfm/moe each built
their own PartitionSpec literals, the compiler derived specs from
``_tp_split`` markers inline, the HostPS router had its own row-shard
constant, and the checkpoint simply trusted whatever sharding the target
leaves carried.  A new model meant new sharding *code* in several places,
and the checkpoint's shard layout was a frozen artifact of whoever saved.

The fix is the ``match_partition_rules`` idiom (SNIPPETS.md [2]): sharding
is DATA — an ordered list of ``(regex-over-leaf-path, PartitionSpec)``
rules — resolved against a pytree's '/'-joined leaf paths.  A
``ShardingAuthority`` bundles one rule tree with (optionally) a mesh and is
what the consumers ask:

- the model spec builders (``parallel/transformer.py``,
  ``models/deepfm.py``, ``parallel/moe.py``) define their layouts as rule
  lists here-adjacent and resolve them through ``match_partition_rules``;
- the compiler (``compiler.py``) turns the program's ``_tp_split`` markers
  into rules via ``tp_split_rules`` and resolves per-var specs through an
  authority instead of open-coding the col/row translation;
- the checkpoint re-sharder (``parallel/checkpoint.py
  restore_checkpoint(authority=)``) uses an authority to place restored
  leaves on the CURRENT mesh — the saved layout no longer dictates the
  restored one (topology-portable checkpoints);
- HostPS sparse-shard IO partitions table rows by ``hostps_row_range`` —
  the one definition of which rank owns which rows — so an elastic resume
  can repartition row shards for a different world size (ft/ckpt.py);
- the multichip dryrun (``__graft_entry__.py``) exercises all of the above
  through the model builders.

Because sharding is derived from (rules, mesh) at use time, the same
checkpoint can be saved by one topology and restored by another: the rules
are re-evaluated against the resumer's mesh, not replayed from the saver's.
"""

import re

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import DP, PP, TP

__all__ = [
    "leaf_paths",
    "match_partition_rules",
    "SkeletonLeaf",
    "ShardingAuthority",
    "tp_split_specs",
    "tp_split_rules",
    "batch_spec",
    "row_sharded_table_spec",
    "hostps_row_range",
    "hostps_row_ranges",
    "transformer_rules",
    "deepfm_rules",
    "moe_rules",
]


def leaf_paths(tree):
    """Flatten `tree` with '/'-joined string paths — the canonical leaf
    addressing every rule matches against AND the checkpoint manifest's
    leaf keys (parallel/checkpoint.py uses this same function), so a rule
    written against a param name also names its checkpoint entry."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = []
    for kp, _ in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        paths.append("/".join(parts))
    return paths, [v for _, v in flat], treedef


def _resolve(rules, name, leaf, strict, default):
    """One leaf's spec: scalars replicate, else first matching rule wins."""
    shape = getattr(leaf, "shape", None)
    if shape is not None and (len(shape) == 0 or int(np.prod(shape)) == 1):
        return P()          # never partition scalars
    for rule, spec in rules:
        if re.search(rule, name) is not None:
            return spec if isinstance(spec, P) else P(*spec)
    if strict:
        raise ValueError(
            "no partition rule matches leaf %r (rules: %s)"
            % (name, [r for r, _ in rules]))
    return P() if default is None else default


def match_partition_rules(rules, tree, strict=True, default=None):
    """Resolve an ordered ``[(regex, PartitionSpec)]`` rule list against a
    pytree -> a pytree of PartitionSpec with the same structure.

    Leaf addressing is ``leaf_paths`` ('/'-joined).  Scalar leaves (shape
    () or one element) always get ``P()`` regardless of rules; leaves
    without a ``.shape`` (structure skeletons) skip that shortcut and must
    match a rule.  First matching rule wins — order rules specific-first.
    strict=False hands unmatched leaves ``default`` (``P()`` when None)
    instead of raising."""
    paths, leaves, treedef = leaf_paths(tree)
    specs = [_resolve(rules, n, v, strict, default)
             for n, v in zip(paths, leaves)]
    return jax.tree_util.tree_unflatten(treedef, specs)


class SkeletonLeaf:
    """Shape-less placeholder leaf for resolving rules against a tree
    STRUCTURE when no live arrays exist yet: having no ``.shape``, it
    skips the scalar-replicate shortcut, so every leaf must match a rule.
    The spec builders (transformer/deepfm/moe) build their skeletons from
    this one class."""


class ShardingAuthority:
    """One rule tree + (optionally) one mesh = every sharding decision.

    The compiler, the checkpoint re-sharder, HostPS IO and the dryrun all
    consume an authority instead of carrying their own PartitionSpec
    literals; swapping the rules (or the mesh) re-derives every layout."""

    def __init__(self, rules, mesh=None, strict=True, default=None):
        self.rules = list(rules)
        self.mesh = mesh
        self.strict = strict
        self.default = default

    # -- specs -----------------------------------------------------------
    def spec(self, name, leaf=None):
        """PartitionSpec for one leaf by path/name."""
        return _resolve(self.rules, name, leaf, self.strict, self.default)

    def spec_tree(self, tree):
        return match_partition_rules(self.rules, tree, strict=self.strict,
                                     default=self.default)

    # -- placements (mesh required) --------------------------------------
    def _require_mesh(self):
        if self.mesh is None:
            raise ValueError("ShardingAuthority has no mesh: construct it "
                             "with mesh= to derive placements")
        return self.mesh

    def sharding(self, name, leaf=None):
        return NamedSharding(self._require_mesh(), self.spec(name, leaf))

    def sharding_tree(self, tree):
        mesh = self._require_mesh()
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self.spec_tree(tree),
            is_leaf=lambda x: isinstance(x, P))

    def shard(self, tree):
        """device_put every leaf with its rule-derived sharding."""
        shardings = self.sharding_tree(tree)
        return jax.tree_util.tree_map(jax.device_put, tree, shardings)

    # -- HostPS row partition --------------------------------------------
    def row_range(self, rank, world, vocab_size):
        return hostps_row_range(rank, world, vocab_size)


# -- compiler: _tp_split markers as rules -------------------------------------

def tp_split_specs(marks, model_axis="model"):
    """``{var_name: PartitionSpec}`` from a program's tensor-parallel
    markers — the one place the marker->spec translation lives.

    marks: ``{var_name: ("col"|"row", ndim)}`` — 'col' shards the LAST dim
    over the model axis (column-parallel fc weight [in, out], its bias,
    col-split embedding); 'row' shards the FIRST dim (row-parallel fc,
    vocab-split embedding).  One pass, exact names: compiler.py resolves
    its vars here directly (a regex rule per exact name would cost a
    linear scan PER VAR — quadratic on big programs — for no generality)."""
    specs = {}
    for name, (kind, nd) in marks.items():
        if kind == "col":
            spec = tuple([None] * (max(nd, 1) - 1) + [model_axis])
        elif kind == "row":
            spec = tuple([model_axis] + [None] * (max(nd, 1) - 1))
        else:
            raise ValueError("unknown tp split kind %r for %r" % (kind, name))
        specs[name] = P(*spec)
    return specs


def tp_split_rules(marks, model_axis="model"):
    """The same translation as an exact-match rule list, for consumers
    that want to COMPOSE tp markers with other rules in one authority."""
    return [(r"^%s$" % re.escape(name), spec)
            for name, spec in sorted(tp_split_specs(marks,
                                                    model_axis).items())]


def batch_spec(axis=DP):
    """THE [batch, ...] data layout: batch split over `axis` (dp), trailing
    dims replicated (pp microbatching happens inside the step).  mesh.py's
    batch_spec and the multichip dryrun's feed specs delegate here."""
    return P(axis)


# -- HostPS / row-sharded embedding tables ------------------------------------

def row_sharded_table_spec(axis=DP):
    """THE row-sharded [V, D] table layout (embedding_spec, the HostPS
    router, DeepFM's tables): rows over `axis`, columns replicated."""
    return P(axis, None)


def hostps_row_range(rank, world, vocab_size):
    """Contiguous row range ``[lo, hi)`` of a [vocab, D] host sparse table
    owned by `rank` in a `world`-process fleet — the single definition of
    the HostPS row partition.  Balanced: the first ``vocab % world`` ranks
    hold one extra row.  The elastic checkpoint re-sharder (ft/ckpt.py)
    uses this to repartition saved row shards for a NEW world size, and the
    RUNTIME shard router (hostps/shard_router.py) routes every live
    pull/push by the same function — checkpoint-time and wire-time
    partitions can never disagree."""
    rank, world, vocab_size = int(rank), int(world), int(vocab_size)
    if world <= 0 or not (0 <= rank < world):
        raise ValueError("rank %d outside world %d" % (rank, world))
    base, extra = divmod(vocab_size, world)
    lo = rank * base + min(rank, extra)
    hi = lo + base + (1 if rank < extra else 0)
    return lo, hi


def hostps_row_ranges(world, vocab_size):
    """Every rank's ``[lo, hi)`` for one world size, ascending rank — the
    shard router's routing table (adjacent, disjoint, covering
    [0, vocab))."""
    return [hostps_row_range(r, world, vocab_size) for r in range(world)]


# -- model rule trees ---------------------------------------------------------
# New models get sharded by ADDING A RULE LIST HERE (or next to the model)
# and resolving it with match_partition_rules — not by writing spec code.

def transformer_rules(cfg):
    """Rule list reproducing the transformer layout: tp shards attention /
    mlp weights when attn_mode == "heads" (ring mode replicates over tp),
    pp leads the stacked-layer arrays when cfg.pp > 1, tok_emb is
    vocab-parallel over tp, and an expert-parallel configuration's routed
    experts ride dp."""
    tp = TP if cfg.attn_mode == "heads" else None
    lead = (PP, None) if cfg.pp > 1 else (None,)

    def L(*dims):       # a [L, ...] (or [pp, L/pp, ...]) stacked-layer leaf
        return P(*(lead + dims))

    # EXPERT-PARALLEL (``cfg.expert_parallel``): the routed experts' leaves,
    # [..., n_experts, E, F] behind their stacking axes, are split over dp on
    # the EXPERTS' axis, device c holding experts c n / dp on; the optimizer's
    # moments follow (``train.state_specs``), ``grad_sync_axes`` sums no
    # gradient over an axis a leaf is split on, and the checkpoint re-sharder
    # reads the same rule, so a state saved at dp 4 loads at dp 2 or 1.
    # Ahead of the rules that hold a per-position stack whole
    riding = [(r"^params_layers/r\d+/we_(gate_up|up|down)$",
               P(None, None, DP)),
              (r"/we_(gate_up|up|down)$", L(DP))] \
        if getattr(cfg, "expert_parallel", False) else []
    return riding + [
        # a stack whose layers own different leaves runs at tp == pp == 1
        # (TransformerConfig): its leading layers' leaves are not stacked,
        # and all of it is whole on every device
        (r"^prefix_layers/|^router_bias$", P()),
        # so does a stack whose runs are stacked [periods, run length, ...]
        # (``run_scan``), and the Mamba mixer's leaves wherever they stand
        (r"^params_layers/r\d+/", P()),
        # (Mamba-1's or Mamba-2's), and the KDA mixer's own
        (r"/(w_in|conv_b|w_x|dt_norm|b_norm|c_norm|w_dt|b_dt|a_log|d_skip"
         r"|gate_norm|w_out)$", P()),
        (r"/(conv_[qkv]|w_fa|w_fb|dt_bias|w_beta|w_ga|w_gb|o_norm)$", P()),
        (r"/(conv_in|conv_w|conv_out|w_gate_up|w_down|ws_gate_up|ws_up"
         r"|ws_down)$", L(None, None)),
        # latent attention runs at tp == 1 (TransformerConfig)
        (r"/(wq_a|wq_b|wkv_a|wkv_b)$", L(None, None)),
        (r"/(q_a_norm|kv_a_norm)$", L(None)),
        (r"^(tok_emb|lm_head)$", P(TP, None)),       # vocab-parallel
        (r"^pos_emb$|^lnf_|^exit_gate_", P()),
        (r"/ln[12](_post)?_(scale|bias)$", L(None)),
        (r"/(wq|wk|wv|wz|bqkv)$", L(None, tp)),
        (r"/wo$", L(tp, None)),
        (r"/(bo|b2)$", L(None)),
        (r"/(w1)$", L(None, tp)),
        (r"/b1$", L(tp)),
        (r"/w2$", L(tp, None)),
        # qk_norm and the MoE FFN run at tp == 1 (TransformerConfig): their
        # leaves are whole on every device
        (r"/(q_norm|k_norm)$", L(None)),
        # and so does an attention position's indexer
        (r"/(wq_idx|wk_idx|w_idx)$", L(None, None)),
        (r"/idx_k_norm_(scale|bias)$", L(None)),
        (r"/(router|wg)$", L(None, None)),
        (r"/we_(gate_up|up|down)$", L(None, None, None)),
    ]


def deepfm_rules(axis=DP):
    """DeepFM: embedding tables row-sharded over `axis` (the same layout
    the HostPS router serves from host RAM past the HBM budget), dense MLP
    + bias replicated."""
    return [
        (r"^(w_linear|embed)$", row_sharded_table_spec(axis)),
        (r"^bias$|^mlp/", P()),
    ]


def moe_rules(ep_axis=DP):
    """MoE: the Switch layer's experts (``w1``, ``w2``) sharded over
    `ep_axis`, the router replicated (its grads must be psum'd over ep); the
    dropless layer's experts (``we_gate_up``, ``we_down``) of a BARE layer
    (``moe.init_dropless_moe_params``) whole on every device.  A decoder's
    experts are ``transformer_rules``': whole, or, with
    ``TransformerConfig.expert_parallel``, split over ``dp`` on the experts'
    axis, which is how the dropless layer is expert-parallel."""
    return [
        (r"^router$", P()),
        (r"^w[12]$", P(ep_axis)),
        (r"^we_(gate_up|down)$", P()),
    ]
