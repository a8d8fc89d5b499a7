"""The causal decoders' trainer, beside the block it trains
(``transformer.py``) and the step it runs (``train.py``): forward, loss,
``logits_at``, a call's observations and the builder, ONE of each for every
decoder of this block.  A model (``models/olmoe.py``, ``smallthinker.py``,
``lfm2.py``, ``brumby.py``, ``mistral4.py``, ``trinity.py``, ``jamba.py``,
``nemotron_h.py``, ``ouro.py``, ``kimi_linear.py``, ``keye_vl2.py``,
``dots3.py``, ``solar_open2.py``, ``sdar.py``, ``kanana2.py``)
is a ``TransformerConfig`` and a label; what its trainer computes and what
it observes follow from the configuration, never from which model it is.

batch dict: ``ids`` int32 [B, S], and where the trainer was built for them
(``build_decoder_trainer(positions=True)``) ``positions`` int32 [3, B, S],
the rotation's three position streams; a BLOCK-DIFFUSION trainer's
(``cfg.block_diffusion``) carries its noise, ``t`` float32 [B, S / Bd] and
``u`` float32 [B, S] (``make_loss_fn``).  The loss builds the next-token
labels itself (``labels[t] = ids[t + 1]``, positions 0..S-2 count) and adds
what the configuration's router asks for: the auxiliary losses, mean over
layers (``ce + router_aux_coef * load_balance + router_z_coef * router_z``),
or the selection biases' next values, or nothing.  A LOOPED stack
(``loop_passes`` > 1: the whole stack run several times over one set of
leaves) has an exit at the end of every pass, and its loss is the exits'
cross entropies weighted by a learned per-token exit distribution
(``transformer.exit_weighted_loss``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import moe, optim
from .. import monitor
from ..monitor import devscope
from .mesh import DP, MeshSpec, local_shard_map
from .train import (StepTrainer, TrainState, make_train_step, shard_pytree,
                    state_specs)
from .transformer import (
    KDA,
    MAMBA,
    MAMBA2,
    RETENTION,
    TransformerConfig,
    embed,
    exit_log_probs,
    exit_weighted_loss,
    final_logits_loss,
    indexer_selection,
    _local_heads,
    _packed_flash_blocks,
    _qkv,
    grad_sync_axes,
    head_logits,
    head_rows_computed,
    init_transformer_params,
    kda_log_decay,
    kda_write_strength,
    mamba2_operands,
    mamba_operands,
    retention_log_decay,
    rms_norm,
    run_layers,
    run_passes,
    transformer_param_specs,
)

__all__ = ["BATCH_SPECS", "POSITION_SPECS", "NOISE_SPECS", "STEPPED",
           "batch_specs", "forward", "weighted_exit_logits", "noised_rows",
           "make_loss_fn", "probe", "DecoderTrainer",
           "build_decoder_trainer"]

BATCH_SPECS = {"ids": P(DP)}
# of a batch that carries its rotation's three position streams [3, B, S]
POSITION_SPECS = {"positions": P(None, DP)}
# of a block-diffusion batch: a noise level a block, t [B, S / Bd], and a
# uniform draw a token, u [B, S]
NOISE_SPECS = {"t": P(DP), "u": P(DP)}
NOISE = tuple(NOISE_SPECS)
STEPPED = {"router_bias"}       # leaves a step sets itself (make_train_step)


def batch_specs(cfg, positions=False):
    """The fields a trainer of ``cfg`` reads of a batch, with their specs."""
    return dict(BATCH_SPECS, **(POSITION_SPECS if positions else {}),
                **(NOISE_SPECS if cfg.block_diffusion else {}))


def forward(params, ids, cfg, positions=None):
    """The stack on ``ids`` [b, S]: the last activation and the layers'
    auxiliary values (the router's, an indexer's loss term), each stacked
    [L]; of a looped stack (``loop_passes`` > 1) every pass's last
    activation [T, b, S, E] and the exit gates' logits [T, b, S]
    (``transformer.run_passes``).  ``positions`` [3, b, S]: the rotation's
    position streams (``cfg.mrope_sections``); None: the token index three
    times, which is plain rotary."""
    x = embed(params, ids, cfg)
    if cfg.loop_passes > 1:
        return run_passes(params, x, cfg)
    return run_layers(params["params_layers"], x, cfg, with_aux=True,
                      prefix=params.get("prefix_layers"),
                      router_bias=params.get("router_bias"),
                      positions=positions)


def weighted_exit_logits(params, ids, at, cfg):
    """A looped stack's float32 logits [b, P, V] at positions ``at`` [P] of
    ``ids`` [b, S]: ``sum_t p_t z_t``, every exit's logits ``z_t`` (the
    final norm and the head on that pass's state) weighted by the
    position's exit distribution."""
    exits, gates = forward(params, ids, cfg)
    p = jnp.exp(exit_log_probs(gates[:, :, at]))
    return sum(p[t][..., None] * head_logits(params, exits[t][:, at], cfg)
               for t in range(cfg.loop_passes))


@devscope.scoped(devscope.NOISE)
def noised_rows(batch, cfg):
    """``(rows [b, 2 S], masked [b, S] bool, level [b, S])`` of a
    block-diffusion batch: token i of a sequence is masked where ``u_i <
    t_{i // Bd}`` (one noise level a block of ``cfg.block_diffusion``
    tokens, ``level`` that level a token), the noised copy ``x_t = where(
    masked, cfg.mask_token_id, ids)`` and under it the clean one: the rows
    the stack runs on."""
    ids = batch["ids"]
    level = jnp.repeat(batch["t"], cfg.block_diffusion, axis=-1)
    masked = batch["u"] < level
    rows = jnp.concatenate(
        [jnp.where(masked, jnp.asarray(cfg.mask_token_id, ids.dtype), ids),
         ids], axis=-1)
    return rows, masked, level


def _denoising_loss(params, batch, cfg):
    """Block diffusion's training loss: the noised rows' logits held to the
    UNSHIFTED clean ids at the masked positions, ``(1 / S) sum_i m_i /
    t_{b(i)} CE(logits_i, ids_i)`` a sequence (BD3-LM's linear schedule;
    the divisor is the sequence's length, not the weights' sum), mean over
    the batch.  The clean rows feed keys and values and no loss; the head
    computes the masked rows' blocks alone."""
    ids = batch["ids"]
    rows, masked, level = noised_rows(batch, cfg)
    x, aux = forward(params, rows, cfg)
    with jax.named_scope(devscope.NOISE):
        x = x[:, :ids.shape[1]]
        weight = jnp.where(masked, 1.0 / level, 0.0).astype(jnp.float32)
    return final_logits_loss(params, x, ids, weight, cfg,
                             divisor=float(ids.size)), aux


def make_loss_fn(cfg: TransformerConfig):
    """Per-device training loss on a batch.  The fields a batch carries, by
    the kind of trainer: ``ids`` int32 [B, S], every one; ``positions``
    int32 [3, B, S], one built with ``positions=True`` (the rotation's three
    streams); ``t`` float32 [B, S / Bd] in (0, 1) and ``u`` float32 [B, S]
    uniform, a block-diffusion one (``cfg.block_diffusion`` = Bd: the noise,
    a level a block and a draw a token; ``_denoising_loss``).

    Where the routing
    rule has selection biases (``moe.SIGMOID_BIASED``): ``(loss, {"router_bias":
    their next values})``, each layer's moved against that layer's load in
    this step (``moe.balance_bias``; ``make_train_step``'s ``stepped``).
    Of a looped stack the exit-weighted loss of its passes."""

    def loss_fn(params, batch):
        if cfg.block_diffusion:
            ce, aux = _denoising_loss(params, batch, cfg)
            return _with_router_terms(ce, aux, params)
        ids = batch["ids"]
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to(
            (jnp.arange(ids.shape[1]) < ids.shape[1] - 1).astype(jnp.float32),
            ids.shape)
        x, aux = forward(params, ids, cfg, batch.get("positions"))
        if cfg.loop_passes > 1:
            return exit_weighted_loss(params, x, aux, labels, mask, cfg)
        ce = final_logits_loss(params, x, labels, mask, cfg)
        if cfg.indexer_heads:
            # the indexer's own term, mean over layers: its leaves' alone
            ce = ce + _dsa_kl_mean(aux, cfg)
        return _with_router_terms(ce, aux, params)

    def _with_router_terms(ce, aux, params):
        if cfg.routing == moe.SIGMOID_BIASED:
            return ce, {"router_bias": moe.balance_bias(
                params["router_bias"], aux["load"], cfg.router_bias_rate)}
        if not (cfg.router_aux_coef or cfg.router_z_coef):
            return ce               # a configuration with no auxiliary loss
        return (ce + cfg.router_aux_coef * jnp.mean(aux["load_balance"])
                + cfg.router_z_coef * jnp.mean(aux["router_z"]))

    return loss_fn


def _dsa_kl_mean(aux, cfg):
    """The indexer's loss term, the mean over the layers THAT HAVE an
    indexer (a layer without one reports zero)."""
    if cfg.indexer_layers == cfg.n_layers:
        return jnp.mean(aux["dsa_kl"])
    return jnp.sum(aux["dsa_kl"]) / cfg.indexer_layers


def _first_layer_input(params, ids, cfg):
    """The first layer's leaves and the normed rows its operator reads."""
    if "prefix_layers" in params:
        pl = params["prefix_layers"]["l0"]
    else:
        layers = params["params_layers"]
        if cfg.run_scan:        # [periods, run length, ...]
            pl = jax.tree.map(lambda a: a[0, 0], layers["r0"])
        else:
            pl = jax.tree.map(lambda a: a[0],
                              layers["p0"] if cfg.per_position else layers)
    return pl, rms_norm(embed(params, ids, cfg), pl["ln1_scale"],
                        cfg.norm_eps)


def _first_of_kind(params, ids, cfg, kind):
    """The leaves of the first position of ``kind`` (a leading layer's, else
    the first period's) and the embedding's rows normed by that position's
    own first norm: what its operator would read were it the first layer."""
    if kind in cfg.prefix_kinds:
        pl = params["prefix_layers"]["l%d" % cfg.prefix_kinds.index(kind)]
    elif cfg.run_scan:      # [periods, run length, ...]
        at = [k for _, k, _ in cfg.runs].index(kind)
        pl = jax.tree.map(lambda a: a[0, 0],
                          params["params_layers"]["r%d" % at])
    else:
        pl = jax.tree.map(lambda a: a[0], params["params_layers"][
            "p%d" % cfg.layer_kinds.index(kind)])
    return pl, rms_norm(embed(params, ids, cfg), pl["ln1_scale"],
                        cfg.norm_eps)


def _mass_selected(params, ids, cfg, queries=256):
    """``(dsa_mass_selected, dsa_pairs_selected)`` of ``probe``: the first
    layer's dense causal softmax, a head a row, summed over the keys its
    indexer selects, and how many (query, key) pairs that layer selects."""
    from ..kernels import indexer as ix

    pl, h = _first_layer_input(params, ids, cfg)
    # the first layer's own shape (a leading layer's, else the period's)
    cfg = cfg.position((cfg.prefix_kinds + cfg.layer_kinds)[0])[0]
    b, S, _ = h.shape
    q, k, _ = _qkv(pl, h, cfg, True)
    scores, tau = indexer_selection(pl, h, cfg)
    first = min(cfg.indexer_topk, S - 1)
    rows = jnp.linspace(first, S - 1, min(queries, S - first)).astype(
        jnp.int32)
    heads, kv_heads = _local_heads(cfg)
    qh = q[:, rows].reshape(b, len(rows), kv_heads, heads // kv_heads, -1)
    kh = k.reshape(b, S, kv_heads, -1)
    s = jnp.einsum("brkgd,bskd->bkgrs", qh, kh,
                   preferred_element_type=jnp.float32) * cfg.head_dim ** -0.5
    causal = jnp.arange(S)[None] <= rows[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    keep = causal & (scores[:, rows] >= tau[:, rows, None])     # [b, r, S]
    return (jnp.mean(jnp.sum(jnp.where(keep[:, None, None], p, 0.0), axis=-1)),
            jnp.sum(ix.selected(scores, tau)))


def probe(params, ids, cfg):
    """``{name: scalar, or [T] of the exits}`` of ``ids`` [b, S]: the
    readings ``DecoderTrainer._observe`` takes of one batch, the entries the
    CONFIGURATION has, each under its ``monitor.train.*`` name, at the
    weights it is handed.  A reading of the whole stack takes the step's
    own forward (run once, stopped before the head); one of a first layer
    takes that layer's leaves and input alone.

    - ``n_experts``: ``moe_load_max_over_mean``, how uneven the routing is,
      busiest expert over the mean, the largest over layers; where a share
      of the experts is held, ``moe_rows_held``, the (token, expert) pairs
      that meet a held expert, summed over layers (the layer counts them);
      where the experts ride dp over more than one device
      (``expert_parallel``), what the exchange did
      (``moe._exchange_aux``): ``moe_rows_sent``, the pairs of the whole
      batch that left their device, summed over layers;
      ``moe_rows_received``, the rows the fullest device's grouped matmuls
      ran over in its fullest layer; ``moe_exchange_fullest``, the most
      rows any device had for one destination in any layer, beside
      ``moe_exchange_capacity``, the rows a round carries a destination;
      ``moe_exchange_tier``, the rounds past the first that the fullest
      layer needed (0 under balance);
    - ``routing`` with selection biases: ``router_bias_abs_max``, the
      largest, any layer;
    - a layer kind is RETENTION: ``retention_gate_mean``, the mean ``e^g``
      over tokens and heads in layer 0: a state decays to 1/e in ``1 / (1 -
      mean)`` tokens or so;
    - the first layer is MAMBA: ``mamba_dt_mean``, the mean step size over
      tokens and channels in that layer, and ``mamba_decay_min``, the
      smallest ``exp(dt * A)`` of any token, channel and state cell there:
      with the mean's own decay ``exp(-dt)`` it tells a state that never
      carries (both near 0) from one that never forgets (both near 1);
    - a layer kind is MAMBA2: ``mamba2_dt_mean`` and ``mamba2_decay_min``
      (the smallest ``exp(dt A)`` of any token and head: how far the carry
      reaches), of the period's first such position as the EMBEDDING hands
      the batch over (the stream as it enters the stack, not as that layer
      finds it: at seeded weights the step sizes are their bias's);
    - a layer kind is KDA: ``kda_decay_mean``, the mean ``e^g`` over tokens,
      heads and channels, and ``kda_decay_min``, the smallest (how far the
      carry reaches), of the FIRST such position (a leading layer's, else the
      period's) as the embedding hands the batch over; where the write
      strengths reach past 1 (``kda_beta_scale`` 2) also
      ``kda_write_over_one_share``, the share of that position's (token,
      head) writes with ``beta`` > 1: the negative eigenvalues in use, about
      one half at seeded weights;
    - ``attn_gate``: ``attn_gate_mean``, the mean of the output gate's
      sigmoid over tokens, heads and columns in the first layer: a gate
      stuck at 0 or 1 is a dead branch;
    - ``loop_passes`` > 1: ``exit_prob_mean`` [T], the mean over tokens of
      each exit's probability (a gate stuck at 0 or 1 is a dead exit), and
      ``exit_entropy_mean``, the mean entropy of a token's exit
      distribution in nats (ln T at most; 0 is a collapsed gate);
    - ``indexer_heads``: ``dsa_kl_mean``, the indexer's loss term, mean over
      layers, and ``dsa_mass_selected``, the share of the DENSE causal
      softmax's mass that lies on the selected keys, in the first layer, at
      up to 256 queries spread over the positions past ``indexer_topk``,
      mean over heads: near the rows' own selected share, ``topk / (t +
      1)``, the indexer knows nothing; at 1 it drops nothing; and
      ``dsa_pairs_selected``, the (query, key) pairs the first layer's
      indexer selects (ties at a threshold kept: at least ``topk`` a row
      past the first ``topk``);
    - ``attn_gate`` "head": ``attn_gate_mean`` over tokens and heads."""
    out = {}
    if cfg.loop_passes > 1:
        log_p = exit_log_probs(forward(params, ids, cfg)[1])
        p = jnp.exp(log_p)
        out["exit_prob_mean"] = jnp.mean(p, axis=(1, 2))
        out["exit_entropy_mean"] = jnp.mean(-jnp.sum(p * log_p, axis=0))
    elif cfg.n_experts:
        aux = forward(params, ids, cfg)[1]
        out["moe_load_max_over_mean"] = jnp.max(aux["load_max_over_mean"])
        if "rows_held" in aux:
            out["moe_rows_held"] = jnp.sum(aux["rows_held"])
        if "exchange_tier" in aux:      # the experts ride dp, and dp > 1
            out["moe_rows_sent"] = jnp.sum(aux["rows_sent"])
            out["moe_rows_received"] = jnp.max(aux["rows_received"])
            out["moe_exchange_fullest"] = jnp.max(aux["exchange_fullest"])
            out["moe_exchange_capacity"] = aux["exchange_capacity"][0]
            out["moe_exchange_tier"] = jnp.max(aux["exchange_tier"])
        if cfg.indexer_heads:
            out["dsa_kl_mean"] = _dsa_kl_mean(aux, cfg)
            out["dsa_mass_selected"], out["dsa_pairs_selected"] = \
                _mass_selected(params, ids, cfg)
    if cfg.routing == moe.SIGMOID_BIASED:
        out["router_bias_abs_max"] = jnp.max(abs(params["router_bias"]))
    mamba_first = cfg.layer_kinds[0] == MAMBA and not cfg.prefix_pattern
    if RETENTION in cfg.layer_kinds or cfg.attn_gate or mamba_first:
        pl, h = _first_layer_input(params, ids, cfg)
    if RETENTION in cfg.layer_kinds:
        out["retention_gate_mean"] = jnp.mean(
            jnp.exp(retention_log_decay(pl, h)))
    if mamba_first:
        dt = mamba_operands(pl, h, cfg, h, 0)[2]
        # the fastest cell of each channel under its largest step
        rate = jnp.max(jnp.exp(pl["a_log"]), axis=-1)
        out["mamba_dt_mean"] = jnp.mean(dt)
        out["mamba_decay_min"] = jnp.exp(
            -jnp.max(jnp.max(dt, axis=(0, 1)) * rate))
    if MAMBA2 in cfg.layer_kinds:
        at = cfg.layer_kinds.index(MAMBA2)
        pl2 = jax.tree.map(lambda a: a[0],
                           params["params_layers"]["p%d" % at])
        dt = mamba2_operands(pl2, rms_norm(
            embed(params, ids, cfg), pl2["ln1_scale"], cfg.norm_eps),
            cfg)[2]
        out["mamba2_dt_mean"] = jnp.mean(dt)
        out["mamba2_decay_min"] = jnp.exp(-jnp.max(
            jnp.max(dt, axis=(0, 1)) * jnp.exp(pl2["a_log"])))
    if KDA in cfg.prefix_kinds + cfg.layer_kinds:
        first = _first_of_kind(params, ids, cfg, KDA)
        decay = jnp.exp(kda_log_decay(*first, cfg))
        out["kda_decay_mean"] = jnp.mean(decay)
        out["kda_decay_min"] = jnp.min(decay)
        if cfg.kda_beta_scale > 1.0:
            out["kda_write_over_one_share"] = jnp.mean(
                kda_write_strength(*first, cfg) > 1.0)
    if cfg.attn_gate:
        out["attn_gate_mean"] = jnp.mean(jax.nn.sigmoid(
            (h @ pl["wz"]).astype(jnp.float32)))
    return out


@dataclasses.dataclass
class DecoderTrainer(StepTrainer):
    """Every causal decoder's trainer.  ``label`` names its programs
    (``<label>.step``, ``<label>.run_steps``)."""

    label: str = "decoder"
    _logits_fn = _probe_fn = None

    def _on_mesh(self, fn, out_specs, *more):
        """``fn(params, ids [b, S], *more)`` jitted over the mesh, the
        sequences split over dp."""
        return jax.jit(local_shard_map(
            fn, self.mesh,
            in_specs=(self.specs["params"], BATCH_SPECS["ids"]) + more,
            out_specs=out_specs))

    def logits_at(self, ids, positions):
        """The head's float32 logits [B, P, V] at ``positions`` [P] of
        ``ids`` [B, S], at the weights as they stand: the step's own forward
        (block, kernels, MoE, the head's norm and matmul) without the loss.
        A block-diffusion trainer takes the whole batch in ``ids``' place (a
        dict: ``ids`` and its noise ``t``, ``u``) and gives the NOISED rows'
        logits, the stack run on both copies as the step runs it.  Under a
        monitor session the call observes its batch as a step's would
        (``_observe``).
        What a check against a reference reads where the scalar loss cannot
        tell (``benchmark/drivers/train_scan_witnessed.py``).  Of a looped
        stack (``loop_passes`` > 1) the WEIGHTED-EXIT logits ``sum_t p_t
        z_t`` (``weighted_exit_logits``): one array in which every pass,
        every gate (its bias, the survival product, the last exit taking
        what is left) and every call of the head shows."""
        cfg = self.cfg
        noise = NOISE if cfg.block_diffusion else ()
        if self._logits_fn is None:
            def logits(params, ids, at, *drawn):
                if cfg.loop_passes > 1:
                    return weighted_exit_logits(params, ids, at, cfg)
                if drawn:       # ``at`` < S: rows of the noised copy
                    ids = noised_rows(dict(zip(noise, drawn), ids=ids),
                                      cfg)[0]
                return head_logits(
                    params, forward(params, ids, cfg)[0][:, at], cfg)

            self._logits_fn = self._on_mesh(
                logits, P(DP), P(), *(NOISE_SPECS[k] for k in noise))
        batch = ids if noise else {"ids": ids}
        self._observe(batch)    # under a monitor session, as a step's call
        return self._logits_fn(
            self.state["params"], jnp.asarray(batch["ids"]),
            jnp.asarray(positions, jnp.int32),
            *(jnp.asarray(batch[k]) for k in noise))

    def _observe(self, batch):
        """Under a monitor session, of a call on ``batch["ids"]`` [..., B,
        S] (any leading step axis): ``probe``'s readings of the call's
        FIRST batch at the weights the call starts from, a gauge an entry
        (``monitor.train.<name>``; ``exit_prob_mean{exit}`` one an exit),
        from ONE program a trainer.  With ``experts_held`` also
        ``moe_rows_held``, the (token, expert) pairs of EVERY batch of the
        call that meet a held expert (a counter: the probe runs on the other
        batches too), and ``moe_held_rows_share``, their share of the call's
        pairs (experts_held / n_experts at uniform routing; 1 of a full set,
        whose every pair is held).  Off the monitor nothing is built, run or
        read back."""
        mon = monitor.active()
        if mon is None:
            return
        cfg, ids, params = self.cfg, batch["ids"], self.state["params"]
        if cfg.block_diffusion:
            ids = self._observe_noise(mon, batch)
        batches = ids.reshape((-1,) + ids.shape[-2:])
        if self._probe_fn is None:
            self._probe_fn = self._on_mesh(
                functools.partial(probe, cfg=cfg), P())
        read = jax.device_get(self._probe_fn(params, batches[0]))
        held = read.pop("moe_rows_held", None)
        for t, prob in enumerate(read.pop("exit_prob_mean", ())):
            mon.registry.gauge("monitor.train.exit_prob_mean",
                               exit=t + 1).set(float(prob))
        for name, value in read.items():
            mon.registry.gauge("monitor.train." + name).set(float(value))
        if cfg.experts_held:
            pairs = int(ids.size) * cfg.experts_per_token * cfg.moe_layers
            held = pairs if held is None else int(held) + sum(
                int(self._probe_fn(params, b)["moe_rows_held"])
                for b in batches[1:])
            mon.registry.counter("monitor.train.moe_rows_held").incr(held)
            mon.registry.gauge("monitor.train.moe_held_rows_share").set(
                held / pairs)


    def _observe_noise(self, mon, batch):
        """A block-diffusion call's own readings, and the rows ``[x_t ;
        x_0]`` of its batches (what the stack's readings are taken on):
        ``bd_masked_share``, the masked tokens over all of the call's;
        ``lm_head_rows``, the rows the head computes for them (whole blocks
        a dp shard, by the function the device code takes its trip count
        from) and their share ``lm_head_rows_share``; ``bd_tiles_a_layer``,
        the steps of the rule's table a head's forward sweep walks; and,
        where a share of the experts is held, ``moe_capacity_rows``, the
        first static capacity a layer's step is compiled for."""
        cfg = self.cfg
        rows, masked, _ = noised_rows(
            {k: jnp.asarray(batch[k]) for k in ("ids",) + NOISE}, cfg)
        dp, (b, s) = self.mesh.shape[DP], masked.shape[-2:]
        live = jax.device_get(masked).reshape(-1, dp, b // dp * s)
        head = int(head_rows_computed(live.sum(-1), live.shape[-1]).sum())
        for name, value in (("bd_masked_share", live.mean()),
                            ("lm_head_rows_share", head / live.size)):
            mon.registry.gauge("monitor.train." + name).set(float(value))
        mon.registry.counter("monitor.train.lm_head_rows").incr(head)
        from ..kernels.flash_attention import kv_blocks

        heads, kv_heads = _local_heads(cfg)
        mon.registry.gauge("monitor.train.bd_tiles_a_layer").set(kv_blocks(
            2 * s, *_packed_flash_blocks(cfg, heads, 2 * s, kv_heads), False,
            blocks=cfg.block_diffusion))
        if cfg.experts_held:
            mon.registry.gauge("monitor.train.moe_capacity_rows").set(
                moe._held_capacities(
                    b // dp * 2 * s * cfg.experts_per_token,
                    cfg.experts_here, cfg.n_experts)[0])
        return rows


def build_decoder_trainer(cfg, mesh_spec: MeshSpec = None, optimizer=None,
                          seed=0, devices=None, label="decoder",
                          positions=False):
    """Mesh, parameters on the mesh, the jitted sharded step and its scan.
    Meshes: ``dp`` any, ``tp == pp == 1`` (the block has no tensor- or
    pipeline-parallel layout yet).  Sequences are split over ``dp`` and
    every leaf is replicated and its gradient all-reduced, EXCEPT the routed
    experts of a configuration with ``expert_parallel``: they ride ``dp``
    (``dp`` has to divide ``n_experts``; each device holds n / dp experts of
    every layer, seeds them in place and keeps their moments; the MoE layer
    exchanges rows, ``moe.dropless_moe_ffn``).  A router's selection biases, where the
    parameters hold them, are the step's to set and not the optimizer's.
    ``positions``: every batch carries ``positions`` int32 [3, B, S] beside
    its ``ids`` (``POSITION_SPECS``); a block-diffusion configuration's
    carries its noise (``NOISE_SPECS``)."""
    mesh_spec = mesh_spec or MeshSpec()
    assert mesh_spec.tp == mesh_spec.pp == cfg.tp == cfg.pp == 1, \
        "the decoder block runs at tp == pp == 1"
    assert not cfg.expert_parallel or cfg.n_experts % mesh_spec.dp == 0, \
        "expert_parallel: dp %d does not divide the %d experts" % (
            mesh_spec.dp, cfg.n_experts)
    mesh = mesh_spec.build(devices=devices)
    optimizer = optimizer or optim.adamw()

    pspecs = transformer_param_specs(cfg)
    # an expert-parallel stack is seeded on the mesh, each device its own
    # experts, and its moments are made from the placed leaves (zeros of a
    # placed array lie where it lies): no device ever holds the whole state
    params = init_transformer_params(
        jax.random.PRNGKey(seed), cfg,
        shardings=jax.tree.map(lambda spec: NamedSharding(mesh, spec), pspecs)
        if cfg.expert_parallel else None)
    state = TrainState.create(params, optimizer)
    sspecs = state_specs(pspecs, state)
    build = make_train_step(make_loss_fn(cfg), mesh, pspecs,
                            grad_sync_axes(cfg), optimizer,
                            batch_specs(cfg, positions),
                            stepped=tuple(STEPPED & set(params)))
    step_fn, multi_fn = build(state), build.multi(state)
    with mesh:
        state = shard_pytree(state, sspecs, mesh)
    return DecoderTrainer(
        cfg=cfg, mesh=mesh, state=state, step_fn=step_fn, specs=sspecs,
        multi_fn=multi_fn, label=label)
