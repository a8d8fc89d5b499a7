"""The causal decoders' trainer, beside the block it trains
(``transformer.py``) and the step it runs (``train.py``): forward, loss,
``logits_at``, a call's observations and the builder, ONE of each for every
decoder of this block.  A model (``models/olmoe.py``, ``smallthinker.py``,
``lfm2.py``, ``brumby.py``, ``mistral4.py``, ``trinity.py``, ``jamba.py``,
``nemotron_h.py``, ``ouro.py``)
is a ``TransformerConfig`` and a label; what its trainer computes and what
it observes follow from the configuration, never from which model it is.

batch dict: ``ids`` int32 [B, S] alone.  The loss builds the next-token
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

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import moe, optim
from .. import monitor
from ..kernels.power_retention import STATE_COLUMNS, state_sweeps
from .mesh import DP, MeshSpec, local_shard_map
from .train import (StepTrainer, TrainState, gauge_flash_grid,
                    make_train_step, shard_pytree, state_specs)
from .transformer import (
    MAMBA,
    MAMBA2,
    RETENTION,
    TransformerConfig,
    embed,
    exit_log_probs,
    exit_weighted_loss,
    final_logits_loss,
    grad_sync_axes,
    head_logits,
    init_transformer_params,
    mamba2_operands,
    mamba_operands,
    retention_log_decay,
    rms_norm,
    run_layers,
    run_passes,
    transformer_param_specs,
    yarn_blend_range,
)

__all__ = ["BATCH_SPECS", "STEPPED", "forward", "weighted_exit_logits",
           "make_loss_fn",
           "DecoderTrainer", "build_decoder_trainer", "gauge_moe_rows",
           "retention_chunks", "retention_state_mb", "retention_state_sweeps",
           "interpolated_pairs", "scaled_positions"]

BATCH_SPECS = {"ids": P(DP)}
STEPPED = {"router_bias"}       # leaves a step sets itself (make_train_step)


def forward(params, ids, cfg):
    """The stack on ``ids`` [b, S]: the last activation and the layers'
    router values, each stacked [L]; of a looped stack (``loop_passes`` >
    1) every pass's last activation [T, b, S, E] and the exit gates' logits
    [T, b, S] (``transformer.run_passes``)."""
    x = embed(params, ids, cfg)
    if cfg.loop_passes > 1:
        return run_passes(params, x, cfg)
    return run_layers(params["params_layers"], x, cfg, with_aux=True,
                      prefix=params.get("prefix_layers"),
                      router_bias=params.get("router_bias"))


def weighted_exit_logits(params, ids, at, cfg):
    """A looped stack's float32 logits [b, P, V] at positions ``at`` [P] of
    ``ids`` [b, S]: ``sum_t p_t z_t``, every exit's logits ``z_t`` (the
    final norm and the head on that pass's state) weighted by the
    position's exit distribution."""
    exits, gates = forward(params, ids, cfg)
    p = jnp.exp(exit_log_probs(gates[:, :, at]))
    return sum(p[t][..., None] * head_logits(params, exits[t][:, at], cfg)
               for t in range(cfg.loop_passes))


def make_loss_fn(cfg: TransformerConfig):
    """Per-device training loss on a batch of ``ids``.  Where the routing
    rule has selection biases (``moe.SIGMOID_BIASED``): ``(loss, {"router_bias":
    their next values})``, each layer's moved against that layer's load in
    this step (``moe.balance_bias``; ``make_train_step``'s ``stepped``).
    Of a looped stack the exit-weighted loss of its passes."""

    def loss_fn(params, batch):
        ids = batch["ids"]
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to(
            (jnp.arange(ids.shape[1]) < ids.shape[1] - 1).astype(jnp.float32),
            ids.shape)
        x, aux = forward(params, ids, cfg)
        if cfg.loop_passes > 1:
            return exit_weighted_loss(params, x, aux, labels, mask, cfg)
        ce = final_logits_loss(params, x, labels, mask, cfg)
        if cfg.routing == moe.SIGMOID_BIASED:
            return ce, {"router_bias": moe.balance_bias(
                params["router_bias"], aux["load"], cfg.router_bias_rate)}
        if not (cfg.router_aux_coef or cfg.router_z_coef):
            return ce               # a configuration with no auxiliary loss
        return (ce + cfg.router_aux_coef * jnp.mean(aux["load_balance"])
                + cfg.router_z_coef * jnp.mean(aux["router_z"]))

    return loss_fn


def retention_chunks(cfg, seq):
    """Chunks a retention layer walks over a sequence of ``seq`` tokens."""
    return seq // min(cfg.retention_chunk, seq)


def retention_state_mb(cfg):
    """The state one retention layer carries along a sequence, in MB: a
    float32 ``STATE_COLUMNS`` x head width a key/value head."""
    return cfg.kv_heads * STATE_COLUMNS * cfg.head_dim * 4 / 1e6


def retention_state_sweeps(cfg, seq):
    """Sweeps of the state's 65 tiles a layer's forward runs over a sequence
    of ``seq`` tokens, as the kernels' own rule has it: one a key/value
    head and chunk where a group's query heads ride one grid step, one a
    query head and chunk where they would not fit the kernels' VMEM."""
    return state_sweeps(cfg.n_heads, cfg.kv_heads, seq,
                        min(cfg.retention_chunk, seq),
                        jnp.dtype(cfg.dtype).itemsize)


def interpolated_pairs(cfg):
    """(first, last) of the rotated pairs whose frequency is WHOLLY the
    interpolated one, ``plain / rope_factor``; None without YaRN."""
    if not cfg.rope_factor > 1:
        return None
    return yarn_blend_range(cfg)[1], cfg.qk_rope_dim // 2 - 1


def scaled_positions(cfg, seq):
    """Positions of a sequence of ``seq`` tokens whose query is scaled by
    more than 1: those from ``rope_original_max`` on."""
    if not cfg.q_scale_beta:
        return 0
    return max(seq - cfg.rope_original_max, 0)


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


def gauge_moe_rows(cfg, tokens):
    """Under a monitor session, of one expert layer's call on ``tokens``
    local tokens, whose sum back is the row kernel's
    (``kernels/moe_rows.py``): ``monitor.kernels.moe_pair_slots`` is the
    (token, expert) pair slots the sum back covers, T * k, which a gather
    would fetch a row for each; ``monitor.kernels.moe_rows_fetch_bound`` the
    rows the kernel can be asked for at the layer's first capacity
    (``moe._held_capacities``: a step past it runs the capacity that has a
    row for every slot; with every expert held, the slots).  Both fixed
    when the step is traced, so gauges.  Where a share of the experts is
    held, the rows fetched over the slots is
    ``monitor.train.moe_rows_held`` / (MoE layers x steps x
    ``moe_pair_slots``): the held experts' share at balanced routing."""
    mon = monitor.active()
    if mon is None:
        return
    slots = tokens * cfg.experts_per_token
    mon.registry.gauge("monitor.kernels.moe_pair_slots").set(slots)
    mon.registry.gauge("monitor.kernels.moe_rows_fetch_bound").set(
        moe._held_capacities(slots, cfg.experts_here, cfg.n_experts)[0])


@dataclasses.dataclass
class DecoderTrainer(StepTrainer):
    """Every causal decoder's trainer.  ``label`` names its programs
    (``<label>.step``, ``<label>.run_steps``)."""

    label: str = "decoder"
    _logits_fn = _routing_fn = _gate_fn = _attn_gate_fn = _mamba_fn = None
    _mamba2_fn = _exits_fn = None

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
        What a check against a reference reads where the scalar loss cannot
        tell (``benchmark/drivers/train_scan_witnessed.py``).  Of a looped
        stack (``loop_passes`` > 1) the WEIGHTED-EXIT logits ``sum_t p_t
        z_t`` (``weighted_exit_logits``): one array in which every pass,
        every gate (its bias, the survival product, the last exit taking
        what is left) and every call of the head shows."""
        cfg = self.cfg
        if self._logits_fn is None:
            def logits(params, ids, at):
                if cfg.loop_passes > 1:
                    return weighted_exit_logits(params, ids, at, cfg)
                return head_logits(
                    params, forward(params, ids, cfg)[0][:, at], cfg)

            self._logits_fn = self._on_mesh(logits, P(DP), P())
        return self._logits_fn(self.state["params"], jnp.asarray(ids),
                               jnp.asarray(positions, jnp.int32))

    def _observe(self, batch):
        """Under a monitor session, of a call on ``batch["ids"]`` [..., B,
        S] (any leading step axis), what the CONFIGURATION has; a forward
        that a reading needs is one of its own that stops before the head,
        at the weights the call starts from.  Off the monitor nothing runs
        or is read back.

        - a layer kind is attention: the flash kernels' grid
          (``train.gauge_flash_grid``);
        - ``n_experts``: ``monitor.train.moe_assignments``, the token-slots
          the call routes (T * k a MoE layer and step; a counter);
          ``moe_load_max_over_mean``, how uneven the routing of the call's
          first batch is, busiest expert over the mean, the largest over
          layers; the row kernel's two (``gauge_moe_rows``);
        - ``experts_held``: ``moe_rows_held``, the (token, expert) pairs of
          EVERY batch of the call that meet a held expert (a counter), and
          ``moe_held_rows_share``, their share of the call's pairs
          (experts_held / n_experts at uniform routing; 1 of a full set);
        - ``routing`` with selection biases: ``router_bias_abs_max``, the
          largest, any layer, as the call starts;
        - a layer kind is RETENTION: ``retention_chunks`` (a layer and
          sequence), ``retention_state_mb`` (the state a layer carries),
          ``retention_state_sweeps`` and ``retention_gate_mean``, the mean
          ``e^g`` over tokens and heads of the call's first batch in layer
          0: a state decays to 1/e in ``1 / (1 - mean)`` tokens or so;
        - the first layer is MAMBA: ``mamba_dt_mean``, the mean step size
          over tokens and channels of the call's first batch in that layer,
          and ``mamba_decay_min``, the smallest ``exp(dt * A)`` of any
          token, channel and state cell there: with the mean's own decay
          ``exp(-dt)`` it tells a state that never carries (both near 0)
          from one that never forgets (both near 1);
        - a layer kind is MAMBA2: ``mamba2_dt_mean`` and
          ``mamba2_decay_min`` (the smallest ``exp(dt A)`` of any token and
          head: how far the carry reaches), of the period's first such
          position on the call's first batch as the EMBEDDING hands it over
          (the stream as it enters the stack, not as that layer finds it:
          at seeded weights the step sizes are their bias's);
        - ``attn_gate``: ``attn_gate_mean``, the mean of the output gate's
          sigmoid over tokens, heads and columns of the call's first batch
          in the first layer: a gate stuck at 0 or 1 is a dead branch;
        - ``latent``: ``mla_latent_bytes_per_token`` (what a layer's keys
          and values come from: the latent and the shared rotary key)
          beside ``mla_expanded_kv_bytes_per_token`` (what the flash
          kernels read: every head's key and value),
          ``yarn_first_interpolated_pair`` / ``_last_``
          (``interpolated_pairs``) and ``q_scaled_positions``
          (``scaled_positions``); all fixed when the step is traced;
        - ``loop_passes`` > 1: ``loop_passes`` (a gauge) and
          ``layer_applications``, passes x layers x the call's steps (a
          counter); of the call's first batch, ``exit_prob_mean{exit}``,
          the mean over tokens of each exit's probability (a gate stuck at
          0 or 1 is a dead exit), and ``exit_entropy_mean``, the mean
          entropy of a token's exit distribution in nats (ln T at most; 0
          is a collapsed gate)."""
        mon = monitor.active()
        if mon is None:
            return
        cfg, ids, params = self.cfg, batch["ids"], self.state["params"]
        seq = ids.shape[-1]
        local = ids.shape[-2] // self.mesh.shape[DP]
        batches = ids.reshape((-1,) + ids.shape[-2:])

        def gauge(name, value):
            mon.registry.gauge("monitor.train." + name).set(value)

        def count(name, amount):
            mon.registry.counter("monitor.train." + name).incr(amount)

        if any(isinstance(k, tuple) for k in cfg.layer_kinds):
            gauge_flash_grid(cfg, local, seq)
        if cfg.n_experts:
            pairs = int(ids.size) * cfg.experts_per_token * cfg.moe_layers
            count("moe_assignments", pairs)
            if self._routing_fn is None:
                def routing(params, ids):
                    aux = forward(params, ids, cfg)[1]
                    # the layer counts the pairs held where it holds a share
                    return (jnp.max(aux["load_max_over_mean"]),
                            jnp.sum(aux.get("rows_held", 0)))

                self._routing_fn = self._on_mesh(routing, (P(), P()))
            share = cfg.experts_here < cfg.n_experts
            read = [self._routing_fn(params, b)
                    for b in (batches if share else batches[:1])]
            gauge("moe_load_max_over_mean", float(read[0][0]))
            if cfg.experts_held:
                # of a full set every pair is held
                held = sum(int(h) for _, h in read) if share else pairs
                count("moe_rows_held", held)
                gauge("moe_held_rows_share", held / pairs)
            gauge_moe_rows(cfg, local * seq)
        if cfg.routing == moe.SIGMOID_BIASED:
            gauge("router_bias_abs_max",
                  float(abs(params["router_bias"]).max()))
        if RETENTION in cfg.layer_kinds:
            gauge("retention_chunks", retention_chunks(cfg, seq))
            gauge("retention_state_mb", retention_state_mb(cfg))
            gauge("retention_state_sweeps", retention_state_sweeps(cfg, seq))
            if self._gate_fn is None:
                def gate_mean(params, ids):
                    pl, h = _first_layer_input(params, ids, cfg)
                    return jnp.mean(jnp.exp(retention_log_decay(pl, h)))

                self._gate_fn = self._on_mesh(gate_mean, P())
            gauge("retention_gate_mean",
                  float(self._gate_fn(params, batches[0])))
        if cfg.layer_kinds[0] == MAMBA and not cfg.prefix_pattern:
            if self._mamba_fn is None:
                def step_sizes(params, ids):
                    pl, h = _first_layer_input(params, ids, cfg)
                    dt = mamba_operands(pl, h, cfg, h, 0)[2]
                    # the fastest cell of each channel under its largest step
                    rate = jnp.max(jnp.exp(pl["a_log"]), axis=-1)
                    return jnp.mean(dt), jnp.exp(-jnp.max(
                        jnp.max(dt, axis=(0, 1)) * rate))

                self._mamba_fn = self._on_mesh(step_sizes, (P(), P()))
            dt_mean, decay_min = self._mamba_fn(params, batches[0])
            gauge("mamba_dt_mean", float(dt_mean))
            gauge("mamba_decay_min", float(decay_min))
        if MAMBA2 in cfg.layer_kinds:
            if self._mamba2_fn is None:
                at = cfg.layer_kinds.index(MAMBA2)

                def step_sizes2(params, ids):
                    pl = jax.tree.map(lambda a: a[0],
                                      params["params_layers"]["p%d" % at])
                    h = rms_norm(embed(params, ids, cfg), pl["ln1_scale"],
                                 cfg.norm_eps)
                    dt = mamba2_operands(pl, h, cfg)[2]
                    return jnp.mean(dt), jnp.exp(-jnp.max(
                        jnp.max(dt, axis=(0, 1)) * jnp.exp(pl["a_log"])))

                self._mamba2_fn = self._on_mesh(step_sizes2, (P(), P()))
            dt_mean, decay_min = self._mamba2_fn(params, batches[0])
            gauge("mamba2_dt_mean", float(dt_mean))
            gauge("mamba2_decay_min", float(decay_min))
        if cfg.attn_gate:
            if self._attn_gate_fn is None:
                def attn_gate_mean(params, ids):
                    pl, h = _first_layer_input(params, ids, cfg)
                    return jnp.mean(jax.nn.sigmoid(
                        (h @ pl["wz"]).astype(jnp.float32)))

                self._attn_gate_fn = self._on_mesh(attn_gate_mean, P())
            gauge("attn_gate_mean",
                  float(self._attn_gate_fn(params, batches[0])))
        if cfg.latent:
            itemsize = cfg.jdtype.itemsize
            gauge("mla_latent_bytes_per_token",
                  (cfg.kv_lora_rank + cfg.qk_rope_dim) * itemsize)
            gauge("mla_expanded_kv_bytes_per_token",
                  cfg.n_heads * (cfg.head_dim + cfg.v_head_dim) * itemsize)
            whole = interpolated_pairs(cfg)
            if whole:
                gauge("yarn_first_interpolated_pair", whole[0])
                gauge("yarn_last_interpolated_pair", whole[1])
            gauge("q_scaled_positions", scaled_positions(cfg, seq))
        if cfg.loop_passes > 1:
            gauge("loop_passes", cfg.loop_passes)
            count("layer_applications",
                  cfg.loop_passes * cfg.n_layers * len(batches))
            if self._exits_fn is None:
                def exits(params, ids):
                    log_p = exit_log_probs(forward(params, ids, cfg)[1])
                    p = jnp.exp(log_p)
                    return (jnp.mean(p, axis=(1, 2)),
                            jnp.mean(-jnp.sum(p * log_p, axis=0)))

                self._exits_fn = self._on_mesh(exits, (P(), P()))
            probs, entropy = self._exits_fn(params, batches[0])
            for t, prob in enumerate(probs):
                mon.registry.gauge("monitor.train.exit_prob_mean",
                                   exit=t + 1).set(float(prob))
            gauge("exit_entropy_mean", float(entropy))


def build_decoder_trainer(cfg, mesh_spec: MeshSpec = None, optimizer=None,
                          seed=0, devices=None, label="decoder"):
    """Mesh, parameters on the mesh, the jitted sharded step and its scan.
    Data parallel only: the block has no tensor-, pipeline- or
    expert-parallel layout yet.  A router's selection biases, where the
    parameters hold them, are the step's to set and not the optimizer's."""
    mesh_spec = mesh_spec or MeshSpec()
    assert mesh_spec.tp == mesh_spec.pp == cfg.tp == cfg.pp == 1, \
        "the decoder block runs at tp == pp == 1"
    mesh = mesh_spec.build(devices=devices)
    optimizer = optimizer or optim.adamw()

    params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
    pspecs = transformer_param_specs(cfg)
    state = TrainState.create(params, optimizer)
    sspecs = state_specs(pspecs, state)
    build = make_train_step(make_loss_fn(cfg), mesh, pspecs,
                            grad_sync_axes(cfg), optimizer, BATCH_SPECS,
                            stepped=tuple(STEPPED & set(params)))
    step_fn, multi_fn = build(state), build.multi(state)
    with mesh:
        state = shard_pytree(state, sspecs, mesh)
    return DecoderTrainer(
        cfg=cfg, mesh=mesh, state=state, step_fn=step_fn, specs=sspecs,
        multi_fn=multi_fn, label=label)
