"""OLMoE-class sparse decoder LM pretraining (Muennighoff et al. 2024,
arXiv:2409.02060; HF ``model_type`` ``olmoe``): a pre-norm decoder block with
RMS norms, QK-norm over the whole projection, rotary positions, causal
attention without biases, and a top-8-of-64 DROPLESS mixture of gated-SiLU
experts in place of the FFN; an untied head.

The block is ``parallel/transformer.py``'s own, chosen by configuration
(``TransformerConfig.norm`` / ``positions`` / ``qk_norm`` / ``bias`` /
``tie_head`` / ``n_experts``), not a second layer function here: the norm,
the projections, the flash kernel, ``run_layers``' scan and remat and the
row-block head are the code BERT runs, so one change to them is measured on
both.  The MoE is ``parallel/moe.py``'s ``dropless_moe_ffn``.

batch dict: ``ids`` int32 [B, S] alone.  The loss builds the next-token
labels itself (``labels[t] = ids[t + 1]``, positions 0..S-2 count) and adds
the router's auxiliary losses, mean over layers:
``ce + router_aux_coef * load_balance + router_z_coef * router_z``.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import monitor
from ..parallel import moe, optim
from ..parallel.mesh import DP, MeshSpec, local_shard_map
from ..parallel.train import (StepTrainer, TrainState, make_train_step,
                              shard_pytree, state_specs)
from ..parallel.transformer import (
    TransformerConfig,
    embed,
    final_logits_loss,
    gauge_flash_grid,
    gauge_moe_rows,
    grad_sync_axes,
    head_logits,
    init_transformer_params,
    run_layers,
    transformer_param_specs,
)

__all__ = ["olmoe_1b_7b_config", "olmoe_tiny_config", "make_loss_fn",
           "OlmoeTrainer", "build_olmoe_trainer"]

BATCH_SPECS = {"ids": P(DP)}
STEPPED = {"router_bias"}       # leaves a step sets itself (make_train_step)


def olmoe_1b_7b_config(**kw):
    """allenai/OLMoE-1B-7B-0125-Instruct ``config.json``; the two router
    coefficients are the paper's (HF ``router_aux_loss_coef`` 0.01, z-loss
    0.001).  Its ``norm_topk_prob`` is false, which is the one routing
    ``parallel/moe.py`` has: the top-k weights are not renormalised."""
    d = dict(vocab_size=50304, hidden=2048, n_layers=16, n_heads=16,
             ffn_hidden=1024, max_seq=4096, causal=True, dtype="bfloat16",
             norm="rms", norm_eps=1e-5, positions="rotary", rope_theta=10000.0,
             qk_norm=True, bias=False, tie_head=False, n_experts=64,
             experts_per_token=8, router_aux_coef=0.01,
             router_z_coef=0.001)
    d.update(kw)
    return TransformerConfig(**d)


def olmoe_tiny_config(**kw):
    """Tiny shapes for the CPU tests: 2 layers, 4 heads of 16, 8 experts of
    width 32, top-2, float32."""
    return olmoe_1b_7b_config(**dict(dict(
        vocab_size=256, hidden=64, n_layers=2, n_heads=4, ffn_hidden=32,
        max_seq=32, n_experts=8, experts_per_token=2, dtype="float32"), **kw))


def _forward(params, ids, cfg):
    """The stack on ``ids`` [b, S]: the last activation and the layers'
    router values, each stacked [L]."""
    return run_layers(params["params_layers"], embed(params, ids, cfg), cfg,
                      with_aux=True, prefix=params.get("prefix_layers"),
                      router_bias=params.get("router_bias"))


def make_loss_fn(cfg: TransformerConfig):
    """Per-device training loss on a batch of ``ids``.  Where the routing
    rule has selection biases (``moe.SIGMOID_BIASED``): ``(loss, {"router_bias":
    their next values})``, each layer's moved against that layer's load in
    this step (``moe.balance_bias``; ``make_train_step``'s ``stepped``)."""

    def loss_fn(params, batch):
        ids = batch["ids"]
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to(
            (jnp.arange(ids.shape[1]) < ids.shape[1] - 1).astype(jnp.float32),
            ids.shape)
        x, aux = _forward(params, ids, cfg)
        ce = final_logits_loss(params, x, labels, mask, cfg)
        if cfg.routing == moe.SIGMOID_BIASED:
            return ce, {"router_bias": moe.balance_bias(
                params["router_bias"], aux["load"], cfg.router_bias_rate)}
        if not (cfg.router_aux_coef or cfg.router_z_coef):
            return ce               # a configuration with no auxiliary loss
        return (ce + cfg.router_aux_coef * jnp.mean(aux["load_balance"])
                + cfg.router_z_coef * jnp.mean(aux["router_z"]))

    return loss_fn


@dataclasses.dataclass
class OlmoeTrainer(StepTrainer):
    label = "olmoe"
    _load_fn = None
    _logits_fn = None

    def logits_at(self, ids, positions):
        """The head's float32 logits [B, P, V] at ``positions`` [P] of
        ``ids`` [B, S], at the weights as they stand: the step's own forward
        (block, kernels, MoE, the head's norm and matmul) without the loss.
        What a check against a reference reads where the scalar loss cannot
        tell (``benchmark/drivers/train_scan_witnessed.py``)."""
        cfg = self.cfg
        if self._logits_fn is None:
            self._logits_fn = jax.jit(local_shard_map(
                lambda params, ids, at: head_logits(
                    params, _forward(params, ids, cfg)[0][:, at], cfg),
                self.mesh, in_specs=(self.specs["params"], P(DP), P()),
                out_specs=P(DP)))
        return self._logits_fn(self.state["params"], jnp.asarray(ids),
                               jnp.asarray(positions, jnp.int32))

    def _observe(self, batch):
        ids = batch["ids"]
        self._count_moe(ids)
        local = ids.shape[-2] // self.mesh.shape[DP]
        gauge_flash_grid(self.cfg, local, ids.shape[-1])
        gauge_moe_rows(self.cfg, local * ids.shape[-1])

    def _count_moe(self, ids):
        """Under a monitor session: the token-slots this call routes
        (``ids`` [..., B, S], any leading step axis; T * k a MoE layer and step) and how
        uneven the routing of the call's first batch is, busiest expert over
        the mean, the largest over layers: a forward of its own that stops
        before the head.  Off the monitor nothing runs or is read back."""
        mon = monitor.active()
        if mon is None:
            return
        cfg = self.cfg
        mon.registry.counter("monitor.train.moe_assignments").incr(
            int(ids.size) * cfg.experts_per_token * cfg.moe_layers)
        if self._load_fn is None:
            self._load_fn = jax.jit(local_shard_map(
                lambda params, ids: jnp.max(
                    _forward(params, ids, cfg)[1]["load_max_over_mean"]),
                self.mesh, in_specs=(self.specs["params"], P(DP)),
                out_specs=P()))
        first = ids.reshape((-1,) + ids.shape[-2:])[0]
        mon.registry.gauge("monitor.train.moe_load_max_over_mean").set(
            float(self._load_fn(self.state["params"], first)))


def build_olmoe_trainer(cfg, mesh_spec: MeshSpec = None, optimizer=None,
                        seed=0, devices=None, trainer=None):
    """Mesh, parameters on the mesh, the jitted sharded step and its scan.
    Data parallel only: the block has no tensor-, pipeline- or
    expert-parallel layout yet.  ``trainer``: the ``OlmoeTrainer`` subclass
    of another sparse decoder of this block (models/smallthinker.py,
    models/lfm2.py).  A router's selection biases, where the parameters
    hold them, are the step's to set and not the optimizer's."""
    mesh_spec = mesh_spec or MeshSpec()
    assert mesh_spec.tp == mesh_spec.pp == cfg.tp == cfg.pp == 1, \
        "the OLMoE block runs at tp == pp == 1"
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
    return (trainer or OlmoeTrainer)(
        cfg=cfg, mesh=mesh, state=state, step_fn=step_fn, specs=sspecs,
        multi_fn=multi_fn)
