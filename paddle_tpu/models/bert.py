"""BERT-style transformer LM pretraining — the flagship perf config
(BASELINE.json: "BERT-base pretraining (fused attention + LAMB optimizer)").

Functional SPMD model over the parallel/ engine: vocab-parallel embedding,
Megatron-SP (or ring/context-parallel) transformer blocks, GPipe pipeline,
vocab-parallel MLM loss.  The reference has no BERT implementation in-tree;
its closest machinery is the fused attention inference op
(operators/fused/multihead_matmul_op.cu) and the LAMB optimizer
(operators/optimizers/lamb_op.h) — both of which this config exercises in
TPU-native form (Pallas/XLA attention + parallel/optim.py lamb).

batch dict: ids/labels int32 [B, S], mask float32 [B, S] (1 where the label
position counts — MLM masked positions, or every position for causal LM).
``mask`` alone says which positions count: with tp=1 the LM head computes
only the rows whose mask is non-zero (transformer._weighted_vocab_nll), so an
MLM batch pays for its 80 predicted positions and not for all 512.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor
from ..parallel import collectives as col
from ..parallel.mesh import DP, PP, TP, MeshSpec
from ..parallel.pipeline import gpipe, split_microbatches
from ..parallel import optim
from ..parallel.train import (StepTrainer, TrainState,
                              make_train_step, shard_pytree, state_specs)
from ..parallel.transformer import (
    TransformerConfig,
    embed,
    final_logits_loss,
    grad_sync_axes,
    head_rows_computed,
    init_transformer_params,
    run_layers,
    transformer_param_specs,
)
from jax.sharding import PartitionSpec as P

__all__ = ["bert_base_config", "bert_tiny_config", "make_loss_fn",
           "build_bert_trainer"]


def bert_base_config(**kw):
    d = dict(vocab_size=30528, hidden=768, n_layers=12, n_heads=12,
             ffn_hidden=3072, max_seq=512, causal=False, dtype="bfloat16")
    d.update(kw)
    return TransformerConfig(**d)


def bert_tiny_config(**kw):
    """Tiny shapes for tests/dryrun (multiples of tp up to 2, heads 4)."""
    d = dict(vocab_size=128, hidden=32, n_layers=4, n_heads=4, ffn_hidden=64,
             max_seq=32, causal=False, dtype="float32")
    d.update(kw)
    return TransformerConfig(**d)


def make_loss_fn(cfg: TransformerConfig, n_microbatches=1):
    """Per-device loss: embeds, runs the (possibly pipelined) stack, computes
    the vocab-parallel MLM loss, and pp-masks it to the last stage."""

    def loss_fn(params, batch):
        ids, labels = batch["ids"], batch["labels"]
        mask = batch["mask"].astype(jnp.float32)

        x_sp = embed(params, ids, cfg)                       # [b, S/tp, E]

        if cfg.pp > 1:
            lp = jax.tree.map(lambda a: a[0], params["params_layers"])
            x_mb = split_microbatches(x_sp, n_microbatches)
            outs = gpipe(lambda p, x: run_layers(p, x, cfg), lp, x_mb, axis=PP)
            x_sp = outs.reshape((-1,) + outs.shape[2:])
            loss = final_logits_loss(params, x_sp, labels, mask, cfg)
            npp = col.axis_size_in(PP)
            is_last = (col.axis_index(PP) == npp - 1).astype(jnp.float32)
            loss = col.psum(loss * is_last, PP)
        else:
            x_sp = run_layers(params["params_layers"], x_sp, cfg)
            loss = final_logits_loss(params, x_sp, labels, mask, cfg)
        return loss

    return loss_fn


def batch_specs(keys=("ids", "labels", "mask")):
    return {k: P(DP) for k in keys}


@dataclasses.dataclass
class BertTrainer(StepTrainer):
    batch_keys: tuple = ("ids", "labels", "mask")
    label = "bert"

    def _observe(self, batch):
        self._count_head_rows(batch["mask"])

    def _count_head_rows(self, mask):
        """Under a monitor session: the rows the LM head computes for these
        batches (``mask`` [..., B, S], any leading step axis), by the
        function the device code takes its trip count from; each dp shard
        has its own count.  Off the monitor nothing is read back."""
        mon = monitor.active()
        if mon is None:
            return
        dp = self.mesh.shape[DP]
        live = (np.asarray(mask) != 0).reshape(
            -1, dp, mask.shape[-2] // dp * mask.shape[-1])
        n = live.shape[-1]
        rows = (head_rows_computed(live.sum(-1), n).sum() if self.cfg.tp == 1
                else live.size)           # the vocab-parallel head is dense
        mon.registry.counter("monitor.train.lm_head_rows").incr(int(rows))
        mon.registry.gauge("monitor.train.lm_head_rows_share").set(
            rows / live.size)


def build_bert_trainer(cfg, mesh_spec: MeshSpec = None, optimizer=None,
                       n_microbatches=1, seed=0, devices=None,
                       batch_keys=("ids", "labels", "mask")):
    """End-to-end setup: mesh, params on mesh, jitted sharded train step.
    The ParallelExecutor-constructor analogue (parallel_executor.cc:393)."""
    mesh_spec = mesh_spec or MeshSpec(dp=1, pp=cfg.pp, tp=cfg.tp)
    assert mesh_spec.pp == cfg.pp and mesh_spec.tp == cfg.tp
    mesh = mesh_spec.build(devices=devices)
    optimizer = optimizer or optim.lamb()

    params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
    pspecs = transformer_param_specs(cfg)
    state = TrainState.create(params, optimizer)
    syncs = grad_sync_axes(cfg)
    loss_fn = make_loss_fn(cfg, n_microbatches=n_microbatches)
    if getattr(mesh_spec, "zero", False):
        # kReduce/ZeRO: optimizer state sharded over dp (parallel/zero.py);
        # build() returns the specs it jitted against — place with exactly
        # those so eligibility logic lives in one place
        from ..parallel.zero import make_zero_train_step
        build = make_zero_train_step(loss_fn, mesh, pspecs, syncs,
                                     optimizer, batch_specs(batch_keys))
        step_fn, sspecs = build(state)
        multi_fn = None
    else:
        sspecs = state_specs(pspecs, state)
        build = make_train_step(loss_fn, mesh, pspecs, syncs,
                                optimizer, batch_specs(batch_keys))
        step_fn = build(state)
        multi_fn = build.multi(state)
    with mesh:
        state = shard_pytree(state, sspecs, mesh)
    return BertTrainer(cfg=cfg, mesh=mesh, state=state, step_fn=step_fn,
                       specs=sspecs, multi_fn=multi_fn,
                       batch_keys=tuple(batch_keys))
