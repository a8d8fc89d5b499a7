"""Brumby-class attention-free decoder LM pretraining (Manifest AI
Brumby-14B-Base, 2025-10; HF ``model_type`` ``brumby``): a dense pre-norm
decoder on the Qwen3-14B skeleton (RMS norms, no bias, 40 query heads on 8
key/value heads of 128, q and k RMS-normed per head, rotate-half rotary
positions, a gated-SiLU FFN of width 17,408 in every layer, an untied head)
whose every layer replaces softmax attention by POWER RETENTION of degree 2
(Buckman, Gelada et al., arXiv:2507.04239): the weight of key j for query t
is ``(q_t . k_j / sqrt(dh))^2`` times ``exp`` of the summed log-decays
between them, ``logsigmoid(u_t @ wg)`` a token and key/value head; the
output is the weighted sum of the values over the sum of the weights.  The
same as a recurrence on a state of 8,256 x 128 numbers a key/value head,
which is how it runs (``kernels/power_retention.py``, chunk by chunk).

Nothing here is a second block: it is ``parallel/transformer.py``'s, by
configuration (``layer_pattern`` of one RETENTION position,
``dense_ffn_hidden`` without experts, ``qk_norm="head"``, ``n_kv_heads``,
``tie_head``); loss, step and builder are ``models/olmoe.py``'s.

A chip may hold its SHARE of a layer: a slice of the vocabulary (a smaller
vocabulary: ids, logits and loss are over the slice).  Retention and the FFN
are whole here; the chips that share a layer run their own sequences.

batch dict: ``ids`` int32 [B, S] alone; the loss is next-token cross
entropy and nothing else.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import monitor
from ..kernels.power_retention import STATE_COLUMNS, state_sweeps
from ..parallel.mesh import DP, local_shard_map
from ..parallel.transformer import (RETENTION, TransformerConfig, embed,
                                    retention_log_decay, rms_norm)
from . import olmoe

__all__ = ["brumby_14b_config", "brumby_tiny_config", "BrumbyTrainer",
           "build_brumby_trainer", "retention_chunks", "retention_state_mb",
           "retention_state_sweeps"]


def brumby_14b_config(n_layers=40, vocab_size=151936, **kw):
    """manifestai/Brumby-14B-Base ``config.json``.  Arguments: the depth and
    the rows of the vocabulary this chip holds.  The operator's own sizes
    (degree 2, one gate a key/value head, the chunk length) are not in the
    published file: ``benchmark/configs/brumby_14b.json`` lists each under
    ``assumed``."""
    d = dict(vocab_size=vocab_size, hidden=5120, n_layers=n_layers,
             n_heads=40, n_kv_heads=8, head_width=128, ffn_hidden=17408,
             dense_ffn_hidden=17408, max_seq=32768, causal=True,
             dtype="bfloat16", norm="rms", norm_eps=1e-6, positions="rotary",
             rope_theta=1e6, layer_pattern=(RETENTION,), qk_norm="head",
             bias=False, tie_head=False, expert_act="silu",
             retention_chunk=1024)
    d.update(kw)
    return TransformerConfig(**d)


def brumby_tiny_config(**kw):
    """Tiny shapes for the CPU tests, every mechanism kept: two layers, 10
    query heads on 2 key/value heads of 128 (a group of 5; 1,280 wide where
    the hidden size is 64), chunks of 16 under S = 64 (4 chunks), a gated
    FFN of width 96, float32."""
    return brumby_14b_config(**dict(dict(
        n_layers=2, vocab_size=256, hidden=64, n_heads=10, n_kv_heads=2,
        ffn_hidden=96, dense_ffn_hidden=96, max_seq=64, dtype="float32",
        retention_chunk=16), **kw))


def retention_chunks(cfg, seq):
    """Chunks a layer walks over a sequence of ``seq`` tokens."""
    return seq // min(cfg.retention_chunk, seq)


def retention_state_mb(cfg):
    """The state one layer carries along a sequence, in MB: a float32
    ``STATE_COLUMNS`` x head width a key/value head."""
    return cfg.kv_heads * STATE_COLUMNS * cfg.head_dim * 4 / 1e6


def retention_state_sweeps(cfg, seq):
    """Sweeps of the state's 65 tiles a layer's forward runs over a sequence
    of ``seq`` tokens, as the kernels' own rule has it: one a key/value
    head and chunk where a group's query heads ride one grid step, one a
    query head and chunk where they would not fit the kernels' VMEM."""
    return state_sweeps(cfg.n_heads, cfg.kv_heads, seq,
                        min(cfg.retention_chunk, seq),
                        jnp.dtype(cfg.dtype).itemsize)


@dataclasses.dataclass
class BrumbyTrainer(olmoe.OlmoeTrainer):
    label = "brumby"
    _gate_fn = None

    def _observe(self, batch):
        """Under a monitor session: ``monitor.train.retention_chunks``
        (chunks a layer and sequence), ``monitor.train.retention_state_mb``
        (the state a layer carries), ``monitor.train.retention_state_sweeps``
        (``retention_state_sweeps``) and ``monitor.train.retention_gate_mean``
        (the mean ``e^g`` over tokens and heads of the call's first batch in
        layer 0, at the weights the call starts from: a state decays to 1/e
        in ``1 / (1 - mean)`` tokens or so).  Off the monitor nothing
        runs."""
        mon = monitor.active()
        if mon is None:
            return
        cfg, ids = self.cfg, batch["ids"]
        mon.registry.gauge("monitor.train.retention_chunks").set(
            retention_chunks(cfg, ids.shape[-1]))
        mon.registry.gauge("monitor.train.retention_state_mb").set(
            retention_state_mb(cfg))
        mon.registry.gauge("monitor.train.retention_state_sweeps").set(
            retention_state_sweeps(cfg, ids.shape[-1]))
        if self._gate_fn is None:
            def gate_mean(params, ids):
                pl = jax.tree.map(lambda a: a[0],
                                  params["params_layers"]["p0"])
                h = rms_norm(embed(params, ids, cfg), pl["ln1_scale"],
                             cfg.norm_eps)
                return jnp.mean(jnp.exp(retention_log_decay(pl, h)))

            self._gate_fn = jax.jit(local_shard_map(
                gate_mean, self.mesh, in_specs=(self.specs["params"], P(DP)),
                out_specs=P()))
        first = ids.reshape((-1,) + ids.shape[-2:])[0]
        mon.registry.gauge("monitor.train.retention_gate_mean").set(
            float(self._gate_fn(self.state["params"], first)))


build_brumby_trainer = functools.partial(
    olmoe.build_olmoe_trainer, trainer=BrumbyTrainer)
