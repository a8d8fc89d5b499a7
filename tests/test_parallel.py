"""Parallel-engine tests on the virtual 8-device CPU mesh.

Contract mirrored from the reference's distributed test harness
(test_dist_base.py:891-928): the distributed step's loss must match the
single-device loss on identical params + batch within a small delta, for
every parallelism mode (dp / tp / sp / pp and combinations).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.parallel import MeshSpec, optim
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.parallel.ring_attention import ring_attention, local_attention
from paddle_tpu.models import bert


def _batch(rng, B, S, V):
    ids = rng.randint(0, V, size=(B, S)).astype(np.int32)
    labels = rng.randint(0, V, size=(B, S)).astype(np.int32)
    mask = (rng.rand(B, S) < 0.3).astype(np.float32)
    mask[:, 0] = 1.0  # never fully-masked
    return {"ids": ids, "labels": labels, "mask": mask}


def _run_steps(cfg, mesh_spec, batch, n_steps=3, n_microbatches=1, seed=0):
    trainer = bert.build_bert_trainer(
        cfg, mesh_spec, optimizer=optim.adam(), n_microbatches=n_microbatches,
        seed=seed,
    )
    losses = []
    for _ in range(n_steps):
        loss = trainer.step(batch, 1e-3)
        losses.append(float(loss))
    return losses


BASE = dict(n_steps=3)


def test_single_device_baseline_finite():
    cfg = bert.bert_tiny_config()
    batch = _batch(np.random.RandomState(0), 8, 32, cfg.vocab_size)
    losses = _run_steps(cfg, MeshSpec(1, 1, 1), batch)
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # learning


@pytest.mark.parametrize(
    "mesh_spec,cfg_kw,mb",
    [
        (MeshSpec(dp=8, pp=1, tp=1), {}, 1),                          # pure DP
        (MeshSpec(dp=2, pp=1, tp=4), {"tp": 4}, 1),                   # TP+SP (+DP)
        (MeshSpec(dp=1, pp=4, tp=1), {"pp": 4}, 4),                   # pipeline
        (MeshSpec(dp=2, pp=2, tp=2), {"pp": 2, "tp": 2}, 2),          # 3-D
        (MeshSpec(dp=1, pp=1, tp=8), {"tp": 8, "attn_mode": "ring"}, 1),  # ring/CP
    ],
)
def test_loss_parity_vs_single_device(mesh_spec, cfg_kw, mb):
    """Dist loss == local loss (delta 1e-3, the reference's tolerance)."""
    rng = np.random.RandomState(42)
    cfg1 = bert.bert_tiny_config()
    batch = _batch(rng, 8, 32, cfg1.vocab_size)
    ref = _run_steps(cfg1, MeshSpec(1, 1, 1), batch, **BASE)

    cfgN = bert.bert_tiny_config(**cfg_kw)
    got = _run_steps(cfgN, mesh_spec, batch, n_microbatches=mb, **BASE)
    np.testing.assert_allclose(got, ref, atol=2e-3, rtol=2e-3)


def test_a_data_parallel_sgd_update_is_the_one_device_update():
    """How FAR a step moves the parameters, which Adam's scale-free update
    cannot show: under SGD every leaf's update on eight shards is the
    one-device update of the same global batch.  (Before PR 73 it was dp
    times that: the loss's ``psum`` over dp transposes to a ``psum`` of the
    dp copies' cotangents; ``collectives.psum_forward``.)"""
    cfg = bert.bert_tiny_config()
    batch = _batch(np.random.RandomState(7), 8, 32, cfg.vocab_size)
    updates = []
    for dp in (1, 8):
        tr = bert.build_bert_trainer(cfg, MeshSpec(dp, 1, 1),
                                     optimizer=optim.sgd(), seed=0)
        before = jax.tree.map(np.asarray, tr.state["params"])
        tr.step(batch, 1.0)
        updates.append(jax.tree.map(lambda a, b: np.asarray(a) - b,
                                    tr.state["params"], before))
    for one, many in zip(*map(jax.tree.leaves, updates)):
        np.testing.assert_allclose(many, one, rtol=1e-3,
                                   atol=1e-5 * np.abs(one).max())
        assert np.abs(one).max() > 0


def test_ring_attention_matches_local():
    """Ring attention over a sharded axis == plain attention, causal+not."""
    rng = np.random.RandomState(0)
    B, S, H, D = 2, 32, 4, 8
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)

    for causal in (False, True):
        o_ref, m, l = local_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                                      causal=causal)
        o_ref = np.asarray(o_ref / np.maximum(np.asarray(l), 1e-30).transpose(0, 2, 1)[..., None])

        mesh = make_mesh(1, 1, 8)
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel.mesh import local_shard_map

        f = local_shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="tp", causal=causal),
            mesh,
            in_specs=(P(None, "tp"), P(None, "tp"), P(None, "tp")),
            out_specs=P(None, "tp"),
        )
        o = np.asarray(f(q, k, v))
        np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-4)


def test_remat_matches():
    cfg = bert.bert_tiny_config(remat=True)
    rng = np.random.RandomState(7)
    batch = _batch(rng, 8, 32, cfg.vocab_size)
    ref = _run_steps(bert.bert_tiny_config(), MeshSpec(1, 1, 1), batch)
    got = _run_steps(cfg, MeshSpec(1, 1, 1), batch)
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
