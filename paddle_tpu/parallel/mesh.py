"""Mesh construction + axis conventions.

Replaces the reference's device-set plumbing: ParallelExecutor's places/NCCL
ring construction (parallel_executor.cc:111-231 InitNCCLCtxs flat +
hierarchical rings; platform/nccl_helper.h:179-246 NCCLCommunicator).  On TPU
the hierarchy (ICI within a slice, DCN across slices) is expressed by mesh
axis ordering and handled natively by XLA — no ring bootstrap, no ncclUniqueId
exchange (c_gen_nccl_id_op.cc:37 equivalent is jax.distributed.initialize,
wired in paddle_tpu/distributed/launch.py).
"""

import dataclasses

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec

__all__ = ["MeshSpec", "make_mesh", "axis_size", "local_shard_map"]

# Canonical axis names.  dp = data parallel (batch), pp = pipeline stages,
# tp = tensor parallel (also carries sequence parallelism).  Expert
# parallelism rides dp: ``TransformerConfig.expert_parallel`` splits a
# decoder's routed experts over it (``rules.transformer_rules``) and the MoE
# layer exchanges rows over it (``moe.dropless_moe_ffn(ep_axis=DP)``).
DP, PP, TP = "dp", "pp", "tp"


@dataclasses.dataclass
class MeshSpec:
    """Declarative mesh shape (the BuildStrategy analogue for topology —
    details/build_strategy.h:125-139 num_trainers / hierarchical knobs)."""

    dp: int = 1
    pp: int = 1
    tp: int = 1
    # ZeRO/kReduce: shard optimizer state over dp (parallel/zero.py — the
    # BuildStrategy.ReduceStrategy.Reduce analogue, build_strategy.h:58)
    zero: bool = False

    @property
    def size(self):
        return self.dp * self.pp * self.tp

    def build(self, devices=None):
        return make_mesh(self.dp, self.pp, self.tp, devices=devices)


def make_mesh(dp=1, pp=1, tp=1, devices=None):
    """Build a Mesh with axes ("dp", "pp", "tp").

    Axis order puts tp innermost so tensor-parallel collectives (the
    latency-critical ones: per-layer all_gather/reduce_scatter) ride the
    fastest ICI links, dp outermost so gradient all-reduce — once per step —
    can cross DCN.  This is the mesh-ordering recipe from the public scaling
    playbook; the reference approximates it with hierarchical NCCL rings
    (nccl_helper.h:246 InitHierarchicalCtxs).
    """
    devices = list(devices) if devices is not None else jax.devices()
    need = dp * pp * tp
    if len(devices) < need:
        raise ValueError(
            "mesh %dx%dx%d needs %d devices, have %d" % (dp, pp, tp, need, len(devices))
        )
    arr = np.array(devices[:need]).reshape(dp, pp, tp)
    return Mesh(arr, (DP, PP, TP))


def axis_size(mesh, name):
    return mesh.shape.get(name, 1)


def local_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with the varying-manual-axes check off: our kernels mix
    replicated and sharded values freely (e.g. replicated params + sharded
    activations), which the strict vma checker rejects."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def batch_spec():
    """PartitionSpec for a [batch, ...] host array fed to the sharded step:
    batch is split over dp (and microbatched over pp inside the step).
    Delegated to the sharding authority (parallel/rules.py batch_spec) —
    the same rule tree the checkpoint re-sharder and model builders use."""
    from . import rules as shard_rules

    return shard_rules.batch_spec(DP)
