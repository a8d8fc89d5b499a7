"""ResNet-50 — functional SPMD model for the ImageNet DP target config
(BASELINE.json config 2: "ResNet-50 / ImageNet image_classification
(data-parallel all-reduce)").

Parity target: the reference book test image-classification models
(python/paddle/fluid/tests/book/test_image_classification.py ResNet) and the
conv/batch_norm/pool op stack (operators/conv_op.cc, batch_norm_op.cc,
pool_op.cc).  TPU-native choices: NHWC layout (XLA's preferred conv layout on
TPU), bf16 compute with f32 BN statistics, batch-stat psum over the dp axis
when sync-BN is requested (sync_batch_norm_pass parity).

Usage mirrors models/bert.py: init_resnet_params -> param and running-
statistics pytrees, make_loss_fn -> per-device loss for
parallel/train.make_train_step (its shape for a model with running state:
the running statistics ride in the TrainState under RUNNING),
build_resnet_trainer -> a StepTrainer.
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..monitor import devscope
from ..monitor.recompile import compile_ledger
from ..parallel import collectives as col
from ..parallel.mesh import DP, MeshSpec
from ..parallel import optim
from ..parallel.train import (RUNNING, StepTrainer, TrainState,
                              make_train_step, shard_pytree, state_specs)

__all__ = ["ResNetConfig", "resnet50_config", "resnet_tiny_config",
           "init_resnet_params", "make_loss_fn", "build_resnet_trainer"]


@dataclasses.dataclass
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64
    dtype: str = "bfloat16"
    sync_bn: bool = False
    bn_momentum: float = 0.9
    image_size: int = 224

    @property
    def blocks(self):
        return {18: (2, 2, 2, 2), 34: (3, 4, 6, 3), 50: (3, 4, 6, 3),
                101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}[self.depth]

    @property
    def bottleneck(self):
        return self.depth >= 50

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


def resnet50_config(**kw):
    return ResNetConfig(**dict(dict(depth=50), **kw))


def resnet_tiny_config(**kw):
    d = dict(depth=18, num_classes=10, width=8, dtype="float32", image_size=32)
    d.update(kw)
    return ResNetConfig(**d)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _conv_init(key, kh, kw, cin, cout, dtype):
    fan_in = kh * kw * cin
    std = (2.0 / fan_in) ** 0.5     # MSRA (initializer.py MSRAInitializer)
    return (jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std).astype(dtype)


def _bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _bn_state_init(c):
    return {"mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32)}


def init_resnet_params(key, cfg: ResNetConfig):
    """Returns (params, bn_state) pytrees.  Layers are dicts keyed by path."""
    with compile_ledger().phase("init_params") as labels:
        params, state = jax.block_until_ready(_init_params(key, cfg))
        labels["leaves"] = len(jax.tree.leaves((params, state)))
    return params, state


def _init_params(key, cfg):
    dt = cfg.jdtype
    keys = iter(jax.random.split(key, 256))
    params, state = {}, {}

    params["conv0"] = _conv_init(next(keys), 7, 7, 3, cfg.width, dt)
    params["bn0"] = _bn_init(cfg.width)
    state["bn0"] = _bn_state_init(cfg.width)

    cin = cfg.width
    for si, nblocks in enumerate(cfg.blocks):
        cmid = cfg.width * (2 ** si)
        cout = cmid * (4 if cfg.bottleneck else 1)
        for bi in range(nblocks):
            name = "s%d_b%d" % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = {}
            if cfg.bottleneck:
                blk["conv1"] = _conv_init(next(keys), 1, 1, cin, cmid, dt)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cmid, dt)
                blk["conv3"] = _conv_init(next(keys), 1, 1, cmid, cout, dt)
                for j in (1, 2, 3):
                    blk["bn%d" % j] = _bn_init(cmid if j < 3 else cout)
                    state.setdefault(name, {})["bn%d" % j] = _bn_state_init(
                        cmid if j < 3 else cout)
            else:
                blk["conv1"] = _conv_init(next(keys), 3, 3, cin, cmid, dt)
                blk["conv2"] = _conv_init(next(keys), 3, 3, cmid, cout, dt)
                for j in (1, 2):
                    blk["bn%d" % j] = _bn_init(cmid if j < 2 else cout)
                    state.setdefault(name, {})["bn%d" % j] = _bn_state_init(
                        cmid if j < 2 else cout)
            if bi == 0 and (cin != cout or stride != 1):
                blk["proj"] = _conv_init(next(keys), 1, 1, cin, cout, dt)
                blk["bnp"] = _bn_init(cout)
                state[name]["bnp"] = _bn_state_init(cout)
            params[name] = blk
            cin = cout

    params["fc_w"] = (jax.random.normal(next(keys), (cin, cfg.num_classes),
                                        jnp.float32) * (1.0 / cin ** 0.5)).astype(dt)
    params["fc_b"] = jnp.zeros((cfg.num_classes,), dt)
    return params, state


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

@devscope.scoped(devscope.CONV)
def _conv(x, w, stride=1, padding="SAME"):
    # Plain XLA conv (no preferred_element_type: XLA's MXU lowering
    # accumulates bf16 convs in f32 regardless) under XLA's own autodiff: a
    # Pallas weight-gradient kernel beat XLA's wgrad emitter ~1.5x in
    # isolation, but a custom VJP here unfuses XLA's conv+BN-grad kOutput
    # fusions and the full step came out slower (measured r4: 1940 vs 2300
    # img/s).
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@devscope.scoped(devscope.CONV)
def _conv0_s2d(x, w7):
    """conv0 (7x7/2, cin=3) via 2x2 space-to-depth: a 4x4 stride-1 conv on
    [B, 112, 112, 12].  cin=3 convs run far off the MXU's useful shapes
    (MLPerf's standard ResNet TPU transform); the weight stays [7,7,3,64] in
    the checkpoint and is re-laid-out here (zero top/left row taps).
    """
    B, H, W, C = x.shape
    x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, H // 2, W // 2, 4 * C)
    # XLA SAME pads 7x7/2 as (lo=2, hi=3): orig window row i (0..6) at
    # output oh is abs row 2*oh - 2 + i = 2*(oh - 1 + r) + dr with
    # i = 2r + dr  =>  w8[j] = w7[j] (zero tap at j=7), s2d pads (1, 2)
    O = w7.shape[-1]
    w8 = jnp.pad(w7, ((0, 1), (0, 1), (0, 0), (0, 0)))
    w4 = w8.reshape(4, 2, 4, 2, 3, O).transpose(0, 2, 1, 3, 4, 5)
    w4 = w4.reshape(4, 4, 12, O)
    return _conv(x, w4, 1, ((1, 2), (1, 2)))


@devscope.scoped(devscope.BN)
def _bn(x, p, s, cfg, train, updates, path):
    # Folded form: y = x*a + b with per-channel a,b.  Stats accumulate in f32
    # via the reduction dtype; the normalize itself stays in x.dtype.  This
    # keeps the big elementwise chain bf16 — the naive (x-m)*rsqrt(...) form
    # makes XLA materialize an f32 copy of the whole activation (3 consumers
    # of the cast), which roughly doubles HBM traffic and is why the r3 bench
    # sat at 14.5% MFU on a memory-bound-on-v5e model.  No kernel: XLA fuses
    # this into the neighbouring convolutions, and a Pallas epilogue in its
    # place broke those fusions apart (1,109 vs 2,788 images/s on the chip,
    # PERF.md section 6).
    if train:
        m = jnp.mean(x, axis=(0, 1, 2), dtype=jnp.float32)
        m2 = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=(0, 1, 2))
        if cfg.sync_bn:
            m = col.pmean(m, DP)
            m2 = col.pmean(m2, DP)
        v = m2 - jnp.square(m)
        mom = cfg.bn_momentum
        updates[path] = {
            "mean": mom * s["mean"] + (1 - mom) * lax.stop_gradient(m),
            "var": mom * s["var"] + (1 - mom) * lax.stop_gradient(v),
        }
    else:
        m, v = s["mean"], s["var"]
    a = p["scale"] * lax.rsqrt(v + 1e-5)
    b = p["bias"] - m * a
    return x * a.astype(x.dtype) + b.astype(x.dtype)


# On the chip the ReLU after a batch norm, and the residual add where a block
# closes, run inside the norm's own pass (XLA fuses them into the one
# instruction that applies scale and shift), so they carry its scope.
_relu = devscope.scoped(devscope.BN)(jax.nn.relu)


def resnet_forward(params, bn_state, images, cfg: ResNetConfig, train=True):
    """images: [B, H, W, 3].  Returns (logits [B, C], new_bn_state)."""
    updates = {}
    x = images.astype(cfg.jdtype)
    if cfg.image_size % 2 == 0 and params["conv0"].shape[0] == 7:
        x = _conv0_s2d(x, params["conv0"])
    else:
        x = _conv(x, params["conv0"], stride=2)
    x = _bn(x, params["bn0"], bn_state["bn0"], cfg, train, updates, "bn0")
    x = _relu(x)
    with jax.named_scope(devscope.POOL):
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")

    for si, nblocks in enumerate(cfg.blocks):
        for bi in range(nblocks):
            name = "s%d_b%d" % (si, bi)
            blk = params[name]
            sblk = bn_state[name]
            stride = 2 if (bi == 0 and si > 0) else 1
            bupd = {}
            shortcut = x
            if cfg.bottleneck:
                y = _conv(x, blk["conv1"], 1)
                y = _relu(_bn(y, blk["bn1"], sblk["bn1"], cfg, train, bupd, "bn1"))
                y = _conv(y, blk["conv2"], stride)
                y = _relu(_bn(y, blk["bn2"], sblk["bn2"], cfg, train, bupd, "bn2"))
                y = _conv(y, blk["conv3"], 1)
                y = _bn(y, blk["bn3"], sblk["bn3"], cfg, train, bupd, "bn3")
            else:
                y = _conv(x, blk["conv1"], stride)
                y = _relu(_bn(y, blk["bn1"], sblk["bn1"], cfg, train, bupd, "bn1"))
                y = _conv(y, blk["conv2"], 1)
                y = _bn(y, blk["bn2"], sblk["bn2"], cfg, train, bupd, "bn2")
            if "proj" in blk:
                shortcut = _conv(x, blk["proj"], stride)
                shortcut = _bn(shortcut, blk["bnp"], sblk["bnp"], cfg, train,
                               bupd, "bnp")
            with jax.named_scope(devscope.BN):
                x = jax.nn.relu(y + shortcut)
            if bupd:
                updates[name] = {**{k: sblk[k] for k in sblk if k not in bupd},
                                 **bupd}

    with jax.named_scope(devscope.POOL):
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))        # global avg pool
    with jax.named_scope(devscope.FC):
        logits = x.astype(cfg.jdtype) @ params["fc_w"] + params["fc_b"]
    new_state = {k: updates.get(k, bn_state[k]) for k in bn_state}
    return logits.astype(jnp.float32), new_state


def make_loss_fn(cfg: ResNetConfig):
    """Per-device loss for parallel/train.make_train_step, in its shape for
    a model with running state: (params, running statistics, batch) ->
    (loss, new running statistics).  The statistics are batch statistics,
    so what a step hands on is their mean over the dp shards."""

    def loss_fn(params, bn_state, batch):
        logits, new_state = resnet_forward(params, bn_state, batch["image"],
                                           cfg, train=True)
        labels = batch["label"]
        with jax.named_scope(devscope.LOSS):
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
            # the value summed over dp, each shard's cotangent its own (a
            # plain psum hands every shard the cotangents of ALL dp copies
            # of the loss: a gradient dp times too large)
            loss = col.psum_forward(jnp.sum(nll), DP) / col.psum(
                jnp.asarray(nll.shape[0], jnp.float32), DP)
        with jax.named_scope(devscope.GRAD_SYNC):
            new_state = jax.tree.map(lambda a: col.pmean(a, DP), new_state)
        return loss, new_state

    return loss_fn


BATCH_SPECS = {"image": P(DP), "label": P(DP)}


@dataclasses.dataclass
class ResNetTrainer(StepTrainer):
    label = "resnet"


def build_resnet_trainer(cfg: ResNetConfig, mesh_spec: MeshSpec = None,
                         optimizer=None, seed=0, devices=None):
    """DP trainer: params replicated, batch sharded over dp, grads psum'd —
    the ParallelExecutor AllReduce mode (parallel_executor.cc:393) as one
    jitted SPMD program."""
    mesh_spec = mesh_spec or MeshSpec(1, 1, 1)
    mesh = mesh_spec.build(devices=devices)
    optimizer = optimizer or optim.momentum(0.9)

    params, bn_state = init_resnet_params(jax.random.PRNGKey(seed), cfg)
    state = TrainState.create(params, optimizer)
    state[RUNNING] = bn_state
    pspecs = jax.tree.map(lambda _: P(), params)
    sspecs = state_specs(pspecs, state)
    build = make_train_step(make_loss_fn(cfg), mesh, pspecs,
                            jax.tree.map(lambda _: (DP,), params),
                            optimizer, BATCH_SPECS)
    step_fn, multi_fn = build(state), build.multi(state)
    with mesh:
        state = shard_pytree(state, sspecs, mesh)
    return ResNetTrainer(cfg=cfg, mesh=mesh, state=state, step_fn=step_fn,
                         specs=sspecs, multi_fn=multi_fn)
