"""What a ResNet step executes, held to arithmetic written out here: batch
norm against its formula, sync-BN over ``dp`` against one device, one
``step`` against a plain ``jax.value_and_grad`` step, ``run_steps`` against
``step``, the stem's space-to-depth form against the 7x7 convolution, and
``make_train_step`` with and without running state (stepped, not trained).
Nothing here is a time: float32 on the simulated CPU devices."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu.models import resnet
from paddle_tpu.parallel import optim
from paddle_tpu.parallel.mesh import DP, MeshSpec, local_shard_map
from paddle_tpu.parallel.train import (RUNNING, TrainState, make_train_step,
                                       shard_pytree, stack_batches,
                                       state_specs)


def _batch(seed, b, size, classes=10):
    rng = np.random.RandomState(seed)
    return {"image": rng.rand(b, size, size, 3).astype(np.float32),
            "label": rng.randint(0, classes, (b,)).astype(np.int32)}


def _trainer(cfg, dp=1, mu=0.9):
    return resnet.build_resnet_trainer(
        cfg, MeshSpec(dp=dp), optimizer=optim.momentum(mu),
        devices=jax.devices()[:dp])


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _close(got, want, tol=1e-3):
    """Leaf by leaf, to ``tol`` of the leaf's largest magnitude.  Two
    compilations of the same float32 arithmetic differ in the last bits, and
    a batch norm over the few values of the last stage's small feature map
    magnifies them (which is why the images here are 64 wide and not 32: at
    2 images a shard and a 1x1 map, scan and step part ways in the second
    digit of the third loss, on the parent as on this tree);
    a wrong formula, a dropped layer or a wrong momentum moves a leaf by its
    own size."""
    got, want = _host(got), _host(want)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=tol * max(np.abs(w).max(), 1e-6),
            err_msg=jax.tree_util.keystr(path))


def _minus(new, old):
    return jax.tree.map(lambda a, b: a - b, _host(new), _host(old))


def _plain_loss(cfg, params, bn_state, batch):
    logits, new_bn = resnet.resnet_forward(params, bn_state, batch["image"],
                                           cfg, train=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll), new_bn


# ---------------------------------------------------------------------------
# _bn against batch norm written out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
def test_bn_is_batch_norm_written_out(train, dtype):
    cfg = resnet.resnet_tiny_config(dtype=dtype, bn_momentum=0.8)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 5, 6, 16) * 2 + 1.5, cfg.jdtype)
    p = {"scale": jnp.asarray(rng.rand(16) + 0.5, jnp.float32),
         "bias": jnp.asarray(rng.randn(16), jnp.float32)}
    s = {"mean": jnp.asarray(rng.randn(16), jnp.float32),
         "var": jnp.asarray(rng.rand(16) + 0.5, jnp.float32)}
    updates = {}
    got = resnet._bn(x, p, s, cfg, train, updates, "bn")
    assert got.dtype == cfg.jdtype

    xf = np.asarray(x, np.float32)
    if train:
        mean, var = xf.mean((0, 1, 2)), xf.var((0, 1, 2))
        _close(updates["bn"],
               {"mean": 0.8 * np.asarray(s["mean"]) + 0.2 * mean,
                "var": 0.8 * np.asarray(s["var"]) + 0.2 * var}, tol=1e-5)
    else:
        mean, var = np.asarray(s["mean"]), np.asarray(s["var"])
        assert updates == {}
    want = ((xf - mean) / np.sqrt(var + 1e-5) * np.asarray(p["scale"])
            + np.asarray(p["bias"]))
    tol = 1e-4 if dtype == "float32" else 6e-2      # bf16: 8 bits of mantissa
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# sync-BN over dp is one device on the whole batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp", [2, 4])
def test_sync_bn_over_dp_is_one_device_on_the_whole_batch(dp):
    cfg = resnet.resnet_tiny_config(sync_bn=True)
    params, bn_state = resnet.init_resnet_params(jax.random.PRNGKey(1), cfg)
    images = _batch(2, 8, 32)["image"]
    want_logits, want_bn = resnet.resnet_forward(params, bn_state, images,
                                                 cfg, train=True)

    mesh = MeshSpec(dp=dp).build(devices=jax.devices()[:dp])
    rep = jax.tree.map(lambda _: P(), (params, bn_state))
    fwd = jax.jit(local_shard_map(
        lambda p, s, x: resnet.resnet_forward(p, s, x, cfg, train=True),
        mesh, in_specs=(*rep, P(DP)), out_specs=(P(DP), rep[1])))
    got_logits, got_bn = fwd(params, bn_state, images)
    _close(got_logits, want_logits)
    _close(got_bn, want_bn)


# ---------------------------------------------------------------------------
# one step at dp=1 against a plain step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("size", [64, 63], ids=["s2d_stem", "plain_stem"])
@pytest.mark.parametrize("depth", [18, 50])
def test_step_is_a_plain_momentum_step(depth, size):
    cfg = resnet.resnet_tiny_config(depth=depth, image_size=size)
    tr = _trainer(cfg)
    lr, mu = 0.01, 0.9
    plain = jax.jit(jax.value_and_grad(
        lambda p, s, b: _plain_loss(cfg, p, s, b), has_aux=True))

    for seed in (0, 1):                 # the second step has momentum to use
        batch = _batch(seed, 8, size)
        old = _host(tr.state)
        (want_loss, want_bn), grads = plain(old["params"], old[RUNNING],
                                            batch)
        velocity = jax.tree.map(lambda v, g: mu * v + g,
                                old["opt"]["velocity"], grads)
        got_loss = tr.step(batch, lr)
        np.testing.assert_allclose(float(got_loss), float(want_loss),
                                   rtol=1e-5)
        _close(tr.state["opt"]["velocity"], velocity)
        _close(_minus(tr.state["params"], old["params"]),
               jax.tree.map(lambda v: -lr * v, velocity))
        _close(tr.state[RUNNING], want_bn)
    assert sorted(tr.state) == sorted([RUNNING, "opt", "params"])


# ---------------------------------------------------------------------------
# run_steps is N steps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp", [1, 2, 4])
def test_run_steps_is_three_steps(dp):
    cfg = resnet.resnet_tiny_config(image_size=64)
    batches = [_batch(seed, 16, 64) for seed in range(3)]
    one, scan = _trainer(cfg, dp), _trainer(cfg, dp)
    start = _host(one.state)
    # 1e-3 a SHARD: the trajectory this test has held since it was written.
    # Until PR 73 a dp step's gradient was dp times the global batch's
    # (``collectives.psum_forward``), so ``step(b, 1e-3)`` moved the state as
    # ``step(b, dp * 1e-3)`` does now, bit for bit (dp is a power of two: the
    # losses and every leaf's distance below read the same digits on the
    # parent and here).  Which trajectory matters: where the two compilations'
    # last bits tip something discrete, leaves part ways by more than the
    # tolerance, on the parent as here (dp 2: 24 leaves past 1e-3, the worst
    # the velocity of ``s1_b1.bn2.bias`` at 3.9e-3, at 5e-4 on the parent and
    # at 1e-3 here; none at 2.5e-4 / 5e-4, at 1e-3 / 2e-3, at 3e-3, at 4e-3)
    lr = dp * 1e-3
    want = [float(one.step(b, lr)) for b in batches]
    got = scan.run_steps(
        stack_batches(scan.mesh, resnet.BATCH_SPECS, batches), lr)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4)
    _close(_minus(scan.state, start), _minus(one.state, start))


# ---------------------------------------------------------------------------
# data parallel: whose statistics, and which way the update points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp", [2, 4])
def test_dp_step_statistics_and_direction(dp):
    batch = _batch(3, 8, 32)
    lr = 0.05

    # without sync-BN each shard normalises by its own batch statistics and
    # the running statistics handed on are the mean of the shards'
    cfg = resnet.resnet_tiny_config()
    tr = _trainer(cfg, dp, mu=0.0)
    params, bn_state = _host(tr.state["params"]), _host(tr.state[RUNNING])
    shards = [resnet.resnet_forward(
        params, bn_state, batch["image"][i * 8 // dp:(i + 1) * 8 // dp], cfg,
        train=True)[1] for i in range(dp)]
    tr.step(batch, lr)
    _close(tr.state[RUNNING],
           jax.tree.map(lambda *xs: np.mean(xs, axis=0), *_host(shards)))

    # with it, the dp step sees the one-device loss of the same global batch
    # and moves the parameters the same way AND as far as the one-device step
    # (since PR 73: the loss's sum over dp hands each shard its own cotangent,
    # ``collectives.psum_forward``; before it every update was dp times the
    # one-device one, which SGD shows and LAMB does not).  (Images stay 32 wide:
    # over the 16k values of a 64-wide stem the one-pass float32 variance
    # E[x^2] - E[x]^2 of the CPU's sequential sums is itself 1e-2 off, and
    # the shards' shorter sums are not, which is no fault of the step.)
    cfg = resnet.resnet_tiny_config(sync_bn=True)
    one, many = _trainer(cfg, 1, mu=0.0), _trainer(cfg, dp, mu=0.0)
    before = _host(one.state["params"])
    np.testing.assert_allclose(float(many.step(batch, lr)),
                               float(one.step(batch, lr)), rtol=1e-5)

    def update(tr):
        return np.concatenate([
            (a - b).astype(np.float64).ravel() for a, b in zip(
                jax.tree.leaves(_host(tr.state["params"])),
                jax.tree.leaves(before))])

    u1, un = update(one), update(many)
    cosine = u1 @ un / np.sqrt((u1 @ u1) * (un @ un))
    assert cosine >= 1 - 1e-6, cosine
    assert abs(np.sqrt((un @ un) / (u1 @ u1)) - 1) < 1e-3
    _close(many.state[RUNNING], one.state[RUNNING])


# ---------------------------------------------------------------------------
# the stem
# ---------------------------------------------------------------------------

def test_conv0_space_to_depth_equivalence():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 32, 32, 3), jnp.float32)
    w7 = jax.random.normal(jax.random.fold_in(key, 1), (7, 7, 3, 8),
                           jnp.float32) * 0.1
    ref = lax.conv_general_dilated(
        x, w7, (2, 2), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = resnet._conv0_s2d(x, w7)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_conv0_keeps_the_checkpoint_layout():
    # the space-to-depth form is made inside the forward; what a checkpoint
    # and the optimizer hold is the published [7, 7, 3, C] weight
    tr = _trainer(resnet.resnet_tiny_config())
    assert tr.state["params"]["conv0"].shape == (7, 7, 3, 8)
    assert tr.state["opt"]["velocity"]["conv0"].shape == (7, 7, 3, 8)
    tr.step(_batch(0, 4, 32), 0.05)
    assert tr.state["params"]["conv0"].shape == (7, 7, 3, 8)


# ---------------------------------------------------------------------------
# make_train_step, with and without running state
# ---------------------------------------------------------------------------

def _toy(running):
    """loss = mean((a * x + b - y)^2) / 2; the running state counts the
    steps and keeps the last loss."""
    mesh = MeshSpec(dp=1).build(devices=jax.devices()[:1])
    params = {"a": jnp.float32(0.5), "b": jnp.float32(-1.0)}
    pspecs = {"a": P(), "b": P()}
    batch_specs = {"x": P(DP), "y": P(DP)}

    def loss(params, batch):
        err = params["a"] * batch["x"] + params["b"] - batch["y"]
        return jnp.mean(jnp.square(err)) / 2

    def loss_running(params, state, batch):
        value = loss(params, batch)
        return value, {"steps": state["steps"] + 1,
                       "last": lax.stop_gradient(value)}

    optimizer = optim.sgd()
    state = TrainState.create(params, optimizer)
    if running:
        state[RUNNING] = {"steps": jnp.int32(0), "last": jnp.float32(0)}
    build = make_train_step(loss_running if running else loss, mesh, pspecs,
                            {"a": (DP,), "b": (DP,)}, optimizer, batch_specs)
    step, multi = build(state), build.multi(state)
    state = shard_pytree(state, state_specs(pspecs, state), mesh)
    rng = np.random.RandomState(0)
    batches = [{"x": rng.randn(8).astype(np.float32),
                "y": rng.randn(8).astype(np.float32)} for _ in range(3)]
    return types.SimpleNamespace(
        mesh=mesh, state=state, step=step, multi=multi, batches=batches,
        staged=stack_batches(mesh, batch_specs, batches))


def _toy_by_hand(batches, lr):
    a, b, losses = np.float32(0.5), np.float32(-1.0), []
    for batch in batches:
        err = a * batch["x"] + b - batch["y"]
        losses.append(np.mean(err ** 2) / 2)
        a, b = a - lr * np.mean(err * batch["x"]), b - lr * np.mean(err)
    return a, b, losses


@pytest.mark.parametrize("running", [False, True],
                         ids=["params_and_opt", "running_state"])
def test_make_train_step_steps_and_scans(running):
    lr = 0.1
    toy = _toy(running)
    a, b, want = _toy_by_hand(toy.batches, lr)
    keys = sorted(["params", "opt"] + [RUNNING] * running)

    state, losses = toy.state, []
    for batch in toy.batches:
        state, loss = toy.step(state, batch, lr)
        losses.append(float(loss))
    # the step donated toy.state: the scan starts from a state of its own
    scanned, scan_losses = toy.multi(_toy(running).state, toy.staged, lr)

    for got_state, got_losses in ((state, losses),
                                  (scanned, np.asarray(scan_losses))):
        assert sorted(got_state) == keys
        np.testing.assert_allclose(got_losses, want, rtol=1e-5)
        np.testing.assert_allclose(float(got_state["params"]["a"]), a,
                                   rtol=1e-5)
        np.testing.assert_allclose(float(got_state["params"]["b"]), b,
                                   rtol=1e-5)
        if running:
            assert int(got_state[RUNNING]["steps"]) == 3
            np.testing.assert_allclose(float(got_state[RUNNING]["last"]),
                                       want[-1], rtol=1e-5)
