"""The dots3-note-prev trainer at the tiny size: its steps through
``parallel/decoder.py``'s one builder, the loss falling, the selection biases
moved by the step and not by the optimizer."""

import jax
import numpy as np

from paddle_tpu.models import dots3

CFG = dots3.dots3_tiny_config(remat=True)
S = 64


def test_a_trainer_steps_and_its_loss_falls():
    from paddle_tpu.parallel.mesh import MeshSpec

    tr = dots3.build_dots3_trainer(CFG, MeshSpec(dp=1), seed=3,
                                   devices=jax.devices()[:1])
    ids = np.random.RandomState(0).randint(0, 256, (2, S)).astype(np.int32)
    before = jax.device_get(tr.state["params"]["router_bias"])
    losses = [float(tr.step({"ids": ids}, 3e-3)) for _ in range(3)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    # the selection biases are the step's to move
    assert np.any(jax.device_get(tr.state["params"]["router_bias"]) != before)
    assert tr.label == "dots3"
