"""Each plain reference against the program on the CPU at tiny sizes, where
both sides compute in float32: the chip-side ``correct`` is then a
comparison already known to hold where both are exact.  The Pallas kernels
run in interpret mode here, so this also holds the reference to the flash
and layer-norm kernels' mathematics."""

import numpy as np
import pytest

from benchmark.harness import batches
from benchmark.reference import bert_base as ref_bert
from benchmark.reference import resnet50 as ref_resnet

from test_bench_harness import TINY_BERT, TINY_RESNET

EXACT = 2e-5          # float32 on both sides, different summation orders


def _host(tree):
    import jax

    return jax.tree.map(np.asarray, tree)


def _bert(n_layers=4):
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import MeshSpec, optim

    cfg = bert.bert_tiny_config(n_layers=n_layers)
    trainer = bert.build_bert_trainer(cfg, MeshSpec(1, 1, 1),
                                      optimizer=optim.lamb(), seed=5)
    batch = batches.host_batch(TINY_BERT["batch_fields"],
                               {"B": 8, "S": 32, "P": 5}, 11, 0)
    return trainer, batch


def test_bert_mlm_loss_with_mask():
    trainer, batch = _bert()
    params = _host(trainer.state["params"])
    got = float(trainer.step(batch, 1e-3))
    want = ref_bert.loss(params, batch, TINY_BERT["model"])
    assert abs(got - want) / want < EXACT, (got, want)


@pytest.mark.parametrize("broken", ["no_mask", "wrong_positions",
                                    "one_layer_less"])
def test_bert_reference_tells_a_broken_model(broken):
    """What ``correct`` is there to catch moves the loss by far more than
    the two agree to."""
    trainer, batch = _bert()
    params = _host(trainer.state["params"])
    good = ref_bert.loss(params, batch, TINY_BERT["model"])
    bad_batch, bad_params = dict(batch), params
    if broken == "no_mask":
        bad_batch["mask"] = np.ones_like(batch["mask"])
    elif broken == "wrong_positions":
        bad_batch["mask"] = np.roll(batch["mask"], 1, axis=1)
    else:
        bad_params = dict(params, params_layers={
            k: v[:-1] for k, v in params["params_layers"].items()})
    bad = ref_bert.loss(bad_params, bad_batch, TINY_BERT["model"])
    assert abs(bad - good) / good > 100 * EXACT, (good, bad)


def test_resnet_train_mode_batch_norm_loss():
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import MeshSpec, optim

    cfg = resnet.resnet_tiny_config()
    trainer = resnet.build_resnet_trainer(cfg, MeshSpec(1, 1, 1),
                                          optimizer=optim.momentum(0.9),
                                          seed=5)
    batch = batches.host_batch(TINY_RESNET["batch_fields"], {"B": 8}, 11, 0)
    params = _host(trainer.state["params"])
    got = float(trainer.step(batch, 1e-2))
    want = ref_resnet.loss(params, batch, TINY_RESNET["model"])
    assert abs(got - want) / want < 10 * EXACT, (got, want)


def test_resnet_bottleneck_and_even_stem():
    """depth 50's block (bottleneck, projection shortcuts) and the
    space-to-depth stem the program takes at even image sizes, against the
    reference's plain 7x7 stride-2 convolution."""
    from paddle_tpu.models import resnet
    from paddle_tpu.parallel import MeshSpec, optim

    cfg = resnet.resnet_tiny_config(depth=50, image_size=32)
    trainer = resnet.build_resnet_trainer(cfg, MeshSpec(1, 1, 1),
                                          optimizer=optim.momentum(0.9),
                                          seed=6)
    batch = batches.host_batch(TINY_RESNET["batch_fields"], {"B": 4}, 12, 0)
    params = _host(trainer.state["params"])
    got = float(trainer.step(batch, 1e-2))
    want = ref_resnet.loss(params, batch, dict(TINY_RESNET["model"], depth=50))
    assert abs(got - want) / want < 10 * EXACT, (got, want)
