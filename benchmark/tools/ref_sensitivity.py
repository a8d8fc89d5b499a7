"""How far the faults that ``correct`` is there to catch move the reference
loss at the published sizes: the reference against itself with a fault put
in (float32 arithmetic, so the device does not matter; runs on the CPU).

    JAX_PLATFORMS=cpu python3 benchmark/tools/ref_sensitivity.py <config> <batch> [seed]
"""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402


def main(name, batch, seed=0):
    import jax

    config = mf.read_json(ROOT, "benchmark", "configs", name + ".json")
    traffic = {"batch": int(batch), "dims": {"S": 512, "P": 80},
               "mesh": {"dp": 1, "pp": 1, "tp": 1}}
    dims = build.cell_dims(config, traffic)
    trainer = build.build_trainer(config, traffic, int(seed),
                                  jax.devices()[:1])
    params = jax.tree.map(np.asarray, trainer.state["params"])
    b = batches.host_batch(config["batch_fields"], dims, int(seed), 0,
                           feed=True)
    ref = mf.module("reference", config["reference"])
    good = ref.loss(params, b, config["model"])
    out = {"config": name, "batch": int(batch), "loss": good,
           "tolerance": ref.TOLERANCE}
    faults = {}
    if "mask" in b:
        faults["no_mask"] = (params, dict(b, mask=np.ones_like(b["mask"])))
        faults["mask_shifted_by_one"] = (params, dict(
            b, mask=np.roll(b["mask"], 1, axis=1)))
        faults["one_layer_less"] = (dict(params, params_layers={
            k: v[:-1] for k, v in params["params_layers"].items()}), b)
        faults["inputs_not_masked"] = (params, dict(b, ids=b["labels"]))
    else:
        drop = {k: v for k, v in params.items() if k != "s2_b5"}
        faults["one_block_less"] = (drop, b)
        swapped = dict(params, s3_b2=params["s3_b1"])
        faults["a_block_with_another_blocks_weights"] = (swapped, b)
        faults["labels_shifted_by_one"] = (params, dict(
            b, label=np.roll(b["label"], 1)))
    for fault, (p, bb) in faults.items():
        bad = ref.loss(p, bb, config["model"])
        out[fault] = {"loss": bad, "relative_change": abs(bad - good) / good}
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:])
