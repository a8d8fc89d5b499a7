"""How far the faults that ``correct`` is there to catch move the
``olmoe_1b_7b`` reference's loss at the published sizes: the reference
against itself with a fault put in (``reference.FAULTS``: top-7 in place of
top-8, renormalised top-k weights, no QK-norm, no rotary embedding, a head
tied to ``tok_emb``, a missing load-balance or router-z loss), on the
weights the program seeds and the cell's first batch.

    python3 benchmark/tools/olmoe_ref_sensitivity.py [batch] [seed] [out.json]

float32 at ``highest`` precision, so the device does not matter to the
numbers: minutes on the chip, an hour on the CPU at batch 4."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402

CELL = "olmoe_1b_7b.s4096_scan"


def main(batch=4, seed=0, out_path=None):
    import jax

    config = mf.read_json(ROOT, "benchmark", "configs", "olmoe_1b_7b.json")
    traffic = dict(mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json"),
                   batch=int(batch))
    trainer = build.build_trainer(config, traffic, int(seed),
                                  jax.devices()[:1])
    params = jax.tree.map(np.asarray, trainer.state["params"])
    del trainer
    b = batches.host_batch(config["batch_fields"],
                           build.cell_dims(config, traffic), int(seed), 0)
    ref = mf.module("reference", config["reference"])
    good = ref.loss(params, b, config["model"])
    out = {"config": config["name"], "batch": int(batch), "seed": int(seed),
           "platform": jax.devices()[0].platform, "loss": good,
           "tolerance": ref.TOLERANCE, "faults": {}}
    print(json.dumps(out), flush=True)
    for fault in ref.FAULTS:
        bad = ref.loss(params, b, config["model"], faults=(fault,))
        change = abs(bad - good) / good
        out["faults"][fault] = {"loss": bad, "relative_change": change,
                                "caught": bool(change > ref.TOLERANCE)}
        print(fault, json.dumps(out["faults"][fault]), flush=True)
    print(json.dumps(out), flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main(*sys.argv[1:4])
