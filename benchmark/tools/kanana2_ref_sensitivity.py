"""How far the faults that ``correct`` is there to catch move the
``kanana_2_30b_a3b`` reference at the published widths and the timed sizes,
by both of the cell's limits: its loss (``TOLERANCE``) and its logits at the
witness's 64 positions of each of the four sequences against the PROGRAM's
on the four chips (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed_mesh.py`` holds a run to; each sequence's
third quartile printed beside it).  The reference with a fault put in
(``reference.FAULTS``: the exchange's (a chip computing with chip 0's expert
of the same local index, the results home in the wrong slots, pairs past a
capacity dropped, the shared expert summed over the chips), the routing's
(no 2.448, the bias inside the weights), attention's (rotate-half, the shared
key rotated twice, a scale of 128^-1/2) and bfloat16 throughout), on the
weights the program seeds on its mesh and the cell's first batch.

    chiprun --chips 4 -- python3 benchmark/tools/kanana2_ref_sensitivity.py \\
        [seed] [out.json] [fault ...]

Faults named after the two are the only ones thrown; ``none`` throws none
and reads the sound program alone.  It is ``jamba_ref_sensitivity.py``'s
procedure (its ``_errors``: one definition of what is read and printed) on
the cell's own mesh: the program's logits come off all four chips.  The
readings are the chips' alone."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from benchmark.tools.jamba_ref_sensitivity import _errors  # noqa: E402

NAME, CELL = "kanana_2_30b_a3b", "kanana_2_30b_a3b.s8192_ep4"


def main(seed=0, out_path=None, *only):
    import jax

    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    chips = traffic["mesh"]["dp"]
    trainer = build.build_trainer(config, traffic, int(seed),
                                  jax.devices()[:chips])
    params = jax.tree.map(np.asarray, trainer.state["params"])
    b = batches.host_batch(config["batch_fields"],
                           build.cell_dims(config, traffic), int(seed), 0)
    ref = mf.module("reference", config["reference"])
    model = config["model"]
    program = np.asarray(trainer.logits_at(
        b["ids"], ref.witness_positions(b["ids"].shape[1])))
    del trainer
    good = ref.loss(params, b, model)
    out = {"config": config["name"], "seed": int(seed),
           "platform": jax.devices()[0].platform, "chips": chips,
           "loss": good, "tolerance": ref.TOLERANCE,
           "logits_tolerance": ref.LOGITS_TOLERANCE, "faults": {}}
    out.update(_errors(ref, program, params, b, model))
    print(json.dumps(dict(out, positions="...")), flush=True)
    for fault in [f for f in only or ref.FAULTS if f != "none"]:
        bad = ref.loss(params, b, model, faults=(fault,))
        change = abs(bad - good) / good
        got = _errors(ref, program, params, b, model, faults=(fault,))
        out["faults"][fault] = dict(
            got, loss=bad, relative_change=change,
            caught_by_loss=not change <= ref.TOLERANCE,
            caught_by_logits=not got["program_logits_error"]
            <= ref.LOGITS_TOLERANCE)
        print(fault, json.dumps(dict(out["faults"][fault], positions="...")),
              flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main(*sys.argv[1:])
