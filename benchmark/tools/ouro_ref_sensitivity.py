"""How far the faults that ``correct`` is there to catch move the
``ouro_2_6b`` reference at the published widths and the timed sizes, by both
of the cell's limits: its loss (``TOLERANCE``) and its weighted-exit logits
at the witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to: the larger of the
``edge`` and the ``spread`` group's MEDIAN of each position's error in units
of ``reference.precision_unit``, each group printed).  The reference with a
fault put in (``reference.FAULTS``: a single pass, no norm between the
passes, the gate without its bias or on the un-normed state, a last exit
that takes its own gate, the entropy term dropped, uniform exit weights, no
output norms, fresh leaves in every pass, bfloat16 throughout), on the
weights the program seeds and the cell's first batch.

    python3 benchmark/tools/ouro_ref_sensitivity.py [seed] [out.json] [fault ...]

Faults named after the two are the only ones thrown; ``none`` throws none
and reads the sound program alone.  The readings of a fault are
``jamba_ref_sensitivity.py``'s (one definition of what is read and printed).

THE CONTROL is printed first, under ``control``: the reference in the
precision below the configuration's (``bfloat16_throughout``) put IN THE
PROGRAM'S PLACE, through the run's own comparison against the float32
reference.  It has to read over ``LOGITS_TOLERANCE``; in the witness's units
it is 1 by construction, so its plain reading (each group's median of
``|bfloat16 - float32| / |float32|``, the unit itself) stands beside it, and
the sound program's in the same plain terms.  The fault
``bfloat16_throughout`` further down is another quantity: the sound program
against the reference in bfloat16, two independent errors added.  The
readings are the chip's alone."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402
from benchmark.tools import jamba_ref_sensitivity as procedure  # noqa: E402

NAME, CELL = "ouro_2_6b", "ouro_2_6b.s4096_scan"


def _plain(ref, each, ids):
    """Each group's median of the positions' plain errors ``each`` [B * P]."""
    n_edge = len(ref.witness_groups(ids.shape[1])["edge"])
    each = each.reshape(ids.shape[0], -1)
    return {"edge": float(np.median(each[:, :n_edge])),
            "spread": float(np.median(each[:, n_edge:]))}


def main(seed=0, out_path=None, *only):
    import jax

    config = mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    trainer = build.build_trainer(config, traffic, int(seed),
                                  jax.devices()[:1])
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
           "platform": jax.devices()[0].platform, "loss": good,
           "tolerance": ref.TOLERANCE,
           "logits_tolerance": ref.LOGITS_TOLERANCE, "faults": {}}
    out.update(procedure._errors(ref, program, params, b, model))
    print(json.dumps(dict(out, positions="...")), flush=True)
    below = ref.logits(params, b, model, ref.PRECISION)
    reading = ref.logits_error(below, params, b, model)
    low_loss = ref.loss(params, b, model, ref.PRECISION)
    out["control"] = {
        "what": "the reference in bfloat16 throughout in the program's "
                "place, against the float32 reference",
        "logits_error": reading,
        "not_correct_by_logits": not reading <= ref.LOGITS_TOLERANCE,
        "plain": _plain(ref, ref.precision_unit(params, b, model), b["ids"]),
        "sound_program_plain": _plain(
            ref, ref.position_errors(program, params, b, model), b["ids"]),
        "loss_relative_error": abs(low_loss - good) / good,
        "not_correct_by_loss": not abs(low_loss - good) / good
        <= ref.TOLERANCE}
    del below
    print("control", json.dumps(out["control"]), flush=True)
    for fault in [f for f in only or ref.FAULTS if f != "none"]:
        bad = ref.loss(params, b, model, faults=(fault,))
        change = abs(bad - good) / good
        got = procedure._errors(ref, program, params, b, model,
                                faults=(fault,))
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
