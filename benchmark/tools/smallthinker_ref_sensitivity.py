"""How far the faults that ``correct`` is there to catch move the
``smallthinker_21b_a3b`` reference at the published sizes, by both of the
cell's limits: its loss (``TOLERANCE``) and its logits at the witness's
positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to).  The reference with a
fault put in (``reference.FAULTS``: full
attention in the windowed layers, rotary embedding on the position-free
layer, a router fed the normed FFN input, SiLU for ReLU, top-5 in place of
top-6, weights not renormalised, query heads mapped to the wrong key/value
head, bfloat16 throughout), on the weights the program seeds and the cell's
first batch.

    python3 benchmark/tools/smallthinker_ref_sensitivity.py [seed] [out.json]

The reference is float32 at ``highest`` precision; the program's logits are
the chip's (bf16, the compiled kernels), so the logits' readings are the
chip's alone: minutes there, hours on the CPU."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import batches, build, manifest as mf  # noqa: E402

NAME, CELL = "smallthinker_21b_a3b", "smallthinker_21b_a3b.s16384_scan"


def _errors(ref, program, *args, **faults):
    """The witness's reading, each position's error that it is a quartile
    of, and the one norm over all rows (which the few positions whose sixth
    expert flipped lead)."""
    each = ref.position_errors(program, *args, **faults)
    want = ref.logits(*args, **faults)
    return {"program_logits_error": ref.logits_error(program, *args,
                                                     **faults),
            "all_rows": float(np.linalg.norm(program - want)
                              / np.linalg.norm(want)),
            "positions": [float(e) for e in each]}


def main(seed=0, out_path=None):
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
    out.update(_errors(ref, program, params, b, model))
    print(json.dumps(dict(out, positions="...")), flush=True)
    for fault in ref.FAULTS:
        bad = ref.loss(params, b, model, faults=(fault,))
        change = abs(bad - good) / good
        got = _errors(ref, program, params, b, model, faults=(fault,))
        out["faults"][fault] = dict(
            got, loss=bad, relative_change=change,
            caught_by_loss=bool(change > ref.TOLERANCE),
            caught_by_logits=bool(
                got["program_logits_error"] > ref.LOGITS_TOLERANCE))
        print(fault, json.dumps(dict(out["faults"][fault], positions="...")),
              flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main(*sys.argv[1:3])
