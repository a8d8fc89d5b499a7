"""How far the faults that ``correct`` is there to catch move the
``jamba2_3b`` reference at the published widths and the timed sizes, by both
of the cell's limits: its loss (``TOLERANCE``) and its logits at the
witness's positions against the PROGRAM's (``LOGITS_TOLERANCE``, what
``drivers/train_scan_witnessed.py`` holds a run to; the larger of the
``edge`` and the ``spread`` group's third quartile, each printed).  The
reference with a fault put in (``reference.FAULTS``: the state dropped at
chunk edges, step sizes without their softplus, the input without its step
size, rates not negated, no skip, no gate, no inner norm on the step sizes'
input / on B and C, the filter without its bias / one tap late, the state
in bfloat16, rotary positions on attention, a second key/value head,
bfloat16 throughout), on the weights the program seeds and the cell's first
batch.

    python3 benchmark/tools/jamba_ref_sensitivity.py [seed] [out.json] [fault ...]

Faults named after the two are the only ones thrown; ``none`` throws none
and reads the sound program alone.  A reading that is no number (a state
that overflows) is a failed comparison: caught.

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

NAME, CELL = "jamba2_3b", "jamba2_3b.s8192_scan"


def _errors(ref, program, *args, **faults):
    """The witness's reading, each position's error that it is a quartile
    of, and the one norm over all rows."""
    each = ref.position_errors(program, *args, **faults)
    want = ref.logits(*args, **faults)
    return {"program_logits_error": ref.logits_error(program, *args,
                                                     **faults),
            "groups": ref.group_errors(program, *args, **faults),
            "all_rows": float(np.linalg.norm(program - want)
                              / np.linalg.norm(want)),
            "quantiles": dict(zip(
                ("min", "q05", "q25", "q50", "q75", "max"),
                (float(q) for q in np.quantile(
                    each, (0.0, 0.05, 0.25, 0.5, 0.75, 1.0))))),
            "positions": [float(e) for e in each]}


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
