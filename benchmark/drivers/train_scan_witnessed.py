"""Driver ``train_scan_witnessed``: ``train_scan``, and before the warm-up
a WITNESS of the forward that the scalar loss cannot give.

At seeded weights and uniform ids a decoder's loss sits at ln V whatever its
attention and routing do (PERF.md section 7 (n), (u)): a wrong band, a wrong
key/value head or a wrong router input moves it by less than bf16 rounding
does.  The head's logits do tell: the program's, at the reference's
``witness_positions`` of batch 0 through the trainer's ``logits_at`` (the
step's own forward: its block, its compiled kernels, its MoE path), against
the reference's, as ``reference.logits_error`` measures them (the third
quartile over the positions of each one's relative error: the reference's
file says why), held to ``reference.LOGITS_TOLERANCE``.

The harness decides ``correct`` from the reference's loss check, the run's
``losses_finite`` and the recompile count (``harness/cellrun.py``), and a
driver's run has no other field that it reads: a failed witness is reported
on the ``witness:`` line and makes ``losses_finite`` false, so the run is
not ``correct``.  A harness that took a driver's own checks would take that
detour out (PERF.md section 7).

Everything else is ``train_scan``'s: staging, the warm-up, the window."""

import json

import jax
import numpy as np

from ..harness import manifest as mf
from . import train_scan


def prepare(ctx):
    ref = mf.module("reference", ctx.config["reference"])
    with ctx.spans.span("bench.stage"):
        staged = train_scan._stage(ctx)
        batch0 = {k: np.asarray(v[0]) for k, v in staged.items()}
    with ctx.spans.span("bench.witness"):
        ids = batch0["ids"]
        logits = np.asarray(ctx.trainer.logits_at(
            ids, ref.witness_positions(ids.shape[1])))
        # a host copy, as the harness makes for its check: the reference
        # keeps its last results, so its loss is not computed twice
        params0 = jax.tree.map(np.asarray, ctx.trainer.state["params"])
        each = ref.position_errors(logits, params0, batch0,
                                   ctx.config["model"])
        err = ref.logits_error(logits, params0, batch0, ctx.config["model"])
        del params0, logits
    witness = {"logits_relative_error": err,
               "largest_of_a_position": float(each.max()),
               "tolerance": ref.LOGITS_TOLERANCE,
               "ok": bool(err <= ref.LOGITS_TOLERANCE)}
    ctx.say("witness: %s" % json.dumps(witness))
    with ctx.spans.span("bench.warmup"):
        losses = np.asarray(ctx.trainer.run_steps(staged, ctx.lr), np.float32)
    return {"staged": staged, "batch0": batch0, "first_loss": float(losses[0]),
            "steps_per_dispatch": int(ctx.traffic["staged_batches"]),
            "witness": witness}


def measure(ctx, st):
    run = train_scan.measure(ctx, st)
    run["losses_finite"] = bool(run["losses_finite"] and st["witness"]["ok"])
    return run
