"""Driver ``train_scan_witnessed_batch``: ``train_scan_witnessed``, whose
witness hands the trainer ALL of batch 0's fields.

A trainer whose forward reads more of a batch than its ``ids`` (a
block-diffusion step NOISES them by the batch's ``t`` and ``u``) cannot be
witnessed on the ids alone: ``logits_at`` takes the batch, and the
reference's ``witness_positions`` are rows of what that forward runs on.
The witness call stands inside a monitor session of its own, outside the
timed window (nothing else of the run is observed): what the program counts
of that call (``monitor.train.*``: the masked share, the head's rows, the
rule's tiles a layer, the held pairs, the routing's balance, the capacity in
force; ``monitor.kernels.*_calls``: which branch each ``supported(shape)``
took as the forward was traced) is printed on the ``counters:`` line and
handed on as the run's ``counters``, which the per-layer readers get.

Everything else is ``train_scan_witnessed``'s and ``train_scan``'s: the
statistic and its limit, staging, the warm-up, the window."""

import json
import os

import jax
import numpy as np

from ..harness import manifest as mf
from . import train_scan, train_scan_witnessed


def _observed(ctx, call):
    """``call()`` under a monitor session of its own: its result and the
    session's ``monitor.train`` / ``monitor.kernels`` rows by name (labels
    in braces)."""
    from paddle_tpu import monitor

    mon = monitor.enable(os.path.join(ctx.out_dir, "monitor"), flight=False)
    try:
        mon.registry.reset()
        out = call()
        rows = {}
        for r in mon.registry.snapshot():
            if r["name"].startswith(("monitor.train.", "monitor.kernels.")):
                labels = ",".join("%s=%s" % kv for kv in sorted(
                    (r["labels"] or {}).items()))
                rows[r["name"] + ("{%s}" % labels if labels else "")] = \
                    r["value"]
        return out, rows
    finally:
        monitor.disable()


def prepare(ctx):
    ref = mf.module("reference", ctx.config["reference"])
    with ctx.spans.span("bench.stage"):
        staged = train_scan._stage(ctx)
        batch0 = {k: np.asarray(v[0]) for k, v in staged.items()}
    with ctx.spans.span("bench.witness"):
        at = ref.witness_positions(batch0["ids"].shape[1])
        logits, counters = _observed(
            ctx, lambda: np.asarray(ctx.trainer.logits_at(batch0, at)))
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
    ctx.say("counters: %s" % json.dumps(counters, sort_keys=True))
    with ctx.spans.span("bench.warmup"):
        losses = np.asarray(ctx.trainer.run_steps(staged, ctx.lr), np.float32)
    return {"staged": staged, "batch0": batch0, "first_loss": float(losses[0]),
            "steps_per_dispatch": int(ctx.traffic["staged_batches"]),
            "witness": witness, "counters": counters}


def measure(ctx, st):
    run = train_scan_witnessed.measure(ctx, st)
    run["counters"] = st["counters"]
    return run
