"""Driver ``train_scan_witnessed_mesh``: ``train_scan_witnessed`` for a
trainer whose layers are SHARED by the chips of its mesh (expert-parallel:
rows exchanged between the chips inside every sparse layer), whose witness
therefore has more to say than a number.

The witness is ``train_scan_witnessed``'s own (the program's logits at the
reference's ``witness_positions`` of EVERY sequence of batch 0, each of which
lives on another chip, against the reference's one-device view of the whole
model, held to ``reference.LOGITS_TOLERANCE``), and beside the statistic it
prints each SEQUENCE's third quartile (``reference.sequence_errors``, where
the reference has it): a fault of one chip's exchange shows in its own
quarter.  The call stands inside a monitor session of its own, outside the
timed window, as ``train_scan_witnessed_batch``'s does (its ``_observed``):
what the program counts of it (``monitor.train.moe_exchange_tier``, the
rounds past the first that the fullest layer's exchange needed;
``moe_exchange_fullest`` beside ``moe_exchange_capacity``; ``moe_rows_sent``,
``moe_rows_received``; ``moe_load_max_over_mean``, ``router_bias_abs_max``;
``monitor.kernels.*_calls``) is printed on the ``counters:`` line and handed
on as the run's ``counters``, which the per-layer readers get.

The routing moves while the window trains, so the same call is made ONCE
MORE after the window (and after the traced dispatches), on the same batch
at the weights the run leaves: its ``monitor.train.*`` readings go into the
counters under ``<name>.end`` and on the ``counters at the end:`` line.  A
cell that changed its round count inside the window shows there, not in a
tail of the step times alone.

Everything else is ``train_scan_witnessed``'s and ``train_scan``'s: the
statistic and its limit, staging, the warm-up, the window."""

import json

import jax
import numpy as np

from ..harness import manifest as mf
from . import train_scan, train_scan_witnessed
from .train_scan_witnessed_batch import _observed


def prepare(ctx):
    ref = mf.module("reference", ctx.config["reference"])
    with ctx.spans.span("bench.stage"):
        staged = train_scan._stage(ctx)
        batch0 = {k: np.asarray(v[0]) for k, v in staged.items()}
    with ctx.spans.span("bench.witness"):
        ids = batch0["ids"]
        at = ref.witness_positions(ids.shape[1])
        logits, counters = _observed(
            ctx, lambda: np.asarray(ctx.trainer.logits_at(ids, at)))
        # a host copy, as the harness makes for its check: the reference
        # keeps its last results, so its loss is not computed twice
        params0 = jax.tree.map(np.asarray, ctx.trainer.state["params"])
        model = ctx.config["model"]
        each = ref.position_errors(logits, params0, batch0, model)
        err = ref.logits_error(logits, params0, batch0, model)
        by_sequence = [float(e) for e in ref.sequence_errors(
            logits, params0, batch0, model)] \
            if hasattr(ref, "sequence_errors") else None
        del params0, logits
    witness = {"logits_relative_error": err,
               "largest_of_a_position": float(each.max()),
               "by_sequence": by_sequence,
               "tolerance": ref.LOGITS_TOLERANCE,
               "ok": bool(err <= ref.LOGITS_TOLERANCE)}
    ctx.say("witness: %s" % json.dumps(witness))
    ctx.say("counters: %s" % json.dumps(counters, sort_keys=True))
    with ctx.spans.span("bench.warmup"):
        losses = np.asarray(ctx.trainer.run_steps(staged, ctx.lr), np.float32)
    return {"staged": staged, "batch0": batch0, "first_loss": float(losses[0]),
            "steps_per_dispatch": int(ctx.traffic["staged_batches"]),
            "witness": witness, "counters": counters, "witness_at": at}


def measure(ctx, st):
    run = train_scan_witnessed.measure(ctx, st)
    # the same observed call at the weights the run leaves: compiled already
    _, after = _observed(ctx, lambda: jax.block_until_ready(
        ctx.trainer.logits_at(st["batch0"]["ids"], st["witness_at"])))
    after = {name + ".end": value for name, value in after.items()
             if name.startswith("monitor.train.")}
    ctx.say("counters at the end: %s" % json.dumps(after, sort_keys=True))
    run["counters"] = dict(st["counters"], **after)
    return run
