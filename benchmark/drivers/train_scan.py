"""Driver ``train_scan``: any trainer with ``run_steps``.

``staged_batches`` distinct batches are staged on the device once; one
dispatch is ``trainer.run_steps`` over all of them (the program's
device-side scan), repeated for ``--seconds``.  One dispatch is kept in
flight behind the one being waited for, so the host's round trip is hidden
as a training loop that reads its losses one dispatch late hides it.  A
timed interval ends when the losses of a dispatch have reached the host,
which every step of it has to finish first.
"""

import time

import numpy as np


def _stage(ctx):
    """The staged batches, leading axis = step.  Host-made fields go
    through the program's ``stack_batches``; a field the configuration
    makes on the device is generated there, into the same sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel.train import stack_batches

    from ..harness import batches

    n = ctx.traffic["staged_batches"]
    fields = ctx.config["batch_fields"]
    mesh, axis = ctx.trainer.mesh, ctx.config["batch_axis"]
    host_fields = [f for f in fields if f.get("where", "host") == "host"]
    staged = {}
    if host_fields:
        made = [batches.host_batch(host_fields, ctx.dims, ctx.seed, i)
                for i in range(n)]
        staged.update(stack_batches(
            mesh, {f["name"]: P(axis) for f in host_fields}, made))
    for f in fields:
        if f.get("where", "host") == "device":
            staged[f["name"]] = batches.device_staged(
                f, ctx.dims, ctx.seed, n, NamedSharding(mesh, P(None, axis)))
    return staged


def prepare(ctx):
    """Stage, then one dispatch that compiles (or loads) the scan; its
    first loss is the loss at the seeded weights on batch 0."""
    with ctx.spans.span("bench.stage"):
        staged = _stage(ctx)
        batch0 = {k: np.asarray(v[0]) for k, v in staged.items()}
    with ctx.spans.span("bench.warmup"):
        losses = np.asarray(ctx.trainer.run_steps(staged, ctx.lr), np.float32)
    return {"staged": staged, "batch0": batch0, "first_loss": float(losses[0]),
            "steps_per_dispatch": int(ctx.traffic["staged_batches"])}


class _Loop:
    def __init__(self, ctx, st):
        self.ctx, self.staged = ctx, st["staged"]
        self.pending = None
        self.done, self.losses = [], []

    def turn(self):
        """Dispatch one scan, then wait for the one before it."""
        ctx = self.ctx
        with ctx.spans.span("bench.dispatch"):
            cur = ctx.trainer.run_steps(self.staged, ctx.lr)
        self.flush()
        self.pending = cur

    def flush(self):
        if self.pending is None:
            return
        with self.ctx.spans.span("bench.sync"):
            self.losses.append(np.asarray(self.pending, np.float32))
        self.done.append(time.perf_counter())
        self.pending = None


def measure(ctx, st):
    from ..harness import tracing

    n = st["steps_per_dispatch"]
    loop = _Loop(ctx, st)
    t0 = time.perf_counter()
    while not loop.done or loop.done[-1] - t0 < ctx.seconds:
        loop.turn()
    loop.flush()
    t1 = loop.done[-1]
    dispatches = len(loop.done)
    completions = list(loop.done)
    if ctx.trace:
        # after the window, so that the profiler costs the timed part
        # nothing: put one dispatch in flight (the trace shows it cut at
        # the start), trace whole dispatches behind it, drain
        loop.turn()
        tracing.start(ctx)
        for _ in range(int(ctx.traffic.get("trace_dispatches", 2)) + 1):
            loop.turn()
        tracing.stop(ctx)
        loop.flush()
    losses = np.concatenate(loop.losses)
    bad = sum(1 for l in loop.losses[:dispatches] if not np.isfinite(l).all())
    return {"t0": t0, "t1": t1, "steps": dispatches * n,
            "attempted": dispatches, "failed": bad,
            "marks": completions, "steps_per_mark": n,
            # between completions; the first dispatch, which starts on an
            # idle device, gives no sample
            "step_ms": [(b - a) * 1e3 / n
                        for a, b in zip(completions, completions[1:])],
            "losses_finite": bool(np.isfinite(losses).all()),
            "last_loss": float(losses[-1])}
