"""Driver ``train_hostfed``: any trainer with ``step``, fed as a user feeds
it: host batches -> ``DeviceFeedPipe(convert=...)`` -> ``trainer.step`` ->
``InFlightWindow.admit(loss)``, closed loop, depths as the program defaults.

A pool of ``host_pool`` distinct host batches (made from the seed in
set-up, in each field's ``feed_dtype``) is cycled.  ``convert`` runs on the
pipe's worker thread: upload (waited for, so that ``bench.convert`` is the
transfer and nothing else), then a jitted cast to the field's dtype where
the two differ.
"""

import itertools
import time

import numpy as np


def _make_convert(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(ctx.trainer.mesh, P(ctx.config["batch_axis"]))
    casts = {}
    for f in ctx.config["batch_fields"]:
        if f.get("feed_dtype", f["dtype"]) != f["dtype"]:
            dtype = jnp.dtype(f["dtype"])
            casts[f["name"]] = jax.jit(lambda x, d=dtype: x.astype(d))

    def convert(raw):
        with ctx.spans.span("bench.convert"):
            put = {k: jax.device_put(v, sharding) for k, v in raw.items()}
            jax.block_until_ready(put)
        return {k: casts[k](v) if k in casts else v for k, v in put.items()}

    return convert


def prepare(ctx):
    from ..harness import batches

    with ctx.spans.span("bench.stage"):
        pool = [batches.host_batch(ctx.config["batch_fields"], ctx.dims,
                                   ctx.seed, i, feed=True)
                for i in range(int(ctx.traffic["host_pool"]))]
    convert = _make_convert(ctx)
    with ctx.spans.span("bench.warmup"):
        first = float(ctx.trainer.step(convert(pool[0]), ctx.lr))
        float(ctx.trainer.step(convert(pool[1 % len(pool)]), ctx.lr))
    return {"pool": pool, "convert": convert, "batch0": pool[0],
            "first_loss": first}


def _feed_loop(ctx, st, finished):
    """Feeds the pool through the pipe until ``finished(steps, elapsed)``;
    returns the start, the end (after the window is drained), the marks at
    which a step left the in-flight window, and the losses."""
    from paddle_tpu.feed_pipe import DeviceFeedPipe, InFlightWindow

    sp, trainer = ctx.spans, ctx.trainer
    # the pipe's worker stops pulling once the pipe is closed
    pipe = DeviceFeedPipe(itertools.cycle(st["pool"]), convert=st["convert"],
                          name="bench_feed")
    window = InFlightWindow()
    feed = iter(pipe)
    marks, losses = [], []
    t0 = time.perf_counter()
    try:
        while True:
            with sp.span("bench.feed_wait"):
                batch = next(feed)
            with sp.span("bench.dispatch"):
                loss = trainer.step(batch, ctx.lr)
            with sp.span("bench.sync"):
                window.admit(loss)
            losses.append(loss)
            marks.append(time.perf_counter())
            if finished(len(marks), marks[-1] - t0):
                break
        with sp.span("bench.sync"):
            window.drain()
        t1 = time.perf_counter()
    finally:
        feed.close()
    return t0, t1, marks, np.asarray([float(l) for l in losses], np.float32)


def _traced_pass(ctx, st):
    """A second, short pass under the profiler, for the device's side
    only.  Tracing starts a few steps in, once the pipe is full."""
    from ..harness import tracing

    seconds = float(ctx.traffic.get("trace_seconds", 3.0))
    lead = int(ctx.traffic.get("trace_lead_steps", 8))
    edge = {}

    def finished(n, _elapsed):
        if n == lead:
            tracing.start(ctx)
            edge["t0"] = time.perf_counter()
        elif n > lead and time.perf_counter() - edge["t0"] >= seconds:
            tracing.stop(ctx)
            return True
        return False

    _, _, marks, _ = _feed_loop(ctx, st, finished)
    # what the profiler costs the host path: compare with step_ms_p50
    traced = sorted(b - a for a, b in zip(marks, marks[1:])
                    if a >= edge["t0"])
    if traced:
        ctx.say("traced pass: %d steps, median %.4f ms a step under the "
                "profiler" % (len(traced), traced[len(traced) // 2] * 1e3))


def measure(ctx, st):
    """In a traced run the program's monitor is on over the whole window
    (the pipe records ``monitor.pipe.feed_stall_ms`` only then; the
    untraced run leaves it off), and the profiler gets a pass of its own
    after the window."""
    counters = {}
    if ctx.trace:
        import os

        from paddle_tpu import monitor

        mon = monitor.enable(os.path.join(ctx.out_dir, "monitor"),
                             flight=False)
        stall = mon.registry.histogram("monitor.pipe.feed_stall_ms")
        stall0 = stall.total
    try:
        t0, t1, marks, losses = _feed_loop(
            ctx, st, lambda _n, elapsed: elapsed >= ctx.seconds)
        if ctx.trace:
            counters["feed_stall_ms"] = stall.total - stall0
    finally:
        if ctx.trace:
            monitor.disable()
    out = {"t0": t0, "t1": t1, "steps": len(marks),
           "attempted": len(marks), "marks": marks, "steps_per_mark": 1,
           "failed": int((~np.isfinite(losses)).sum()),
           "step_ms": [(b - a) * 1e3 for a, b in zip(marks, marks[1:])],
           "losses_finite": bool(np.isfinite(losses).all()),
           "last_loss": float(losses[-1]), "counters": counters}
    if ctx.trace:
        _traced_pass(ctx, st)
    return out
