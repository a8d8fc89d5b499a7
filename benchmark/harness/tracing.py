"""Starting and stopping the profiler for the traced part of a run."""

import os
import shutil
import time


def start(ctx):
    """Device tracing on; the Python tracer off (it records every call of
    the host loop and slows the very thread whose stalls the trace is to
    show); the host tracer at the level the traffic file gives:

    - 1 (the default) keeps TraceMe events, so the benchmark's spans are
      ``jax.profiler.TraceAnnotation``s in the trace itself;
    - 0 records no host event at all.  A host-fed cell needs it: with any
      host tracing on, the runtime's transfer thread records 270,000 events
      per 19 MB upload and the upload takes 0.87 s in place of 18 ms (my
      chip runs, PR 22, levels 2 and 1 alike).  The benchmark's host-clock
      spans are then laid beside the device's operations by ``anchored``.
    """
    level = int(ctx.traffic.get("trace_host_level", 1))
    ctx.trace_dir = os.path.join(ctx.out_dir, "trace")
    ctx.trace_returned = _start(ctx.trace_dir, level)
    ctx.spans.annotate = level > 0


def _start(trace_dir, host_level):
    """Returns the host clock at which ``start_trace`` returned."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = host_level
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return time.perf_counter()


def stop(ctx):
    import jax

    ctx.spans.annotate = False
    jax.profiler.stop_trace()


def clock_lead(ctx):
    """Seconds by which the trace's clock is ahead of ``start_trace``'s
    return: 25 us on the CPU, about 50 ms on the v5e (its clock starts
    when the session does, before the device tracer is up).  Measured by a
    throwaway trace with one annotation in it."""
    import jax

    from . import trace_reduce

    probe = os.path.join(ctx.out_dir, "trace_clock")
    returned = _start(probe, 1)
    at = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "clock"):
        pass
    jax.profiler.stop_trace()
    xplane = trace_reduce.find_xplane(probe)
    spans = trace_reduce.Reduced(
        trace_reduce.load_xplane(xplane)).host_spans if xplane else []
    return spans[0][1] / 1e9 - (at - returned) if spans else 0.0


def anchored(ctx):
    """The benchmark's host-clock spans on the trace's clock, in ns, for a
    trace that could hold no annotation."""
    zero = ctx.trace_returned - clock_lead(ctx)
    return [(n, (t0 - zero) * 1e9, (t1 - zero) * 1e9)
            for n, t0, t1, _ in ctx.spans.records if t1 >= zero]
