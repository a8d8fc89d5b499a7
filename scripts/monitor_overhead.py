#!/usr/bin/env python
"""Measure monitor-subsystem overhead on the executor step loop.

Acceptance gates: telemetry on the bench step loop must cost < 2% vs
monitor-off (monitor issue), the MemScope owner-attribution sampler must
cost < 2% of run time at its production cadence (``--memscope``,
memscope issue), the span tracer must cost <= 0.5% of
step-loop time on its DISABLED path and <= 2% enabled (tracer issue), and
the TrainSentinel health bundle must cost < 1% on top of the monitored
loop (sentinel issue — the bundle is a handful of fused reductions riding
the step plus one tiny host readback per sample_every steps), and the
FleetScope phase accounting (fleetscope issue) must keep the fully-loaded
monitored loop under the same 2% envelope while the DISABLED-span hook
path stays under its 0.5% gate (phase hooks live inside monitor-gated
branches: an unmonitored run pays only the no-op span + one active()
read).  This probe runs the same jitted executor.run step loop six ways —
monitor off, monitor on without phase accounting (the historical
comparison point), monitor on + FleetScope phase accounting (the default
production shape), monitor on + sentinel (default halt policy, sampled),
monitor on with tracing off, monitor on sampling device time every step
(worst case) — and microbenchmarks the disabled ``trace.span`` call
directly (hook sites stay instrumented when tracing is off; their cost is
spans/step x the no-op call).  Run on CPU or TPU:

    JAX_PLATFORMS=cpu python scripts/monitor_overhead.py [--steps 300]

``--check`` is the fast CI shape of the disabled-path gates (small
program, short loop, exit 0/2) — cheap enough that tier-1 runs it as a
smoke while the full sweep stays a perf bench.  Since the FleetServe
round it also gates the router's dispatch/reply hot path: ``_pick`` +
``_note_reply`` + the disabled wire span, microbenched with no tracer
installed, must cost <= 0.5% of a 1ms request floor (~50x below the CPU
fleet's observed p50) — i.e. tracing-off routing is effectively free.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build(batch=256, hidden=512):
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[hidden], dtype="float32")
        h = fluid.layers.fc(x, hidden, act="relu")
        # BOUNDED objective (mean of squares -> 0), not mean(fc): the bare
        # linear loss is unbounded below, so a long enough probe loop
        # drives the params to -inf — and the sentinel mode then (rightly)
        # trips mid-measurement
        loss = fluid.layers.mean(fluid.layers.square(fluid.layers.fc(h, 1)))
        fluid.optimizer.SGD(0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.random.RandomState(0).rand(batch, hidden).astype("f4")}
    return exe, main, feed, loss


def loop(exe, main, feed, loss, steps):
    # warmup/compile outside the timed region
    exe.run(main, feed=feed, fetch_list=[loss.name])
    t0 = time.perf_counter()
    for _ in range(steps):
        exe.run(main, feed=feed, fetch_list=[loss.name])
    return (time.perf_counter() - t0) / steps


def disabled_span_cost(n=200_000, reps=3):
    """Per-call cost of ``trace.span`` with NO tracer installed — exactly
    what every instrumented hook site pays on an unmonitored run.  Min of
    ``reps`` timed passes with the cyclic GC paused: both gates bound the
    INTRINSIC cost of the hot path, and a collection pause (or a stolen
    slice of CPU) landing inside the timed window is measurement noise,
    not hook cost — tier-1 runs this right after a suite full of jax
    garbage."""
    import gc

    from paddle_tpu.monitor import trace

    assert trace.active_tracer() is None
    best = float("inf")
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                with trace.span("probe"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
    finally:
        if was_enabled:
            gc.enable()
    return best


def spans_per_step(exe, main_prog, feed, loss, steps=64):
    """Spans the instrumented hot paths emit per executor.run step,
    counted from the live tracer's rings."""
    import tempfile

    from paddle_tpu import monitor

    # tracing=True explicitly: the whole point is counting tracer spans,
    # so PADDLE_TPU_TRACE=0 in the environment must not null the tracer
    mon = monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_spans_"),
                         tracing=True, trace_ring=steps * 32)
    try:
        exe.run(main_prog, feed=feed, fetch_list=[loss.name])   # warm
        c0 = mon.tracer.record_count()
        for _ in range(steps):
            exe.run(main_prog, feed=feed, fetch_list=[loss.name])
        return (mon.tracer.record_count() - c0) / steps
    finally:
        monitor.disable()


def warm_precompile_probe(steps=48):
    """Confirm the WarmStart background pre-compile thread (warm.py
    notify_commit) adds NO tracer-visible step overhead: a monitored
    executor step loop runs while the thread compiles-and-persists ballast
    executables, and must emit exactly as many tracer spans and per-step
    timeline events as the baseline loop — all pre-compilation lives on
    the daemon thread, whose only timeline trace is its own ``compile``
    announcements (counted separately, a handful per RUN, not per step).
    Wall time is reported for context only: a background XLA compile
    legitimately competes for CPU, which is not what this gate bounds."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from paddle_tpu import monitor, warm

    exe, main_prog, feed, loss = build(batch=64, hidden=128)
    mon = monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_warm_"),
                         tracing=True, trace_ring=steps * 64)
    out = {}
    try:
        exe.run(main_prog, feed=feed, fetch_list=[loss.name])   # warm

        def measure():
            c0 = mon.tracer.record_count()
            n0 = mon.timeline._n
            t0 = time.perf_counter()
            for _ in range(steps):
                exe.run(main_prog, feed=feed, fetch_list=[loss.name])
            dt = (time.perf_counter() - t0) / steps
            # the in-memory tail ring holds the last 256 events and this
            # loop emits far fewer, so the newest (n1-n0) entries ARE the
            # loop's events
            n1 = mon.timeline._n
            new = mon.timeline.tail()[-(n1 - n0):] if n1 > n0 else []
            ev_step = sum(1 for e in new if e.get("ev") != "compile")
            ev_compile = sum(1 for e in new if e.get("ev") == "compile")
            spans = (mon.tracer.record_count() - c0) / steps
            return dt, spans, ev_step / steps, ev_compile

        dt0, spans0, ev0, _ = measure()

        warm.configure(tempfile.mkdtemp(prefix="mon_ovh_warmstore_"))

        def ballast():
            import numpy as _np
            n = 0
            for i in range(6):
                wc = warm.WarmCallable(
                    lambda x, _i=i: jnp.tanh(x @ x.T).sum() + _i,
                    {"kind": "overhead_ballast", "i": i},
                    label="ballast%d" % i)
                wc.ensure(jax.ShapeDtypeStruct((128, 128), _np.float32))
                n += 1
            return n

        warm.register_precompiler(ballast, name="overhead_ballast")
        t = warm.notify_commit(0)
        dt1, spans1, ev1, ev_compile = measure()
        alive_during = t is not None and t.is_alive()
        warm.join_background(60)
        precompiled = warm.stats()["precompiled"]

        out = {"step_ms_base": round(dt0 * 1e3, 4),
               "step_ms_precompile": round(dt1 * 1e3, 4),
               "spans_per_step_base": round(spans0, 3),
               "spans_per_step_precompile": round(spans1, 3),
               "events_per_step_base": round(ev0, 3),
               "events_per_step_precompile": round(ev1, 3),
               "precompile_extra_spans_per_step": round(spans1 - spans0, 3),
               "precompile_extra_events_per_step": round(ev1 - ev0, 3),
               # the thread's own `compile` announcements: per RUN, not
               # per step — reported, not gated
               "precompile_compile_events": ev_compile,
               "precompile_thread_overlapped_loop": bool(alive_during),
               "precompiled": precompiled,
               "steps": steps}
        out["pass_warm_precompile_no_tracer_overhead"] = (
            precompiled >= 1
            and out["precompile_extra_spans_per_step"] <= 0
            and out["precompile_extra_events_per_step"] <= 0)
    finally:
        monitor.disable()
        warm.reset()
    return out


def memscope_probe(steps=120, samples=64):
    """MemScope attribution cost gate (<2% of step time): with owners
    registered (scope built-in + an explicit ballast provider), measure (a)
    the direct per-sample cost of the owner-classified memory snapshot, (b)
    that cost amortized at the production sampling cadence (the default
    ``memory_interval_s=2.0`` — attribution is TIME-sampled, never
    per-step), and (c) the end-to-end worst case: the monitored step loop
    with ``memory_interval_s=0`` (a full attribution walk EVERY step) vs
    the same loop sampling effectively never.  The gate bounds (b): what a
    production run actually pays."""
    import tempfile

    import jax.numpy as jnp
    from paddle_tpu import monitor
    from paddle_tpu.monitor import memscope

    exe, main_prog, feed, loss = build()
    ballast = [jnp.ones((64, 64), jnp.float32) for _ in range(16)]
    memscope.register_owner("ballast", lambda: ballast)
    try:
        # baseline: monitored loop, memory sampling pushed out of the run
        monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_ms_"),
                       memory_interval_s=1e9)
        dt_base = loop(exe, main_prog, feed, loss, steps)
        monitor.disable()
        # direct per-sample attribution cost (owners registered, the live
        # set includes the loop's params + ballast)
        mon = monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_ms_"),
                             memory_interval_s=1e9)
        exe.run(main_prog, feed=feed, fetch_list=[loss.name])
        t0 = time.perf_counter()
        for _ in range(samples):
            monitor.sample_memory(mon.registry, mon.timeline)
        sample_ms = (time.perf_counter() - t0) / samples * 1e3
        monitor.disable()
        # worst case: a sample (live_arrays walk + owner classify) on
        # EVERY step — deliberately pathological, reported not gated
        monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_ms_"),
                       memory_interval_s=0.0)
        dt_every = loop(exe, main_prog, feed, loss, steps)
        monitor.disable()
    finally:
        memscope.unregister_owner("ballast")
        monitor.disable()
    interval_ms = 2000.0      # the production default memory_interval_s
    out = {"step_ms_monitored": round(dt_base * 1e3, 4),
           "step_ms_sample_every_step": round(dt_every * 1e3, 4),
           "memscope_sample_ms": round(sample_ms, 4),
           # fraction of run wall the default-cadence sampler consumes:
           # one sample_ms every interval_ms of run — the gated number
           "memscope_overhead_pct": round(sample_ms / interval_ms * 100, 4),
           "memscope_every_step_pct": round(
               (dt_every / dt_base - 1) * 100, 2),
           "steps": steps, "samples": samples}
    out["pass_memscope_lt_2pct"] = out["memscope_overhead_pct"] < 2.0
    return out


def watchtower_probe(polls=150, probes=300):
    """Watchtower alert-engine + canary bookkeeping cost gate (<2% of
    wall at the production 1 Hz poll/probe cadence — the memscope
    amortization idiom).  Three numbers: (a) per-poll cost of a
    Watchtower running the fleet DEFAULT_RULES over a live 3-replica
    monitor root where every poll sees one fresh exposition rewrite plus
    timeline growth (the drill's steady state: incremental reparse, FSM
    advance, atomic state write); (b) the canary's per-probe BOOKKEEPING
    cost against a zero-wire stub router (allclose + gauges +
    skew/freshness reads — wire time belongs to the fleet, not the
    prober); (c) the disabled path: with no watchtower process running,
    the serving side's only new cost is the timeline flush-kind
    membership test per emit, microbenched against the router gate's
    1ms request floor (~0 by construction — alerting is pull-based)."""
    import tempfile

    from paddle_tpu.monitor import timeline as timeline_mod
    from paddle_tpu.monitor import watchtower as wt_mod
    from paddle_tpu.monitor.exporters import write_prometheus
    from paddle_tpu.monitor.registry import StatRegistry
    from paddle_tpu.serving.canary import CanaryProber

    root = tempfile.mkdtemp(prefix="mon_ovh_wt_")
    regs = {}
    for name in ("replica-0", "replica-1", "replica-2", "router"):
        os.makedirs(os.path.join(root, name), exist_ok=True)
        reg = regs[name] = StatRegistry()
        # a realistic exposition: the serve gauges the rules watch plus
        # a latency histogram (quantile samples) and the freshness gauge
        reg.gauge("serve.version").set(1)
        reg.gauge("online.train_wall").set(time.time())
        reg.counter("serve.engine.completed").incr()
        h = reg.histogram("fleet.request_ms" if name == "router"
                          else "serve.latency_ms")
        for i in range(64):
            h.observe(5.0 + (i % 7))
        write_prometheus(os.path.join(root, name, "metrics.prom"), reg)
    events_path = os.path.join(root, "router", "events.jsonl")

    wt = wt_mod.Watchtower(wt_mod.DEFAULT_RULES, out_dir=root)
    for name in sorted(regs):
        wt.add_prom_source(name, os.path.join(root, name, "metrics.prom"))
    wt.add_timeline_source("router", events_path)
    replicas = ["replica-0", "replica-1", "replica-2"]
    spent = 0.0
    with open(events_path, "a") as ef:
        wt.poll()                      # cold poll: first full parse
        for i in range(polls):
            name = replicas[i % 3]     # one replica re-exports per poll
            write_prometheus(os.path.join(root, name, "metrics.prom"),
                             regs[name])
            ef.write(json.dumps({"ts": time.time(), "ev": "step", "i": i})
                     + "\n")
            ef.flush()
            t0 = time.perf_counter()
            wt.poll()
            spent += time.perf_counter() - t0
    poll_ms = spent / polls * 1e3

    class _StubRouter:                 # zero-wire: bookkeeping only
        def __init__(self, want):
            self._want = want

        def submit(self, feed):
            return [self._want]

        def snapshot(self):
            return {r: {"version": 1} for r in range(3)}

    want = np.zeros((8, 4), np.float32)
    canary = CanaryProber(_StubRouter(want), [({"x": want}, want)],
                          registry=StatRegistry(), mon_root=root)
    canary.probe_once()                # warm
    t0 = time.perf_counter()
    for _ in range(probes):
        canary.probe_once()
    probe_ms = (time.perf_counter() - t0) / probes * 1e3

    n = 200_000
    flush_set = timeline_mod.FLUSH_EVENTS
    t0 = time.perf_counter()
    for _ in range(n):
        "step" in flush_set            # noqa: the per-emit flush test
    check_ns = (time.perf_counter() - t0) / n * 1e9

    interval_ms = 1000.0     # the drill/production cadence: 1 Hz each
    out = {"watchtower_poll_ms": round(poll_ms, 4),
           "canary_probe_ms": round(probe_ms, 4),
           # fraction of wall the 1 Hz poll + 1 Hz probe together
           # consume — the gated number
           "watchtower_overhead_pct": round(
               (poll_ms + probe_ms) / interval_ms * 100, 4),
           "timeline_flush_check_ns": round(check_ns, 1),
           # one membership test per timeline emit vs the 1ms request
           # floor: the whole serving-path cost of alerting being OFF
           "watchtower_disabled_pct": round(
               check_ns / (ROUTER_REQUEST_FLOOR_MS * 1e6) * 100, 6),
           # sanity: the probe measures the steady state, not a firing
           # storm (rules are shaped so nothing trips here)
           "watchtower_alerts": len(wt.alerts()),
           "polls": polls, "probes": probes}
    out["pass_watchtower_lt_2pct"] = out["watchtower_overhead_pct"] < 2.0
    out["pass_watchtower_disabled_lt_0_5pct"] = (
        out["watchtower_disabled_pct"] <= 0.5)
    return out


def router_dispatch_cost(n=10_000, reps=12):
    # n/reps shape: many SHORT windows, best-of — a virtualized tier-1
    # box sees multi-ms CPU-steal bursts that a long window cannot dodge
    # but a 30ms one usually can; the best rep is the steal-free cost
    """Per-dispatch cost of the FleetRouter hot path with NO tracer
    installed: one disabled ``trace.span`` (the wire's request hook),
    ``_pick`` over a 3-replica fleet (lattice-fit + load + round-robin
    scoring under the router lock, now including each replica's breaker
    ``admit`` check) and the LoadShield per-request bookkeeping the
    submit path added — the retry budget's lock-free earn, the shed
    policy's watermark verdict over the live mean load, and
    ``_note_reply`` with a latency sample (piggybacked-load fold-in plus
    the breaker's EWMA update).  Pure bookkeeping by design — no
    filesystem, no syscalls — so tracing-off dispatch must be
    effectively free next to any real request's wire+engine wall."""
    import tempfile

    from paddle_tpu.monitor import trace
    from paddle_tpu.serving.router import FleetRouter

    assert trace.active_tracer() is None
    router = FleetRouter(tempfile.mkdtemp(prefix="mon_ovh_router_"),
                         replicas=(0, 1, 2))
    # the hello-shape identity _pick scores on, minus the wire round trip
    # (the probe bounds the BOOKKEEPING, which is the hot path's design
    # contract: "pure bookkeeping, no I/O")
    for info in router._replicas.values():
        info.batch_buckets = (2, 4, 8)
        info.max_batch = 8
    reply = {"depth": 1, "inflight": 2, "version": 1}
    best = float("inf")
    # same measurement hygiene as disabled_span_cost: the 0.5% budget is
    # on the dispatch bookkeeping itself, so pause the cyclic GC for the
    # timed windows — a collection sweeping another test's garbage
    # mid-rep reads as a spurious gate breach on a loaded tier-1 box
    import gc

    was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            b = router.budget
            for i in range(n):
                # submit's inlined per-primary budget earn + shed guard
                t = b.tokens + b.ratio
                b.tokens = t if t < b.cap else b.cap
                if router._shed_armed:
                    router.shed.verdict(1, router._mean_load())
                with trace.span("hostps.wire.request"):
                    info = router._pick(2 + (i & 3))
                router._note_reply(info, reply, ms=1.0)
            best = min(best, (time.perf_counter() - t0) / n)
    finally:
        if was_enabled:
            gc.enable()
    return best


# the request floor the router gate divides by: 1ms is ~50x below the
# CPU fleet's observed p50 (serve_bench --fleet), so <=0.5% of it is a
# deliberately conservative absolute bound (<=5us per dispatch)
ROUTER_REQUEST_FLOOR_MS = 1.0


def check_probe(steps=32):
    """Fast CI shape of the disabled-path gates: small program, short
    loop, the same formula as the full sweep (spans/step x the no-op
    span cost, as a fraction of the unmonitored step), PLUS the
    FleetRouter dispatch/reply hot path (_pick + _note_reply + the
    disabled wire span) bounded at 0.5% of a 1ms request floor — cheap
    enough for tier-1, while the full ``monitor_overhead.py`` run stays
    the perf-bench."""
    import tempfile

    from paddle_tpu import monitor

    monitor.disable()
    exe, main_prog, feed, loss = build(batch=64, hidden=128)
    dt_off = loop(exe, main_prog, feed, loss, steps)
    span_ns = disabled_span_cost(n=50_000)
    n_spans = spans_per_step(exe, main_prog, feed, loss, steps=16)
    monitor.disable()
    router_s = router_dispatch_cost()
    out = {"step_ms_off": round(dt_off * 1e3, 4),
           "trace_disabled_span_ns": round(span_ns * 1e9, 1),
           "trace_spans_per_step": round(n_spans, 2),
           "trace_disabled_pct": round(
               n_spans * span_ns / dt_off * 100, 4),
           "router_dispatch_us": round(router_s * 1e6, 3),
           "router_dispatch_pct": round(
               router_s / (ROUTER_REQUEST_FLOOR_MS * 1e-3) * 100, 4),
           "steps": steps}
    out["pass_trace_disabled_lt_0_5pct"] = out["trace_disabled_pct"] <= 0.5
    out["pass_router_dispatch_lt_0_5pct"] = (
        out["router_dispatch_pct"] <= 0.5)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--reps", type=int, default=5,
                    help="take the best of N reps per mode (noise floor)")
    ap.add_argument("--check", action="store_true",
                    help="fast CI gate: exit 0 iff the disabled-tracer "
                         "path costs <= 0.5%% of step-loop time AND the "
                         "FleetRouter dispatch/reply bookkeeping costs "
                         "<= 0.5%% of a 1ms request floor (small "
                         "program, short loop — the tier-1 smoke shape)")
    ap.add_argument("--warm", action="store_true",
                    help="probe the WarmStart background pre-compile "
                         "thread for tracer-visible step overhead")
    ap.add_argument("--memscope", action="store_true",
                    help="probe the MemScope owner-attribution sampler: "
                         "per-sample cost, cadence-amortized overhead "
                         "(the <2%% gate), and the sample-every-step "
                         "worst case")
    ap.add_argument("--watchtower", action="store_true",
                    help="probe the Watchtower alert engine + canary "
                         "bookkeeping: per-poll and per-probe cost "
                         "amortized at the 1 Hz production cadence (the "
                         "<2%% gate) and the disabled-path flush-kind "
                         "check (~0); exits 0/2 on the gates")
    args = ap.parse_args()

    if args.check:
        out = check_probe(steps=max(8, min(args.steps, 48)))
        print(json.dumps(out))
        return 0 if (out["pass_trace_disabled_lt_0_5pct"]
                     and out["pass_router_dispatch_lt_0_5pct"]) else 2
    if args.warm:
        print(json.dumps(warm_precompile_probe(steps=max(8, args.steps // 6))))
        return
    if args.memscope:
        print(json.dumps(memscope_probe(steps=max(16, args.steps // 3))))
        return
    if args.watchtower:
        out = watchtower_probe(polls=max(32, args.steps // 2),
                               probes=args.steps)
        print(json.dumps(out))
        return 0 if (out["pass_watchtower_lt_2pct"]
                     and out["pass_watchtower_disabled_lt_0_5pct"]) else 2

    import tempfile

    from paddle_tpu import monitor

    exe, main_prog, feed, loss = build()
    best = {}
    # interleave modes across reps so drift hits all modes equally
    for _ in range(args.reps):
        for mode in ("off", "on", "on_fleetscope", "on_sentinel",
                     "on_no_trace", "on_every_step"):
            if mode == "off":
                monitor.disable()
            else:
                every = 1 if mode == "on_every_step" else 8
                monitor.enable(tempfile.mkdtemp(prefix="mon_ovh_"),
                               device_time_every=every,
                               tracing=(mode != "on_no_trace"),
                               # "on" pins phases OFF so the historical 2%
                               # gate keeps its pre-FleetScope meaning;
                               # on_fleetscope measures the new default
                               # (phase accounting enabled)
                               phases=(mode != "on"))
                if mode == "on_sentinel":
                    # default config: halt policy, sampled bundle readback
                    # — the shape every production run pays
                    from paddle_tpu.monitor import sentinel as sentinel_mod

                    sentinel_mod.enable()
            dt = loop(exe, main_prog, feed, loss, args.steps)
            best[mode] = min(best.get(mode, float("inf")), dt)
    monitor.disable()

    span_ns = disabled_span_cost()
    n_spans = spans_per_step(exe, main_prog, feed, loss)
    monitor.disable()

    out = {"step_ms_off": round(best["off"] * 1e3, 4),
           "step_ms_on": round(best["on"] * 1e3, 4),
           "step_ms_on_fleetscope": round(
               best["on_fleetscope"] * 1e3, 4),
           "step_ms_on_sentinel": round(best["on_sentinel"] * 1e3, 4),
           "step_ms_on_no_trace": round(best["on_no_trace"] * 1e3, 4),
           "step_ms_on_every_step": round(best["on_every_step"] * 1e3, 4),
           "overhead_pct": round(
               (best["on"] / best["off"] - 1) * 100, 2),
           # FleetScope phase accounting rides the monitored loop; its
           # fully-loaded cost vs monitor-off is what the 2% envelope
           # bounds
           "fleetscope_overhead_pct": round(
               (best["on_fleetscope"] / best["off"] - 1) * 100, 2),
           # the sentinel gate compares against the MONITORED loop (with
           # phase accounting, the same config the sentinel mode runs):
           # the bundle rides an already-telemetered step, and that
           # marginal cost is what the <1% budget bounds
           "sentinel_overhead_pct": round(
               (best["on_sentinel"] / best["on_fleetscope"] - 1) * 100, 2),
           "overhead_no_trace_pct": round(
               (best["on_no_trace"] / best["off"] - 1) * 100, 2),
           "overhead_every_step_pct": round(
               (best["on_every_step"] / best["off"] - 1) * 100, 2),
           "trace_disabled_span_ns": round(span_ns * 1e9, 1),
           "trace_spans_per_step": round(n_spans, 2),
           # disabled-path tracer cost: instrumentation that stays in the
           # code when nothing is recording
           "trace_disabled_pct": round(
               n_spans * span_ns / best["off"] * 100, 4),
           "steps": args.steps}
    out["pass_lt_2pct"] = out["overhead_pct"] < 2.0
    out["pass_trace_disabled_lt_0_5pct"] = out["trace_disabled_pct"] <= 0.5
    out["pass_sentinel_lt_1pct"] = out["sentinel_overhead_pct"] < 1.0
    out["pass_fleetscope_lt_2pct"] = out["fleetscope_overhead_pct"] < 2.0
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
