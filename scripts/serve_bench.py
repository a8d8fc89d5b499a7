#!/usr/bin/env python
"""serve_bench: the ServeLoop receipts — static vs continuous batching.

Drives an open-loop mixed-size request generator (a burst of small CTR
scoring requests with periodic large ones — the head-of-line-blocking
shape production traffic actually has) against the serving engine in BOTH
modes over one exported artifact:

- ``static``: the reference's thread-pool shape — one request at a time,
  run to completion; a 256-row request ahead of a 2-row one makes the
  small one wait (that IS the baseline's p99);
- ``continuous``: per-step admit/evict on the pre-compiled bucket lattice
  — small requests ride the very next step alongside the giant's rows.

Both modes serve sparse CTR lookups through a READ-ONLY HostPS embedding
(HotRowCache in front, zero table writes — asserted) and pre-compile every
lattice point at start through the WarmStart store, with the strict
RecompileDetector armed: ``--check`` fails on a single steady-state
recompile.

Gates (--check):
  1. correctness: sampled request results match a direct predictor run
     (allclose; within-bucket padding is bit-exact and unit-tested —
     different buckets may differ in the final ulp, like any batching
     server);
  2. zero recompiles in both modes (strict detector green) and every
     lattice point pre-compiled;
  3. read-only lookup never wrote the table (rows_initialized unchanged);
  4. continuous beats static on p99 latency;
  5. continuous QPS >= 0.9x static (padding waste reclaimed, not traded).

Emits one JSON metric line per mode (``serve_static`` /
``serve_continuous`` with p50_ms/p99_ms/qps/occupancy) that
``perf_ledger.py`` trends from the committed ``SERVE_r*.json`` snapshots;
``--record OUT.json`` writes the snapshot file itself.

``--trace`` runs the TraceMesh leg instead: a TWO-process serve — this
process runs the continuous engine with tracing on, its CTR lookups
routed through a ``ShardRouter`` to a HostPS shard-server subprocess
(also traced) — then fuses both monitor dirs with
``scripts/trace_merge.py`` and asserts the merged chrome trace carries
cross-process flow arrows from the serving request's wire pull into the
shard server's ``hostps.wire.serve`` span (serving request -> HostPS wire
pull -> reply, one connected picture in Perfetto).

Usage:
    python scripts/serve_bench.py --check [--smoke] [--record SERVE_rNN.json]
    python scripts/serve_bench.py --trace --check
"""

import argparse
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_OUT_LINES = []


def say(line):
    print(line)
    sys.stdout.flush()
    _OUT_LINES.append(line)


def build_artifact(workdir, rng):
    """Train-a-little and export the serving model: dense x[12] + looked-up
    emb[16] -> fc(16, relu) -> score[1], exported with a symbolic batch
    dim so ONE artifact serves every lattice bucket."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.inference import export_inference_model

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data("x", shape=[12], dtype="float32")
        ev = fluid.layers.data("emb", shape=[16], dtype="float32")
        yv = fluid.layers.data("y", shape=[1], dtype="float32")
        cat = fluid.layers.concat([xv, ev], axis=1)
        h = fluid.layers.fc(cat, size=16, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, yv))
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)
    for _ in range(3):
        exe.run(main, feed={"x": rng.rand(32, 12).astype("f4"),
                            "emb": rng.rand(32, 16).astype("f4"),
                            "y": rng.rand(32, 1).astype("f4")},
                fetch_list=[loss])
    fluid.io.save_inference_model(workdir, ["x", "emb"], [pred], exe,
                                  main_program=main)
    export_inference_model(workdir, feed_shapes={"x": (4, 12),
                                                 "emb": (4, 16)},
                           poly_batch=True)
    return workdir


def request_trace(n_requests, large_rows, rng, vocab, ids_per_row=4):
    """Deterministic open-loop trace: mostly 1-4 row requests, every 5th a
    ``large_rows`` one — the mixed-size distribution the continuous mode
    exists for.  Large requests land EARLY in each cycle so the static
    baseline's head-of-line blocking is exercised, not dodged."""
    import numpy as np

    trace = []
    for i in range(n_requests):
        rows = large_rows if i % 5 == 1 else int(rng.randint(1, 5))
        trace.append({
            "x": rng.rand(rows, 12).astype("f4"),
            "ids": rng.randint(0, vocab, size=(rows, ids_per_row)
                               ).astype("i8")})
    return trace


def make_lookup(vocab, dim, cache_slots, seed=7):
    from paddle_tpu.hostps.service import HostPSEmbedding
    from paddle_tpu.hostps.table import HostSparseTable
    from paddle_tpu.serving import CTRLookup

    table = HostSparseTable(vocab, dim, seed=seed, name="serve_ctr")
    emb = HostPSEmbedding(table, cache_slots=cache_slots, read_only=True)
    return table, emb, CTRLookup(emb, "ids", out_name="emb")


def run_mode(mode, artifact_dir, lattice, lookup, trace, timeout):
    from paddle_tpu.inference import load_exported_model
    from paddle_tpu.serving import ServeEngine

    ep = load_exported_model(artifact_dir)
    eng = ServeEngine(
        ep, lattice,
        feed_spec={"x": ((12,), "float32"), "emb": ((16,), "float32")},
        lookups=[lookup], mode=mode, queue_capacity=len(trace) + 2,
        name="serve_%s" % mode)
    t0 = time.perf_counter()
    eng.start()
    precompile_s = time.perf_counter() - t0
    reqs = [eng.submit({"x": t["x"], "ids": t["ids"]}) for t in trace]
    for r in reqs:
        r.result(timeout=timeout)
    summary = eng.stop()
    summary["precompile_s"] = round(precompile_s, 3)
    summary["precompile_sources"] = eng.precompile_sources
    return summary, reqs, ep


def verify_sample(reqs, trace, artifact_dir, lookup, k=12):
    """Sampled correctness: engine result vs a direct (exact-shape)
    predictor run over the same rows — every size class covered."""
    import numpy as np
    from paddle_tpu.inference import load_exported_model

    ref = load_exported_model(artifact_dir)
    idx = sorted(set(list(range(min(k, len(reqs))))
                     + [i for i in range(len(reqs))
                        if trace[i]["x"].shape[0] > 8][:2]))
    for i in idx:
        feed = {"x": trace[i]["x"], "ids": trace[i]["ids"]}
        feed = lookup(dict(feed))
        (want,) = ref.run(feed)
        (got,) = (r.result() for r in [reqs[i]])
        if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
            return False, i
    return True, None


def shard_worker(args):
    """The ``--shard-worker`` subprocess entry: serve one shard of the
    ``serve_ctr`` table over the file wire, tracing on, until the driver
    drops the DONE marker.  Its monitor dir's trace.json is one of the
    per-process traces the driver fuses.  Defaults keep the ``--trace``
    leg's shape (shard 1 of world 2); the ``--fleet`` leg runs it as the
    whole-table owner (world 1, shard 0) with a deliberately slow inbox
    poll, making every replica's lookup a latency-bound remote pull."""
    from paddle_tpu import monitor
    from paddle_tpu.hostps.shard_router import ShardServer
    from paddle_tpu.hostps.table import HostSparseTable
    from paddle_tpu.parallel.rules import hostps_row_ranges

    monitor.enable(args.mon_dir, tracing=True)
    rr = hostps_row_ranges(args.world, args.vocab)[args.shard]
    table = HostSparseTable(args.vocab, args.dim, seed=7, name="serve_ctr",
                            row_range=rr)
    srv = ShardServer(table, args.wire_dir, args.shard, poll=args.poll)
    srv.start(restore=False)
    done = os.path.join(args.wire_dir, "BENCH_DONE")
    deadline = time.time() + args.timeout
    while not os.path.exists(done) and time.time() < deadline:
        time.sleep(0.05)
    srv.stop()
    monitor.disable()
    return 0


def trace_leg(args):
    """The TraceMesh receipts: serve continuously across TWO traced
    processes (engine here, HostPS shard server in a subprocess), fuse the
    per-process traces with trace_merge.py, and assert the merged chrome
    trace connects serving request -> wire pull -> shard reply with
    cross-process flow arrows."""
    import subprocess

    import numpy as np
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.hostps.shard_router import (ShardRouter,
                                                ShardedHostPSEmbedding)
    from paddle_tpu.hostps.table import HostSparseTable
    from paddle_tpu.parallel.rules import hostps_row_ranges
    from paddle_tpu.serving import BucketLattice, CTRLookup

    rng = np.random.RandomState(0)
    lattice = BucketLattice([2, 4, 8])
    n_requests = args.requests or 24
    vocab, dim, cache_slots = 512, 4, 64
    workdir = tempfile.mkdtemp(prefix="serve_bench_trace_")
    wire = os.path.join(workdir, "wire")
    os.makedirs(wire)
    mon_serve = os.path.join(workdir, "mon-serve")
    mon_shard = os.path.join(workdir, "mon-shard")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    worker = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--shard-worker",
         "--wire-dir", wire, "--mon-dir", mon_shard,
         "--vocab", str(vocab), "--dim", str(dim),
         "--timeout", str(args.timeout)], env=env)
    say("serve_bench[trace]: two-process leg: serving engine (this pid) + "
        "HostPS shard worker pid %d, wire=%s" % (worker.pid, wire))

    failures = []
    monitor.enable(mon_serve, tracing=True)
    try:
        build_artifact(workdir, rng)
        trace = request_trace(n_requests, 4 * lattice.max_batch, rng, vocab)
        local = HostSparseTable(vocab, dim, seed=7, name="serve_ctr",
                                row_range=hostps_row_ranges(2, vocab)[0])
        router = ShardRouter(local, world=2, rank=0, wire_dir=wire)
        router.connect(timeout=60.0)
        emb = ShardedHostPSEmbedding(router, cache_slots=cache_slots)

        class _ReadOnlyView:
            # CTRLookup's no-write gate, satisfied bench-side: this leg
            # only ever pulls, but HostPSEmbedding reserves read_only=True
            # for local tables (its fast path speaks a pull signature the
            # router does not), so the serving engine gets a pull-only
            # facade over the sharded embedding instead
            read_only = True
            dim = emb.dim

            def pull(self, ids):
                return emb.pull(ids)

        lookup = CTRLookup(_ReadOnlyView(), "ids", out_name="emb")
        summary, _reqs, _ep = run_mode("continuous", workdir, lattice,
                                       lookup, trace, args.timeout)
        if summary["completed"] != n_requests:
            failures.append("completed %d of %d requests"
                            % (summary["completed"], n_requests))
        say("serve_bench[trace]: continuous p50=%.2fms p99=%.2fms "
            "qps=%.1f over the wire (platform=%s)"
            % (summary["p50_ms"], summary["p99_ms"], summary["qps"],
               jax.default_backend()))
    finally:
        monitor.disable()
        open(os.path.join(wire, "BENCH_DONE"), "w").close()
    worker.wait(timeout=60)
    if worker.returncode != 0:
        failures.append("shard worker exited rc=%d" % worker.returncode)

    merged_path = os.path.join(workdir, "merged_trace.json")
    tm = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "trace_merge.py"),
         "--dir", mon_serve, "--dir", mon_shard, "--out", merged_path],
        env=env, capture_output=True, text=True, timeout=120)
    for line in (tm.stdout or "").splitlines():
        say("serve_bench[trace]: %s" % line)
    if tm.returncode != 0:
        failures.append("trace_merge rc=%d: %s"
                        % (tm.returncode, (tm.stderr or "").strip()[-400:]))
    else:
        with open(merged_path) as f:
            events = json.load(f)["traceEvents"]
        spans = [e for e in events if e.get("ph") == "X"]
        pids = sorted({e["pid"] for e in spans})
        flows = sum(1 for e in events if e.get("ph") in ("s", "f"))
        n_req = sum(1 for e in spans if e["name"] == "serve.request")
        n_srv = sum(1 for e in spans if e["name"] == "hostps.wire.serve")
        if len(pids) < 2:
            failures.append("merged trace covers pids %s — expected both "
                            "processes" % pids)
        if flows < 1:
            failures.append("no cross-process flow arrows in the merged "
                            "trace (wire link lost)")
        if not n_req:
            failures.append("no serve.request spans in the merged trace")
        if not n_srv:
            failures.append("no hostps.wire.serve spans in the merged "
                            "trace (shard side untraced)")
        say("serve_bench[trace]: merged %d spans across pids %s: %d "
            "serve.request, %d hostps.wire.serve, %d flow arrows -> %s"
            % (len(spans), pids, n_req, n_srv, flows, merged_path))

    rc = 0
    if failures:
        rc = 1
        for f in failures:
            say("serve_bench[trace]: FAIL %s" % f)
    elif args.check:
        say("serve_bench[trace]: PASS (serving request -> HostPS wire "
            "pull -> reply fused into one Perfetto trace)")
    return rc


def _fleet_drive(router, clients, seconds, vocab, samples=None,
                 mid_hook=None):
    """Closed-loop fleet load: ``clients`` threads each submit-and-wait in
    a loop for ``seconds``.  Closed-loop is the honest shape for a scaling
    proof — offered load rises only when the fleet actually absorbs it, so
    aggregate QPS IS capacity, not an arrival-rate echo."""
    import threading

    import numpy as np

    lock = threading.Lock()
    lats, errors = [], []
    stop_at = [float("inf")]

    def one(cid):
        crng = np.random.RandomState(1000 + cid)
        while time.perf_counter() < stop_at[0]:
            # 2/4-row mix: enough size variety to exercise bucket-fit
            # routing, deterministic enough that per-step bucket fill is
            # identical in the 1- and 3-replica legs (the scaling proof
            # must compare step RATES, not occupancy luck)
            rows = int(crng.choice((2, 4)))
            feed = {"x": crng.rand(rows, 12).astype("f4"),
                    "ids": crng.randint(0, vocab, (rows, 4)).astype("i8")}
            t0 = time.perf_counter()
            try:
                outs = router.submit(feed)
            except Exception as e:                  # a drop: gate trips
                with lock:
                    errors.append("client %d: %r" % (cid, e))
                return
            ms = (time.perf_counter() - t0) * 1e3
            with lock:
                lats.append(ms)
                if samples is not None and len(samples) < 8:
                    samples.append((feed, outs))

    threads = [threading.Thread(target=one, args=(c,), daemon=True)
               for c in range(clients)]
    t0 = time.perf_counter()
    stop_at[0] = t0 + seconds
    for t in threads:
        t.start()
    if mid_hook is not None:
        time.sleep(seconds * 0.5)
        try:
            mid_hook()
        except Exception as e:
            with lock:
                errors.append("mid_hook: %r" % (e,))
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    arr = np.asarray(lats) if lats else np.zeros(1)
    return {"completed": len(lats), "errors": errors,
            "wall_s": round(wall, 2),
            "qps": round(len(lats) / wall, 1),
            "p50_ms": round(float(np.percentile(arr, 50)), 2),
            "p99_ms": round(float(np.percentile(arr, 99)), 2)}


def fleet_leg(args):
    """The FleetServe receipts: 1 -> 3 ServeEngine replica processes
    behind a FleetRouter, one shared WarmStart store, sparse rows pulled
    from a read-only ShardPS owner process.  Measures aggregate QPS with
    the same closed-loop client set against 1 then 3 replicas and gates
    scaling >= 0.8x linear, zero fleet-wide recompiles, warm-store sharing
    (replica 1/2 deserialize what replica 0 compiled), zero drops, a
    rolling version swap, and the autoscale signal in both directions."""
    import subprocess

    import numpy as np
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.inference import load_exported_model
    from paddle_tpu.serving import FleetRouter
    from paddle_tpu.serving.fleet import FleetManager, autoscale_signal

    rng = np.random.RandomState(0)
    vocab, dim = 512, 4
    leg_s = args.leg_secs or (4.0 if args.smoke else 10.0)
    clients = args.fleet_clients or 16
    workdir = tempfile.mkdtemp(prefix="serve_bench_fleet_")
    fleet_wire = os.path.join(workdir, "fleet-wire")
    ps_wire = os.path.join(workdir, "ps-wire")
    mon_root = os.path.join(workdir, "monitor")
    mon = monitor.enable(os.path.join(mon_root, "router"))
    say("serve_bench[fleet]: clients=%d leg=%.0fs ps_poll=%.0fms "
        "platform=%s" % (clients, leg_s, args.ps_poll * 1e3,
                         jax.default_backend()))
    build_artifact(workdir, rng)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_WARM_SYNC_PUBLISH="1")
    worker = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--shard-worker",
         "--wire-dir", ps_wire, "--mon-dir", os.path.join(mon_root, "shard"),
         "--vocab", str(vocab), "--dim", str(dim),
         "--world", "1", "--shard", "0", "--poll", str(args.ps_poll),
         "--timeout", str(args.timeout)], env=env)
    say("serve_bench[fleet]: ShardPS owner pid %d serves the whole "
        "%d-row table read-only — replicas hold NO embedding copy"
        % (worker.pid, vocab))
    mgr = FleetManager(
        fleet_wire, workdir, mon_root,
        feeds=["x:12:float32", "emb:16:float32"], buckets="2,4,8",
        workers=8, queue_capacity=512,
        ctr={"wire_dir": ps_wire, "world": 1, "vocab": vocab, "dim": dim,
             "ids": "ids", "out": "emb"}, env=env)
    # 4ms reply poll: on a one-core host the router's 16 waiter threads
    # are pure GIL+syscall overhead while they poll — halving the wakeup
    # rate costs ~2ms latency against a ~50ms pull floor
    router = FleetRouter(fleet_wire, poll=0.004)
    failures, samples, load_sig = [], [], {}
    res1 = res3 = None
    stats = {}

    # Watchtower false-positive gate (ISSUE 19): the whole clean bench —
    # spawns, saturation, rolling swap — runs under live alerting and
    # must end with ZERO fired alerts.  Replica liveness via exposition
    # absence; client-visible p99 against a generous 2s SLO a healthy
    # fleet never approaches.
    import threading as _threading

    from paddle_tpu.monitor import watchtower as _wtm

    wt = _wtm.Watchtower(
        [{"name": "replica_dead", "kind": "absence",
          "metric": "paddle_tpu_serve_version",
          "stale_s": 5.0, "source": "replica-*"},
         {"name": "p99_burn", "kind": "burn_rate",
          "metric": 'paddle_tpu_fleet_request_ms{quantile="0.99"}',
          "op": ">", "value": 2000.0, "objective": 0.9,
          "short_s": 2.0, "long_s": 8.0, "factor": 1.0,
          "source": "router"}],
        out_dir=os.path.join(mon_root, "router"), timeline=mon.timeline)
    wt.add_prom_source("router",
                       os.path.join(mon_root, "router", "metrics.prom"))
    for rid in (0, 1, 2):
        wt.add_prom_source(
            "replica-%d" % rid,
            os.path.join(mon_root, "replica-%d" % rid, "metrics.prom"))
    wt.add_timeline_source(
        "router", os.path.join(mon_root, "router", "timeline.jsonl"))
    wt_fired = []
    wt_stop = _threading.Event()

    def _wt_loop():
        while not wt_stop.is_set():
            mon.export_prometheus()
            wt_fired.extend(wt.poll())
            wt_stop.wait(0.5)

    wt_thread = _threading.Thread(target=_wt_loop, name="wt-poll",
                                  daemon=True)
    wt_thread.start()

    try:
        t0 = time.perf_counter()
        mgr.spawn(0)
        mgr.wait_ready([0], timeout=args.timeout)
        router.add_replica(0)
        say("serve_bench[fleet]: replica 0 READY in %.1fs (cold: compiles "
            "the lattice, publishes the shared warm store)"
            % (time.perf_counter() - t0))

        res1 = _fleet_drive(router, clients, leg_s, vocab)
        say(json.dumps({"metric": "fleet_1", "serve": True, "unit": "ms",
                        "platform": jax.default_backend(), "replicas": 1,
                        "clients": clients, **{k: res1[k] for k in
                        ("qps", "p50_ms", "p99_ms", "completed")}}))

        t1 = time.perf_counter()
        mgr.spawn(1)
        mgr.spawn(2)
        mgr.wait_ready([1, 2], timeout=args.timeout)
        router.add_replica(1)
        router.add_replica(2)
        say("serve_bench[fleet]: replicas 1+2 READY in %.1fs (warm: "
            "deserialize replica 0's executables)"
            % (time.perf_counter() - t1))

        def _mid():
            router.publish_gauges()
            d, why, ml = autoscale_signal(router.snapshot(),
                                          min_replicas=1, max_replicas=4,
                                          high_load=3.0)
            load_sig.update(desired=d, reason=why, mean_load=round(ml, 2))

        res3 = _fleet_drive(router, clients, leg_s, vocab,
                            samples=samples, mid_hook=_mid)
        say(json.dumps({"metric": "fleet_3", "serve": True, "unit": "ms",
                        "platform": jax.default_backend(), "replicas": 3,
                        "clients": clients, **{k: res3[k] for k in
                        ("qps", "p50_ms", "p99_ms", "completed")}}))

        for rid in (0, 1, 2):
            try:
                stats[rid] = router.stats(rid)
            except Exception as e:
                failures.append("stats(%d) failed: %r" % (rid, e))

        # rolling deploy: flip every replica to version 2 (the artifact's
        # own state — call-compatible by construction) with zero drain
        router.rolling_swap(2, os.path.join(workdir, "__params__.npz"),
                            deadline=max(30.0, args.timeout / 4))
        post = _fleet_drive(router, 4, 1.5, vocab)
        versions = {}
        for rid in (0, 1, 2):
            try:
                versions[rid] = router.stats(rid).get("version")
            except Exception as e:
                failures.append("post-swap stats(%d): %r" % (rid, e))
        say("serve_bench[fleet]: rolling swap -> versions %s, %d requests "
            "served post-swap" % (versions, post["completed"]))

        # stop the watchtower BEFORE the autoscale retire: a retired
        # replica's frozen exposition is not an incident. Everything up
        # to here — cold spawn, saturation, kill-free swap — ran under
        # live alerting and must have fired nothing.
        wt_stop.set()
        wt_thread.join(timeout=10)
        fired = [a for st, a in wt_fired if st == "firing"]
        if fired:
            failures.append("watchtower fired on a clean run: %r"
                            % [(a["rule"], a["source"]) for a in fired])
        else:
            say("serve_bench[fleet]: zero alerts OK — %d watchtower polls "
                "over the full bench, 0 fired" % wt._polls)

        # LoadShield false-positive gate: the shield (inert defaults)
        # rode every dispatch of this clean bench and must have DONE
        # nothing — zero sheds, zero retry tokens spent, zero breaker
        # trips, zero degraded replies.  A shield that acts on a healthy
        # saturated fleet is a shield nobody can leave enabled.
        shield = router.shield_snapshot()
        if (shield["sheds"] or shield["budget"]["spent"]
                or shield["degraded"]
                or any(b["trips"] for b in shield["breakers"].values())):
            failures.append("the INERT shield acted on a clean run: %r"
                            % shield)
        else:
            say("serve_bench[fleet]: shield clean OK — 0 sheds, 0 retry "
                "tokens spent, 0 breaker trips, 0 degraded replies "
                "across %d dispatches" % shield["dispatched"])

        # autoscale, both directions: saturated -> scale-up signal was
        # sampled mid-leg; idle -> scale-down, actuated as a real retire
        router.stats_all()
        d_idle, why_idle, ml_idle = autoscale_signal(
            router.snapshot(), min_replicas=1, max_replicas=4,
            high_load=3.0)
        action, rid_r = mgr.apply_autoscale(router, d_idle)
        rc_retired = (mgr.procs[rid_r].returncode
                      if action == "retire" else None)
        say("serve_bench[fleet]: autoscale under load -> %s; idle -> "
            "desired=%d (%s) -> %s replica %s (rc=%s)"
            % (load_sig, d_idle, why_idle, action, rid_r, rc_retired))

        # graceful drain of the remainder (retire is the clean path; the
        # SIGKILL path is chaos_drill --fleet's job)
        for rid in list(router.replica_ids()):
            router.retire(rid)
            mgr.wait(rid, timeout=30.0)
    finally:
        wt_stop.set()
        wt_thread.join(timeout=10)
        monitor.disable()
        os.makedirs(ps_wire, exist_ok=True)
        open(os.path.join(ps_wire, "BENCH_DONE"), "w").close()
        mgr.stop_all()
    worker.wait(timeout=60)

    # sampled correctness: fleet answer vs a direct local run over the
    # SAME deterministic table (seed-addressed rows, both sides)
    table, emb, lookup = make_lookup(vocab, dim, cache_slots=0)
    ref = load_exported_model(workdir)
    for i, (feed, outs) in enumerate(samples[:6]):
        (want,) = ref.run(lookup(dict(feed)))
        if not np.allclose(outs[0], want, rtol=1e-5, atol=1e-6):
            failures.append("sample %d: fleet result mismatch" % i)

    # -- gates -------------------------------------------------------------
    if len(stats) < 3:
        failures.append("only %d/3 replicas answered stats" % len(stats))
    qps1, qps3 = res1["qps"], res3["qps"]
    scaling = round(qps3 / qps1, 2) if qps1 else 0.0
    if qps3 < 0.8 * 3 * qps1:
        failures.append(
            "aggregate qps %.1f with 3 replicas is %.2fx of the "
            "1-replica %.1f — below the 0.8x-linear (2.4x) gate"
            % (qps3, scaling, qps1))
    for rid, s in stats.items():
        if s["recompiles"]:
            failures.append("replica %d: %d steady-state recompiles"
                            % (rid, s["recompiles"]))
        if s.get("new_compiled_sigs"):
            failures.append("replica %d: %d signatures compiled after "
                            "start" % (rid, s["new_compiled_sigs"]))
    if stats:
        cold = stats.get(0, {})
        for rid in (1, 2):
            warm = stats.get(rid, {})
            src = warm.get("precompile_sources", {})
            if src.get("compiled"):
                failures.append(
                    "replica %d compiled %d lattice points itself — the "
                    "shared warm store should have served them"
                    % (rid, src["compiled"]))
            if (cold.get("precompile_s") and warm.get("precompile_s")
                    and warm["precompile_s"] > 0.5 * cold["precompile_s"]):
                failures.append(
                    "replica %d precompile %.2fs not << replica 0's "
                    "%.2fs — warm sharing unproven"
                    % (rid, warm["precompile_s"], cold["precompile_s"]))
    for leg, res in (("1-replica", res1), ("3-replica", res3)):
        for err in res["errors"]:
            failures.append("%s leg dropped a request: %s" % (leg, err))
        if not res["completed"]:
            failures.append("%s leg completed zero requests" % leg)
    if set(versions.values()) != {2}:
        failures.append("rolling swap incomplete: versions %s" % versions)
    if load_sig.get("desired", 0) <= 3:
        failures.append("saturated fleet did not signal scale-up: %s"
                        % load_sig)
    if d_idle >= 3:
        failures.append("idle fleet still wants %d replicas (%s)"
                        % (d_idle, why_idle))
    if action != "retire" or rc_retired != 0:
        failures.append("autoscale retire did not happen cleanly: "
                        "action=%s rc=%s" % (action, rc_retired))
    if worker.returncode != 0:
        failures.append("ShardPS owner exited rc=%d" % worker.returncode)

    say("serve_bench[fleet]: qps 1-replica=%.1f 3-replica=%.1f -> "
        "scaling %.2fx (gate >= 2.40x); p99 %.1fms -> %.1fms"
        % (qps1, qps3, scaling, res1["p99_ms"], res3["p99_ms"]))
    say(json.dumps({"metric": "fleet", "serve": True, "fleet": True,
                    "platform": jax.default_backend(), "replicas": 3,
                    "clients": clients, "qps_1": qps1, "qps_3": qps3,
                    "qps_scaling": scaling,
                    "recompiles": sum(s["recompiles"]
                                      for s in stats.values()),
                    "warm_precompile_s": {
                        str(r): stats.get(r, {}).get("precompile_s")
                        for r in (0, 1, 2)},
                    "dropped": sum(len(r["errors"])
                                   for r in (res1, res3)),
                    "swap_version": 2,
                    "autoscale": {"under_load": load_sig,
                                  "idle_desired": d_idle}}))

    rc = 0
    if failures:
        rc = 1
        for f in failures:
            say("serve_bench[fleet]: FAIL %s" % f)
    elif args.check:
        say("serve_bench[fleet]: PASS (3 replicas, %.2fx >= 2.40x QPS "
            "scaling, 0 recompiles fleet-wide, warm store shared, "
            "0 dropped, rolling swap + autoscale green)" % scaling)
    if args.record:
        shown = [a for a in (sys.argv[1:])
                 if not a.startswith("--record")
                 and a != os.path.basename(args.record)
                 and a != args.record]
        snap = {"cmd": "python scripts/serve_bench.py " + " ".join(shown),
                "rc": rc, "tail": "\n".join(_OUT_LINES) + "\n"}
        with open(args.record, "w") as f:
            json.dump(snap, f, indent=1)
        say("serve_bench[fleet]: recorded %s" % args.record)
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(description="ServeLoop bench + CI gate")
    ap.add_argument("--check", action="store_true",
                    help="gate p99/QPS/recompiles/read-only; exit 1 on "
                         "failure")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 budget: tiny lattice, short trace")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--record", default=None, metavar="OUT.json",
                    help="write the SERVE_r*.json snapshot (rc + stdout "
                         "tail, the BENCH_r* idiom)")
    ap.add_argument("--timeout", type=float, default=180.0)
    ap.add_argument("--trace", action="store_true",
                    help="TraceMesh leg: two traced processes (engine + "
                         "HostPS shard server), fused by trace_merge.py "
                         "with cross-process flow arrows asserted")
    ap.add_argument("--fleet", action="store_true",
                    help="FleetServe leg: router + replica processes over "
                         "one shared warm store and a read-only ShardPS "
                         "owner; gates 1->3 replica QPS scaling >= 0.8x "
                         "linear, fleet-wide zero recompiles, warm-store "
                         "sharing, a rolling swap, and autoscale signals")
    ap.add_argument("--fleet-clients", type=int, default=None,
                    help="closed-loop client threads for --fleet "
                         "(default 16, smoke 12)")
    ap.add_argument("--leg-secs", type=float, default=None,
                    help="--fleet: seconds per measured leg "
                         "(default 10, smoke 4)")
    ap.add_argument("--ps-poll", type=float, default=0.05,
                    help="--fleet: ShardPS owner inbox poll seconds — the "
                         "deliberate remote-pull latency floor that makes "
                         "replica throughput latency-bound, standing in "
                         "for the device step on this CPU-only host "
                         "(default 0.05)")
    ap.add_argument("--shard-worker", action="store_true",
                    help=argparse.SUPPRESS)    # subprocess entry
    ap.add_argument("--wire-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--mon-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--vocab", type=int, default=512,
                    help=argparse.SUPPRESS)
    ap.add_argument("--dim", type=int, default=4, help=argparse.SUPPRESS)
    ap.add_argument("--world", type=int, default=2, help=argparse.SUPPRESS)
    ap.add_argument("--shard", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--poll", type=float, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.shard_worker:
        return shard_worker(args)
    if args.trace:
        return trace_leg(args)
    if args.fleet:
        return fleet_leg(args)
    import numpy as np
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.serving import BucketLattice

    rng = np.random.RandomState(0)
    if args.smoke:
        lattice = BucketLattice([2, 4, 8])
        n_requests = args.requests or 30
        vocab, dim, cache_slots = 512, 4, 64
    else:
        lattice = BucketLattice([4, 8, 16, 32, 64])
        n_requests = args.requests or 150
        vocab, dim, cache_slots = 4096, 4, 256
    large_rows = 4 * lattice.max_batch

    workdir = tempfile.mkdtemp(prefix="serve_bench_")
    mon_dir = os.path.join(workdir, "monitor")
    monitor.enable(mon_dir)
    say("serve_bench: lattice=%s requests=%d large_rows=%d platform=%s"
        % (lattice.describe(), n_requests, large_rows,
           jax.default_backend()))
    build_artifact(workdir, rng)
    trace = request_trace(n_requests, large_rows, rng, vocab)
    table, emb, lookup = make_lookup(vocab, dim, cache_slots)
    rows_before = table.rows_initialized

    results = {}
    failures = []
    for mode in ("static", "continuous"):
        summary, reqs, _ep = run_mode(mode, workdir, lattice, lookup,
                                      trace, args.timeout)
        ok, bad = verify_sample(reqs, trace, workdir, lookup)
        if not ok:
            failures.append("%s: request %d result mismatch" % (mode, bad))
        results[mode] = summary
        rec = {"metric": "serve_%s" % mode, "serve": True, "mode": mode,
               "unit": "ms", "platform": jax.default_backend(),
               "requests": n_requests,
               "p50_ms": summary["p50_ms"], "p99_ms": summary["p99_ms"],
               "qps": summary["qps"],
               "latency_mean_ms": summary["latency_mean_ms"],
               "occupancy": summary.get("occupancy_avg"),
               "steps": summary["steps"], "rows": summary["rows"],
               "recompiles": summary["recompiles"],
               "lattice_points": summary["points"],
               "precompile_s": summary["precompile_s"],
               "cache_hit_rate": (round(emb.cache.hit_rate, 4)
                                  if emb.cache else None)}
        say(json.dumps(rec))

    st, ct = results["static"], results["continuous"]
    say("serve_bench: static    p50=%.2fms p99=%.2fms qps=%.1f "
        "occupancy=%.3f steps=%d"
        % (st["p50_ms"], st["p99_ms"], st["qps"],
           st.get("occupancy_avg", 0), st["steps"]))
    say("serve_bench: continuous p50=%.2fms p99=%.2fms qps=%.1f "
        "occupancy=%.3f steps=%d"
        % (ct["p50_ms"], ct["p99_ms"], ct["qps"],
           ct.get("occupancy_avg", 0), ct["steps"]))

    # -- gates ------------------------------------------------------------
    for mode, s in results.items():
        if s["recompiles"]:
            failures.append("%s: %d steady-state recompiles (strict gate "
                            "should have made this impossible)"
                            % (mode, s["recompiles"]))
        if s["points"] != len(lattice):
            failures.append("%s: %d/%d lattice points pre-compiled"
                            % (mode, s["points"], len(lattice)))
        if s["completed"] != n_requests:
            failures.append("%s: completed %d of %d requests"
                            % (mode, s["completed"], n_requests))
        if s.get("new_compiled_sigs"):
            failures.append("%s: %d signatures compiled AFTER the lattice "
                            "pre-compile — steady state met XLA"
                            % (mode, s["new_compiled_sigs"]))
    if table.rows_initialized != rows_before:
        failures.append(
            "read-only CTR lookup WROTE the table: rows_initialized "
            "%d -> %d" % (rows_before, table.rows_initialized))
    if not ct["p99_ms"] < st["p99_ms"]:
        failures.append(
            "continuous p99 %.2fms did not beat static %.2fms — the "
            "whole point of per-step admit/evict"
            % (ct["p99_ms"], st["p99_ms"]))
    if not ct["qps"] >= 0.9 * st["qps"]:
        failures.append("continuous qps %.1f fell below 0.9x static %.1f"
                        % (ct["qps"], st["qps"]))
    monitor.disable()

    rc = 0
    if args.check:
        if failures:
            rc = 1
            for f in failures:
                say("serve_bench: FAIL %s" % f)
        else:
            say("serve_bench: PASS (continuous p99 %.2fms < static "
                "%.2fms, qps %.1f vs %.1f, 0 recompiles, %d lattice "
                "points warm, read-only table untouched)"
                % (ct["p99_ms"], st["p99_ms"], ct["qps"], st["qps"],
                   len(lattice)))
    if args.record:
        shown = [a for a in (argv or sys.argv[1:])
                 if not a.startswith("--record")
                 and a != os.path.basename(args.record) and a != args.record]
        snap = {"cmd": "python scripts/serve_bench.py " + " ".join(shown),
                "rc": rc, "tail": "\n".join(_OUT_LINES) + "\n"}
        with open(args.record, "w") as f:
            json.dump(snap, f, indent=1)
        say("serve_bench: recorded %s" % args.record)
    return rc


if __name__ == "__main__":
    sys.exit(main())
