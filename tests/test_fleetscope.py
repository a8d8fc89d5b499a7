"""FleetScope (cross-rank performance attribution): clock-aligned fleet
traces, per-step phase ledgers, straggler attribution, the trace_summary
skew gate, the fleet_top phase/straggler columns, and the perf ledger over
the committed BENCH trajectory."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import fleetscope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")


@pytest.fixture(autouse=True)
def _fresh_monitor():
    monitor.disable()
    monitor.default_registry().reset()
    yield
    monitor.disable()
    monitor.default_registry().reset()


# -- phase ledger -----------------------------------------------------------

def test_phase_ledger_accumulate_and_drain():
    led = fleetscope.PhaseLedger()
    led.add("compute", 2.0)
    led.add("compute", 3.0)
    led.add("feed_stall", 1.5)
    led.add("fetch", 0.0)          # zero/negative contributions are dropped
    led.add("ckpt", -1.0)
    assert led.peek() == {"compute": 5.0, "feed_stall": 1.5}
    assert led.drain() == {"compute": 5.0, "feed_stall": 1.5}
    assert led.drain() == {}       # drained means drained


def test_phase_ledger_thread_safety():
    led = fleetscope.PhaseLedger()

    def adder():
        for _ in range(1000):
            led.add("compute", 1.0)

    threads = [threading.Thread(target=adder) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert led.drain() == {"compute": 4000.0}


def _build(hidden=16):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[hidden], dtype="float32")
        loss = fluid.layers.mean(
            fluid.layers.square(fluid.layers.fc(x, 1)))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.random.RandomState(0).rand(8, hidden).astype("f4")}
    return exe, main, feed, loss


def test_executor_steps_carry_phase_ledger(tmp_path):
    """A monitored executor loop writes a ``phases`` ledger into every
    steady-state step event (compute present), phase gauges + cumulative
    counters into the registry, and the cum counters reach metrics.prom
    (the fleet_top feed)."""
    exe, main, feed, loss = _build()
    out = str(tmp_path / "mon")
    mon = monitor.enable(out, device_time_every=1)
    for _ in range(4):
        exe.run(main, feed=feed, fetch_list=[loss.name])
    reg = mon.registry
    assert reg.gauge("monitor.phase.compute_ms").value > 0
    assert reg.gauge("monitor.phase.compute_ms_cum").value > 0
    monitor.disable()

    steps = monitor.read_events(os.path.join(out, "timeline.jsonl"), "step")
    steady = [e for e in steps if not e.get("compiled")]
    assert steady, "expected steady-state steps"
    assert all("phases" in e for e in steady)
    assert all(e["phases"].get("compute", 0) > 0 for e in steady)
    # feed conversion happened inline (no pipe in this loop)
    assert any("feed_stall" in e["phases"] for e in steady)
    prom = open(os.path.join(out, "metrics.prom")).read()
    assert "paddle_tpu_monitor_phase_compute_ms_cum" in prom
    totals = fleetscope.phase_totals_from_prom(
        monitor.parse_prometheus_text(prom))
    assert totals.get("compute", 0) > 0


def test_phase_gauge_zeroes_when_phase_absent(tmp_path):
    """The per-step gauge means THIS step: a ckpt phase paid two steps ago
    must read 0 on later steps (the cum total keeps the run sum)."""
    mon = monitor.enable(str(tmp_path / "mon"))
    mon.phase_add("compute", 2.0)
    mon.phase_add("ckpt", 500.0)
    mon.record_step(0, 5.0)
    assert mon.registry.gauge("monitor.phase.ckpt_ms").value == 500.0
    mon.phase_add("compute", 2.0)
    mon.record_step(1, 5.0)
    assert mon.registry.gauge("monitor.phase.ckpt_ms").value == 0
    assert mon.registry.gauge("monitor.phase.ckpt_ms_cum").value == 500.0
    assert mon.registry.gauge("monitor.phase.compute_ms_cum").value == 4.0
    monitor.disable()


def test_phases_opt_out(tmp_path):
    exe, main, feed, loss = _build()
    mon = monitor.enable(str(tmp_path / "mon"), phases=False)
    assert mon.phases is None
    for _ in range(2):
        exe.run(main, feed=feed, fetch_list=[loss.name])
    monitor.disable()
    steps = monitor.read_events(
        str(tmp_path / "mon" / "timeline.jsonl"), "step")
    assert steps and all("phases" not in e for e in steps)


def test_checkpoint_phases_ckpt_and_barrier(tmp_path):
    """A synchronous single-rank save attributes staging cost to ``ckpt``
    and the COMMIT poll to ``barrier_wait`` in the active session's
    ledger."""
    from paddle_tpu.parallel import checkpoint as ck

    mon = monitor.enable(str(tmp_path / "mon"))
    ck.save_checkpoint(str(tmp_path / "ck"),
                       {"w": np.arange(8, dtype=np.float32)}, step=1)
    acc = mon.phases.drain()
    assert acc.get("ckpt", 0) > 0
    assert "barrier_wait" in acc       # rank 0 polled (its own index)
    monitor.disable()


# -- clock anchors ----------------------------------------------------------

def test_epoch_beacon_publish_and_read(tmp_path):
    d = str(tmp_path / "fleet")
    rec = fleetscope.publish_epoch(d, rank=0)
    got = fleetscope.read_epoch(d, timeout=0.0)
    assert got["epoch_wall"] == rec["epoch_wall"]
    assert fleetscope.read_epoch(str(tmp_path / "nope"), timeout=0.0) is None


def test_measure_clock_skew_small_on_local_fs(tmp_path):
    skew = fleetscope.measure_clock_skew(str(tmp_path), rank=0)
    assert skew is not None and abs(skew) < 5000.0   # same host, same clock


def test_monitor_publishes_clock_json(tmp_path, monkeypatch):
    """Every session writes clock.json; in a (simulated) fleet the non-zero
    rank adopts rank 0's epoch beacon and measures its skew."""
    out = str(tmp_path / "mon")
    monitor.enable(out)
    monitor.disable()
    clk = fleetscope.read_clock(out)
    assert clk["world"] == 1 and clk["epoch_wall"] == clk["wall0"]

    # fleet shape: rank 0 publishes into the shared parent, rank 1 reads it
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    w0 = str(tmp_path / "fleet" / "rank-0")
    monitor.enable(w0)
    monitor.disable()
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    w1 = str(tmp_path / "fleet" / "rank-1")
    monitor.enable(w1)
    monitor.disable()
    c0, c1 = fleetscope.read_clock(w0), fleetscope.read_clock(w1)
    assert c0["rank"] == 0 and c1["rank"] == 1
    assert c1["epoch_wall"] == c0["epoch_wall"]     # ONE fleet epoch
    assert c1["clock_skew_ms"] is not None
    # the beacon + both ranks' anchors ride the chrome trace export
    tr = json.load(open(os.path.join(w1, "trace.json")))
    assert tr["otherData"]["epoch_wall"] == c0["epoch_wall"]
    assert tr["otherData"]["rank"] == 1


# -- synthetic n=2 fleet ----------------------------------------------------

EPOCH = 1700000000.0


def _write_worker(d, rank, step_s, stall_ms, offset_s=0.0, steps=20,
                  skew_ms=0.0):
    """One synthetic rank: timeline with phased step events, clock.json,
    and a minimal chrome trace — the monitor-session artifact layout."""
    os.makedirs(d, exist_ok=True)
    wall0 = EPOCH + offset_s
    with open(os.path.join(d, "timeline.jsonl"), "w") as f:
        for s in range(steps):
            f.write(json.dumps({
                "ev": "step", "step": s, "ts": wall0 + s * step_s,
                "host_ms": step_s * 1e3,
                "phases": {"compute": 8.0, "feed_stall": stall_ms},
            }) + "\n")
    json.dump({"rank": rank, "world": 2, "wall0": wall0,
               "epoch_wall": EPOCH, "clock_skew_ms": skew_ms,
               "fleet_dir": os.path.dirname(d)},
              open(os.path.join(d, "clock.json"), "w"))
    json.dump({"traceEvents": [
        {"ph": "M", "pid": 7, "tid": 0, "ts": 0, "name": "process_name",
         "args": {"name": "worker"}},
        {"ph": "X", "pid": 7, "tid": 1, "name": "executor.run",
         "cat": "executor", "ts": 1000.0, "dur": 500.0}],
        "displayTimeUnit": "ms",
        "otherData": {"pid": 7, "t0_unix": wall0, "epoch_wall": EPOCH,
                      "clock_skew_ms": skew_ms, "rank": rank}},
        open(os.path.join(d, "trace.json"), "w"))


def _fleet_dirs(tmp_path, slow_stall=7.0, slow_rate=0.016):
    w0 = str(tmp_path / "fleet" / "w0")
    w1 = str(tmp_path / "fleet" / "w1")
    _write_worker(w0, 0, 0.010, 1.0)
    # rank 1: slower steps, inflated feed_stall, a constant 0.3s startup
    # offset (must NOT read as skew), and a measured 50ms clock skew
    _write_worker(w1, 1, slow_rate, slow_stall, offset_s=0.3, skew_ms=50.0)
    return w0, w1


def test_fleet_attribution_names_rank_and_phase(tmp_path):
    w0, w1 = _fleet_dirs(tmp_path)
    events = {lab: monitor.read_events(os.path.join(d, "timeline.jsonl"))
              for lab, d in (("w0", w0), ("w1", w1))}
    clocks = {lab: fleetscope.read_clock(d)
              for lab, d in (("w0", w0), ("w1", w1))}
    fa = fleetscope.fleet_attribution(events, clocks=clocks)
    assert fa["straggler"]["rank"] == "w1"
    assert fa["straggler"]["phase"] == "feed_stall"
    assert fa["straggler"]["excess_ms"] == pytest.approx(6.0)
    assert fa["step_skew_ms"]["p50"] == pytest.approx(6.0, abs=1e-6)
    # 6ms spread over a 10/16ms pooled median step
    assert 0.3 < fa["step_skew_frac"] < 0.7
    assert fa["workers"]["w1"]["clock_skew_ms"] == 50.0
    assert fa["workers"]["w0"]["slowest_steps"] == 0


def test_fleet_attribution_needs_joinable_fleet(tmp_path):
    w0 = str(tmp_path / "solo")
    _write_worker(w0, 0, 0.010, 1.0)
    ev = monitor.read_events(os.path.join(w0, "timeline.jsonl"))
    assert fleetscope.fleet_attribution({"w0": ev}) is None
    # disjoint step ranges cannot join either
    w1 = str(tmp_path / "disjoint")
    _write_worker(w1, 1, 0.010, 1.0)
    ev1 = [dict(e, step=e["step"] + 100) for e in ev]
    assert fleetscope.fleet_attribution({"w0": ev, "w1": ev1}) is None


def test_duration_skew_ignores_constant_offset(tmp_path):
    """Two equal-speed ranks with a large startup offset are NOT skewed:
    the skew metric is duration-based."""
    w0 = str(tmp_path / "a")
    w1 = str(tmp_path / "b")
    _write_worker(w0, 0, 0.010, 1.0)
    _write_worker(w1, 1, 0.010, 1.0, offset_s=5.0)   # 500 steps "late"
    events = {"w0": monitor.read_events(os.path.join(w0, "timeline.jsonl")),
              "w1": monitor.read_events(os.path.join(w1, "timeline.jsonl"))}
    fa = fleetscope.fleet_attribution(events)
    assert fa["step_skew_ms"]["p50"] == pytest.approx(0.0, abs=1e-6)
    assert fa["step_skew_frac"] == pytest.approx(0.0, abs=1e-6)


def test_trace_summary_fleet_section_and_skew_gate(tmp_path):
    """The CLI end-to-end over a synthetic n=2 fleet: report names the
    straggler rank + phase and per-rank clock_skew_ms; the skew gate
    passes a loose budget, fails a tight one, and fails with a single
    timeline; --merge-trace writes ONE epoch-aligned Perfetto file."""
    w0, w1 = _fleet_dirs(tmp_path)
    script = os.path.join(SCRIPTS, "trace_summary.py")
    merged = str(tmp_path / "merged_trace.json")

    res = subprocess.run(
        [sys.executable, script, "--timeline", w0, "--timeline", w1,
         "--merge-trace", merged],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "STRAGGLER" in res.stdout and "w1" in res.stdout
    assert "feed_stall" in res.stdout
    assert "clock_skew_ms=50.0" in res.stdout

    # merged Perfetto file: both ranks as distinct pids on one epoch,
    # rank 1's track shifted by its offset MINUS its measured clock skew
    m = json.load(open(merged))
    pids = {e["pid"] for e in m["traceEvents"]}
    assert pids == {0, 1}
    assert m["otherData"]["epoch_wall"] == EPOCH
    w1meta = m["otherData"]["workers"]["w1"]
    assert w1meta["shift_us"] == pytest.approx(250000.0)   # 300ms - 50ms
    assert w1meta["clock_skew_ms"] == pytest.approx(50.0)
    xs = sorted(e["ts"] for e in m["traceEvents"] if e.get("ph") == "X")
    assert xs == [1000.0, 251000.0]

    def check(*extra):
        return subprocess.run(
            [sys.executable, script, "--check"] + list(extra),
            capture_output=True, text=True, timeout=60)

    loose = check("--timeline", w0, "--timeline", w1,
                  "--max-step-skew-frac", "1.0")
    assert loose.returncode == 0, loose.stdout + loose.stderr
    assert "straggler rank=w1 phase=feed_stall" in loose.stdout
    assert "clock_skew_ms[w1]=50.0" in loose.stdout
    summary = json.loads(loose.stdout.strip().splitlines()[-1])
    assert summary["fleet"]["straggler"]["rank"] == "w1"
    assert summary["workers"]["w1"]["clock_skew_ms"] == 50.0

    tight = check("--timeline", w0, "--timeline", w1,
                  "--max-step-skew-frac", "0.2")
    assert tight.returncode == 2
    assert "step_skew_frac" in tight.stderr

    solo = check("--timeline", w0, "--max-step-skew-frac", "1.0")
    assert solo.returncode == 2     # no fleet to join is a failure


def test_fleetscope_live_scanner_exports_gauges(tmp_path):
    """FleetScope.scan tails the rank timelines incrementally and exports
    fleet.straggler{rank} + skew gauges; HeartBeatMonitor drives it."""
    from paddle_tpu.monitor.registry import StatRegistry

    w0, w1 = _fleet_dirs(tmp_path)
    fs = fleetscope.FleetScope([w0, w1])
    reg = StatRegistry()
    attr = fs.scan(registry=reg)
    assert attr["straggler"]["rank"] == "1"      # labels default to index
    assert reg.gauge("fleet.straggler", rank="1").value == 1
    assert reg.gauge("fleet.straggler", rank="0").value == 0
    assert reg.gauge("fleet.step_skew_ms").value == pytest.approx(6.0)

    # incremental: append more steps to w0's timeline, rescan picks them up
    with open(os.path.join(w0, "timeline.jsonl"), "a") as f:
        for s in range(20, 25):
            f.write(json.dumps({"ev": "step", "step": s,
                                "ts": EPOCH + s * 0.010,
                                "host_ms": 10.0}) + "\n")
    attr2 = fs.scan(registry=reg)
    assert attr2["workers"]["0"]["steps"] == 25

    # a PARTIAL trailing line (the writer's buffered flush cadence) must
    # not be consumed: the completed remainder lands on the next scan
    rec = json.dumps({"ev": "step", "step": 25, "ts": EPOCH + 0.25,
                      "host_ms": 10.0})
    with open(os.path.join(w0, "timeline.jsonl"), "a") as f:
        f.write(rec[:20])
    fs.scan(registry=reg)
    with open(os.path.join(w0, "timeline.jsonl"), "a") as f:
        f.write(rec[20:] + "\n")
    attr3 = fs.scan(registry=reg)
    assert attr3["workers"]["0"]["steps"] == 26   # step 25 was NOT lost

    # heartbeat wiring: the monitor-side scan exports through the default
    # registry without touching the liveness verdicts
    from paddle_tpu.distributed.heartbeat import HeartBeatMonitor

    hb = str(tmp_path / "hb")
    os.makedirs(hb)
    for r in (0, 1):
        open(os.path.join(hb, "done-%d" % r), "w").write("0.0")
    hbm = HeartBeatMonitor(hb, 2, monitor_dirs=[w0, w1])
    status = hbm.worker_status()
    assert set(status.values()) == {"COMPLETED"}
    assert monitor.default_registry().gauge(
        "fleet.straggler", rank="1").value == 1


# -- fleet_top columns ------------------------------------------------------

def _write_prom(path, step, phases):
    lines = ["# TYPE paddle_tpu_monitor_health_step gauge",
             "paddle_tpu_monitor_health_step %d" % step,
             "paddle_tpu_monitor_health_loss 0.5",
             "paddle_tpu_monitor_health_steps_per_sec 10.0"]
    for ph, ms in phases.items():
        lines.append("# TYPE paddle_tpu_monitor_phase_%s_ms_cum gauge" % ph)
        lines.append("paddle_tpu_monitor_phase_%s_ms_cum %.1f" % (ph, ms))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def test_fleet_top_phase_and_straggler_columns(tmp_path):
    w0, w1 = tmp_path / "w0", tmp_path / "w1"
    w0.mkdir(), w1.mkdir()
    _write_prom(str(w0 / "metrics.prom"), step=120,
                phases={"compute": 900.0, "feed_stall": 50.0})
    # rank 1 is BEHIND with a dominant barrier_wait excess
    _write_prom(str(w1 / "metrics.prom"), step=100,
                phases={"compute": 900.0, "barrier_wait": 400.0})
    script = os.path.join(SCRIPTS, "fleet_top.py")
    args = [sys.executable, script, "--monitor-dir", str(w0),
            "--monitor-dir", str(w1), "--once", "--check"]
    res = subprocess.run(args, capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "top_phase" in res.stdout and "strag" in res.stdout
    assert "* barrier_wait" in res.stdout

    res = subprocess.run(args[:-1] + ["--json"], capture_output=True,
                         text=True, timeout=60)
    rows = json.loads(res.stdout.strip().splitlines()[-1])["ranks"]
    assert rows[0]["top_phase"] == "compute"
    assert rows[0]["straggler"] is None
    assert rows[1]["straggler"]["phase"] == "barrier_wait"


def test_attribute_from_totals_prefers_behind_rank():
    totals = {0: {"compute": 900.0, "feed_stall": 50.0},
              1: {"compute": 900.0, "feed_stall": 300.0}}
    # without step gauges: largest accounted total decides
    rank, phase, excess = fleetscope.attribute_from_totals(totals)
    assert (rank, phase) == (1, "feed_stall") and excess > 0
    # with step gauges: the rank furthest BEHIND decides even when its
    # accounted total is smaller
    rank, phase, _ = fleetscope.attribute_from_totals(
        {0: {"compute": 900.0, "ckpt": 500.0},
         1: {"compute": 1200.0}},
        steps_by_rank={0: 80, 1: 120})
    assert rank == 0 and phase == "ckpt"
    assert fleetscope.attribute_from_totals({0: {"compute": 1.0}}) is None


# -- perf ledger ------------------------------------------------------------

def test_perf_ledger_passes_steady_history(tmp_path):
    """THE acceptance gate: a five-snapshot BENCH trajectory whose worst
    step-to-step wobble is well under the 5% tolerance passes --check, and
    the table carries value + mfu + ceiling-relative rows."""
    bert = "bert_base_pretrain_tokens_per_sec_per_chip"
    resnet = "resnet50_imagenet_images_per_sec_per_chip"
    for n, (tok, mfu) in enumerate(
            [(100000.0, 0.40), (120000.0, 0.48), (150000.0, 0.60),
             (149000.0, 0.596), (151000.0, 0.604)], start=1):
        recs = [{"metric": resnet, "value": 2600.0 + n, "mfu": 0.163,
                 "mfu_ceiling_memroofline": 0.249},
                {"metric": bert, "value": tok, "mfu": mfu}]
        json.dump({"n": n, "rc": 0,
                   "tail": "\n".join(json.dumps(r) for r in recs) + "\n"},
                  open(str(tmp_path / ("BENCH_r%02d.json" % n)), "w"))
    script = os.path.join(SCRIPTS, "perf_ledger.py")
    res = subprocess.run(
        [sys.executable, script, "--check", "--history-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "perf_ledger --check: PASS" in res.stdout
    assert bert + "/value" in res.stdout
    assert resnet + "/mfu" in res.stdout
    assert "/mfu_ceiling_rel" in res.stdout


def _snap(path, n, value, mfu):
    json.dump({"n": n, "rc": 0, "tail": json.dumps(
        {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
         "value": value, "mfu": mfu}) + "\n"}, open(path, "w"))


def test_perf_ledger_fails_on_injected_regression(tmp_path):
    _snap(str(tmp_path / "BENCH_r01.json"), 1, 100000.0, 0.50)
    _snap(str(tmp_path / "BENCH_r02.json"), 2, 70000.0, 0.35)
    script = os.path.join(SCRIPTS, "perf_ledger.py")
    res = subprocess.run(
        [sys.executable, script, "--check", "--history-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 2
    assert "REGRESSION metric=bert_base_pretrain_tokens_per_sec_per_chip" \
        in res.stderr
    assert "field=value" in res.stderr and "field=mfu" in res.stderr
    # a generous tolerance waves the same history through
    res = subprocess.run(
        [sys.executable, script, "--check", "--history-dir", str(tmp_path),
         "--tolerance", "0.5"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0


def test_perf_ledger_current_run_gates(tmp_path):
    """--current appends this run as the newest snapshot: an improvement
    passes, a drop fails naming the metric (the bench follow-up path)."""
    _snap(str(tmp_path / "BENCH_r01.json"), 1, 100000.0, 0.50)
    script = os.path.join(SCRIPTS, "perf_ledger.py")
    good = str(tmp_path / "good.jsonl")
    open(good, "w").write(json.dumps(
        {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
         "value": 104000.0, "mfu": 0.52}) + "\n")
    res = subprocess.run(
        [sys.executable, script, "--check", "--history-dir", str(tmp_path),
         "--current", good], capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    bad = str(tmp_path / "bad.jsonl")
    open(bad, "w").write(json.dumps(
        {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
         "value": 80000.0, "mfu": 0.40}) + "\n")
    res = subprocess.run(
        [sys.executable, script, "--check", "--history-dir", str(tmp_path),
         "--current", bad], capture_output=True, text=True, timeout=60)
    assert res.returncode == 2
    assert "cur=8e+04" in res.stderr


@pytest.mark.slow
def test_monitor_overhead_on_fleetscope_mode():
    """The probe's new mode reports fleetscope overhead + gates (full-size
    runs measure the real numbers; this smoke asserts the plumbing)."""
    res = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "monitor_overhead.py"),
         "--steps", "30", "--reps", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert "step_ms_on_fleetscope" in out
    assert "fleetscope_overhead_pct" in out
    assert "pass_fleetscope_lt_2pct" in out
    assert out["pass_trace_disabled_lt_0_5pct"]
