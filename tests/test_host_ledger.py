"""The host half of monitor.recompile's ledger: ``call`` records of a
trainer's ``step`` / ``run_steps``, ``gc`` records of Python's collector,
``stall`` records of the watch's late beats, and ``window(t0, t1)`` over
them.  Collections and beats are fed by hand to a ledger of the test's own;
a tiny ``StepTrainer`` and the real collector write to the process's one."""

import gc
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.monitor import recompile
from paddle_tpu.monitor.recompile import (FIRST_CALL, WATCH_THREAD,
                                          CompileLedger, compile_ledger)
from paddle_tpu.monitor.registry import StatRegistry
from paddle_tpu.parallel.train import StepTrainer

TRACE = "/jax/core/compile/jaxpr_trace_duration"


class _Tiny(StepTrainer):
    label = "tiny"


def _trainer():
    def step(state, batch, lr):
        w = state["params"] - lr * batch.mean()
        return {"params": w}, (w * w).sum()

    def multi(state, batches, lr):
        return jax.lax.scan(lambda s, b: step(s, b, lr), state, batches)

    return _Tiny(cfg=None, mesh=None, state={"params": jnp.ones((4,))},
                 step_fn=jax.jit(step), specs={}, multi_fn=jax.jit(multi))


def _host_since(t0):
    return compile_ledger().between(t0, time.perf_counter(), host=True)


@pytest.mark.parametrize("method,batch", [
    ("step", jnp.ones((4,))), ("run_steps", jnp.ones((3, 4)))])
def test_trainer_call_is_a_record_the_first_inside_its_phase(method, batch):
    ledger, tr = compile_ledger(), _trainer()
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(getattr(tr, method)(batch, 0.1))
    calls = [r for r in _host_since(t0) if r["kind"] == "call"
             and r["name"] == "tiny." + method]
    assert len(calls) == 3
    phase, = [r for r in ledger.between(t0, time.perf_counter())
              if r["kind"] == "phase" and r["name"] == FIRST_CALL
              and r["labels"] == {"program": "tiny." + method}]
    assert phase["t0"] <= calls[0]["t0"] and calls[0]["t1"] <= phase["t1"]
    assert calls[1]["t0"] > phase["t1"]
    for r in calls:
        assert r["thread"] == "MainThread" and r["t1"] > r["t0"]
        assert 0 <= r["thread_cpu_s"] <= r["cpu_s"] + 1e-3
    # the first call traced and compiled on this thread: CPU beside wall
    assert calls[0]["thread_cpu_s"] > 0.2 * (calls[0]["t1"] - calls[0]["t0"])
    # a turn: the gap since the call before returned, with its CPU
    assert calls[2]["gap_s"] == pytest.approx(
        calls[2]["t0"] - calls[1]["t1"], abs=1e-9)
    assert calls[2]["gap_cpu_s"] >= 0 and calls[2]["gap_thread_cpu_s"] >= 0


def test_first_call_on_a_thread_has_no_gap():
    ledger, seen = CompileLedger(StatRegistry()), []

    def work():
        with ledger.call("t.step"):
            pass
        seen.extend(ledger.host_records)

    th = threading.Thread(target=work, name="worker-7")
    th.start()
    th.join(10)
    assert not th.is_alive()
    record, = seen
    assert record["thread"] == "worker-7" and record["gap_s"] is None \
        and record["gap_cpu_s"] is None and record["gap_thread_cpu_s"] is None


def test_call_shows_in_a_monitor_sessions_trace(tmp_path):
    from paddle_tpu import monitor

    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        with compile_ledger().call("traced.step"):
            pass
        names = [e["name"] for e in
                 mon.tracer.to_chrome_trace()["traceEvents"]]
    finally:
        monitor.disable()
    assert "traced.step" in names


def test_calls_evict_no_phase_or_compile_record():
    ledger = CompileLedger(StatRegistry())
    with ledger.phase("init_params"):
        ledger.on_duration(TRACE, 0.0, fun_name="step")
    for _ in range(20000):
        with ledger.call("t.step"):
            pass
    assert [r["kind"] for r in ledger.records] == ["trace", "phase"]
    assert len(ledger.host_records) == recompile._MAX_HOST_RECORDS
    assert ledger.between(0.0, time.perf_counter()) == list(ledger.records)


def test_collection_leaves_a_gc_record():
    assert gc.callbacks.count(compile_ledger().on_gc) == 1
    t0 = time.perf_counter()
    gc.collect()
    full = [r for r in _host_since(t0) if r["kind"] == "gc"
            and r["generation"] == 2]
    assert full and full[-1]["collected"] >= 0 \
        and full[-1]["t1"] > full[-1]["t0"] \
        and full[-1]["thread"] == "MainThread"


def test_beats_by_hand_late_punctual_and_inside_a_collection():
    ledger = CompileLedger(StatRegistry())
    assert ledger.beat(10.0) is None               # the first: nothing due
    assert ledger.beat(10.011) is None             # punctual
    assert ledger.beat(10.021 + 0.049) is None     # late, under the limit
    late = ledger.beat(10.5)
    assert late["kind"] == "stall" and late["t0"] == pytest.approx(10.08) \
        and late["t1"] == 10.5 and late["gc"] is False
    assert late["cpu_s"] >= 0 and late["switches"] >= 0
    # a collection on the real clock, and beats around it
    now = time.perf_counter()
    ledger.beat(now - 1.0)
    ledger.on_gc("start", {"generation": 2})
    ledger.on_gc("stop", {"generation": 2, "collected": 7})
    closed = ledger.host_records[-1]
    assert closed["kind"] == "gc" and closed["generation"] == 2 \
        and closed["collected"] == 7 and closed["t0"] >= now
    marked = ledger.beat(closed["t1"] + 0.2)   # due before it closed
    assert marked["gc"] is True
    assert ledger.beat(closed["t1"] + 0.211) is None
    assert ledger.beat(closed["t1"] + 0.5)["gc"] is False
    ledger.on_gc("start", {"generation": 1})       # one under way
    assert ledger.beat(closed["t1"] + 0.9)["gc"] is True
    # a stop with no start (the ledger came between them) is no record
    fresh = CompileLedger(StatRegistry())
    fresh.on_gc("stop", {"generation": 0, "collected": 0})
    assert not fresh.host_records


def test_throttled_time_is_read_where_a_cpu_stat_says(tmp_path):
    stat = tmp_path / "cpu.stat"
    stat.write_text("usage_usec 5\nnr_throttled 1\nthrottled_usec 1000\n")
    ledger = CompileLedger(StatRegistry())
    assert ledger.beat(1.0) is None and ledger.beat(2.0)[
        "throttled_usec"] is None                  # no file: not known
    fd = recompile.os.open(str(stat), recompile.os.O_RDONLY)
    try:
        ledger._cpu_stat = (fd, b"throttled_usec", 1)
        ledger.beat(3.0)
        stat.write_text("usage_usec 9\nnr_throttled 2\nthrottled_usec "
                        "251000\n")
        assert ledger.beat(4.0)["throttled_usec"] == 250000
    finally:
        recompile.os.close(fd)
    found = recompile._open_cpu_stat()             # this machine's, if any
    if found is not None:
        assert found[1] in (b"throttled_usec", b"throttled_time")
        recompile.os.close(found[0])


def test_import_and_the_ledger_start_no_watch_thread():
    code = ("import threading, paddle_tpu\n"
            "from paddle_tpu.monitor.recompile import compile_ledger\n"
            "led = compile_ledger()\n"
            "with led.phase('init_params'): pass\n"
            "print(sorted(t.name for t in threading.enumerate()))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True).stdout
    assert "MainThread" in out and WATCH_THREAD not in out


def test_watch_is_one_daemon_started_at_a_first_call():
    ledger = compile_ledger()
    for _ in range(2):
        with ledger.phase(FIRST_CALL, program="watch.step"):
            pass
    ledger.start_watch()
    mine = [t for t in threading.enumerate() if t.name == WATCH_THREAD]
    assert len(mine) == 1 and mine[0].daemon and mine[0].is_alive()
    # a ledger of a test's own never starts one
    own = CompileLedger(StatRegistry())
    with own.phase(FIRST_CALL):
        pass
    assert [t for t in threading.enumerate()
            if t.name == WATCH_THREAD] == mine
    # it beats: within a second the process's ledger has been given a beat
    deadline = time.perf_counter() + 5.0
    while ledger._beat is None and time.perf_counter() < deadline:
        time.sleep(0.02)
    assert ledger._beat is not None


def _call(t0, t1, gap=None, cpu=0.0, name="t.run_steps"):
    return {"kind": "call", "name": name, "t0": t0, "t1": t1,
            "thread": "MainThread", "cpu_s": cpu, "thread_cpu_s": cpu,
            "gap_s": gap, "gap_cpu_s": None if gap is None else 0.25,
            "gap_thread_cpu_s": None if gap is None else 0.125}


def _stall(t0, t1, gc_inside=False):
    return {"kind": "stall", "t0": t0, "t1": t1, "cpu_s": 0.0,
            "gc": gc_inside, "switches": 0, "throttled_usec": None,
            "thread": WATCH_THREAD}


def _gc(generation, t0, t1):
    return {"kind": "gc", "generation": generation, "t0": t0, "t1": t1,
            "collected": 7, "thread": "MainThread"}


def test_window_sums_hand_fed_records():
    # collections 103-103.5 (a stall 102.9-103.6 around it) and 99.9-100.1
    # (half inside); a trace 101-101.4 with a nested one; a stall clear of
    # both 105-105.2; calls at 100.5, 102 and 104
    ledger = CompileLedger(StatRegistry())
    ledger.host_records.extend([_gc(2, 103.0, 103.5), _gc(0, 99.9, 100.1)])
    ledger.records.extend(
        {"kind": k, "name": n, "t0": a, "t1": b, "thread": "MainThread",
         "parent": None} for k, n, a, b in [
             ("trace", "multi", 101.0, 101.4), ("trace", "dot", 101.1, 101.2),
             ("phase", "stage_batches", 106.0, 107.0)])
    ledger.host_records.extend([
        _call(100.5, 100.6), _call(102.0, 102.4, gap=1.4, cpu=0.3),
        _call(104.0, 104.1, gap=1.6), _call(109.9, 110.2, gap=5.8),
        _stall(102.9, 103.6, gc_inside=True), _stall(105.0, 105.2)])
    got = ledger.window(100.0, 110.0)
    assert got["calls"] == 3                      # the last straddles the end
    assert got["call_p50_s"] == pytest.approx(0.1)
    assert got["call_max_s"] == pytest.approx(0.4)
    assert got["compile_s"] == pytest.approx(0.4)     # a union, no phase
    assert got["gc_s"] == pytest.approx(0.6)          # 0.5 + the half inside
    # the stall around the collection counts for what lies outside it
    assert got["stall_s"] == pytest.approx(0.2 + 0.2)
    assert got["turn"] == {"name": "t.run_steps", "t0": pytest.approx(100.6),
                           "t1": 102.4, "wall_s": pytest.approx(1.8),
                           "cpu_s": pytest.approx(0.55),
                           "thread_cpu_s": pytest.approx(0.425)}
    assert [r["t0"] for r in got["longest"]["call"]] == [102.0, 109.9, 100.5]
    assert [r["generation"] for r in got["longest"]["gc"]] == [2, 0]
    assert [r["t0"] for r in got["longest"]["stall"]] == [102.9, 105.0]
    # a stall wholly inside a collection is the collection's, counted once
    inside = CompileLedger(StatRegistry())
    inside.host_records.extend([_gc(2, 1.0, 2.0),
                                _stall(1.2, 1.9, gc_inside=True)])
    got = inside.window(0.0, 3.0)
    assert got["gc_s"] == pytest.approx(1.0) and got["stall_s"] == 0.0


def test_window_of_an_empty_stretch():
    got = CompileLedger(StatRegistry()).window(5.0, 6.0)
    assert got == {"calls": 0, "call_p50_s": None, "call_max_s": None,
                   "compile_s": 0.0, "gc_s": 0.0, "stall_s": 0.0,
                   "turn": None,
                   "longest": {"call": [], "gc": [], "stall": []}}
