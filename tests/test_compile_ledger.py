"""monitor.recompile's compile ledger: what ``jax.monitoring`` says of a
trace, a lowering, a backend compile or a cache load, under the phases the
trainers' set-up marks.  Synthetic events go to a ledger of the test's own;
real compiles are heard by the process's one."""

import threading
import time

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import compile_cache
from paddle_tpu.models import bert, resnet
from paddle_tpu.monitor import recompile
from paddle_tpu.monitor.recompile import (FIRST_CALL, CompileLedger,
                                          compile_ledger, union_seconds)
from paddle_tpu.monitor.registry import StatRegistry, default_registry
from paddle_tpu.parallel import MeshSpec, optim
from paddle_tpu.parallel.train import stack_batches

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def _since(ledger, t0):
    return ledger.between(t0, time.perf_counter())


def _fresh_jit(tag):
    """A jitted function no other test has compiled."""
    def fn(x):
        return jnp.tanh(x @ x) + tag
    fn.__name__ = "ledger_probe_%d" % tag
    return jax.jit(fn)


def test_listener_hears_trace_lower_backend_by_name_once():
    ledger, fn = compile_ledger(), _fresh_jit(1)
    t0 = time.perf_counter()
    fn(jnp.ones((8, 8))).block_until_ready()
    mine = [r for r in _since(ledger, t0) if "ledger_probe_1" in r["name"]]
    assert [(r["kind"], r["name"]) for r in mine] == [
        ("trace", "ledger_probe_1"), ("lower", "jit(ledger_probe_1)"),
        ("backend", "jit(ledger_probe_1)")]
    assert all(r["t0"] >= t0 and r["t1"] > r["t0"] and r["parent"] is None
               and r["thread"] == "MainThread" for r in mine)
    # matmul and tanh are traced inside it, and heard
    assert sum(1 for r in _since(ledger, t0) if r["kind"] == "trace"
               and mine[0]["t0"] <= r["t0"] and r["t1"] <= mine[0]["t1"]) >= 3
    # jit's cached call path never reaches a listener
    t1 = time.perf_counter()
    fn(jnp.ones((8, 8))).block_until_ready()
    assert _since(ledger, t1) == []


def test_one_ledger_one_listener():
    from jax._src import monitoring as jm

    ledger = compile_ledger()
    assert compile_ledger() is ledger
    compile_cache.place()                   # goes through it too
    assert jm.get_event_duration_listeners().count(ledger.on_duration) == 1
    assert jm.get_event_listeners().count(ledger.on_event) == 1


def _feed(ledger, event, secs, name):
    """One duration event that ends now."""
    ledger.on_duration(event, secs, fun_name=name)


def test_nested_traces_sum_to_their_union():
    assert union_seconds([(0, 1), (0.2, 0.4), (0.9, 1.5), (3, 4)]) == 2.5
    assert union_seconds([]) == 0.0
    ledger = CompileLedger(StatRegistry())
    _feed(ledger, TRACE, 0.002, "matmul")
    _feed(ledger, TRACE, 0.001, "tanh")
    _feed(ledger, TRACE, 0.5, "my_step")    # heard last, holds both
    _feed(ledger, LOWER, 0.0, "jit(my_step)")
    _feed(ledger, BACKEND, 0.0, "jit(my_step)")
    traces = [r for r in ledger.records if r["kind"] == "trace"]
    assert [r["name"] for r in traces] == ["matmul", "tanh", "my_step"]
    assert sum(r["t1"] - r["t0"] for r in traces) == pytest.approx(
        0.503, abs=1e-3)
    assert union_seconds((r["t0"], r["t1"]) for r in traces) == \
        pytest.approx(0.5, abs=1e-3)
    # the table holds programs: what was only traced inside one has no row
    row, = ledger.table()
    assert row["name"] == "my_step" and row["n"] == 1
    assert row["trace_s"] == pytest.approx(0.5, abs=1e-3)


@pytest.mark.parametrize("event,cached", [(HIT, True), (MISS, False)])
def test_cache_event_marks_the_program_that_follows(event, cached):
    # place() sets no cache on the CPU: the cache's events are fed by hand
    ledger, reg = compile_ledger(), default_registry()
    fn, x = _fresh_jit(2 + cached), jnp.ones((4, 4))
    name = "monitor.compile.cache_%s" % ("hits" if cached else "misses")
    before = reg.counter(name).value
    misses = reg.counter("monitor.compile.cache_misses").value
    t0 = time.perf_counter()
    jax.monitoring.record_event(event)
    fn(x).block_until_ready()
    built = [r for r in _since(ledger, t0) if r["kind"] == "backend"]
    assert [r["cached"] for r in built] == [cached]
    row, = ledger.table(_since(ledger, t0))
    assert (row["loaded"], row["compiled"]) == (int(cached), int(not cached))
    assert reg.counter(name).value == before + 1
    # the mark is spent: the next program is its own, and with no event
    # before it (JAX says "miss" only where it writes the cache) a compile
    t1 = time.perf_counter()
    _fresh_jit(4 + cached)(x).block_until_ready()
    assert [r["cached"] for r in _since(ledger, t1)
            if r["kind"] == "backend"] == [False]
    assert reg.counter("monitor.compile.cache_misses").value == \
        misses + 2 - cached


def test_saved_seconds_go_to_their_program():
    ledger = CompileLedger(StatRegistry())
    ledger.on_event(HIT)
    ledger.on_duration("/jax/compilation_cache/compile_time_saved_sec", 54.0)
    # the cache's own read is inside the backend event: no record of its own
    ledger.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.2)
    _feed(ledger, BACKEND, 0.25, "jit(multi)")
    _feed(ledger, BACKEND, 0.25, "jit(other)")
    built, other = ledger.records
    assert built["cached"] is True and built["saved_s"] == 54.0
    assert other["cached"] is False and other["saved_s"] == 0.0
    hist = ledger.registry.histogram("monitor.compile.seconds",
                                     kind="backend")
    assert hist.calls == 2 and hist.total == pytest.approx(0.5)


def test_phase_is_the_parent_on_its_own_thread_only():
    ledger = compile_ledger()
    started, done = threading.Event(), threading.Event()

    def other():
        started.wait(30)
        _fresh_jit(7)(jnp.ones((4, 4))).block_until_ready()
        done.set()

    worker = threading.Thread(target=other, name="ledger_other")
    worker.start()
    t0 = time.perf_counter()
    with ledger.phase("x", why="test") as labels:
        with ledger.phase("y"):
            _fresh_jit(6)(jnp.ones((4, 4))).block_until_ready()
        started.set()
        assert done.wait(60)
        labels["late"] = 1
    worker.join(30)
    assert not worker.is_alive()
    got = _since(ledger, t0)
    by_name = {r["name"]: r for r in got if r["kind"] == "backend"}
    assert by_name["jit(ledger_probe_6)"]["parent"] == "y"
    theirs = by_name["jit(ledger_probe_7)"]
    assert theirs["parent"] is None and theirs["thread"] == "ledger_other"
    phases = {r["name"]: r for r in got if r["kind"] == "phase"}
    assert phases["y"]["parent"] == "x" and phases["x"]["parent"] is None
    assert phases["x"]["labels"] == {"why": "test", "late": 1}
    assert phases["x"]["t0"] <= phases["y"]["t0"] <= phases["y"]["t1"] \
        <= phases["x"]["t1"]
    ms = default_registry().histogram("monitor.setup.phase_ms", phase="x")
    assert ms.calls >= 1


def test_phase_shows_in_a_monitor_sessions_trace(tmp_path):
    from paddle_tpu import monitor

    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        with compile_ledger().phase("traced", bytes=3):
            pass
        names = [e["name"] for e in
                 mon.tracer.to_chrome_trace()["traceEvents"]]
    finally:
        monitor.disable()
    assert "setup.traced" in names


def test_records_are_bounded(monkeypatch):
    monkeypatch.setattr(recompile, "_MAX_RECORDS", 8)
    ledger = CompileLedger(StatRegistry())
    for i in range(20):
        _feed(ledger, BACKEND, 0.0, "jit(p%d)" % i)
    assert len(ledger.records) == 8 and ledger.total_records == 20
    assert ledger.records[-1]["name"] == "jit(p19)"


# widths no other test builds: the eager init's programs are cached by shape
# for the life of the process, and a worker runs other files before this one
_BERT = bert.bert_tiny_config(vocab_size=136, hidden=48, ffn_hidden=80)
_RESNET = resnet.resnet_tiny_config(width=12, num_classes=11)


def _bert_batch(rng, b, s=32):
    return {"ids": rng.randint(0, 136, (b, s)).astype("int32"),
            "labels": rng.randint(0, 136, (b, s)).astype("int32"),
            "mask": (rng.rand(b, s) < 0.3).astype("float32")}


def _build_bert(_rng):
    tr = bert.build_bert_trainer(_BERT, MeshSpec(dp=1),
                                 devices=jax.devices()[:1])
    return tr, lambda b: tr.run_steps(stack_batches(
        tr.mesh, bert.batch_specs(), [_bert_batch(_rng, b)] * 2), 1e-3)


def _build_resnet(_rng):
    tr = resnet.build_resnet_trainer(
        _RESNET, MeshSpec(dp=1),
        optimizer=optim.momentum(0.9), devices=jax.devices()[:1])
    return tr, lambda b: tr.step(
        {"image": _rng.rand(b, 32, 32, 3).astype("float32"),
         "label": _rng.randint(0, 11, (b,)).astype("int32")}, 1e-2)


@pytest.mark.parametrize("build,program", [
    (_build_bert, "bert.run_steps"), (_build_resnet, "resnet.step")])
def test_trainer_set_up_by_phase(build, program):
    ledger, reg = compile_ledger(), default_registry()
    again = reg.counter("monitor.compile.after_first_call")
    t0 = time.perf_counter()
    _tr, call = build(np.random.RandomState(0))
    built = _since(ledger, t0)
    phases = [r for r in built if r["kind"] == "phase"]
    names = [r["name"] for r in phases]
    for name in ("init_params", "init_opt_state", "place"):
        assert name in names, names
    assert FIRST_CALL not in names
    init = max((r for r in phases if r["name"] == "init_params"),
               key=lambda r: r["t1"] - r["t0"])
    assert init["labels"]["leaves"] > 10
    placed = [r for r in phases if r["name"] == "place"][-1]
    assert placed["labels"]["bytes"] > 0 and placed["parent"] is None
    # the eager leaf-by-leaf programs are init_params' children
    assert sum(1 for r in built if r["kind"] == "backend"
               and r["parent"] == "init_params") > 3

    t1 = time.perf_counter()
    assert np.isfinite(np.asarray(call(4), np.float32)).all()
    first = _since(ledger, t1)
    call_phase, = [r for r in first if r["kind"] == "phase"
                   and r["name"] == FIRST_CALL]
    assert call_phase["labels"] == {"program": program}
    step, = [r for r in first if r["kind"] == "backend"
             and r["parent"] == FIRST_CALL]
    assert call_phase["t0"] <= step["t0"] and step["t1"] <= call_phase["t1"]
    rows = ledger.table(first)
    assert rows[0]["parent"] == FIRST_CALL and rows[0]["trace_s"] > 0 \
        and rows[0]["lower_s"] > 0 and rows[0]["backend_s"] > 0
    if program.endswith("run_steps"):
        staged = [r for r in first if r["kind"] == "phase"
                  and r["name"] in ("stage_batches", "place")]
        assert [(r["name"], r["parent"]) for r in staged] == [
            ("place", "stage_batches"), ("stage_batches", None)]
        assert staged[1]["labels"]["bytes"] == staged[0]["labels"]["bytes"]

    # the same shape again: no phase but the staging's, no program, no alarm
    before = again.value
    t2 = time.perf_counter()
    call(4)
    assert [r for r in _since(ledger, t2) if r["kind"] in (
        "backend", "lower") or r["name"] == FIRST_CALL] == []
    # another trainer's eager init runs programs of the same names as the
    # first's (jit(broadcast_in_dim), ...): under init_params, no alarm
    build(np.random.RandomState(1))
    assert again.value == before
    # a new batch shape: the step compiles again, under no phase
    t3 = time.perf_counter()
    call(2)
    late = [r for r in _since(ledger, t3) if r["kind"] == "backend"
            and r["name"] == step["name"]]
    assert len(late) == 1 and late[0]["parent"] is None
    assert again.value == before + 1
