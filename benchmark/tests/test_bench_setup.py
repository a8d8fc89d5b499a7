"""The five set-up readers: the program's compile ledger joined with the
benchmark's spans.  On a synthetic ledger for the arithmetic, through a tiny
cell on the CPU for the plumbing (seconds of a CPU run are never a device
metric: they are looked at for presence and sign only)."""

import os
import time

import pytest

from benchmark.harness import manifest as mf, setup_time
from benchmark.harness.spans import Spans

from test_bench_harness import CELLS, ROOT, write_tree

NAMES = {"setup_init_s": ("s", "program_span"),
         "setup_trace_lower_s": ("s", "program_counter"),
         "setup_compile_s": ("s", "program_counter"),
         "setup_cache_misses": ("count", "program_counter"),
         "setup_unattributed_share": ("%", "program_span")}


def _read(name, spans, cell):
    return mf.module("layer_metrics", name).read(None, spans, {}, cell)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_entry_by_name(name):
    m = mf.load(ROOT)
    entry, = [e for e in m["per_layer"] if e["name"] == name]
    unit, source = NAMES[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "train driver",
                     "moves": "setup_s"}          # every cell: no workloads
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
    for cell in m["workloads"]:
        assert entry in mf.metrics_of(m, "per_layer", cell["name"])


def _record(kind, name, t0, t1, parent=None, thread="MainThread", **more):
    r = {"kind": kind, "name": name, "t0": float(t0), "t1": float(t1),
         "thread": thread, "parent": parent}
    if kind == "backend":
        r.update(cached=False, saved_s=0.0)
    if kind == "phase":
        r["labels"] = {}
    r.update(more)
    return r


@pytest.fixture()
def synthetic(monkeypatch):
    """Set-up on a clock that starts at 100: build 100-110, stage 110-111,
    warm-up 111-120, reference 120-123, window from 124."""
    from paddle_tpu.monitor.recompile import CompileLedger
    from paddle_tpu.monitor.registry import StatRegistry

    ledger = CompileLedger(StatRegistry())
    ledger.records.extend([
        _record("backend", "jit(before)", 98, 99),      # before the build
        # init 100-104, three small programs inside it, one loaded
        _record("trace", "_normal", 100.0, 100.5, "init_params"),
        _record("lower", "jit(_normal)", 100.5, 101.0, "init_params"),
        _record("backend", "jit(_normal)", 101, 102, "init_params"),
        _record("backend", "jit(_normal)", 102, 103, "init_params",
                cached=True, saved_s=7.0),
        _record("phase", "init_params", 100, 104),
        _record("phase", "init_opt_state", 104, 105),
        _record("phase", "place", 105, 106),
        # staging: its place is not init
        _record("phase", "place", 110.2, 110.8, "stage_batches"),
        _record("phase", "stage_batches", 110.1, 110.9),
        # the first call: trace 111-113 (a jnp function's inside it and
        # another thread's across its end: union, not sum), lower 113-114,
        # backend 114-117
        _record("trace", "matmul", 111.5, 112, "first_call"),
        _record("trace", "multi", 111, 113, "first_call"),
        _record("trace", "convert", 112, 113.5, None, thread="feeder"),
        _record("lower", "jit(multi)", 113, 114, "first_call"),
        _record("backend", "jit(multi)", 114, 117, "first_call"),
        _record("phase", "first_call", 111, 117.5,
                labels={"program": "x.run_steps"}),
        # the benchmark's own checks: a witness's forward, inside the
        # warm-up's span here, and the reference's float32 programs
        _record("lower", "jit(witness)", 118.0, 118.5),
        _record("backend", "jit(witness)", 118.5, 119.0),
        _record("trace", "ref", 120, 121),
        _record("backend", "jit(ref)", 121, 122.5),
        # inside the window: a recompile, not set-up
        _record("backend", "jit(multi)", 125, 126),
    ])
    monkeypatch.setattr(setup_time, "ledger", lambda: ledger)
    spans = Spans()
    spans.records.extend([("bench.build", 100.0, 110.0, "MainThread"),
                          ("bench.stage", 110.0, 111.0, "MainThread"),
                          ("bench.warmup", 111.0, 120.0, "MainThread"),
                          ("bench.witness", 118.0, 119.0, "MainThread"),
                          ("bench.reference", 120.0, 123.0, "MainThread")])
    lines = []
    cell = {"t0": 124.0, "t1": 144.0, "step_ms": [90.0, 100.0, 500.0],
            "traffic": {"staged_batches": 10}, "say": lines.append}
    return spans, cell, lines


def test_readers_on_a_synthetic_ledger(synthetic):
    spans, cell, lines = synthetic
    # init 100-106 as one union; the staging's place is left out
    assert _read("setup_init_s", spans, cell) == pytest.approx(6.0)
    # 100-101 in init, 111-114 in the first call: the nested trace and the
    # other thread's (112-113.5) lie inside and add nothing; the
    # reference's is left out
    assert _read("setup_trace_lower_s", spans, cell) == pytest.approx(4.0)
    # 101-103 and 114-117; not the reference's, not the witness's, not the
    # window's, not what came before the build
    assert _read("setup_compile_s", spans, cell) == pytest.approx(5.0)
    assert _read("setup_cache_misses", spans, cell) == 2.0
    # build + warm-up 19 s; records cover 100-106 and 111-117.5 = 12.5 s;
    # ten steps at the median 100 ms = 1 s on the device; 5.5 s are nobody's
    assert _read("setup_unattributed_share", spans, cell) == pytest.approx(
        100.0 * 5.5 / 19.0)
    # and where they are: after the state was placed (the harness's host
    # copy), and after the first call (the warm-up's steps, the witness)
    where = [l.strip() for l in lines if "in no record" in l]
    assert where == [
        "in no record: 4.000 s of bench.build, after phase place and "
        "before its end",
        "in no record: 2.500 s of bench.warmup, after phase first_call and "
        "before its end"]
    text = "\n".join(lines)
    assert "2 programs compiled, 1 loaded" in text and "7.000 s" in text
    assert "init_params" in text and "x.run_steps" in text
    # init_params' self time: 4 s less the 3 s recorded inside it
    init_line, = [l for l in lines if l.lstrip().startswith("init_params")]
    assert "self    1.0000  programs   2" in init_line
    rows = [l.split()[0] for l in lines[lines.index(
        [l for l in lines if l.lstrip().startswith("program")][0]) + 1:]
        if l.startswith("  ")]
    assert rows[:2] == ["multi", "_normal"]      # costliest first
    assert "build + warmup 19.000 s" in text and "overlap 3.000 s" in text


def test_unattributed_is_signed_and_needs_a_base(synthetic):
    spans, cell, _ = synthetic
    # 20 s "on the device" where the warm-up had 9: taken away too much,
    # and said so
    cell["step_ms"] = [2000.0]
    assert _read("setup_unattributed_share", spans, cell) == pytest.approx(
        100.0 * (6.5 - 20.0) / 19.0)
    cell["step_ms"] = []                         # a window without samples
    assert _read("setup_unattributed_share", spans, cell) == pytest.approx(
        100.0 * 6.5 / 19.0)
    assert _read("setup_init_s", Spans(), cell) is None      # no bench.build


@pytest.mark.parametrize("name", sorted(NAMES))
def test_none_on_a_program_without_the_ledger(synthetic, monkeypatch, name):
    spans, cell, lines = synthetic
    monkeypatch.setattr(setup_time, "ledger", lambda: None)
    assert _read(name, spans, cell) is None and lines == []


def test_ledger_is_looked_up_in_the_program(monkeypatch):
    from paddle_tpu.monitor import recompile

    assert setup_time.ledger() is recompile.compile_ledger()
    monkeypatch.delattr(recompile, "compile_ledger")         # the parent's
    assert setup_time.ledger() is None


@pytest.mark.parametrize("cell", ["bert_tiny.scan", "resnet_tiny.hostfed"])
def test_a_tiny_cell_reports_all_five(tmp_path, cell):
    import jax

    from benchmark.harness.cellrun import run_cell

    root, m = write_tree(tmp_path, mf.load(ROOT), {cell: CELLS[cell]})
    lines = []
    out = run_cell(root, m, cell, seed=5, seconds=0.3, trace=1,
                   t_start=time.perf_counter(), devices=jax.devices()[:1],
                   say=lines.append)
    assert out["correct"] is True, lines
    got = {n: out["metrics"][n] for n in NAMES}
    for name, (unit, _) in NAMES.items():
        assert got[name]["unit"] == unit
    setup = next(float(l.split()[1]) for l in lines if l.startswith("setup:"))
    for name in ("setup_init_s", "setup_trace_lower_s", "setup_compile_s"):
        assert 0.0 < got[name]["value"] < setup
    # no persistent cache on the CPU: what was built was compiled, the step
    # at least (an earlier test's trainer leaves the init programs in jit's
    # own caches, which no event reports)
    assert got["setup_cache_misses"]["value"] >= 1
    assert -100.0 < got["setup_unattributed_share"]["value"] < 100.0
    text = "\n".join(lines)
    assert "set-up, two views" in text and "first_call" in text
    assert "0 loaded from the cache" in text
