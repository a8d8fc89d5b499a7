"""The six readers of the measured window: the program's ``call``, ``gc``
and ``stall`` records and its compile records joined with the harness's
facts of the window.  On a synthetic ledger for the arithmetic, through
tiny cells on the CPU for the plumbing (milliseconds of a CPU run are never
a device metric: they are looked at for presence and sign only)."""

import os

import pytest

from benchmark.harness import manifest as mf, setup_time, trace_reduce, \
    window_time
from benchmark.harness.spans import Spans

from test_bench_harness import CELLS, ROOT, write_tree

NAMES = {"dispatch_ms_p50": ("ms", "program_span"),
         "dispatch_ms_max": ("ms", "program_span"),
         "window_compile_ms": ("ms", "program_counter"),
         "window_gc_ms": ("ms", "program_counter"),
         "window_stall_ms": ("ms", "program_span"),
         "window_lost_unattributed_share": ("%", "program_span")}
# the cells whose window loses under 50 ms run after run (ledger, PR 64:
# 2 to 45 ms) have nothing for the last reader to read
UNDER_THE_FLOOR = {"resnet50.b128_scan", "resnet50.b128_hostfed",
                   "resnet50.b256_scan", "brumby_14b.s16384_scan",
                   "ouro_2_6b.s4096_scan"}


def _read(name, spans, cell, trace=None):
    return mf.module("layer_metrics", name).read(trace, spans, {}, cell)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_entry_by_name(name):
    m = mf.load(ROOT)
    entry, = [e for e in m["per_layer"] if e["name"] == name]
    unit, source = NAMES[name]
    listed = entry.pop("workloads", None)
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": "train driver",
                     "moves": "train_throughput"}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
    cells = [c["name"] for c in m["workloads"]]
    if name == "window_lost_unattributed_share":
        assert listed == [c for c in cells if c not in UNDER_THE_FLOOR]
    else:
        assert listed is None                     # every cell
    # appended: the accepted entries stand where they stood
    assert [e["name"] for e in m["per_layer"][-6:]] == list(NAMES)


def _call(t0, t1, gap=None, cpu=0.0, name="x.run_steps"):
    return {"kind": "call", "name": name, "t0": t0, "t1": t1,
            "thread": "MainThread", "cpu_s": cpu, "thread_cpu_s": cpu,
            "gap_s": gap, "gap_cpu_s": None if gap is None else 0.001,
            "gap_thread_cpu_s": None if gap is None else 0.001}


def _stall(t0, t1, **more):
    return dict({"kind": "stall", "t0": t0, "t1": t1, "cpu_s": 0.002,
                 "gc": False, "switches": 3, "throttled_usec": 180000.0,
                 "thread": "paddle_tpu.ledger_watch"}, **more)


def _gc(generation, t0, t1):
    return {"kind": "gc", "generation": generation, "t0": t0, "t1": t1,
            "collected": 41, "thread": "MainThread"}


def _trace(name, t0, t1):
    return {"kind": "trace", "name": name, "t0": t0, "t1": t1,
            "thread": "MainThread", "parent": None}


@pytest.fixture()
def synthetic(monkeypatch):
    """A scanned window on a clock that starts at 200: ten dispatches of ten
    steps, 2 s each, the first completion at 202.5 (``multi`` is traced
    again for 0.4 s at the first dispatch), a collection of 0.3 s at 207
    with a late beat around it, a late beat of 0.2 s at 212, the window's
    end at 220.9: units over the window read 4.3 % under the median."""
    from paddle_tpu.monitor.recompile import CompileLedger
    from paddle_tpu.monitor.registry import StatRegistry

    ledger = CompileLedger(StatRegistry())
    ledger.records.extend([
        _trace("multi", 199.0, 199.5),                 # the warm-up's
        _trace("multi", 200.05, 200.45), _trace("dot", 200.1, 200.2)])
    ledger.host_records.extend(
        [_call(199.0, 199.9, name="x.run_steps")]
        + [_call(200.0, 200.5, gap=0.1, cpu=0.45)]
        + [_call(200.6 + 2 * i, 200.604 + 2 * i, gap=1.9) for i in range(9)]
        + [_gc(0, 199.1, 199.2), _gc(2, 207.0, 207.3), _gc(0, 209.0, 209.01),
           _stall(206.95, 207.35, gc=True), _stall(212.0, 212.2)])
    monkeypatch.setattr(setup_time, "ledger", lambda: ledger)
    lines = []
    # marks at 202.5, 204.5, ... with the collection's 0.3 s and the stall's
    # 0.1 s in two of the nine intervals
    step_ms = [200.0] * 7 + [230.0, 210.0]
    window_s = 2.5 + sum(step_ms) * 10 / 1e3
    cell = {"t0": 200.0, "t1": 200.0 + window_s, "window_s": window_s,
            "step_ms": step_ms, "traffic": {"staged_batches": 10},
            "throughput": 5.0, "window_rate": 100 * 1.0 / window_s,
            "say": lines.append}
    return Spans(), cell, lines


def test_readers_on_a_synthetic_ledger(synthetic):
    spans, cell, lines = synthetic
    # ten calls inside the window: one of 500 ms, nine of 4 ms
    assert _read("dispatch_ms_p50", spans, cell) == pytest.approx(4.0)
    assert _read("dispatch_ms_max", spans, cell) == pytest.approx(500.0)
    # the trace of multi with the one nested in it, not the warm-up's
    assert _read("window_compile_ms", spans, cell) == pytest.approx(400.0)
    # two collections, not the one before the window
    assert _read("window_gc_ms", spans, cell) == pytest.approx(310.0)
    # the beat around the collection counts for what sticks out of it
    assert _read("window_stall_ms", spans, cell) == pytest.approx(300.0)
    # 100 units at 5 a second are 20 s of a window of 20.9: it lost 0.9 s...
    got = window_time.lost(cell)
    assert got["lost_s"] == pytest.approx(0.9)
    assert got["first_s"] == pytest.approx(2.5)
    assert got["start_s"] == pytest.approx(0.5)
    # ... of which the start is 0.5 (0.4 of it the trace, inside the start
    # and counted once); the later collections and beats, 0.61 s, explain
    # the 0.4 s lost after the start and no more: nothing is left
    share = _read("window_lost_unattributed_share", spans, cell)
    assert share == pytest.approx(0.0, abs=1e-9)
    text = "\n".join(lines)
    assert "10 calls of the program in the window" in text
    assert "call x.run_steps +0.000 ms 500.000 ms long (process CPU " \
        "450.000 ms" in text
    assert "the longest turn" in text and "wall 1904.000 ms" in text
    assert "2 records (2 trace, 0 lower, 0 backend)" in text
    assert "trace multi +50.000 ms 400.000 ms long" in text
    assert "gc generation 2 +7000.000 ms 300.000 ms long (41 collected" \
        in text
    assert "stall +12000.000 ms 200.000 ms long (process CPU 2.000 ms, 3 " \
        "involuntary switches, throttled 180.000 ms)" in text
    assert "a collection inside" in text
    assert "lost 900.000 ms of 20.900 s | the start 500.000 ms (2500.000 " \
        "ms to the first completion less a median dispatch; compile " \
        "400.000 + gc 0.000 + stall 0.000 ms" in text
    assert "after it compile 0.000 + gc 310.000 + stall 300.000 ms, of " \
        "which 400.000 ms can be of the 400.000 ms lost after the start" \
        in text and "+ unattributed 0.000 ms" in text
    # without the later records the 0.4 s are nobody's
    ledger = setup_time.ledger()
    calls = [r for r in ledger.host_records if r["kind"] == "call"]
    ledger.host_records.clear()
    ledger.host_records.extend(calls)
    assert _read("window_lost_unattributed_share", spans, cell) \
        == pytest.approx(100.0 * 0.4 / 0.9)
    # and a window that lost nothing after its start leaves the late beats
    # behind its dispatches out: signed, near zero
    ledger.host_records.append(_stall(212.0, 213.2))
    cell["throughput"] = 100.0 / (cell["window_s"] - 0.49)
    assert _read("window_lost_unattributed_share", spans, cell) \
        == pytest.approx(100.0 * -0.01 / 0.49)


def test_nothing_to_attribute_under_the_floor(synthetic):
    spans, cell, lines = synthetic
    # the same window at a rate that leaves 40 ms out
    cell["throughput"] = 100.0 / (cell["window_s"] - 0.040)
    assert _read("window_lost_unattributed_share", spans, cell) is None
    assert any("lost under 50 ms, nothing to attribute" in l for l in lines)
    # the other five read on
    assert _read("window_stall_ms", spans, cell) == pytest.approx(300.0)


def test_a_program_without_the_records_reads_nothing(synthetic, monkeypatch):
    spans, cell, lines = synthetic

    class Older:                  # PR 35's ledger: records, between, table
        def between(self, t0, t1):
            return []

    for ledger in (None, Older()):
        monkeypatch.setattr(setup_time, "ledger", lambda led=ledger: led)
        for name in NAMES:
            assert _read(name, spans, cell) is None
        assert window_time.under_gaps(_reduced([]), spans) is None
    assert lines == []


def test_no_call_no_dispatch_time(synthetic):
    spans, cell, _ = synthetic
    setup_time.ledger().host_records.clear()
    assert _read("dispatch_ms_p50", spans, cell) is None
    assert _read("dispatch_ms_max", spans, cell) is None
    assert _read("window_gc_ms", spans, cell) == 0.0


# -- the traced part ----------------------------------------------------------

def _reduced(host_events, ops=((0, 40e6), (40.2e6, 60e6), (60.017e6, 99e6))):
    """A one-chip trace of 100 ms: three operations with gaps of 200 us and
    17 us between them and 1 ms at the end, and the host's events."""
    return trace_reduce.Reduced({"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace_reduce.OPS_LINE,
             "events": [["fusion.%d" % i, a, b - a]
                        for i, (a, b) in enumerate(ops)]},
            {"name": trace_reduce.MODULES_LINE,
             "events": [["jit_multi", 0.0, 100e6]]}]},
        {"name": trace_reduce.HOST_PLANE, "lines": [
            {"name": "main", "events": [list(e) for e in host_events]}]}]})


def test_clock_zero_from_the_spans_on_both_clocks():
    # the run: six dispatches and six syncs; the profiler saw the last three
    # dispatches and syncs four and five (the last sync came after it
    # stopped).  The trace's zero is at 995.25 s of the host's clock.
    zero, spans = 995.25, Spans()
    for i in range(6):
        spans.records.append(("bench.dispatch", 990.0 + 2 * i,
                              990.0012 + 2 * i + 1e-5 * i, "MainThread"))
        spans.records.append(("bench.sync", 990.002 + 2 * i,
                              991.99 + 2 * i + 3e-4 * i, "MainThread"))
    traced = [(n, t0, t1) for n, t0, t1, _ in spans.records
              if t0 > 995 and not (n == "bench.sync" and t0 > 1000)]
    assert len(traced) == 5
    # the two clocks disagree by up to 2 us a reading
    jitter = [0.0, 2e-6, -1e-6, 1e-6, -2e-6]
    trace = _reduced([(n, (t0 - zero + j) * 1e9, (t1 - t0) * 1e9)
                      for (n, t0, t1), j in zip(traced, jitter)])
    got, pairs, spread = window_time.clock_zero(spans, trace)
    assert pairs == 5 and got == pytest.approx(zero, abs=2.1e-6)
    assert spread == pytest.approx(4e-6, abs=1e-7)
    # no span of the benchmark's in the trace: no pair
    assert window_time.clock_zero(spans, _reduced([])) is None
    # anchored spans (host-fed, no annotation) give their own zero back
    anchored = _reduced([(n, (t0 - 993.5) * 1e9, (t1 - t0) * 1e9)
                         for n, t0, t1, _ in spans.records if t1 >= 993.5])
    got, pairs, spread = window_time.clock_zero(spans, anchored)
    assert got == pytest.approx(993.5, abs=1e-9) and spread < 1e-9


def test_the_programs_record_under_each_idle_gap(synthetic):
    spans, cell, lines = synthetic
    zero = 300.0
    spans.records.extend([
        ("bench.dispatch", 300.0395, 300.0405, "MainThread"),
        ("bench.sync", 300.0406, 300.0995, "MainThread")])
    trace = _reduced([(n, (t0 - zero) * 1e9, (t1 - t0) * 1e9)
                      for n, t0, t1, _ in spans.records])
    setup_time.ledger().host_records.extend([
        _call(300.0396, 300.0404, gap=1.9),          # under the 200 us gap
        _gc(0, 300.0993, 300.0996)])                 # in the last ms
    (got, pairs, _), gaps = window_time.under_gaps(trace, spans)
    assert pairs == 2 and got == pytest.approx(zero)
    assert [(round(s * 1e6), span, r and r["kind"])
            for s, _, span, r in gaps] == [
        (1000, "bench.sync", "gc"), (200, "bench.dispatch", "call"),
        (17, "bench.sync", None)]
    assert _read("dispatch_ms_max", spans, cell, trace) == pytest.approx(500)
    text = "\n".join(lines)
    assert "the trace's clock by 2 spans on both clocks" in text
    assert "idle 200.000 us at +40.000 ms of the trace, under " \
        "bench.dispatch: call x.run_steps +39.600 ms 0.800 ms long" in text
    assert "idle 17.000 us at +60.000 ms of the trace, under bench.sync: " \
        "no record of the program" in text
    # host-fed: the trace holds no annotation, and the log says so
    del lines[:]
    cell["traffic"]["trace_host_level"] = 0
    _read("dispatch_ms_max", spans, cell, trace)
    assert any("tracing.anchored's" in l for l in lines)


# -- through a tiny cell ------------------------------------------------------

@pytest.mark.parametrize("cell", ["bert_tiny.scan", "resnet_tiny.hostfed"])
def test_the_readers_through_a_tiny_cell(tmp_path, cell):
    import time

    import jax

    from benchmark.harness.cellrun import run_cell

    root, m = write_tree(tmp_path, mf.load(ROOT), {cell: CELLS[cell]})
    lines = []
    out = run_cell(root, m, cell, seed=5, seconds=0.4, trace=1,
                   t_start=time.perf_counter(), devices=jax.devices()[:1],
                   say=lines.append)
    assert out["correct"] is True, lines
    got = {n: out["metrics"][n]["value"] for n in NAMES
           if n in out["metrics"]}
    assert set(NAMES) - set(got) <= {"window_lost_unattributed_share"}
    assert 0 < got["dispatch_ms_p50"] <= got["dispatch_ms_max"]
    assert got["window_gc_ms"] >= 0 and got["window_stall_ms"] >= 0
    text = "\n".join(lines)
    label = "bert" if cell.startswith("bert") else "resnet"
    method = "run_steps" if cell.endswith("scan") else "step"
    assert "dispatch_ms_max: call %s.%s +" % (label, method) in text
    assert "window, two views: the benchmark's marks lost" in text
    if cell.endswith("scan"):
        # the scan is traced again on the state a step returned, at the
        # window's first dispatch: a trace record, and no backend compile
        assert got["window_compile_ms"] > 0
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert "trace multi +" in text
    else:
        assert got["window_compile_ms"] == 0
