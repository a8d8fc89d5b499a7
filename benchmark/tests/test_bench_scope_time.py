"""Device time by the program's scopes: the join of the trace's seconds per
instruction with the program's map (``harness/scope_time.py``) on a
synthetic trace and a hand-written map, the five entries and their reader
files, and the tiny cells end to end on the CPU, where no device metric is
printed."""

import importlib
import os
import time

import pytest

from benchmark.harness import manifest as mf, scope_time, trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ONE_CHIP = ["bert_base.s512_scan", "resnet50.b128_scan",
            "resnet50.b128_hostfed"]
# name -> (cells, layer)
ENTRIES = {
    "lm_head_time_share": (["bert_base.s512_scan"], "model code"),
    "bn_time_share": (["resnet50.b128_scan", "resnet50.b128_hostfed"],
                      "model code"),
    "optimizer_time_share": (ONE_CHIP, "model code"),
    "backward_time_share": (ONE_CHIP, "model code"),
    "scope_unattributed_share": (ONE_CHIP, "device"),
}


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 1000]]}]}


# two devices, 1000 ns each, no overlap, busy 900 and 1000
TRACE = {"planes": [
    _plane("/device:TPU:0", [
        ["while.4", 0, 1000],                 # control flow: no operation
        ["fusion.1", 0, 300],                 # lm_head forward
        ["fusion.2", 300, 200],               # lm_head backward
        ["flash_fwd.48", 500, 100],           # attention forward
        ["fusion.3", 600, 100],               # optimizer
        ["copy-done.7", 700, 100],            # in the map, no scope
        ["fusion.77", 800, 100],              # in no map
        # gap 900..1000
    ]),
    _plane("/device:TPU:1", [
        ["fusion.1", 0, 500],
        ["fusion.2", 500, 200],
        ["flash_fwd.48", 700, 100],
        ["fusion.3", 800, 100],
        ["all-reduce.10", 900, 100],          # grad_sync
    ]),
]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"bert.run_steps": {
    "fusion.1": P + "jvp(lm_head)/lm_head/dot_general",
    "fusion.2": P + "transpose(jvp(lm_head))/lm_head/dot_general",
    "flash_fwd.48": P + "jvp()/while/body/closed_call/attention/flash_fwd",
    "fusion.3": P + "optimizer/sqrt",
    "copy-done.7": "",
    "all-reduce.10": P + "grad_sync/psum",
    "fusion.99": P + "jvp(embed)/gather",     # never ran: no time
}}


def _fake_program(monkeypatch, maps):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    calls = []
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: calls.append(1) or maps)
    return calls


def test_join_on_a_synthetic_trace(monkeypatch):
    calls = _fake_program(monkeypatch, MAPS)
    trace, lines = tr.Reduced(TRACE), []
    cell = {"say": lines.append}
    table = scope_time.seconds(trace, cell)
    # mean over the two devices, in seconds
    assert table == pytest.approx({
        ("forward", "lm_head"): 400e-9, ("backward", "lm_head"): 200e-9,
        ("forward", "attention"): 100e-9, ("optimizer", "optimizer"): 100e-9,
        ("forward", None): 50e-9, (scope_time.UNMAPPED, None): 50e-9,
        ("grad_sync", "grad_sync"): 50e-9})
    assert trace.busy_s == pytest.approx(950e-9)
    assert sum(table.values()) == pytest.approx(trace.busy_s)

    readers = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
               for n in ENTRIES}
    assert readers == pytest.approx({
        "lm_head_time_share": 100 * 600 / 950,
        "bn_time_share": 0.0,
        "optimizer_time_share": 100 * 100 / 950,
        "backward_time_share": 100 * 200 / 950,
        # in the map without a scope, and in no map at all
        "scope_unattributed_share": 100 * 100 / 950})
    by_scope = {}
    for (_, scope), s in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + s
    assert 100 * sum(by_scope.values()) / trace.busy_s == pytest.approx(100)
    # five readers, one map, one printed table whose last row is the sum
    assert calls == [1]
    assert sum("device seconds by phase and scope" in l for l in lines) == 1
    assert lines[0].startswith("scope map: 7 instructions of 1 program(s) "
                               "(bert.run_steps)")
    assert lines[-1].split()[0] == "sum" and lines[-1].endswith("100.000 %")
    # another reduced trace is joined anew
    scope_time.seconds(tr.Reduced(TRACE), cell)
    assert calls == [1, 1]


def test_two_programs_that_disagree_on_a_name(monkeypatch):
    """``step`` and ``run_steps`` number their instructions alike: a name
    that means two things is nobody's."""
    other = {"fusion.1": P + "jvp()/while/body/closed_call/mlp/dot_general",
             "fusion.2": P + "transpose(jvp(lm_head))/lm_head/dot_general"}
    _fake_program(monkeypatch, dict(MAPS, **{"bert.step": other}))
    table = scope_time.seconds(tr.Reduced(TRACE), {"say": lambda _l: None})
    assert ("forward", "lm_head") not in table
    assert table[(scope_time.UNMAPPED, None)] == pytest.approx(450e-9)
    assert table[("backward", "lm_head")] == pytest.approx(200e-9)


def test_no_trace_or_no_devscope_gives_nothing(monkeypatch):
    cell = {"say": lambda _l: None}
    assert scope_time.share(None, cell, lambda *_k: True) is None
    empty = tr.Reduced({"planes": []})
    assert scope_time.share(empty, cell, lambda *_k: True) is None
    # the program of an earlier commit has no monitor.devscope
    import paddle_tpu.monitor

    monkeypatch.delattr(paddle_tpu.monitor, "devscope", raising=False)
    monkeypatch.setitem(__import__("sys").modules,
                        "paddle_tpu.monitor.devscope", None)
    trace = tr.Reduced(TRACE)
    for name in ENTRIES:
        assert mf.module("layer_metrics", name).read(
            trace, None, {}, cell) is None


def test_the_five_entries_and_their_reader_files():
    m = mf.load(ROOT)
    entries = {e["name"]: e for e in m["per_layer"]}
    assert list(entries)[-5:] == list(ENTRIES)      # appended, in order
    for name, (cells, layer) in ENTRIES.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "lower", "device_trace", "train_throughput")
        assert e["workloads"] == cells and e["layer"] == layer
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
        assert callable(mf.module("layer_metrics", name).read)
    # the four-chip cell pays for none of them
    assert not set(ENTRIES) & {e["name"] for e in mf.metrics_of(
        m, "per_layer", "bert_base.s512_dp4")}


@pytest.mark.parametrize("cell,label", [("bert_tiny.scan", "bert.run_steps"),
                                        ("resnet_tiny.hostfed",
                                         "resnet.step")])
def test_tiny_cells_run_with_the_new_readers(tmp_path, cell, label):
    """On the CPU the trace has no device plane: the new readers are called
    and print no device metric; the program has registered what it ran."""
    import jax

    from paddle_tpu.monitor import devscope
    from test_bench_harness import CELLS, write_tree

    from benchmark.harness.cellrun import run_cell

    root, m = write_tree(tmp_path, mf.load(ROOT), {cell: CELLS[cell]})
    assert set(ENTRIES) <= {e["name"] for e in mf.metrics_of(
        m, "per_layer", cell)}
    lines = []
    out = run_cell(root, m, cell, seed=2**31 + 11, seconds=0.2, trace=1,
                   t_start=time.perf_counter(), devices=jax.devices()[:1],
                   say=lines.append)
    assert out["correct"] is True, lines
    assert "step_ms_p50" in out["metrics"]
    assert not set(ENTRIES) & set(out["metrics"])
    assert not any("scope map" in l for l in lines)
    assert label in [p[0] for p in devscope._programs]
