"""The trace reduction, on a synthetic trace worked out by hand (overlapping
operations, a gap, control flow that contains its body, a collective half
hidden by compute) and on recorded fixtures cut from real v5e traces."""

import json
import os

import pytest

from benchmark.harness import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _plane(name, ops, modules=None):
    lines = [{"name": "XLA Ops", "events": ops}]
    if modules:
        lines.append({"name": "XLA Modules", "events": modules})
    return {"name": name, "lines": lines}


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 8)]) == [(0, 2), (3, 5),
                                                         (8, 10)]
    assert tr.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


SYNTHETIC = {"planes": [
    _plane("/device:TPU:0", modules=[["jit_step(1)", 0, 1000]], ops=[
        # a while loop that contains everything up to 600: no operation
        ["while.1", 0, 600],
        ["fusion.1", 0, 200],
        ["flash_fwd.3", 150, 150],            # overlaps fusion.1 by 50
        # gap 300..400
        ["fusion.1", 400, 100],
        ["flash_bwd_fused", 500, 100],
        # all-reduce 600..800; compute hides 700..800 of it
        ["all-reduce.7", 600, 200],
        ["fusion.9", 700, 200],               # runs on to 900
        # gap 900..1000
    ]),
    _plane("/device:TPU:1", modules=[["jit_step(1)", 0, 1000]], ops=[
        ["fusion.1", 0, 500],
        # asynchronous pair: the interval runs from 500 to 900, compute
        # covers 550..850 of it
        ["all-reduce-start.2", 500, 10],
        ["fusion.9", 550, 300],
        ["all-reduce-done.2", 850, 50],
        # gap 900..1000
    ]),
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.dispatch", 280, 60], ["bench.sync", 340, 160],
        ["bench.feed_wait", 940, 100]]}]},
    {"name": "/host:metadata", "lines": []},
]}


def test_synthetic_trace_by_hand():
    r = tr.Reduced(SYNTHETIC)
    assert r and len(r.devices) == 2
    assert r.window == (0, 1000)
    d0, d1 = r.devices
    # device 0: busy 0..300, 400..900 = 800; the while is not counted
    assert d0["busy_ns"] == 800 and "while.1" not in d0["by_name"]
    assert d0["gaps"] == [(300, 400), (900, 1000)]
    assert d0["by_name"]["fusion.1"] == 300 and d0["count"]["fusion.1"] == 2
    assert d0["collective_ns"] == 200 and d0["collective_exposed_ns"] == 100
    # device 1: busy 0..510, 550..900 = 860
    assert d1["busy_ns"] == 860
    assert d1["collective_ns"] == 400
    # exposed: 500..550 and 850..900
    assert d1["collective_exposed_ns"] == 100
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(830e-9)
    assert r.collective_s == pytest.approx(300e-9)
    assert r.collective_exposed_s == pytest.approx(100e-9)
    flash = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")
    # flash_fwd.3 is clipped by nothing: 150 + 100 on device 0, over 2 planes
    assert r.seconds_of_kernels(flash) == pytest.approx(125e-9)
    assert r.count_of_kernels(("flash_fwd",)) == 0.5
    top = r.top_ops(3)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(400e-9)
    gaps = r.top_gaps(5)
    assert [g[1] for g in gaps[:3]] == pytest.approx([100e-9] * 3)
    assert sorted(g[0] for g in gaps[:3]) == ["bench.feed_wait",
                                              "bench.feed_wait", "bench.sync"]
    assert gaps[3] == ["unattributed", pytest.approx(40e-9)]


@pytest.mark.parametrize("name,control_flow,short", [
    ("%while.4 = (s32[]{:T(128)}, f32[768]{0:T(1024)}) while((s32[]{:T(128)}, "
     "f32[768]{0:T(1024)}) %tuple.1), condition=%cond, body=%body", True,
     "while.4"),
    ("%my_loop = (s32[]) while((s32[]) %t), condition=%c, body=%b", True,
     "my_loop"),
    ("%fusion.2345 = (f32[64,512]{1,0:T(8,128)S(1)}, f32[768]{0:T(1024)S(1)}) "
     "fusion(bf16[64,512,768]{2,1,0:T(8,128)(2,1)S(1)} %copy-done.9), "
     "kind=kOutput, calls=%fused_computation.252.clone.clone", False,
     "fusion.2345"),
    ("%flash_fwd.59 = bf16[64,512,768]{2,1,0:T(8,128)(2,1)} custom-call("
     "bf16[64,512,768] %x), custom_call_target=\"tpu_custom_call\"", False,
     "flash_fwd.59"),
    ("%all-reduce.11 = bf16[100]{0} all-reduce(bf16[100]{0} %x), "
     "replica_groups={{0,1,2,3}}", False, "all-reduce.11"),
    ("while.1", True, "while.1"), ("call.3", True, "call.3"),
    ("fusion.1", False, "fusion.1"),
])
def test_names_as_the_v5e_trace_gives_them(name, control_flow, short):
    """The trace names a device operation by its whole HLO line."""
    assert tr.is_control_flow(name) is control_flow
    assert tr.short_name(name) == short


def test_a_short_event_inside_a_long_one_makes_no_container():
    """Seen on one of four chips: a zero-length copy-done stamped inside a
    2 ms all-reduce.  Both are operations."""
    trace = {"planes": [_plane(
        "/device:TPU:0", modules=[["m", 0, 2000]],
        ops=[["all-reduce.11", 0, 2000], ["copy-done.162", 1999, 0],
             ["copy-done.3", 500, 1]])]}
    r = tr.Reduced(trace)
    assert r.devices[0]["busy_ns"] == 2000
    assert r.devices[0]["collective_ns"] == 2000
    assert r.devices[0]["collective_exposed_ns"] == 1999


def test_events_are_clipped_to_the_programs_window():
    trace = {"planes": [_plane(
        "/device:TPU:0", modules=[["jit_step(1)", 100, 200]],
        ops=[["fusion.1", 50, 100], ["fusion.2", 250, 100]])]}
    r = tr.Reduced(trace)
    assert r.window == (100, 300)
    assert r.devices[0]["busy_ns"] == 100      # 100..150 and 250..300
    assert r.devices[0]["gaps"] == [(150, 250)]


def test_window_is_what_every_plane_recorded():
    """Four chips start and stop recording apart: a plane's head start is
    no idle time of the others."""
    trace = {"planes": [
        _plane("/device:TPU:0", modules=[["m", 0, 1000]],
               ops=[["fusion.1", 0, 1000]]),
        _plane("/device:TPU:1", modules=[["m", 200, 900]],
               ops=[["fusion.1", 200, 900]])]}
    r = tr.Reduced(trace)
    assert r.window == (200, 1000)
    assert r.busy_s == pytest.approx(800e-9) and r.top_gaps() == []


def test_no_device_plane_reads_as_nothing():
    assert not tr.Reduced({"planes": [{"name": "/host:CPU", "lines": []}]})


def _fixtures():
    if not os.path.isdir(FIXTURES):
        return []
    return sorted(f for f in os.listdir(FIXTURES) if f.endswith(".json"))


@pytest.mark.parametrize("name", _fixtures())
def test_recorded_fixture(name):
    """A few hundred events cut from a real v5e trace, with the values the
    reduction has to give, worked out by hand when the fixture was cut
    (``expect`` in the file says how)."""
    with open(os.path.join(FIXTURES, name)) as f:
        fx = json.load(f)
    r = tr.Reduced(fx["trace"])
    want = fx["expect"]
    assert len(r.devices) == want["devices"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.collective_s == pytest.approx(want["collective_s"], rel=1e-9,
                                           abs=1e-15)
    assert r.collective_exposed_s == pytest.approx(
        want["collective_exposed_s"], rel=1e-9, abs=1e-15)
    for kernel, seconds in want["kernel_s"].items():
        assert r.seconds_of_kernels((kernel,)) == pytest.approx(seconds,
                                                                rel=1e-9)
