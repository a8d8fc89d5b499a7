"""The six memory readers: the program's account of its device memory
(``harness/memory_account.py`` over ``paddle_tpu.monitor.memscope``).  On a
synthetic account for the arithmetic, through tiny cells on the CPU for the
plumbing (a CPU run's bytes are the compiler's and the live arrays', never
an allocator's: ``peak_over_step_gb`` gives nothing there)."""

import os
import time

import pytest

from benchmark.harness import manifest as mf, memory_account
from benchmark.harness.spans import Spans

from test_bench_harness import CELLS, ROOT, write_tree

NAMES = {"step_state_gb": ("GB", "train driver"),
         "step_batches_gb": ("GB", "train driver"),
         "step_temp_gb": ("GB", "model code"),
         "step_need_gb": ("GB", "device"),
         "peak_over_step_gb": ("GB", "device"),
         "hbm_unattributed_share": ("%", "device")}
GB = 10 ** 9


def _read(name, spans, cell):
    return mf.module("layer_metrics", name).read(None, spans, {}, cell)


@pytest.mark.parametrize("name", sorted(NAMES))
def test_entry_by_name(name):
    m = mf.load(ROOT)
    entry, = [e for e in m["per_layer"] if e["name"] == name]
    unit, layer = NAMES[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "peak_hbm_gb"}      # every cell: no workloads
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))
    for cell in m["workloads"]:
        assert entry in mf.metrics_of(m, "per_layer", cell["name"])


def _mark(in_use, peak, reserved, peak_reserved, **more):
    return dict({"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                 "bytes_reserved": reserved,
                 "peak_bytes_reserved": peak_reserved, "device": "TPU_0"},
                **more)


@pytest.fixture()
def synthetic():
    """A run on a clock that starts at 100: build 100-110, stage 110-111,
    warm-up 111-120, reference 120-123, window 124-144.  The state is 9 GB,
    the staged batches 0.25, the step's temporaries 6; the reference check
    holds 3 GB beside the state for a while, which is what raises the first
    peak for good."""
    spans = Spans()
    spans.records.extend([("bench.build", 100.0, 110.0, "MainThread"),
                          ("bench.stage", 110.0, 111.0, "MainThread"),
                          ("bench.warmup", 111.0, 120.0, "MainThread"),
                          ("bench.reference", 120.0, 123.0, "MainThread"),
                          ("bench.dispatch", 124.0, 124.1, "MainThread")])
    cell = {"t0": 124.0, "t1": 144.0, "chips": 1, "say": lambda line: None,
            "traffic": {"staged_batches": 2}}
    ledger = {"argument_bytes": 9 * GB + GB // 4, "output_bytes": 9 * GB,
              "alias_bytes": 9 * GB, "temp_bytes": 6 * GB,
              "generated_code_bytes": GB // 10}
    need = 9 * GB + GB // 4 + 6 * GB + GB // 10
    phase_marks = [
        (104.0, "init_params", _mark(3 * GB, 3 * GB, 0, 0)),
        (108.0, "init_opt_state", _mark(9 * GB, 9 * GB, 0, 0)),
        (109.0, "place", _mark(9 * GB, 9 * GB + GB // 2, 0, 0)),
        (110.9, "stage_batches", _mark(9 * GB + GB // 4, 9 * GB + GB // 2,
                                       0, 0)),
        (119.0, "first_call", _mark(9 * GB + GB // 4, 9 * GB + GB // 2,
                                    6 * GB, 6 * GB))]
    after = _mark(9 * GB + GB // 4 + GB // 100, 12 * GB + GB // 4, 6 * GB,
                  6 * GB)
    owners = {"params": 3 * GB, "opt_state": 6 * GB,
              "staged_batches": GB // 4, "unattributed": GB // 100}
    got = memory_account.reduce("toy.run_steps", ledger, need, after, owners,
                                phase_marks, spans.records, cell)
    got.update(largest=[], seconds=0.0, moved={}, temp_bytes=6 * GB,
               in_window={})
    memory_account._accounts[spans] = got
    return spans, cell, got


def test_the_six_readers_on_a_synthetic_account(synthetic):
    spans, cell, got = synthetic
    assert _read("step_state_gb", spans, cell) == 9.0
    assert _read("step_batches_gb", spans, cell) == 0.25
    assert _read("step_temp_gb", spans, cell) == 6.0
    assert _read("step_need_gb", spans, cell) == pytest.approx(15.35)
    # the two peaks together 18.25 GB: 2.9 of it is not the step
    assert _read("peak_over_step_gb", spans, cell) == pytest.approx(2.9)
    assert _read("step_need_gb", spans, cell) \
        + _read("peak_over_step_gb", spans, cell) == pytest.approx(
            got["peak_bytes"] / 1e9)
    # of 9.26 GB in use, 0.01 no owner holds
    assert _read("hbm_unattributed_share", spans, cell) == pytest.approx(
        100 * 0.01 / 9.26)


def test_which_stretch_raised_each_peak(synthetic):
    _, _, got = synthetic
    rose, before, at, stretches = got["raised"]["peak_bytes_in_use"]
    assert (rose, before, at) == (2 * GB + 3 * GB // 4, "first_call",
                                  "after the window")
    # between the first call's close and the reading after the window: the
    # rest of the warm-up, the reference check, the window
    assert stretches == ["bench.warmup", "bench.reference", "the window"]
    rose, before, at, stretches = got["raised"]["peak_bytes_reserved"]
    assert (rose, before, at) == (6 * GB, "stage_batches", "first_call")
    assert stretches == ["bench.stage", "bench.warmup"]
    assert memory_account.last_raise(
        [(1.0, "a", {"x": 0}), (2.0, "b", {"x": 0})], [], "x") is None


def test_an_estimate_gives_no_peak_and_prints_no_sum(synthetic):
    spans, cell, got = synthetic
    got["estimated"] = True
    lines = []
    memory_account._say(lines.append, got, "need: toy")
    assert _read("peak_over_step_gb", spans, cell) is None
    assert _read("step_need_gb", spans, cell) is not None
    assert not [line for line in lines if "two peaks together" in line]
    assert any("ESTIMATED" in line for line in lines)


def test_the_program_the_window_ran():
    ledgers = {"bert.step": {}, "bert.run_steps": {}}
    assert memory_account.program_of(
        ledgers, {"traffic": {"staged_batches": 4}}) == "bert.run_steps"
    assert memory_account.program_of(
        ledgers, {"traffic": {"host_pool": 8}}) == "bert.step"
    assert memory_account.program_of({}, {"traffic": {}}) is None


def test_a_tree_without_the_program_s_part_gives_nothing(monkeypatch):
    from paddle_tpu.monitor import memscope

    monkeypatch.delattr(memscope, "trainer_ledgers")
    spans = Spans()
    cell = {"t0": 1.0, "t1": 2.0, "chips": 1, "say": lambda line: None,
            "traffic": {"staged_batches": 2}}
    assert memory_account.account(spans, cell) is None
    for name in NAMES:
        assert _read(name, spans, cell) is None


@pytest.mark.parametrize("cell", ["bert_tiny.scan", "bert_tiny.dp4",
                                  "resnet_tiny.hostfed"])
def test_a_tiny_cell_reads_its_account(tmp_path, cell):
    import jax

    from benchmark.harness.cellrun import run_cell

    root, m = write_tree(tmp_path, mf.load(ROOT), CELLS)
    lines = []
    out = run_cell(root, m, cell, 5, 0.2, 1, time.perf_counter(),
                   jax.devices()[:CELLS[cell][2]], say=lines.append)
    assert out["correct"] is True, lines
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # the CPU keeps no allocator's peak: no share of one is given
    assert "peak_over_step_gb" not in got
    assert got["step_need_gb"] > got["step_temp_gb"] > 0
    assert got["step_need_gb"] >= got["step_state_gb"] + got["step_temp_gb"]
    assert got["step_state_gb"] > 0
    scanned = "scan" in cell or "dp4" in cell
    assert (got["step_batches_gb"] > 0) == scanned
    assert 0 <= got["hbm_unattributed_share"] < 5.0
    kind = ".run_steps" if scanned else ".step"
    said = "\n".join(lines)
    assert "memory account: program " in said and kind + " on " in said
    assert "need: " in said and "first_call" in said
    assert said.count("memory account:") == 1          # asked once a run
    assert ": 0 phases (so no watermark), 0 lowerings, 0 compiles" in said
    assert "asking moved no peak" in said
