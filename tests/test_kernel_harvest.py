"""KernelHarvest receipts: bench mfu_ceiling_rel emission, the
perf_ledger mfu_ceiling_rel gate (tolerated-absent for historical
snapshots) and chip_microbench sparse probes + --json artifact."""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "scripts"))


# ---------------------------------------------------------------------------
# bench.py _emit / _roofline_from
# ---------------------------------------------------------------------------

def test_emit_attaches_mfu_ceiling_rel(capsys):
    import bench

    bench._emit({"metric": "m1", "mfu": 0.2,
                 "mfu_ceiling_memroofline": 0.25})
    bench._emit({"metric": "m2", "mfu": 0.2})          # no ceiling -> no rel
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert out[0]["mfu_ceiling_rel"] == 0.8
    assert "mfu_ceiling_rel" not in out[1]


def test_roofline_from_derives_and_stays_absent():
    import bench

    v5e = bench.PEAKS["TPU v5 lite"]
    r = bench._roofline_from(1e12, 1e10, v5e)
    assert r["roofline_ai_flops_per_byte"] == 100.0
    assert 0 < r["mfu_ceiling_memroofline"] <= 1.0
    assert bench._roofline_from(0, 1e10, v5e) == {}
    assert bench._roofline_from(1e12, 1e10, None) == {}   # cpu: no peaks


def test_env_cpu_has_no_peaks_and_unknown_accelerator_raises(monkeypatch):
    import types

    import bench
    import jax

    assert bench._env()[1:] == (False, None)
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(RuntimeError, match="TPU v99"):
        bench._env()


def test_bench_all_runs_every_config_and_exits_nonzero_on_failure(
        monkeypatch, capsys):
    import bench

    ran = []

    def ok(name):
        return lambda: ran.append(name)

    def boom():
        ran.append("nmt")
        raise RuntimeError("mosaic refused the kernel")

    monkeypatch.setattr(bench, "bench_resnet50", ok("resnet50"))
    monkeypatch.setattr(bench, "bench_nmt", boom)
    monkeypatch.setattr(bench, "bench_deepfm", ok("deepfm"))
    monkeypatch.setattr(bench, "bench_bert", ok("bert"))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--model", "all"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    assert ran == ["resnet50", "nmt", "deepfm", "bert"]   # none hidden
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metric"] == "nmt" and "mosaic refused" in line["error"]
    assert line["platform"] == "cpu" and "mfu" not in line


# ---------------------------------------------------------------------------
# perf_ledger: the committed history must gate green with the new field,
# and a measured-then-regressed mfu_ceiling_rel must fail naming it
# ---------------------------------------------------------------------------

def _snap(tmp_path, label, recs):
    lines = "\n".join(json.dumps(r) for r in recs)
    (tmp_path / ("BENCH_%s.json" % label)).write_text(
        json.dumps({"rc": 0, "tail": lines}))


def test_perf_ledger_history_green_with_new_field(tmp_path):
    """A history whose older snapshots predate the ceiling fields (r01-r04
    carried none, r05 carried the ceiling but no ratio) gates green."""
    import perf_ledger

    assert "mfu_ceiling_rel" in perf_ledger.CHECK_FIELDS
    _snap(tmp_path, "r04", [{"metric": "x", "value": 2595.0, "mfu": 0.1643}])
    _snap(tmp_path, "r05", [{"metric": "x", "value": 2619.2, "mfu": 0.1631,
                             "mfu_ceiling_memroofline": 0.249}])
    assert perf_ledger.main(["--history-dir", str(tmp_path),
                             "--check"]) == 0


def test_perf_ledger_gates_ceiling_rel_regression(tmp_path, capsys):
    import perf_ledger

    _snap(tmp_path, "r01", [{"metric": "x", "value": 100.0, "mfu": 0.2,
                             "mfu_ceiling_rel": 0.8}])
    _snap(tmp_path, "r02", [{"metric": "x", "value": 101.0, "mfu": 0.2,
                             "mfu_ceiling_rel": 0.5}])
    rc = perf_ledger.main(["--history-dir", str(tmp_path), "--check"])
    err = capsys.readouterr().err
    assert rc == 2
    assert "mfu_ceiling_rel" in err and "metric=x" in err


def test_perf_ledger_tolerates_absent_ceiling_rel(tmp_path):
    import perf_ledger

    # history never measured a ceiling; the new snapshot measures one for
    # the first time -> no prior point, not gated
    _snap(tmp_path, "r01", [{"metric": "x", "value": 100.0, "mfu": 0.2}])
    _snap(tmp_path, "r02", [{"metric": "x", "value": 101.0, "mfu": 0.2,
                             "mfu_ceiling_rel": 0.4}])
    assert perf_ledger.main(["--history-dir", str(tmp_path),
                             "--check"]) == 0
    # and a snapshot that STOPS measuring it is likewise not gated
    _snap(tmp_path, "r03", [{"metric": "x", "value": 102.0, "mfu": 0.2}])
    assert perf_ledger.main(["--history-dir", str(tmp_path),
                             "--check"]) == 0


def test_perf_ledger_derives_rel_from_old_ceiling_records(tmp_path):
    """r05-era records carry mfu + mfu_ceiling_memroofline but no explicit
    ratio; the ledger derives it so the trend row is continuous."""
    import perf_ledger

    _snap(tmp_path, "r01", [{"metric": "x", "value": 1.0, "mfu": 0.163,
                             "mfu_ceiling_memroofline": 0.249}])
    _snap(tmp_path, "r02", [{"metric": "x", "value": 1.0, "mfu": 0.2,
                             "mfu_ceiling_rel": 0.81}])
    runs = perf_ledger.load_history(str(tmp_path))
    trend, _ = perf_ledger.build_trend(runs)
    series = dict(trend["x"]["mfu_ceiling_rel"])
    assert abs(series["r01"] - 0.163 / 0.249) < 1e-6
    assert series["r02"] == 0.81


# ---------------------------------------------------------------------------
# chip_microbench: sparse probes + machine-readable artifact
# ---------------------------------------------------------------------------

def test_chip_microbench_sparse_json(tmp_path):
    import chip_microbench

    out = tmp_path / "chip.json"
    rc = chip_microbench.main([
        "--probe", "sparse", "--vocab", "2000", "--batch", "64",
        "--fields", "4", "--dim", "5", "--iters", "2",
        "--json", str(out)])
    assert rc == 0
    art = json.loads(out.read_text())
    names = [r["name"] for r in art["probes"]]
    assert any("gather" in n for n in names)
    assert any("scatter-add dup" in n for n in names)
    assert any("sorted-unique" in n for n in names)
    assert any("segment-kernel" in n for n in names)
    for r in art["probes"]:
        # gbps can round to 0.00 at these deliberately tiny CPU shapes;
        # presence + a positive time/bytes model is the artifact contract
        assert r["ms"] > 0 and "gbps" in r and r["bytes_model"] > 0
    roof = art["sparse_roofline"]
    assert roof["deepfm_step_floor_ms"] > 0
    assert roof["deepfm_examples_per_sec_ceiling"] > 0
    assert roof["best_update"] in ("scatter-add dup",
                                   "scatter-add sorted-unique",
                                   "segment-kernel")
    # the floor is self-consistent with its ingredients (each field is
    # independently rounded to 4 decimals, so allow that much slack)
    assert abs(roof["deepfm_step_floor_ms"]
               - (roof["gather_ms"] + roof["best_update_ms"])) < 5e-4
