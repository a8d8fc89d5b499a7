"""The harness is driven by data: a new configuration, a new cell and a new
per-layer entry are files and manifest entries, and no harness file is
edited to run them.  Also: no chip, no number; names and units; the last
line's keys."""

import copy
import io
import json
import os
import time
from contextlib import redirect_stdout

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness.device import NoChip, check_devices
from benchmark.harness.peaks import UnlistedDevice

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_BERT = {
    "name": "bert_tiny", "unit_of_work": "token", "units_per_step": ["B", "S"],
    "model": {"hidden_size": 32, "num_hidden_layers": 4,
              "num_attention_heads": 4, "intermediate_size": 64,
              "vocab_size": 128},
    "config_factory": {"path": "paddle_tpu.models.bert.bert_tiny_config",
                       "kwargs": {}},
    "trainer_builder": {"path": "paddle_tpu.models.bert.build_bert_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.lamb", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-3,
    "batch_fields": [
        {"name": "labels", "shape": ["B", "S"], "dtype": "int32",
         "gen": {"kind": "randint", "low": 0, "high": 128}},
        {"name": "mask", "shape": ["B", "S"], "dtype": "float32",
         "gen": {"kind": "k_hot", "k": "P"}},
        {"name": "ids", "shape": ["B", "S"], "dtype": "int32",
         "gen": {"kind": "masked_copy", "of": "labels", "mask": "mask",
                 "fill": 3}}],
    "flops": "transformer_mlm_train",
    "reference": "bert_base"}

TINY_RESNET = {
    "name": "resnet_tiny", "unit_of_work": "image", "units_per_step": ["B"],
    "model": {"depth": 18, "width": 8, "num_classes": 10, "image_size": 32},
    "config_factory": {"path": "paddle_tpu.models.resnet.resnet_tiny_config",
                       "kwargs": {}},
    "trainer_builder": {
        "path": "paddle_tpu.models.resnet.build_resnet_trainer", "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.momentum",
                  "kwargs": {"mu": 0.9}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-2,
    "batch_fields": [
        {"name": "image", "shape": ["B", 32, 32, 3], "dtype": "float32",
         "feed_dtype": "uint8", "where": "device",
         "gen": {"kind": "randint", "low": 0, "high": 256}},
        {"name": "label", "shape": ["B"], "dtype": "int32",
         "gen": {"kind": "randint", "low": 0, "high": 10}}],
    "flops": "resnet_train",
    "reference": "resnet50"}

SCAN = {"driver": "train_scan", "batch": 8, "staged_batches": 2,
        "trace_dispatches": 1}
HOSTFED = {"driver": "train_hostfed", "batch": 8, "host_pool": 3,
           "trace_host_level": 0,
           "trace_seconds": 0.2, "trace_lead_steps": 2}
ONE = {"dp": 1, "pp": 1, "tp": 1}
BERT_DIMS = {"S": 32, "P": 5}

# cell -> (configuration, traffic, chips): every pairing of the two tiny
# configurations with the two drivers, and the dp=4 layout
CELLS = {
    "bert_tiny.scan": (TINY_BERT, dict(SCAN, mesh=ONE, dims=BERT_DIMS), 1),
    "bert_tiny.dp4": (TINY_BERT, dict(SCAN, mesh=dict(ONE, dp=4),
                                      dims=BERT_DIMS), 4),
    "bert_tiny.hostfed": (TINY_BERT, dict(HOSTFED, mesh=ONE,
                                          dims=BERT_DIMS), 1),
    "resnet_tiny.scan": (TINY_RESNET, dict(SCAN, mesh=ONE, dims={}), 1),
    "resnet_tiny.hostfed": (TINY_RESNET, dict(HOSTFED, mesh=ONE, dims={}), 1),
}


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def write_tree(tmp_path, manifest, cells):
    """A tree that holds ONLY new data files and a manifest with new
    entries; the harness code it runs through is the repo's, untouched."""
    m = copy.deepcopy(manifest)
    m["configs"], m["workloads"] = [], []
    for kind in ("end_to_end", "per_layer"):
        for e in m[kind]:
            e.pop("workloads", None)
    os.makedirs(tmp_path / "benchmark" / "configs")
    os.makedirs(tmp_path / "benchmark" / "traffic")
    for cell, (config, traffic, chips) in cells.items():
        path = "benchmark/configs/%s.json" % config["name"]
        (tmp_path / path).write_text(json.dumps(config))
        (tmp_path / "benchmark" / "traffic" / (cell + ".json")).write_text(
            json.dumps(traffic))
        if not any(c["name"] == config["name"] for c in m["configs"]):
            m["configs"].append({"name": config["name"], "source": "test",
                                 "file": path, "reduced": [], "why": "tiny"})
        m["workloads"].append({"name": cell, "config": config["name"],
                               "traffic": cell.split(".")[1], "chips": chips,
                               "why": "tiny"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    return str(tmp_path), mf.load(str(tmp_path))


@pytest.fixture()
def temp_tree(tmp_path, manifest):
    return write_tree(tmp_path, manifest, CELLS)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_new_files_run_through_both_drivers(temp_tree, cell, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    root, m = temp_tree
    chips = CELLS[cell][2]
    lines = []
    out = run_cell(root, m, cell, seed=3, seconds=0.3, trace=trace,
                   t_start=time.perf_counter(),
                   devices=jax.devices()[:chips], say=lines.append)
    assert out["correct"] is True, lines
    assert out["attempted"] >= 1 and out["failed"] == 0
    names = {e["name"] for e in mf.metrics_of(
        m, "per_layer" if trace else "end_to_end", cell)}
    assert set(out["metrics"]) <= names
    if not trace:
        assert set(out["metrics"]) == names
        assert out["metrics"]["train_throughput"]["value"] > 0
    else:
        # the CPU gives no device plane: the trace readers find nothing and
        # their metrics are left out, the host-side ones are there
        assert "step_ms_p50" in out["metrics"]
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert "device_idle_share" not in out["metrics"]
        assert "model_mfu" not in out["metrics"]
        if "hostfed" in cell:
            assert out["metrics"]["feed_stall_share"]["value"] >= 0
            assert out["metrics"]["h2d_ms_p50"]["value"] > 0
    assert os.path.exists(os.path.join(root, "benchmark", "out", cell,
                                       "last_run.json"))


def test_sustained_rate_leaves_a_single_stall_out():
    from benchmark.harness.cellrun import sustained_rate

    # 10 units a mark, a mark every 0.1 s, one stall of 3 s in 20 s
    marks, t = [], 0.0
    for i in range(170):
        t += 3.1 if i == 80 else 0.1
        marks.append(t)
    assert sustained_rate(marks, 10) == pytest.approx(100.0)
    # units over the whole window would read 15 % low
    assert 170 * 10 / marks[-1] < 86
    # what slows every slice shows: every fourth step twice as long
    marks, t = [], 0.0
    for i in range(200):
        t += 0.2 if i % 4 == 0 else 0.1
        marks.append(t)
    assert sustained_rate(marks, 10) == pytest.approx(80.0)
    # dispatches longer than a slice: one slice each
    assert sustained_rate([1.8, 3.6, 5.4], 100) == pytest.approx(100 / 1.8)
    assert sustained_rate([0.1, 0.2], 10) is None
    assert sustained_rate([], 10) is None


def test_what_the_median_leaves_out_is_reported(temp_tree):
    """Units over the whole window are kept beside the sustained rate, and
    ``window_lost_share`` is the part of the one the other does not hold."""
    import jax

    from benchmark.harness.cellrun import run_cell
    from benchmark.layer_metrics import window_lost_share

    root, m = temp_tree
    for cell, units_per_dispatch in (("bert_tiny.scan", 2 * 8 * 32),
                                     ("resnet_tiny.hostfed", 8)):
        lines = []
        out = run_cell(root, m, cell, 2, 0.3, 1, time.perf_counter(),
                       jax.devices()[:1], say=lines.append)
        with open(os.path.join(root, "benchmark", "out", cell,
                               "last_run.json")) as f:
            last = json.load(f)
        assert last["window_rate"] == pytest.approx(
            out["attempted"] * units_per_dispatch / last["window_s"])
        assert any("%.6f units/s over the whole window" % last["window_rate"]
                   in l for l in lines)
        assert out["metrics"]["window_lost_share"]["value"] == pytest.approx(
            100 * (1 - last["window_rate"]
                   / last["end_to_end"]["train_throughput"]))
    assert window_lost_share.read(None, None, {}, {
        "throughput": 100.0, "window_rate": 84.0}) == pytest.approx(16.0)
    assert window_lost_share.read(None, None, {}, {}) is None


NEW_FLOPS = '''
def per_unit(model, dims):
    return 6.0 * model["hidden_size"] * dims["S"]
'''

NEW_GENERATOR = '''
"""Skewed integers in [0, high): rank r with weight 1 / (r + 1)."""
import numpy as np


def _p(gen):
    w = 1.0 / np.arange(1, gen["high"] + 1)
    return w / w.sum()


def host(rng, shape, dtype, gen, dims, made):
    return rng.choice(gen["high"], size=shape, p=_p(gen)).astype(dtype)


def device(key, shape, dtype, gen, dims):
    import jax
    import jax.numpy as jnp

    return jax.random.choice(key, gen["high"], shape,
                             p=jnp.asarray(_p(gen))).astype(dtype)
'''


def test_a_new_family_brings_its_flops_and_generator_as_files(
        tmp_path, manifest, monkeypatch):
    """What the next model family needs beyond data files -- a FLOP count
    and a kind of generated field (host-made and device-made) -- is a new
    file under ``benchmark/flops`` and ``benchmark/generators``, found by
    the name in the configuration; no harness file is edited.  The new
    files are written outside the repo and put on the two packages' paths,
    which is what adding them to the directories does."""
    import jax

    import benchmark.flops
    import benchmark.generators
    from benchmark.harness.cellrun import run_cell
    from benchmark.layer_metrics import model_mfu

    for pkg, name, text in ((benchmark.flops, "toy_train", NEW_FLOPS),
                            (benchmark.generators, "skewed", NEW_GENERATOR)):
        d = tmp_path / pkg.__name__.rpartition(".")[2]
        os.makedirs(d)
        (d / (name + ".py")).write_text(text)
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(d)])

    skewed = {"kind": "skewed", "high": 128}
    bert = dict(TINY_BERT, name="bert_skewed", flops="toy_train",
                batch_fields=[dict(TINY_BERT["batch_fields"][0], gen=skewed)]
                + TINY_BERT["batch_fields"][1:])
    resnet = dict(TINY_RESNET, name="resnet_skewed", batch_fields=[
        dict(TINY_RESNET["batch_fields"][0], gen=skewed),
        TINY_RESNET["batch_fields"][1]])
    cells = {"bert_skewed.scan": (bert, dict(SCAN, mesh=ONE, dims=BERT_DIMS),
                                  1),
             "resnet_skewed.scan": (resnet, dict(SCAN, mesh=ONE, dims={}), 1)}
    root, m = write_tree(tmp_path / "tree", manifest, cells)
    for cell in cells:
        lines = []
        out = run_cell(root, m, cell, 4, 0.2, 0, time.perf_counter(),
                       jax.devices()[:1], say=lines.append)
        assert out["correct"] is True, lines
    said = []
    got = model_mfu.read(None, None, {}, {
        "config": bert, "dims": dict(BERT_DIMS, B=8), "chips": 1,
        "peaks": {"bf16_flops": 1e9}, "throughput": 1000.0,
        "say": said.append})
    assert got == pytest.approx(100.0 * 1000.0 * 6 * 32 * 32 / 1e9)
    # a configuration that names no count: the reader finds nothing
    no_count = {k: v for k, v in bert.items() if k != "flops"}
    assert model_mfu.read(None, None, {}, {
        "config": no_count, "dims": BERT_DIMS, "chips": 1,
        "peaks": {"bf16_flops": 1e9}, "throughput": 1.0,
        "say": said.append}) is None


def test_a_generator_without_a_device_form_is_refused_on_the_device():
    from benchmark.harness import batches

    field = dict(TINY_BERT["batch_fields"][1], where="device")
    with pytest.raises(ValueError, match="not made on the device"):
        batches.device_staged(field, dict(BERT_DIMS, B=8), 0, 2, None)


@pytest.mark.parametrize("name,model,dims,want", [
    # 3 * (12 * (8E^2 + 4EF + 4SE) + 2EV * 80/512), ISSUE 22's arithmetic
    ("transformer_mlm_train",
     {"hidden_size": 768, "intermediate_size": 3072, "num_hidden_layers": 12,
      "vocab_size": 30528}, {"S": 512, "P": 80},
     3 * (12 * (4718592 + 9437184 + 1572864) + 46891008 * 80 / 512)),
    # torchvision's count of ResNet-50's multiply-accumulates, x 2 x 3
    ("resnet_train", {"depth": 50, "width": 64, "num_classes": 1000,
                      "image_size": 224}, {}, 6 * 4089184256),
])
def test_required_flops(name, model, dims, want):
    from benchmark.harness import flops

    assert flops.per_unit({"flops": name, "model": model}, dims) == want


def test_flash_attention_needs_and_roofs():
    from benchmark.flops import flash_attention
    from benchmark.harness import flops

    need = flash_attention.required(64, 512, 768)
    assert need["fwd"]["flops"] == 4 * 64 * 512 * 512 * 768
    assert need["bwd"]["flops"] == 2 * need["fwd"]["flops"]
    assert need["fwd"]["bytes"] == 4 * 64 * 512 * 768 * 2
    assert flash_attention.required(64, 512, 768, causal=True)["fwd"][
        "flops"] == need["fwd"]["flops"] / 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    sec, binds = flops.least_seconds(need["fwd"]["flops"],
                                     need["fwd"]["bytes"], peaks)
    assert binds == "compute" and sec == need["fwd"]["flops"] / 197e12
    assert flops.least_seconds(1.0, 819e9, peaks) == (1.0, "memory")


def test_same_seed_same_batches():
    from benchmark.harness import batches

    dims = {"B": 4, "S": 32, "P": 5}
    a = batches.host_batch(TINY_BERT["batch_fields"], dims, 7, 0)
    b = batches.host_batch(TINY_BERT["batch_fields"], dims, 7, 0)
    c = batches.host_batch(TINY_BERT["batch_fields"], dims, 8, 0)
    assert all((a[k] == b[k]).all() for k in a)
    assert any((a[k] != c[k]).any() for k in a)
    assert (a["mask"].sum(axis=1) == 5).all()
    assert (a["ids"][a["mask"] == 1] == 3).all()
    assert (a["ids"][a["mask"] == 0] == a["labels"][a["mask"] == 0]).all()


def test_host_spans_on_the_trace_clock(tmp_path):
    """Where the host tracer has to stay off, the benchmark's host-clock
    spans are laid on the trace's clock through a throwaway trace."""
    import jax.numpy as jnp

    from benchmark.harness import tracing
    from benchmark.harness.cellrun import Ctx
    from benchmark.harness.spans import Spans

    ctx = Ctx()
    ctx.out_dir, ctx.spans = str(tmp_path), Spans()
    ctx.traffic = {"trace_host_level": 0}
    jnp.ones(3).block_until_ready()
    with ctx.spans.span("bench.before"):
        pass
    time.sleep(0.02)    # start_trace takes 50 us here: keep clear of jitter
    tracing.start(ctx)
    assert ctx.spans.annotate is False
    with ctx.spans.span("bench.dispatch"):
        time.sleep(0.01)
    tracing.stop(ctx)
    (name, start, end), = tracing.anchored(ctx)
    assert name == "bench.dispatch"
    assert 0 <= start < 5e6 and 10e6 <= end - start < 15e6


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices,chips,error", [
    ([_FakeDevice("cpu", "cpu")], 1, NoChip),
    ([], 1, NoChip),
    ([_FakeDevice("tpu", "TPU v5 lite")], 4, NoChip),
    ([_FakeDevice("tpu", "TPU v9 imaginary")], 1, UnlistedDevice),
])
def test_no_chip_no_number(devices, chips, error):
    with pytest.raises(error):
        check_devices(devices, chips)


def test_listed_chip_passes():
    devs = [_FakeDevice("tpu", "TPU v5 lite")] * 4
    used, peaks = check_devices(devs, 4)
    assert len(used) == 4 and peaks["bf16_flops"] == 197e12


def test_run_py_refuses_the_cpu(manifest):
    from benchmark import run

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", manifest["workloads"][0]["name"],
                       "--seed", "0", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert buf.getvalue().strip() == ""


@pytest.mark.parametrize("where,value", [
    (("workloads", 0, "name"), "has space"),
    (("workloads", 0, "name"), "a/b"),
    (("workloads", 0, "traffic"), "x,y"),
    (("configs", 0, "name"), "x" * 65),
    (("end_to_end", 0, "unit"), "tokens per second"),
    (("end_to_end", 0, "unit"), "µs"),
    (("per_layer", 0, "name"), "étape"),
    (("per_layer", 0, "better"), "bigger"),
    (("per_layer", 0, "source"), "guess"),
    (("end_to_end", 0, "source"), "program_span"),
    (("workloads", 0, "chips"), 2),
])
def test_manifest_refuses(manifest, where, value):
    m = copy.deepcopy(manifest)
    m[where[0]][where[1]][where[2]] = value
    with pytest.raises(mf.ManifestError):
        mf.validate(m)


def test_manifest_refuses_unknown_metric_key(manifest):
    m = copy.deepcopy(manifest)
    m["per_layer"][0]["why"] = "no such key"
    with pytest.raises(mf.ManifestError):
        mf.validate(m)


def test_manifest_of_the_repo_names_files_that_exist(manifest):
    for c in manifest["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["name"] + ".json"))
    for e in manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", e["name"] + ".py"))
    for c in manifest["configs"]:
        config = mf.read_json(ROOT, c["file"])
        for kind, names in (
                ("flops", [config["flops"]]),
                ("reference", [config["reference"]]),
                ("generators", [f["gen"]["kind"]
                                for f in config["batch_fields"]])):
            for name in names:
                assert os.path.exists(os.path.join(
                    ROOT, "benchmark", kind, name + ".py")), (kind, name)
    for w in manifest["workloads"]:
        assert mf.metrics_of(manifest, "per_layer", w["name"])
        assert len(w["why"]) <= 200


def test_last_line_keys(temp_tree):
    """The last line carries exactly the contract's keys."""
    import jax

    from benchmark.harness.cellrun import run_cell

    root, m = temp_tree
    out = run_cell(root, m, "bert_tiny.scan", 1, 0.2, 0, time.perf_counter(),
                   jax.devices()[:1], say=lambda s: None)
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"}
    json.dumps(out)
