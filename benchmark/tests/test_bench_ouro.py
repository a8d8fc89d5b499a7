"""What PR 54 adds to the benchmark: the ``ouro_2_6b`` configuration file
against the program's factory and the catalog's keys, the required FLOPs of
its looped step against a hand count, the four new readers on a synthetic
reduced trace (and reading nothing without their scope or kernels, or where
too much time carries no scope), the new cell's files, a tiny copy of the
configuration through the harness on the CPU (and one with a fault in its
reference), and the new entries: additions after the existing ones, nothing
else changed."""

import importlib
import json
import os
import subprocess
import time

import pytest

from benchmark.flops import ouro_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "ouro_2_6b", "ouro_2_6b.s4096_scan"
NEW = {"loop_scan_time_share": ("lower", "model code"),
       "exit_heads_time_share": ("lower", "model code"),
       "loop_mlp_time_share": ("lower", "model code"),
       "flash_mha16_roofline": ("higher", "kernels")}
# the catalog's config of Ouro-2.6B, as published
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4,
    "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152}
REDUCED = {"num_hidden_layers": 12}
ASSUMED = {"exit_entropy_coef": 0.1}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_one_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's, and the passes
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "vocab_size",
                "total_ut_steps"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == ASSUMED
    assert set(config["changed"]) == set(REDUCED) | {
        "arithmetic", "shares", "compiler_count"}
    for key in ("sandwich_norms", "loop", "exit_gate", "loss", "logits",
                "attention", "rotary_pairing", "seeding", "optimizer",
                "compute_dtype", "state_bytes", "remat", "sequence_length",
                "documents", "labels", "ids"):
        assert key in config["assumed"], key
    assert "modeling_ouro.py" in config["assumed"]["sandwich_norms"]
    assert "DEPARTURE" in config["assumed"]["seeding"]
    assert "RECALLS" in config["assumed"]["sequence_length"]
    assert "RING of four" in config["deployment"]
    assert "GB" in config["changed"]["compiler_count"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    cfg = build._call(config["config_factory"])
    assert cfg.layer_kinds == ((None, True),)       # full, rotary: every layer
    got = {
        "head_dim": cfg.head_dim, "hidden_act": cfg.expert_act,
        "hidden_size": cfg.hidden, "intermediate_size": cfg.dense_ffn_hidden,
        "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": cfg.max_seq, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": cfg.n_heads,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.kv_heads,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_scaling": None,
        "rope_theta": cfg.rope_theta if cfg.positions == "rotary" else None,
        "sliding_window": None, "tie_word_embeddings": cfg.tie_head,
        "total_ut_steps": cfg.loop_passes, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": cfg.vocab_size,
        "exit_entropy_coef": cfg.exit_entropy_coef}
    assert got == config["model"]
    assert cfg.dense_stack and cfg.post_norm and not cfg.per_position \
        and not cfg.n_experts and not cfg.bias and not cfg.qk_norm
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.tp == cfg.pp == 1
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.vocab_size, full.loop_passes) == (
        48, 49152, 4)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_required_flops_against_a_hand_count(config):
    E, S, V, F, H, dh = 2048, 4096, 49152, 5632, 16, 128
    matmuls = 2 * (4 * E * H * dh + 3 * E * F)
    assert matmuls == 2 * 51_380_224
    scores = 4 * dh * H * (S + 1) / 2
    assert (round(matmuls / 1e6, 1), round(scores / 1e6, 1)) == (102.8, 16.8)
    layer = matmuls + scores
    assert layer == ouro_train.layer_flops_per_token(config["model"], S)
    exits = 2 * E * V + 2 * E
    assert exits == ouro_train.exit_flops_per_token(config["model"])
    forward = 4 * (12 * layer + exits)
    got = ouro_train.per_unit(config["model"], {"S": S, "B": 2})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(forward / 1e9, 2) == 6.54 and round(got / 1e9, 1) == 19.6
    assert flops.per_unit(config, {"S": S, "B": 2}) == got
    # linear in the passes: a leaf used four times is multiplied four times
    once = ouro_train.per_unit(dict(config["model"], total_ut_steps=1),
                               {"S": S, "B": 2})
    assert got == pytest.approx(4 * once, rel=1e-12)
    # the issue's shares of the forward pass: the four heads 12 % (3.4 % in
    # the whole model), attention 14 % of a layer, the FFN 51 % of the step
    assert round(4 * 2 * E * V / forward, 2) == 0.12
    whole = 4 * (48 * layer + exits)
    assert round(4 * 2 * E * V / whole, 3) == 0.034
    assert round((scores + 2 * 2 * E * H * dh) / layer, 2) == 0.28
    assert round(scores / layer, 2) == 0.14
    assert round(48 * 6 * E * F / forward, 2) == 0.51
    # the parameters by the same widths: the published 2.6B, and the cut
    per_layer = 4 * E * E + 3 * E * F + 4 * E
    assert per_layer == 51_388_416
    assert 48 * per_layer + 2 * V * E + E + E + 1 == 2_667_974_657
    params = 12 * per_layer + 2 * V * E + E + E + 1
    assert params == 817_991_681 and round(params * 8 / 1e9, 2) == 6.54


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 800_000_000]]}]}


# one device, a traced stretch of 800 ms, busy 760 ms: a quarter of a step
# (ONE pass of 12 layers: 24 flash forwards under remat, 12 backward)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 800_000_000],                     # control flow
    ["fusion.1", 0, 20_000_000],                     # the passes' own
    ["fusion.2", 20_000_000, 30_000_000],            # the layers' scan's own
    ["fusion.3", 50_000_000, 10_000_000],            # the gate
    ["fusion.4", 60_000_000, 90_000_000],            # the heads
] + [["flash_fwd.%d" % i, 150_000_000 + 2_000_000 * i, 2_000_000]
     for i in range(24)] + [
    ["flash_bwd_fused.%d" % i, 198_000_000 + 6_000_000 * i, 6_000_000]
    for i in range(12)] + [
    ["fusion.7", 270_000_000, 100_000_000],          # attention projections
    ["fusion.8", 370_000_000, 390_000_000],          # mlp
])]}
P = "jit(multi)/while/body/closed_call/"
LOOP = P + "jvp(loop_scan)/while/body/"
MAPS = {"ouro.run_steps": {
    "fusion.1": P + "transpose(jvp(loop_scan))/while/body/add_any",
    "fusion.2": LOOP + "layer_scan/while/body/dynamic_slice",
    "fusion.3": LOOP + "exit_gate/dot_general",
    "fusion.4": P + "jvp(lm_head)/lm_head/while/body/dot_general",
    **{"flash_fwd.%d" % i: LOOP + "layer_scan/while/body/closed_call/"
       "checkpoint/attention/flash_fwd" for i in range(24)},
    **{"flash_bwd_fused.%d" % i: P + "transpose(jvp(loop_scan))/while/body/"
       "layer_scan/while/body/closed_call/checkpoint/attention/"
       "flash_bwd_fused" for i in range(12)},
    "fusion.7": LOOP + "layer_scan/while/body/closed_call/checkpoint/"
                       "attention/dot_general",
    "fusion.8": LOOP + "layer_scan/while/body/closed_call/checkpoint/mlp/"
                       "dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_four_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(760e-3)
    cell = _cell(config, lines, throughput=4500.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    assert read["loop_scan_time_share"] == pytest.approx(100 * 50 / 760)
    assert read["exit_heads_time_share"] == pytest.approx(100 * 100 / 760)
    assert read["loop_mlp_time_share"] == pytest.approx(100 * 390 / 760)
    # 24 forward calls and 12 backward of 16 heads of 128 at B = 2, causal
    pairs = 2 * 4096 * 4096 / 2 * 16 * 128
    assert read["flash_mha16_roofline"] == pytest.approx(
        100 * (24 * 4 * pairs + 12 * 8 * pairs) / 197e12 / 120e-3)
    assert read["flash_mha16_roofline"] < 100
    for head, words in (
            ("loop_scan_time_share: 0.020000 s under loop_scan",
             ("0.030000 s under layer_scan",)),
            ("exit_heads_time_share: 0.010000 s under exit_gate",
             ("0.090000 s under lm_head",)),
            ("loop_mlp_time_share: 0.390000 s under mlp", ()),
            ("flash_mha16_roofline: least", ("of 0.120000 s taken",))):
        assert any(l.startswith(head) and all(w in l for w in words)
                   for l in lines), (head, lines)
    assert not any(l.startswith("flash_causal_roofline") for l in lines)
    # model_mfu reads the configuration's own count
    mfu = mf.module("layer_metrics", "model_mfu").read(trace, None, {}, cell)
    assert mfu == pytest.approx(100 * 4500.0 * 19.62998e9 / 197e12, rel=1e-5)
    assert mfu < 100


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """No trace, an empty trace, a program without the scope or the kernels
    (the parent commit's, a plain stack's), another configuration's cell: no
    number and no error."""
    cell = _cell(config, [], throughput=4500.0)
    for name in NEW:
        read = mf.module("layer_metrics", name).read
        assert read(None, None, {}, cell) is None
        assert read(tr.Reduced({"planes": []}), None, {}, cell) is None
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    # a plain stack: ``mlp``, ``lm_head`` and ``layer_scan`` without the loop
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"bert.run_steps": {
        "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
        "fusion.8": P + "jvp(layer_scan)/while/body/mlp/dot_general",
        "fusion.2": P + "jvp(layer_scan)/while/body/dynamic_slice"}})
    bare = tr.Reduced({"planes": [_plane("/device:TPU:0", [
        ["while.4", 0, 20_000_000], ["fusion.9", 0, 1_000_000],
        ["fusion.8", 1_000_000, 1_000_000],
        ["fusion.2", 2_000_000, 1_000_000]])]})
    for name in NEW:
        assert mf.module("layer_metrics", name).read(
            bare, None, {}, cell) is None
    # the flash reader on a configuration that is not looped
    other = mf.read_json(ROOT, "benchmark", "configs", "olmoe_1b_7b.json")
    assert mf.module("layer_metrics", "flash_mha16_roofline").read(
        tr.Reduced(TRACE), None, {}, dict(cell, config=other)) is None
    # lost scopes: over 5 % unattributed, the shares of the scopes are not
    # reported and say why; the kernels' own, by name, are
    lost = dict(MAPS["ouro.run_steps"], **{"fusion.7": "copy-fusion"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"ouro.run_steps": lost})
    lines = []
    for name in ("loop_scan_time_share", "exit_heads_time_share",
                 "loop_mlp_time_share"):
        assert mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, dict(cell, say=lines.append)) is None
        assert any(l.startswith(name) and "carries no scope" in l
                   for l in lines), (name, lines)
    assert mf.module("layer_metrics", "flash_mha16_roofline").read(
        tr.Reduced(TRACE), None, {}, cell) is not None


def test_new_entries_are_additions_at_the_end(manifest):
    """The configuration, the cell and the four metrics stand after
    everything the parent commit's file holds, and nothing that was there
    changed (read off git where the checkout has the parent)."""
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
        assert callable(mf.module("layer_metrics", name).read)
    names = {key: [e["name"] for e in manifest[key]]
             for key in ("configs", "workloads", "per_layer")}
    at = names["per_layer"].index("loop_scan_time_share")
    assert names["per_layer"][at:at + 4] == list(NEW)
    assert names["per_layer"][at - 1] == "flash_gqa16_roofline"
    assert names["configs"].index(NAME) == 1 + names["configs"].index(
        "nemotron3_nano_30b_a3b")
    assert names["workloads"].index(CELL) == 1 + names["workloads"].index(
        "nemotron3_nano_30b_a3b.s8192_scan")
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s4096_scan", 1) and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics that list no cells report in the new cell by themselves
    got = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert got == set(NEW) | {
        "step_ms_p50", "window_lost_share", "recompiles_in_window",
        "model_mfu", "device_idle_share", "setup_init_s",
        "setup_trace_lower_s", "setup_compile_s", "setup_cache_misses",
        "setup_unattributed_share", "step_state_gb", "step_batches_gb",
        "step_temp_gb", "step_need_gb", "peak_over_step_gb",
        "hbm_unattributed_share"}
    # no existing metric took the new cell, and no other cell the new ones
    for e in manifest["per_layer"]:
        if e["name"] not in NEW:
            assert CELL not in e.get("workloads", ())
    for w in manifest["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {e["name"] for e in mf.metrics_of(
                manifest, "per_layer", w["name"])}
    try:
        before = json.loads(subprocess.run(
            ["git", "show", "37c698c:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        return          # a checkout without the parent commit
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert manifest[key] == before[key], key
    for key in ("configs", "workloads", "per_layer"):
        assert manifest[key][:len(before[key])] == before[key], key


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert {k: traffic[k] for k in ("driver", "mesh", "batch", "dims",
                                    "staged_batches", "trace_dispatches")} == {
        "driver": "train_scan_witnessed", "mesh": {"dp": 1, "pp": 1, "tp": 1},
        "batch": 2, "dims": {"S": 4096}, "staged_batches": 2,
        "trace_dispatches": 1}
    (ids,) = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0,
                          "high": config["vocab_size"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "ouro_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "head_dim": 16,
              "intermediate_size": 96, "rms_norm_eps": 1e-6,
              "rope_theta": 1e6, "num_hidden_layers": 2,
              "total_ut_steps": 3, "exit_entropy_coef": 0.1,
              "vocab_size": 256, "tie_word_embeddings": False,
              "rope_scaling": None, "sliding_window": None,
              "layer_types": ["full_attention"] * 48},
    "config_factory": {"path": "paddle_tpu.models.ouro.ouro_tiny_config",
                       "kwargs": {"remat": True}},
    "trainer_builder": {"path": "paddle_tpu.models.ouro.build_ouro_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "ouro_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "ouro_tiny.scan"
    traffic = {"driver": "train_scan_witnessed", "batch": 2,
               "staged_batches": 2, "trace_dispatches": 1,
               "mesh": {"dp": 1, "pp": 1, "tp": 1}, "dims": {"S": 64}}
    root, m = write_tree(tmp_path, manifest, {cell: (TINY, traffic, 1)})
    lines = []
    out = run_cell(root, m, cell, seed=2147483659, seconds=0.3, trace=trace,
                   t_start=time.perf_counter(), devices=jax.devices()[:1],
                   say=lines.append)

    def said(head):
        return json.loads([l for l in lines if l.startswith(head)][0]
                          [len(head):])

    return out, said, lines


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_copy_runs_through_the_harness(tmp_path, manifest, trace):
    """The configuration's files through ``run_cell`` on the CPU at the
    tiny size: builder, the witnessed scan driver, the reference's check of
    the timed path's own first loss and of its weighted-exit logits in both
    groups, and the new readers finding no device plane."""
    out, said, lines = _run_tiny(tmp_path, manifest, trace)
    assert out["correct"] is True, lines
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert said("reference: ")["relative_error"] < 1e-5
    witness = said("witness: ")
    # float32 against float32, in units of a bfloat16 rounding
    assert witness["ok"] and witness["logits_relative_error"] < 1e-3
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert not set(NEW) & set(out["metrics"])       # no device plane
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


@pytest.mark.parametrize("fault", ["one_pass", "no_norm_between_passes",
                                   "fresh_leaves_each_pass"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import ouro_2_6b as reference

    assert fault in reference.FAULTS
    forward = reference.forward
    monkeypatch.setattr(
        reference, "forward",
        lambda params, ids, model, faults=(), **kw: forward(
            params, ids, model, tuple(faults) + (fault,), **kw))
    monkeypatch.setattr(reference, "_last", {})
    out, said, lines = _run_tiny(tmp_path, manifest, 0)
    witness = said("witness: ")
    assert not witness["ok"], lines
    assert witness["logits_relative_error"] > reference.LOGITS_TOLERANCE
    assert out["correct"] is False and out["failed"] == 0
