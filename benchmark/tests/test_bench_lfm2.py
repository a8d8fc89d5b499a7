"""What PR 33 adds to the benchmark: the ``lfm2_8b_a1b`` configuration file
against the program's factory and the catalog's keys, the required FLOPs of
its step against a hand count, the kernels' needs, the five new readers on
a synthetic reduced trace, the new cell's files, a tiny copy of the
configuration through the harness on the CPU (and one with a fault in its
reference), and the new entries looked up BY NAME (their place in the lists
is the next PR's to move: PERF.md section 7 (k))."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import flash_attention_gqa, lfm2_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "lfm2_8b_a1b", "lfm2_8b_a1b.s8192_scan"
NEW = {"short_conv_time_share": ("lower", "model code"),
       "short_conv_roofline": ("higher", "kernels"),
       "moe_biased_time_share": ("lower", "model code"),
       "moe_biased_roofline": ("higher", "kernels"),
       "flash_gqa64_roofline": ("higher", "kernels")}
LAYER_TYPES = ["full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
               for i in range(24)]
# the catalog's config of LFM2-8B-A1B, as published
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 9, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_four_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "conv_L_cache"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: a whole period and >= 4 layers after the dense one, >= 8
    # experts, >= 1/8 of the vocabulary
    after = config["num_hidden_layers"] - config["num_dense_layers"]
    assert after % 4 == 0 and after >= 4 and config["num_dense_layers"] >= 1
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "moe_router_width": PUBLISHED["num_experts"],
        "moe_first_expert_held": 0,
        "first_expert_layer": PUBLISHED["num_dense_layers"]}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for key in ("tie_word_embeddings", "dense_width", "bias_rule",
                "bias_seeding", "conv", "attention", "auxiliary_loss",
                "optimizer", "state_bytes", "remat", "documents", "ids"):
        assert key in config["assumed"], key
    assert "four v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.models import lfm2
    from paddle_tpu.parallel import moe, transformer as T

    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = {
        "conv_L_cache": cfg.conv_taps, "conv_bias": cfg.bias,
        "hidden_size": cfg.hidden, "intermediate_size": cfg.dense_ffn_hidden,
        "max_position_embeddings": cfg.max_seq, "model_type": "lfm2_moe",
        "moe_intermediate_size": cfg.ffn_hidden,
        "norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "norm_topk_prob": cfg.routing == moe.SIGMOID_BIASED,
        "use_expert_bias": cfg.routing == moe.SIGMOID_BIASED,
        "routed_scaling_factor": 1,
        "num_attention_heads": cfg.n_heads,
        "num_dense_layers": len(cfg.prefix_kinds),
        "num_experts": cfg.experts_here, "moe_router_width": cfg.n_experts,
        "moe_first_expert_held": cfg.first_expert,
        "first_expert_layer": lfm2.PUBLISHED_DENSE_LAYERS,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.kv_heads,
        "rope_theta": cfg.rope_theta if cfg.positions == "rotary" else None,
        "vocab_size": cfg.vocab_size}
    assert got == {k: v for k, v in model.items() if k != "layer_types"}
    assert cfg.head_dim * cfg.n_heads == cfg.hidden and cfg.head_dim == 64
    # layer_types stands whole; the program reads it at 0 and 2..9
    assert model["layer_types"] == LAYER_TYPES == list(lfm2.LAYER_TYPES)
    kinds = list(cfg.prefix_kinds) + list(cfg.layer_kinds) * cfg.n_periods
    assert ["conv" if k == T.CONV else "full_attention" for k in kinds] == \
        lfm2_train.layer_types(model) == \
        [LAYER_TYPES[i] for i in [0] + list(range(2, 10))]
    assert all(k == T.CONV or k == (None, True) for k in kinds)
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.qk_norm == "head" and cfg.tie_head and cfg.expert_act == "silu"
    assert cfg.router_input == "ffn" and cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    assert cfg.router_bias_rate == 1e-3
    # the published model's expert layers are the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.experts_here, full.vocab_size, len(full.prefix_kinds)) == (
        32, 65536, 2)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_required_flops_against_a_hand_count(config):
    E, S, V = 2048, 8192, 16384
    conv = 2 * E * 3 * E + 2 * E * E
    assert conv == 33_554_432
    projections = 2 * E * (2 * 2048 + 2 * 512)                # q, o, k, v
    pairs = 4 * 2048 * (S + 1) / 2                            # QK^T and PV
    assert projections == 20_971_520 and round(pairs / 1e6, 2) == 33.56
    dense = 6 * E * 7168
    experts = 1.0 * 6 * E * 1792                              # 4 x 8 / 32 held
    router = 2 * E * 32
    head = 2 * E * V
    assert (dense, experts, router, head) == (
        88_080_384, 22_020_096, 131_072, 67_108_864)
    forward = (7 * conv + 2 * (projections + pairs) + dense
               + 8 * (experts + router) + head)
    got = lfm2_train.per_unit(config["model"], {"S": S, "B": 2})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 2.03                        # ISSUE 33's
    assert flops.per_unit(config, {"S": S, "B": 2}) == got
    # the issue's shares of the forward pass
    for part, share in ((7 * conv, 0.35), (8 * (experts + router), 0.26),
                        (dense, 0.13), (2 * (projections + pairs), 0.16),
                        (head, 0.10)):
        assert round(part / forward, 2) == share
    # one period alone, the fallback
    one = lfm2_train.per_unit(dict(config["model"], num_hidden_layers=5),
                              {"S": S, "B": 2})
    assert one == pytest.approx(3.0 * (forward - 3 * conv - projections
                                       - pairs - 4 * (experts + router)))


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S, T = 8192, 16384
    need = flash_attention_gqa.required(2, S, 32, 8, 64)
    assert need["fwd"]["flops"] == 4.0 * 2 * (S * (S + 1) // 2) * 2048
    q, kv = 2 * S * 2048 * 2, 2 * S * 512 * 2      # q, o at 32 heads; k, v at 8
    assert need["fwd"]["bytes"] == 2 * q + 2 * kv
    assert need["bwd"]["bytes"] == 4 * q + 4 * kv
    assert flops.least_seconds(need["fwd"]["flops"], need["fwd"]["bytes"],
                               peaks)[1] == "compute"
    conv = lfm2_train.short_conv(model, T)
    assert conv["flops"] == 3 * 33_554_432 * T
    assert conv["bytes"] == 3 * (4 * 2048 * 2048 + 2 * T * 2048) * 2
    sec, binds = flops.least_seconds(conv["flops"], conv["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 8.37
    experts = lfm2_train.expert_matmuls(model, T)
    assert lfm2_train.held_experts_per_token(model) == 1.0
    assert experts["flops"] == 3 * 22_020_096 * T
    weights = 8 * 3 * 2048 * 1792 * 2
    rows = 16384 * 2048 * 2                         # a quarter of 65,536 pairs
    assert experts["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(experts["flops"], experts["bytes"],
                                     peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 5.49


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of the cell's
# nine layers (16 tgmm = 2 a layer x 8 expert layers)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                      # control flow
    ["fusion.1", 0, 4_000_000],                      # conv, forward
    ["fusion.2", 4_000_000, 4_000_000],              # conv, recomputed
    ["fusion.3", 8_000_000, 8_000_000],              # conv, backward
    ["fusion.4", 16_000_000, 1_000_000],             # router
    ["flash_fwd.1", 17_000_000, 1_000_000],
    ["flash_fwd.2", 18_000_000, 1_000_000],
    ["flash_fwd.3", 19_000_000, 1_000_000],          # two layers, recomputed
    ["flash_fwd.4", 20_000_000, 1_000_000],
    ["flash_bwd_dq.1", 21_000_000, 1_000_000],
    ["flash_bwd_dq.2", 22_000_000, 1_000_000],
    ["flash_bwd_dkv.1", 23_000_000, 1_000_000],
    ["flash_bwd_dkv.2", 24_000_000, 1_000_000],
] + [["gmm.%d" % i, 25_000_000 + 200_000 * i, 200_000] for i in range(32)] + [
    ["tgmm.%d" % i, 31_400_000 + 100_000 * i, 100_000] for i in range(16)] + [
    ["fusion.9", 33_000_000, 3_000_000],             # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"lfm2.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/short_conv/short_conv/"
                    "dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "short_conv/short_conv/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/short_conv/short_conv/"
                    "dot_general",
    "fusion.4": P + "jvp()/while/body/closed_call/moe/moe/router/dot_general",
    **{"flash_fwd.%d" % i: P + "jvp()/attention/flash_fwd"
       for i in (1, 2, 3, 4)},
    **{"flash_bwd_dq.%d" % i: P + "transpose(jvp())/checkpoint/attention/"
                                  "flash_bwd_dq" for i in (1, 2)},
    **{"flash_bwd_dkv.%d" % i: P + "transpose(jvp())/checkpoint/attention/"
                                   "flash_bwd_dkv" for i in (1, 2)},
    **{"gmm.%d" % i: P + "jvp()/moe/moe/branch_0_fun/gmm" for i in range(32)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/branch_0_fun/tgmm"
       for i in range(16)},
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_five_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # the scope short_conv: 4 + 4 + 8 ms of 36 busy, all phases
    assert read["short_conv_time_share"] == pytest.approx(100 * 16 / 36)
    # 16 tgmm events = 2 a layer and step x 8 expert layers: one step
    conv = 3 * 33_554_432 * 16384 / 197e12
    assert read["short_conv_roofline"] == pytest.approx(
        100 * 7 * conv / 16e-3)
    # moe + router scopes: 1 + 6.4 + 1.6 ms
    assert read["moe_biased_time_share"] == pytest.approx(100 * 9 / 36)
    experts = 3 * 22_020_096 * 16384 / 197e12
    assert read["moe_biased_roofline"] == pytest.approx(
        100 * 8 * experts / 8e-3)
    need = flash_attention_gqa.required(2, 8192, 32, 8, 64)
    least = (4 * need["fwd"]["flops"] + 2 * need["bwd"]["flops"]) / 197e12
    assert read["flash_gqa64_roofline"] == pytest.approx(100 * least / 8e-3)
    for head, words in (
            ("short_conv_roofline: least", ("compute binds", "7 layers",
                                            "1.000 steps traced")),
            ("moe_biased_roofline: least", ("1.000 steps traced",
                                            "32 gmm and 16 tgmm")),
            ("flash_gqa64_roofline: least", ("fwd 4 calls", "bwd 2 calls")),
            ("short_conv_time_share: 0.016000 s", ())):
        assert any(l.startswith(head) and all(w in l for w in words)
                   for l in lines), (head, lines)


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """No trace, an empty trace, a program without the scope or the kernels
    (the parent commit's): no number and no error."""
    cell = _cell(config, [], throughput=1e4)
    for name in NEW:
        read = mf.module("layer_metrics", name).read
        assert read(None, None, {}, cell) is None
        assert read(tr.Reduced({"planes": []}), None, {}, cell) is None
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"bert.run_steps": {
        "fusion.9": P + "jvp(lm_head)/lm_head/dot_general"}})
    bare = tr.Reduced({"planes": [_plane("/device:TPU:0", [
        ["while.4", 0, 20_000_000], ["fusion.9", 0, 1_000_000]])]})
    for name in NEW:
        assert mf.module("layer_metrics", name).read(
            bare, None, {}, cell) is None
    # lost scopes: over 5 % unattributed, the shares are not reported
    lost = dict(MAPS["lfm2.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(32)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"lfm2.run_steps": lost})
    for name in ("short_conv_time_share", "moe_biased_time_share"):
        assert mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell) is None


def test_new_entries_by_name(manifest):
    """Looked up by name: their distance from the end of the lists is the
    next PR's to change (PERF.md section 7 (k))."""
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
        assert callable(mf.module("layer_metrics", name).read)
    names = list(entries)
    assert [names.index(n) for n in NEW] == sorted(names.index(n) for n in NEW)
    assert min(names.index(n) for n in NEW) > names.index("moe_held_roofline")
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_scan", 1) and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics that list no cells report in the new cell by themselves
    got = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert got == set(NEW) | {"step_ms_p50", "window_lost_share",
                              "recompiles_in_window", "model_mfu",
                              "device_idle_share"}
    # no existing metric took the new cell, and no other cell the new ones
    for e in manifest["per_layer"]:
        if e["name"] not in NEW:
            assert CELL not in e.get("workloads", ())
    for w in manifest["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {e["name"] for e in mf.metrics_of(
                manifest, "per_layer", w["name"])}


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert {k: traffic[k] for k in ("driver", "mesh", "batch", "dims",
                                    "staged_batches", "trace_dispatches")} == {
        "driver": "train_scan_witnessed", "mesh": {"dp": 1, "pp": 1, "tp": 1},
        "batch": 2, "dims": {"S": 8192}, "staged_batches": 2,
        "trace_dispatches": 2}
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
    "name": "lfm2_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 5,
              "num_dense_layers": 1, "first_expert_layer": 2,
              "layer_types": LAYER_TYPES, "norm_eps": 1e-5,
              "rope_theta": 1000000, "conv_L_cache": 3,
              "num_experts_per_tok": 2, "num_experts": 2,
              "moe_router_width": 8, "moe_first_expert_held": 2,
              "norm_topk_prob": True, "use_expert_bias": True,
              "routed_scaling_factor": 1, "vocab_size": 256},
    "config_factory": {"path": "paddle_tpu.models.lfm2.lfm2_tiny_config",
                       "kwargs": {"remat": True}},
    "trainer_builder": {"path": "paddle_tpu.models.lfm2.build_lfm2_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "lfm2_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "lfm2_tiny.scan"
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
    the timed path's own first loss and of its logits, and the new readers
    finding no device plane."""
    out, said, lines = _run_tiny(tmp_path, manifest, trace)
    assert out["correct"] is True, lines
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert said("reference: ")["relative_error"] < 1e-5
    witness = said("witness: ")
    assert witness["ok"] and witness["logits_relative_error"] < 1e-5
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert not set(NEW) & set(out["metrics"])       # no device plane
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


@pytest.mark.parametrize("fault", ["gate_dropped", "acausal_taps",
                                   "wrong_kv_head"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``.  (A routing fault is not among
    them: at the tiny share, 2 of 8 experts and top-2, half the positions
    meet no held expert in any layer and the witness's first quartile is
    theirs; at the cell's sizes no position is such, PERF.md section 6.)"""
    from benchmark.reference import lfm2_8b_a1b as reference

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
