"""What PR 67 adds to the benchmark: the ``solar_open2_250b`` configuration
file against the program's factory and the catalog's keys, the required
FLOPs of its step against a hand count, the kernels' needs, the eight new
readers on a synthetic reduced trace, the new cell's files, a tiny copy of
the configuration through the harness on the CPU (and one with a fault in its
reference), and the new entries looked up BY NAME: that they are PRESENT and
list the one cell, not where they stand (PERF.md section 7 (k))."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import solar_open2_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "solar_open2_250b", "solar_open2_250b.s4096_scan"
NEW = {"kda64_time_share": ("lower", "model code"),
       "kda64_chunk_time_share": ("lower", "kernels"),
       "kda64_chunk_roofline": ("higher", "kernels"),
       "kda64_outside_chunk_share": ("lower", "model code"),
       "gqa_nope_gated_time_share": ("lower", "model code"),
       "flash_gqa64q8_roofline": ("higher", "kernels"),
       "moe_held10of320_time_share": ("lower", "model code"),
       "moe_held10of320_roofline": ("higher", "kernels")}
LINEAR = {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
          "num_kv_heads": None}
GQA_LAYERS = [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44]
# the catalog's config of Solar-Open2-250B, as published
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": LINEAR, "hidden_size": 4096,
    "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": GQA_LAYERS,
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": 4, "n_routed_experts": 10,
           "vocab_size": 24576}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_the_catalog_s_row_is_the_published_config_here():
    """Where the catalog is installed, PUBLISHED is its row, key for key."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    row, = [r for r in rows if r["name"] == "Solar-Open2-250B"]
    assert row["config"] == PUBLISHED


def test_file_holds_every_published_key_but_the_three_reduced(config,
                                                              manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's, the nested group
    # whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "n_shared_experts",
                "linear_attn_config"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: a whole period of four (no leading dense layer), 10 >= 8
    # experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] == 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "router_width": PUBLISHED["n_routed_experts"],
        "first_expert_held": 0}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for text in ("48 -> 4", "320 -> 10", "196,608 -> 24,576"):
        assert any(text in v for v in config["changed"].values()), text
    for count in ("137.7 M", "109.1 M", "1,420.9 M", "11.37 GB",
                  "1,420,916,544", "13.97 GB"):
        assert count in config["changed"]["arithmetic"], count
    assert [k[0] for k in list(config["assumed"])] == list("abcdefgh")
    for key, word in (("a_kda_internals", "rank of head_dim 128"),
                      ("b_neg_eigval", "2 sigmoid"),
                      ("c_full_proj", "rank-128 pairs"),
                      ("d_gqa_form", "NOTHING is rotated"),
                      ("e_routing", "e_score_correction_bias"),
                      ("f_dense_width", "read by no layer"),
                      ("g_seeding", "5e-3 a step"),
                      ("h_training", "8 bytes")):
        assert word in config["assumed"][key], key
    assert "thirty-two v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.parallel import moe, transformer as T

    cfg = build._call(config["config_factory"])
    model = config["model"]
    n = cfg.n_layers
    kinds = cfg.prefix_kinds + cfg.layer_kinds * cfg.n_periods
    got = {
        "first_k_dense_replace": len(cfg.prefix_kinds),
        "hidden_size": cfg.hidden, "head_dim": cfg.head_dim,
        "max_position_embeddings": cfg.max_seq,
        "moe_intermediate_size": cfg.ffn_hidden,
        "norm_topk_prob": cfg.routing == moe.SIGMOID_BIASED,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "n_routed_experts": cfg.experts_here, "router_width": cfg.n_experts,
        "first_expert_held": cfg.first_expert,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": n,
        "n_shared_experts": cfg.shared_ffn_hidden // cfg.ffn_hidden,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "routed_scaling_factor": cfg.route_scale,
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "use_rope": cfg.positions is not None,
        "use_gqa_gate": cfg.attn_gate is True,
        "kda_allow_neg_eigval": cfg.kda_beta_scale == 2.0}
    assert got == {k: model[k] for k in got}
    # keys no layer reads, as published
    assert {k: model[k] for k in set(model) - set(got)} == {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "rope_theta": 10000, "intermediate_size": 10240, "gqa_interval": 3,
        "gqa_layers": GQA_LAYERS, "kda_use_full_proj": False,
        "linear_attn_config": LINEAR}
    # the published list, up to the depth held, is the stack's kinds
    assert [i for i, k in enumerate(kinds) if k != T.KDA] == [
        i for i in GQA_LAYERS if i < n]
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.d_conv) == (
        LINEAR["num_heads"], LINEAR["head_dim"],
        LINEAR["short_conv_kernel_size"])
    assert cfg.kda_gate_rank == solar_open2_train.GATE_RANK == 128
    assert cfg.kda_chunk == solar_open2_train.CHUNK == 64
    assert cfg.causal and cfg.remat and cfg.run_scan \
        and cfg.dtype == "bfloat16" and not cfg.dense_ffn_hidden
    assert cfg.router_input == "ffn" and cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size) == (
        48, 320, 196608)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_parameters_against_the_issue_s_count(config):
    """137.7 M a KDA mixer, 109.1 M a GQA one, 1,420.9 M held here."""
    E, P, R = 4096, 8192, 128
    kda = 4 * E * P + 2 * (E * R + R * P) + E * 64 + 3 * 4 * P + 64 + P + 128
    gqa = 3 * E * P + 2 * E * 1024
    expert = shared = 3 * E * 1280
    router, norms = E * 320, 2 * E
    assert (round(kda / 1e6, 1), round(gqa / 1e6, 1),
            round(expert / 1e6, 2), round(router / 1e6, 2)) == (
        137.7, 109.1, 15.73, 1.31)
    ffn = shared + router + 10 * expert + norms
    # the selection biases [4, 320] and the final norm beside the leaves
    held = gqa + 3 * kda + 4 * ffn + 2 * 24576 * E + E + 4 * 320
    assert held == 1_420_916_544 and round(8 * held / 1e9, 2) == 11.37
    assert round((shared + router + 320 * expert) / 1e9, 2) == 5.05
    # sixteen chips (20 held) would not leave room for a step
    assert round(8 * (held + 4 * 10 * expert) / 1e9, 1) == 16.4


def test_required_flops_against_a_hand_count(config):
    E, S, V, P, R = 4096, 4096, 24576, 8192, 128
    projections = 2 * (4 * E * P + 2 * (E * R + R * P) + E * 64)
    rule = 7 * 64 * 128 * 128
    gqa = 2 * E * (3 * P + 2 * 1024)
    pairs = 4 * 64 * 128 * (S + 1) / 2
    shared = 6 * E * 1280
    experts = 0.25 * 6 * E * 1280                       # 8 x 10 / 320 held
    router, head = 2 * E * 320, 2 * E * V
    assert (projections, rule, gqa, shared, experts, router, head) == (
        275_251_200, 7_340_032, 218_103_808, 31_457_280, 7_864_320,
        2_621_440, 201_326_592)
    assert round(pairs / 1e6, 1) == 67.1
    forward = 3 * (projections + rule) + gqa + pairs \
        + 4 * (shared + experts + router) + head
    assert round(forward / 1e6) == 1502
    got = solar_open2_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got * S / 1e12, 2) == 18.46            # TFLOP a step
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    parts = solar_open2_train.parts_per_token(config["model"], S)
    assert {k: round(v / forward, 2) for k, v in parts.items()} == {
        "kda": 0.56, "gqa": 0.19, "ffn": 0.11, "head": 0.13}
    assert solar_open2_train.layer_counts(config["model"]) == (3, 1)
    assert solar_open2_train.layer_counts(
        dict(config["model"], num_hidden_layers=48)) == (36, 12)


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S = 4096
    need = solar_open2_train.flash_gqa(model, 1, S)
    pairs = S * (S + 1) // 2 * 64
    assert need["fwd"]["flops"] == 4.0 * pairs * 128
    assert need["bwd"]["flops"] == 2 * need["fwd"]["flops"]
    assert need["fwd"]["bytes"] == 2 * S * (64 + 8) * 128 * 2
    sec, binds = flops.least_seconds(need["fwd"]["flops"],
                                     need["fwd"]["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 1.40
    rule = solar_open2_train.delta_rule(model, S)
    assert rule["flops"] == 3 * 7 * 64 * 128 * 128 * S
    operands = S * (4 * 8192 * 2 + 8192 * 4 + 64 * 4)
    states = 64 * 64 * 128 * 128 * 4
    assert rule["bytes"] == 3 * operands + 2 * states
    sec, binds = flops.least_seconds(rule["flops"], rule["bytes"], peaks)
    # the operands and a kept state a chunk: HBM binds, 2.1 ms a layer
    assert binds == "memory" and round(sec * 1e3, 2) == 2.13
    experts = solar_open2_train.expert_matmuls(model, S)
    assert solar_open2_train.held_experts_per_token(model) == 0.25
    assert experts["flops"] == 3 * 7_864_320 * S
    weights = 10 * 3 * 4096 * 1280 * 2
    rows = 1024 * 4096 * 2                  # a thirty-second of 32,768 pairs
    assert experts["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(experts["flops"], experts["bytes"],
                                     peaks)
    # 102 rows an expert: the weights' bytes bind, not the MXU
    assert binds == "memory" and round(sec * 1e3, 2) == 1.21


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of the cell's
# four layers (1 flash_bwd_fused = the one grouped-query layer; 8 tgmm = 2 a
# layer x 4)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                      # control flow
    ["fusion.1", 0, 2_000_000],                      # kda, forward
    ["fusion.2", 2_000_000, 2_000_000],              # kda, recomputed
    ["fusion.3", 4_000_000, 4_000_000],              # kda, backward
    ["kda_chunk_fwd.1", 8_000_000, 3_000_000],       # kda_chunk, forward
    ["kda_chunk_fwd.2", 11_000_000, 3_000_000],      # kda_chunk, recomputed
    ["kda_chunk_bwd.1", 14_000_000, 6_000_000],      # kda_chunk, backward
    ["fusion.7", 20_000_000, 1_500_000],             # attention projections
    ["fusion.8", 21_500_000, 500_000],               # the gate
    ["flash_fwd.1", 22_000_000, 1_000_000],
    ["flash_fwd.2", 23_000_000, 1_000_000],          # recomputed
    ["flash_bwd_fused.1", 24_000_000, 3_000_000],
] + [["gmm.%d" % i, 27_000_000 + 200_000 * i, 200_000] for i in range(16)]
  + [["tgmm.%d" % i, 30_200_000 + 100_000 * i, 100_000] for i in range(8)]
  + [["fusion.9", 31_000_000, 5_000_000]])]}         # lm_head
P = "jit(multi)/while/body/closed_call/"
MAPS = {"solar_open2.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/kda/kda/dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/kda/"
                    "kda/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/kda/kda/dot_general",
    "kda_chunk_fwd.1": P + "jvp()/while/body/closed_call/kda/kda/kda_chunk/"
                           "kda_chunk_fwd",
    "kda_chunk_fwd.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                           "kda/kda/kda_chunk/kda_chunk_fwd",
    "kda_chunk_bwd.1": P + "transpose(jvp())/checkpoint/kda/kda/kda_chunk/"
                           "kda_chunk_bwd",
    "fusion.7": P + "jvp()/while/body/closed_call/attention/dot_general",
    "fusion.8": P + "jvp()/while/body/closed_call/attention/attn_gate/mul",
    "flash_fwd.1": P + "jvp()/attention/flash_fwd",
    "flash_fwd.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "attention/flash_fwd",
    "flash_bwd_fused.1": P + "transpose(jvp())/checkpoint/attention/"
                             "flash_bwd_fused",
    **{"gmm.%d" % i: P + "jvp()/moe/moe/branch_0_fun/gmm" for i in range(16)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/branch_0_fun/tgmm"
       for i in range(8)},
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_eight_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # kda 2 + 2 + 4 ms, kda_chunk 3 + 3 + 6 ms
    assert read["kda64_time_share"] == pytest.approx(100 * 20 / 36)
    assert read["kda64_chunk_time_share"] == pytest.approx(100 * 12 / 36)
    assert read["kda64_outside_chunk_share"] == pytest.approx(100 * 8 / 36)
    # attention: 1.5 ms of projections, 0.5 of the gate, 5 ms of kernels
    assert read["gqa_nope_gated_time_share"] == pytest.approx(100 * 7 / 36)
    # gmm 16 x 0.2 and tgmm 8 x 0.1 ms
    assert read["moe_held10of320_time_share"] == pytest.approx(100 * 4 / 36)
    # one flash_bwd_fused = the grouped-query layer's backward: one step,
    # 3 KDA layers
    rule = solar_open2_train.delta_rule(config["model"], 4096)
    assert read["kda64_chunk_roofline"] == pytest.approx(
        100 * 3 * rule["bytes"] / 819e9 / 12e-3)
    need = solar_open2_train.flash_gqa(config["model"], 1, 4096)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) / 197e12
    assert read["flash_gqa64q8_roofline"] == pytest.approx(
        100 * least / 5e-3)
    # 8 tgmm events = 2 a layer and step x 4 layers: one step
    experts = solar_open2_train.expert_matmuls(config["model"], 4096)
    assert read["moe_held10of320_roofline"] == pytest.approx(
        100 * 4 * experts["bytes"] / 819e9 / 4e-3)
    for head, words in (
            ("moe_held10of320_roofline: least", ("1.000 steps traced",
                                                 "memory binds",
                                                 "16 gmm and 8 tgmm")),
            ("flash_gqa64q8_roofline: least", ("fwd 2 calls",
                                               "bwd 1 calls")),
            ("kda64_chunk_roofline: least", ("memory binds", "3 layers",
                                             "1.000 steps traced")),
            ("kda64_time_share: 0.020000 s", ("0.012000 s",)),
            ("kda_chunk_time_share: 0.012000 s", ()),
            ("kda64_outside_chunk_share: 0.008000 s",
             ("projections' least",)),
            ("gated_attn_time_share: 0.007000 s", ("0.000500 s",))):
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
    lost = dict(MAPS["solar_open2.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(16)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"solar_open2.run_steps": lost})
    for name in NEW:
        got = mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell)
        by_rule = name in ("kda64_time_share", "kda64_outside_chunk_share",
                           "gqa_nope_gated_time_share",
                           "moe_held10of320_time_share")
        assert (got is None) == by_rule, name


def test_new_entries_by_name(manifest):
    """Looked up by name, present, and each lists the one cell; where they
    stand in the lists is the next PR's to change (PERF.md section 7
    (k))."""
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
        assert callable(mf.module("layer_metrics", name).read)
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s4096_scan", 1) and len(cell["why"]) <= 200
    assert "balanced routing" in cell["why"] and "32x" in cell["why"]
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == NAME] == [CELL]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) == 19 and len(manifest["configs"]) == 15
    # the metrics that list no cells report in the new cell by themselves
    got = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert got >= set(NEW) | {"step_ms_p50", "window_lost_share",
                              "recompiles_in_window", "model_mfu",
                              "device_idle_share", "setup_init_s"}
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
        "batch": 1, "dims": {"S": 4096}, "staged_batches": 2,
        "trace_dispatches": 1}
    (ids,) = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0,
                          "high": config["vocab_size"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    from benchmark.reference import solar_open2_250b as reference

    assert len(reference.witness_positions(4096)) == 287
    assert "287 positions" in traffic["about"]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "solar_open2_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": dict(
        PUBLISHED, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2,
        linear_attn_config=dict(LINEAR, head_dim=16, num_heads=2),
        moe_intermediate_size=32, num_experts_per_tok=2, n_routed_experts=4,
        router_width=8, first_expert_held=4, num_hidden_layers=4,
        vocab_size=256),
    "config_factory": {
        "path": "paddle_tpu.models.solar_open2.solar_open2_tiny_config",
        "kwargs": {"remat": True}},
    "trainer_builder": {
        "path": "paddle_tpu.models.solar_open2.build_solar_open2_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "solar_open2_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "solar_open2_tiny.scan"
    traffic = {"driver": "train_scan_witnessed", "batch": 1,
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


@pytest.mark.parametrize("fault", ["beta_unscaled", "gqa_gate_dropped",
                                   "kv_head_mod"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import solar_open2_250b as reference

    assert fault in reference.FAULTS
    forward = reference.forward
    monkeypatch.setattr(
        reference, "forward",
        lambda params, ids, model, faults=(), **kw: forward(
            params, ids, model, tuple(faults) + (fault,), **kw))
    monkeypatch.setattr(reference, "_last", {})
    out, said, lines = _run_tiny(tmp_path, manifest, 0)
    assert out["correct"] is False
    assert not said("witness: ")["ok"]
