"""What PR 58 adds to the benchmark: the ``kimi_linear_48b_a3b``
configuration file against the program's factory and the catalog's keys, the
required FLOPs of its step against a hand count, the kernels' needs, the
seven new readers on a synthetic reduced trace, the new cell's files, a tiny
copy of the configuration through the harness on the CPU (and one with a
fault in its reference), and the new entries looked up BY NAME: that they
are PRESENT and list the one cell, not where they stand (PERF.md section 7
(k))."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import kimi_linear_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "kimi_linear_48b_a3b", "kimi_linear_48b_a3b.s16384_scan"
NEW = {"kda_time_share": ("lower", "model code"),
       "kda_chunk_time_share": ("lower", "kernels"),
       "kda_chunk_roofline": ("higher", "kernels"),
       "kda_outside_chunk_share": ("lower", "model code"),
       "mla_nope_time_share": ("lower", "model code"),
       "flash_qk192_v128_roofline": ("higher", "kernels"),
       "moe_held16of256_roofline": ("higher", "kernels")}
LINEAR = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
          "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                         21, 22, 23, 25, 26],
          "num_heads": 32, "short_conv_kernel_size": 4}
# the catalog's config of Kimi-Linear-48B-A3B-Instruct, as published
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": LINEAR, "mla_use_nope": True,
    "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 20480}


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
    row, = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
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
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "head_dim", "num_experts_per_token",
                "num_attention_heads", "num_shared_experts",
                "linear_attn_config"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: a whole period behind the dense layer, 16 >= 8 experts, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] == 1 + 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "moe_router_width": PUBLISHED["num_experts"],
        "moe_first_expert_held": 0}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for text in ("27 -> 5", "256 -> 16", "163,840 -> 20,480"):
        assert any(text in v for v in config["changed"].values()), text
    for count in ("39.51 M", "29.11 M", "828.9 M", "6.63 GB",
                  "828,926,848"):
        assert count in config["changed"]["arithmetic"], count
    assert [k[0] for k in list(config["assumed"])] == list("abcdefgh")
    for key, word in (("a_kda_gates", "rank of 128"),
                      ("b_l2_norm", "1e-6"), ("c_seeding", "U(1, 16)"),
                      ("d_latent_form", "192^-1/2"),
                      ("e_routing", "e_score_correction_bias"),
                      ("f_training", "no auxiliary loss"),
                      ("g_state_bytes", "8 bytes"),
                      ("h_bias_rule", "5e-3 a step")):
        assert word in config["assumed"][key], key
    assert "sixteen v5e chips" in config["deployment"]
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
        "hidden_act": cfg.expert_act, "hidden_size": cfg.hidden,
        "intermediate_size": cfg.dense_ffn_hidden,
        "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank or None,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "mla_use_nope": cfg.positions is None,
        "rope_scaling": cfg.rope_factor or None,
        "model_max_length": cfg.max_seq,
        "moe_intermediate_size": cfg.ffn_hidden,
        "moe_renormalize": cfg.routing == moe.SIGMOID_BIASED,
        "moe_router_activation_func":
            "sigmoid" if cfg.routing == moe.SIGMOID_BIASED else None,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "num_experts": cfg.experts_here, "moe_router_width": cfg.n_experts,
        "moe_first_expert_held": cfg.first_expert,
        "num_experts_per_token": cfg.experts_per_token,
        "num_hidden_layers": n,
        "num_shared_experts": cfg.shared_ffn_hidden // cfg.ffn_hidden,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "routed_scaling_factor": cfg.route_scale,
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size}
    assert got == {k: model[k] for k in got}
    # keys no layer reads, as published
    assert {k: model[k] for k in set(model) - set(got)} == {
        "head_dim": 72, "model_type": "kimi_linear", "moe_layer_freq": 1,
        "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
        "num_nextn_predict_layers": 0, "rope_theta": 10000,
        "linear_attn_config": LINEAR}
    # the published lists, up to the depth held, are the stack's kinds
    assert [i + 1 for i, k in enumerate(kinds) if k == T.KDA] == [
        i for i in LINEAR["kda_layers"] if i <= n]
    assert [i + 1 for i, k in enumerate(kinds) if k != T.KDA] == [
        i for i in LINEAR["full_attn_layers"] if i <= n]
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.d_conv) == (
        LINEAR["num_heads"], LINEAR["head_dim"],
        LINEAR["short_conv_kernel_size"])
    assert cfg.kda_gate_rank == kimi_linear_train.GATE_RANK == 128
    assert cfg.kda_chunk == kimi_linear_train.CHUNK == 64
    assert cfg.head_dim == 192 and cfg.causal and cfg.remat \
        and cfg.run_scan and cfg.dtype == "bfloat16"
    assert cfg.router_input == "ffn" and cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    # the published model is the factory's default, less its last half period
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size) == (
        25, 256, 163840)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_parameters_against_the_issue_s_count(config):
    """39.51 M a KDA mixer, 29.11 M a latent one, 828.9 M held here."""
    E, P, R = 2304, 4096, 128
    kda = 4 * E * P + 2 * (E * R + R * P) + E * 32 + 3 * 4 * P + 32 + P + 128
    latent = E * 6144 + E * 576 + 512 + 512 * 8192 + P * E
    expert = shared = 3 * E * 1024
    router, dense, norms = E * 256, 3 * E * 9216, 2 * E
    assert (round(kda / 1e6, 2), round(latent / 1e6, 2),
            round(expert / 1e6, 2), round(dense / 1e6, 2)) == (
        39.51, 29.11, 7.08, 63.70)
    sparse = shared + router + 16 * expert + norms
    first = kda + dense + norms
    assert round(first / 1e6, 2) == 103.22
    assert round((kda + sparse) / 1e6, 2) == 160.43
    assert round((latent + sparse) / 1e6, 2) == 150.03
    # the selection biases [4, 256] and the final norm beside the leaves
    held = first + 3 * (kda + sparse) + latent + sparse \
        + 2 * 20480 * E + E + 4 * 256
    assert held == 828_926_848 and round(8 * held / 1e9, 2) == 6.63
    assert round((shared + router + 256 * expert) / 1e9, 2) == 1.82


def test_required_flops_against_a_hand_count(config):
    E, S, V, P, R = 2304, 16384, 20480, 4096, 128
    projections = 2 * (4 * E * P + 2 * (E * R + R * P) + E * 32)
    rule = 7 * 32 * 128 * 128
    chains = 2 * (E * 6144 + E * 576 + 512 * 8192 + P * E)
    pairs = 2 * 32 * (192 + 128) * (S + 1) / 2
    dense, shared = 6 * E * 9216, 6 * E * 1024
    experts = 0.5 * 6 * E * 1024                        # 8 x 16 / 256 held
    router, head = 2 * E * 256, 2 * E * V
    assert (projections, rule, chains, dense, shared, experts, router,
            head) == (78_921_728, 3_670_016, 58_228_736, 127_401_984,
                      14_155_776, 7_077_888, 1_179_648, 94_371_840)
    assert round(pairs / 1e6, 1) == 167.8
    forward = 4 * (projections + rule) + chains + pairs + dense \
        + 4 * (shared + experts + router) + head
    assert round(forward / 1e6) == 868
    got = kimi_linear_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 2.60
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    parts = kimi_linear_train.parts_per_token(config["model"], S)
    assert {k: round(v / forward, 2) for k, v in parts.items()} == {
        "kda": 0.38, "latent": 0.26, "ffn": 0.25, "head": 0.11}
    assert kimi_linear_train.layer_counts(config["model"]) == (4, 1, 1, 4)
    assert kimi_linear_train.layer_counts(
        dict(config["model"], num_hidden_layers=27)) == (20, 7, 1, 26)


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S = 16384
    assert kimi_linear_train.head_dim(model) == 192
    need = kimi_linear_train.flash_two_widths(model, 1, S)
    pairs = S * (S + 1) // 2 * 32
    assert need["fwd"]["flops"] == 2.0 * pairs * 320
    assert need["bwd"]["flops"] == 2 * need["fwd"]["flops"]
    assert need["fwd"]["bytes"] == 2 * S * 32 * (192 + 128) * 2
    sec, binds = flops.least_seconds(need["fwd"]["flops"],
                                     need["fwd"]["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 13.95
    rule = kimi_linear_train.delta_rule(model, S)
    assert rule["flops"] == 3 * 7 * 32 * 128 * 128 * S
    operands = S * (4 * 4096 * 2 + 4096 * 4 + 32 * 4)
    states = 256 * 32 * 128 * 128 * 4
    assert rule["bytes"] == 3 * operands + 2 * states
    sec, binds = flops.least_seconds(rule["flops"], rule["bytes"], peaks)
    # the operands and a kept state a chunk: HBM binds, 4.3 ms a layer
    assert binds == "memory" and round(sec * 1e3, 2) == 4.27
    experts = kimi_linear_train.expert_matmuls(model, S)
    assert kimi_linear_train.held_experts_per_token(model) == 0.5
    assert experts["flops"] == 3 * 7_077_888 * S
    weights = 16 * 3 * 2304 * 1024 * 2
    rows = 8192 * 2304 * 2                  # a sixteenth of 131,072 pairs
    assert experts["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(experts["flops"], experts["bytes"],
                                     peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 1.77


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of the cell's
# five layers (1 flash_bwd_fused = the one latent layer; 8 tgmm = 2 a sparse
# layer x 4)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                      # control flow
    ["fusion.1", 0, 2_000_000],                      # kda, forward
    ["fusion.2", 2_000_000, 2_000_000],              # kda, recomputed
    ["fusion.3", 4_000_000, 4_000_000],              # kda, backward
    ["fusion.4", 8_000_000, 3_000_000],              # kda_chunk, forward
    ["fusion.5", 11_000_000, 3_000_000],             # kda_chunk, recomputed
    ["fusion.6", 14_000_000, 6_000_000],             # kda_chunk, backward
    ["fusion.7", 20_000_000, 2_000_000],             # latent chains
    ["flash_fwd.1", 22_000_000, 1_000_000],
    ["flash_fwd.2", 23_000_000, 1_000_000],          # recomputed
    ["flash_bwd_fused.1", 24_000_000, 3_000_000],
] + [["gmm.%d" % i, 27_000_000 + 200_000 * i, 200_000] for i in range(16)]
  + [["tgmm.%d" % i, 30_200_000 + 100_000 * i, 100_000] for i in range(8)]
  + [["fusion.9", 31_000_000, 5_000_000]])]}         # lm_head
P = "jit(multi)/while/body/closed_call/"
MAPS = {"kimi_linear.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/kda/kda/dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/kda/"
                    "kda/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/kda/kda/dot_general",
    "fusion.4": P + "jvp()/while/body/closed_call/kda/kda/kda_chunk/"
                    "checkpoint/while/body/dot_general",
    "fusion.5": P + "transpose(jvp())/checkpoint/rematted_computation/kda/"
                    "kda/kda_chunk/checkpoint/while/body/dot_general",
    "fusion.6": P + "transpose(jvp())/checkpoint/kda/kda/kda_chunk/"
                    "checkpoint/while/body/dot_general",
    "fusion.7": P + "jvp()/while/body/closed_call/latent_attention/"
                    "dot_general",
    "flash_fwd.1": P + "jvp()/latent_attention/flash_fwd",
    "flash_fwd.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "latent_attention/flash_fwd",
    "flash_bwd_fused.1": P + "transpose(jvp())/checkpoint/latent_attention/"
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


def test_the_seven_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # kda 2 + 2 + 4 ms, kda_chunk 3 + 3 + 6 ms
    assert read["kda_time_share"] == pytest.approx(100 * 20 / 36)
    assert read["kda_chunk_time_share"] == pytest.approx(100 * 12 / 36)
    assert read["kda_outside_chunk_share"] == pytest.approx(100 * 8 / 36)
    # latent_attention: 2 ms of chains and 5 ms of kernels
    assert read["mla_nope_time_share"] == pytest.approx(100 * 7 / 36)
    # one flash_bwd_fused = one latent layer's backward: one step, 4 KDA
    # layers
    rule = kimi_linear_train.delta_rule(config["model"], 16384)
    assert read["kda_chunk_roofline"] == pytest.approx(
        100 * 4 * rule["bytes"] / 819e9 / 12e-3)
    need = kimi_linear_train.flash_two_widths(config["model"], 1, 16384)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) / 197e12
    assert read["flash_qk192_v128_roofline"] == pytest.approx(
        100 * least / 5e-3)
    # 8 tgmm events = 2 a layer and step x 4 sparse layers: one step
    experts = 3 * 7_077_888 * 16384 / 197e12
    assert read["moe_held16of256_roofline"] == pytest.approx(
        100 * 4 * experts / 4e-3)
    for head, words in (
            ("moe_held16of256_roofline: least", ("1.000 steps traced",
                                                 "16 gmm and 8 tgmm")),
            ("flash_qk192_v128_roofline: least", ("fwd 2 calls",
                                                  "bwd 1 calls")),
            ("kda_chunk_roofline: least", ("memory binds", "4 layers",
                                           "1.000 steps traced")),
            ("kda_time_share: 0.020000 s", ("0.012000 s",)),
            ("kda_chunk_time_share: 0.012000 s", ()),
            ("kda_outside_chunk_share: 0.008000 s", ("projections' least",)),
            ("mla_nope_time_share: 0.007000 s", ())):
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
    lost = dict(MAPS["kimi_linear.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(16)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"kimi_linear.run_steps": lost})
    for name in NEW:
        got = mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell)
        by_rule = name in ("kda_time_share", "kda_outside_chunk_share",
                           "mla_nope_time_share")
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
        NAME, "s16384_scan", 1) and len(cell["why"]) <= 200
    assert "balanced routing" in cell["why"] and "16x" in cell["why"]
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == NAME] == [CELL]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
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
        "batch": 1, "dims": {"S": 16384}, "staged_batches": 2,
        "trace_dispatches": 1}
    (ids,) = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0,
                          "high": config["vocab_size"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    from benchmark.reference import kimi_linear_48b_a3b as reference

    assert len(reference.witness_positions(16384)) == 296
    assert "296 positions" in traffic["about"]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "kimi_linear_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": dict(
        PUBLISHED, hidden_size=64, intermediate_size=96, kv_lora_rank=32,
        num_attention_heads=2, num_key_value_heads=2,
        linear_attn_config=dict(LINEAR, head_dim=16, num_heads=2),
        moe_intermediate_size=32, num_experts_per_token=2, num_experts=4,
        moe_router_width=8, moe_first_expert_held=4, num_hidden_layers=5,
        vocab_size=256),
    "config_factory": {
        "path": "paddle_tpu.models.kimi_linear.kimi_linear_tiny_config",
        "kwargs": {"remat": True}},
    "trainer_builder": {
        "path": "paddle_tpu.models.kimi_linear.build_kimi_linear_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "kimi_linear_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "kimi_linear_tiny.scan"
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


@pytest.mark.parametrize("fault", ["no_subtraction", "gate_before_norm",
                                   "shared_key_rotated"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import kimi_linear_48b_a3b as reference

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
