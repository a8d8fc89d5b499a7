"""What PR 45 adds to the benchmark: the ``trinity_large_preview``
configuration file against the program's factory and the catalog's keys, the
required FLOPs of its step against a hand count, the kernels' needs, the
seven new readers on a synthetic reduced trace, the new cell's files, a tiny
copy of the configuration through the harness on the CPU (and one with a
fault in its reference), and the new entries looked up BY NAME: that they
are PRESENT and list the one cell, not where they stand (PERF.md section 7
(k))."""

import importlib
import json
import math
import os
import time

import pytest

from benchmark.flops import flash_attention_gqa, trinity_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "trinity_large_preview", "trinity_large_preview.s6144_scan"
NEW = {"gated_attn_time_share": ("lower", "model code"),
       "gated_attn_outside_flash_share": ("lower", "model code"),
       "swa_gqa6_flash_roofline": ("higher", "kernels"),
       "post_norm_time_share": ("lower", "model code"),
       "shared_expert_w3072_time_share": ("lower", "model code"),
       "moe_held8of256_time_share": ("lower", "model code"),
       "moe_held8of256_roofline": ("higher", "kernels")}
LAYER_TYPES = ["full_attention" if i % 4 == 3 else "sliding_attention"
               for i in range(60)]
# the catalog's config of Trinity-Large-Preview, as published
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": LAYER_TYPES, "load_balance_coeff": 5e-05,
    "max_position_embeddings": 262144, "model_type": "afmoe",
    "moe_intermediate_size": 3072, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 48, "num_dense_layers": 6, "num_expert_groups": 1,
    "num_experts": 256, "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 25024}


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
    row, = [r for r in rows if r["name"] == "Trinity-Large-Preview"]
    assert row["config"] == PUBLISHED


def test_file_holds_every_published_key_but_the_four_reduced(config,
                                                             manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "num_shared_experts", "sliding_window",
                "route_scale"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: the dense layers once and one period of four sparse layers, 8
    # routed experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["num_dense_layers"] == 4
    assert config["num_experts"] == 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "moe_router_width": PUBLISHED["num_experts"],
        "moe_first_expert_held": 0,
        "first_expert_layer": PUBLISHED["num_dense_layers"]}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for text in ("60 -> 5", "6 -> 1", "256 -> 8", "200,192 -> 25,024"):
        assert any(text in v for v in config["changed"].values()), text
    for count in ("1,604.0 M", "12.83 GB", "62.91 M", "398.6 G",
                  "at most 16.4 GB"):
        assert count in config["changed"]["arithmetic"], count
    assert [k[0] for k in config["assumed"]] == list("abcdefghijk")
    for key, word in (("a_output_gate", "BEFORE o_proj"),
                      ("b_qk_norm", "each head"),
                      ("c_positions", "rotate-half"),
                      ("d_norms", "SUM"),
                      ("e_embedding_multiplier", "sqrt(hidden_size)"),
                      ("f_router", "1e-20"),
                      ("g_bias_rule", "SMEBU"),
                      ("h_training", "AdamW"),
                      ("i_seeded_init", "N(0, 1/3072)"),
                      ("j_state_bytes", "8 bytes")):
        assert word in config["assumed"][key], key
    assert "thirty-two v5e chips" in config["deployment"]
    assert "layers 5 to 9" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.models import trinity
    from paddle_tpu.parallel import moe

    cfg = build._call(config["config_factory"])
    model = config["model"]
    window, = {k[0] for k in cfg.layer_kinds + cfg.prefix_kinds if k[0]}
    got = {
        "global_attn_every_n_layers": len(cfg.layer_kinds),
        "head_dim": cfg.head_dim, "hidden_act": cfg.expert_act,
        "hidden_size": cfg.hidden, "intermediate_size": cfg.dense_ffn_hidden,
        "layer_types": list(trinity.LAYER_TYPES),
        "load_balance_coeff": cfg.router_bias_rate,
        "max_position_embeddings": cfg.max_seq, "model_type": "afmoe",
        "moe_intermediate_size": cfg.ffn_hidden,
        "mup_enabled": cfg.embed_scale == math.sqrt(cfg.hidden),
        "n_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
        "topk_group": 1, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "num_dense_layers": len(cfg.prefix_kinds),
        "first_expert_layer": trinity.PUBLISHED_DENSE_LAYERS,
        "num_experts": cfg.experts_here, "moe_router_width": cfg.n_experts,
        "moe_first_expert_held": cfg.first_expert,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers,
        "num_shared_experts": cfg.shared_ffn_hidden // cfg.ffn_hidden,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_scaling": None, "rope_theta": cfg.rope_theta,
        "route_norm": cfg.routing == moe.SIGMOID_BIASED,
        "route_scale": cfg.route_scale,
        "score_func": "sigmoid" if cfg.routing == moe.SIGMOID_BIASED
        else None,
        "sliding_window": window, "tie_word_embeddings": cfg.tie_head,
        "use_grouped_mm": True, "vocab_size": cfg.vocab_size}
    assert got == model
    # the kinds the factory builds are layer_types at published layers 5..9
    kinds = [(window, True) if model["layer_types"][i] == "sliding_attention"
             else (None, False) for i in trinity_train.layer_indices(model)]
    assert trinity_train.layer_indices(model) == [5, 6, 7, 8, 9]
    assert list(cfg.prefix_kinds + cfg.layer_kinds) == kinds
    assert cfg.attn_gate and cfg.post_norm and cfg.qk_norm == "head"
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.positions == "rotary" and cfg.router_input == "ffn"
    assert cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, len(full.prefix_kinds), full.experts_here,
            full.vocab_size) == (58, 6, 256, 200192)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_parameters_against_the_issue_s_count(config):
    """62.91 M of attention a layer, 28.31 M an expert, 1,604.0 M held
    here, 398.6 G published."""
    E = 3072
    attention = 3 * E * 6144 + 2 * E * 1024
    expert = shared = 3 * E * 3072
    dense, router = 3 * E * 12288, E * 256
    norms = 4 * E + 2 * 128
    assert round(attention / 1e6, 2) == 62.91
    assert round(expert / 1e6, 2) == 28.31 and round(dense / 1e6, 2) == 113.25
    sparse_here = attention + router + shared + 8 * expert + norms
    dense_layer = attention + dense + norms
    assert round(sparse_here / 1e6, 1) == 318.5
    assert round(dense_layer / 1e6, 1) == 176.2
    held = dense_layer + 4 * sparse_here + 2 * 25024 * E + E
    assert round(held / 1e6, 1) == 1604.0 and round(8 * held / 1e9, 2) == 12.83
    whole = 6 * dense_layer + 54 * (sparse_here + 248 * expert) \
        + 2 * 200192 * E + E
    assert round(whole / 1e9, 1) == 398.6
    # the tree the factory builds holds exactly that
    import jax
    import numpy as np

    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    shapes = jax.eval_shape(
        lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == held + 4 * 256            # the selection biases beside them


def test_required_flops_against_a_hand_count(config):
    E, S, V = 3072, 6144, 25024
    projections = 2 * E * (3 * 6144 + 2 * 1024)
    full = 4 * 6144 * (S + 1) / 2
    sliding = 4 * 6144 * (4096 * 4097 / 2 + (S - 4096) * 4096) / S
    dense, shared = 6 * E * 12288, 6 * E * 3072
    experts = 0.125 * 6 * E * 3072                     # 4 x 8 / 256 held
    router, head = 2 * E * 256, 2 * E * V
    assert (projections, dense, shared, experts, router, head) == (
        125_829_120, 226_492_416, 56_623_104, 7_077_888, 1_572_864,
        153_747_456)
    assert round(full / 1e6, 1) == 75.5 and round(sliding / 1e6, 1) == 67.1
    # a sliding layer's query sees 2,731 keys on average, the full one 3,072
    assert round(sliding / (4 * 6144)) == 2731 and round(full / (4 * 6144)) == 3072
    forward = (5 * projections + 4 * sliding + full + dense
               + 4 * (shared + experts + router) + head)
    assert round(forward / 1e6) == 1614                 # ISSUE 45's
    got = trinity_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 4.84
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    # the issue's shares of the forward pass
    for part, share in ((5 * projections + 4 * sliding + full, 0.603),
                        (5 * projections, 0.390), (4 * sliding + full, 0.213),
                        (4 * shared, 0.140), (dense, 0.140), (head, 0.095),
                        (4 * experts, 0.018), (4 * router, 0.004)):
        assert round(part / forward, 3) == share
    parts = trinity_train.parts(config["model"], {"S": S})
    assert parts["pairs"] == pytest.approx(4 * sliding + full)
    assert sum(parts.values()) == pytest.approx(forward)


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S = 6144
    assert trinity_train.layer_windows(model) == [4096, 4096, None, 4096,
                                                  4096]
    need = flash_attention_gqa.required(1, S, 48, 8, 128)
    assert need["fwd"]["flops"] == 4.0 * (S * (S + 1) // 2) * 6144
    q_tile, kv_tile = S * 6144 * 2, S * 1024 * 2
    assert need["fwd"]["bytes"] == 2 * q_tile + 2 * kv_tile
    sec, binds = flops.least_seconds(need["fwd"]["flops"],
                                     need["fwd"]["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 2.35
    banded = flash_attention_gqa.required(1, S, 48, 8, 128, 4096)
    assert banded["fwd"]["flops"] == 4.0 * 6144 * (
        4096 * 4097 // 2 + 2048 * 4096)
    experts = trinity_train.expert_matmuls(model, S)
    assert trinity_train.held_experts_per_token(model) == 0.125
    assert experts["flops"] == 3 * 7_077_888 * S
    weights = 8 * 3 * 3072 * 3072 * 2
    rows = 768 * 3072 * 2                    # a thirty-second of 24,576 pairs
    assert experts["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(experts["flops"], experts["bytes"],
                                     peaks)
    # 96 rows an expert: the weights' bytes bind, 1.69 ms against 0.66
    assert binds == "memory" and round(sec * 1e3, 2) == 1.69
    assert round(experts["flops"] / peaks["bf16_flops"] * 1e3, 2) == 0.66


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of the cell's
# five layers (8 tgmm = 2 a sparse layer x 4 sparse layers)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                      # control flow
    ["fusion.1", 0, 2_000_000],                      # projections, forward
    ["fusion.2", 2_000_000, 2_000_000],              # projections, recomputed
    ["fusion.3", 4_000_000, 3_000_000],              # projections, backward
    ["fusion.4", 7_000_000, 1_000_000],              # the gate
    ["fusion.5", 8_000_000, 1_000_000],              # router
    ["fusion.6", 9_000_000, 4_000_000],              # shared expert
    ["fusion.7", 13_000_000, 1_000_000],             # output norms
] + [["flash_swa_fwd.%d" % i, 14_000_000 + 400_000 * i, 400_000]
     for i in range(8)] + [                          # 4 layers, recomputed
    ["flash_fwd.%d" % i, 17_200_000 + 400_000 * i, 400_000]
    for i in range(2)] + [
    ["flash_swa_bwd_fused.%d" % i, 18_000_000 + 1_000_000 * i, 1_000_000]
    for i in range(4)] + [
    ["flash_bwd_fused.0", 22_000_000, 2_000_000]] + [
    ["gmm.%d" % i, 26_000_000 + 200_000 * i, 200_000] for i in range(16)] + [
    ["tgmm.%d" % i, 29_200_000 + 100_000 * i, 100_000] for i in range(8)] + [
    ["fusion.8", 24_000_000, 2_000_000],             # the dense FFN
    ["fusion.9", 30_000_000, 6_000_000],             # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"trinity.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/attention/dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "attention/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/attention/dot_general",
    "fusion.4": P + "jvp()/while/body/closed_call/attention/attn_gate/mul",
    "fusion.5": P + "jvp()/while/body/closed_call/moe/moe/router/dot_general",
    "fusion.6": P + "jvp()/while/body/closed_call/shared_expert/dot_general",
    "fusion.7": P + "jvp()/while/body/closed_call/moe/post_norm/mul",
    "fusion.8": P + "jvp()/mlp/dot_general",
    **{"flash_swa_fwd.%d" % i: P + "jvp()/attention/flash_swa_fwd"
       for i in range(8)},
    **{"flash_fwd.%d" % i: P + "jvp()/attention/flash_fwd"
       for i in range(2)},
    **{"flash_swa_bwd_fused.%d" % i: P + "transpose(jvp())/checkpoint/"
                                         "attention/flash_swa_bwd_fused"
       for i in range(4)},
    "flash_bwd_fused.0": P + "transpose(jvp())/checkpoint/attention/"
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
    # attention + attn_gate: 2 + 2 + 3 ms of projections, 1 of the gate, 10
    # of kernels
    assert read["gated_attn_time_share"] == pytest.approx(100 * 18 / 36)
    assert read["gated_attn_outside_flash_share"] == pytest.approx(
        100 * 8 / 36)
    assert read["post_norm_time_share"] == pytest.approx(100 * 1 / 36)
    assert read["shared_expert_w3072_time_share"] == pytest.approx(
        100 * 4 / 36)
    # moe + router scopes: 1 + 3.2 + 0.8 ms; the output norm is not theirs
    assert read["moe_held8of256_time_share"] == pytest.approx(100 * 5 / 36)
    # 8 tgmm events = 2 a sparse layer and step x 4 sparse layers: one step
    need = trinity_train.expert_matmuls(config["model"], 6144)
    experts = need["bytes"] / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert read["moe_held8of256_roofline"] == pytest.approx(
        100 * 4 * experts / 4e-3)
    full = flash_attention_gqa.required(1, 6144, 48, 8, 128)
    banded = flash_attention_gqa.required(1, 6144, 48, 8, 128, 4096)
    least = (2 * full["fwd"]["flops"] + full["bwd"]["flops"]
             + 8 * banded["fwd"]["flops"] + 4 * banded["bwd"]["flops"]
             ) / 197e12
    assert read["swa_gqa6_flash_roofline"] == pytest.approx(
        100 * least / 10e-3)
    for head, words in (
            ("moe_held8of256_roofline: least", ("1.000 steps traced",
                                                "16 gmm and 8 tgmm",
                                                "memory binds")),
            ("swa_flash_roofline: least", ("full fwd 2 calls",
                                                "windowed bwd 4 calls")),
            ("gated_attn_time_share: 0.018000 s", ("0.001000 s",)),
            ("gated_attn_outside_flash_share: 0.018000 s", ("0.010000 s",)),
            ("post_norm_time_share: 0.001000 s", ()),
            ("shared_expert_time_share: 0.004000 s", ())):
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
    # a compiler that fuses the gate into attention's matmuls (the v5e's):
    # no instruction of attn_gate's own, and the readers read attention's
    fused = dict(MAPS["trinity.run_steps"], **{
        "fusion.4": P + "jvp()/while/body/closed_call/attention/dot_general"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"trinity.run_steps": fused})
    assert mf.module("layer_metrics", "gated_attn_time_share").read(
        tr.Reduced(TRACE), None, {}, cell) == pytest.approx(100 * 18 / 36)
    # a program whose vocabulary has no such scope (the parent's): nothing
    monkeypatch.setattr(devscope, "VOCABULARY", tuple(
        w for w in devscope.VOCABULARY if w != "attn_gate"))
    for name in ("gated_attn_time_share", "gated_attn_outside_flash_share"):
        assert mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell) is None
    monkeypatch.undo()
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    # lost scopes: over 5 % unattributed, the shares are not reported
    lost = dict(MAPS["trinity.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(16)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"trinity.run_steps": lost})
    for name in NEW:
        got = mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell)
        assert (got is None) == name.endswith("_share"), name


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
        NAME, "s6144_scan", 1) and len(cell["why"]) <= 200
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
        "batch": 1, "dims": {"S": 6144}, "staged_batches": 2,
        "trace_dispatches": 1}
    (ids,) = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0,
                          "high": config["vocab_size"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    # the first capacity's tiles: 1,024 rows compiled, 768 expected; the
    # second is every pair
    from paddle_tpu.parallel import moe

    assert moe._held_capacities(24576, 8, 256) == (1024, 24576)


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "trinity_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "num_attention_heads": 6,
              "num_key_value_heads": 2, "head_dim": 128,
              "rms_norm_eps": 1e-5, "rope_theta": 10000,
              "sliding_window": 24, "layer_types": LAYER_TYPES,
              "score_func": "sigmoid", "route_norm": True,
              "route_scale": 2.448, "mup_enabled": True,
              "num_shared_experts": 1, "tie_word_embeddings": False,
              "num_experts_per_tok": 2, "num_experts": 4,
              "moe_router_width": 8, "moe_first_expert_held": 4,
              "num_dense_layers": 1, "first_expert_layer": 6,
              "num_hidden_layers": 5, "intermediate_size": 96,
              "moe_intermediate_size": 32, "vocab_size": 256},
    "config_factory": {
        "path": "paddle_tpu.models.trinity.trinity_tiny_config",
        "kwargs": {"remat": True, "experts_held": 4, "first_expert": 4,
                   "shared_ffn_hidden": 32}},
    "trainer_builder": {
        "path": "paddle_tpu.models.trinity.build_trinity_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "trinity_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "trinity_tiny.scan"
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


@pytest.mark.parametrize("fault", ["gate_dropped",
                                   "ffn_output_norm_dropped",
                                   "route_scale_one",
                                   "embedding_multiplier_dropped"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import trinity_large_preview as reference

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
