"""What PR 39 adds to the benchmark: the ``mistral_small_4_119b``
configuration file against the program's factory and the catalog's keys, the
required FLOPs of its step against a hand count, the kernels' needs, the six
new readers on a synthetic reduced trace, the new cell's files, a tiny copy
of the configuration through the harness on the CPU (and one with a fault in
its reference), and the new entries looked up BY NAME: that they are
PRESENT and list the one cell, not where they stand (PERF.md section 7
(k))."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import flash_attention_gqa, mistral4_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "mistral_small_4_119b", "mistral_small_4_119b.s16384_scan"
NEW = {"mla_time_share": ("lower", "model code"),
       "mla_flash_roofline": ("higher", "kernels"),
       "mla_outside_flash_share": ("lower", "model code"),
       "shared_expert_time_share": ("lower", "model code"),
       "moe_held8_time_share": ("lower", "model code"),
       "moe_held8_roofline": ("higher", "kernels")}
ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
# the catalog's config of Mistral-Small-4-119B-2603, as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024,
    "qk_head_dim": 128, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_parameters": ROPE,
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 4, "n_routed_experts": 8, "vocab_size": 16384}


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
    row, = [r for r in rows if r["name"] == "Mistral-Small-4-119B-2603"]
    assert row["config"] == PUBLISHED


def test_file_holds_every_published_key_but_the_three_reduced(config,
                                                              manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "head_dim",
                "num_experts_per_tok", "num_attention_heads",
                "n_shared_experts", "rope_parameters"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: four layers (the period is one layer), 8 routed experts, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "moe_router_width": PUBLISHED["n_routed_experts"],
        "moe_first_expert_held": 0}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for text in ("36 -> 4", "128 -> 8", "131,072 -> 16,384"):
        assert any(text in v for v in config["changed"].values()), text
    for count in ("14.16 GB", "16.32 GB", "1,154.5 M", "9.24 GB"):
        assert count in config["changed"]["arithmetic"], count
    assert [k[0] for k in list(config["assumed"])[:5]] == list("abcde")
    for key, word in (("a_softmax_scale_and_rotary_factor", "DeepSeek-V3"),
                      ("b_query_scale", "Llama-4"),
                      ("c_scoring", "scoring_func"),
                      ("d_training", "no auxiliary loss"),
                      ("e_state_bytes", "8 bytes")):
        assert word in config["assumed"][key], key
    assert "sixteen v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.parallel import moe

    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = {
        "attention_bias": cfg.bias, "mlp_bias": cfg.bias,
        "first_k_dense_replace": len(cfg.prefix_kinds),
        "head_dim": cfg.head_dim, "qk_head_dim": cfg.head_dim,
        "hidden_act": cfg.expert_act, "hidden_size": cfg.hidden,
        "intermediate_size": 12288,         # no dense layer to use it
        "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "max_position_embeddings": cfg.max_seq, "model_type": "mistral4",
        "moe_intermediate_size": cfg.ffn_hidden, "n_group": 1,
        "topk_group": 1, "n_routed_experts": cfg.experts_here,
        "moe_router_width": cfg.n_experts,
        "moe_first_expert_held": cfg.first_expert,
        "n_shared_experts": cfg.shared_ffn_hidden // cfg.ffn_hidden,
        "norm_topk_prob": cfg.routing == moe.TOP_K_SOFTMAX,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_interleave": cfg.latent, "routed_scaling_factor": 1,
        "sliding_window": None, "tie_word_embeddings": cfg.tie_head,
        "vocab_size": cfg.vocab_size,
        "rope_parameters": {
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "factor": cfg.rope_factor,
            "llama_4_scaling_beta": cfg.q_scale_beta,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim,
            "original_max_position_embeddings": cfg.rope_original_max,
            "rope_theta": cfg.rope_theta, "rope_type": "yarn",
            "type": "yarn"}}
    assert got == model
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.positions == "rotary" and not cfg.qk_norm
    assert cfg.router_input == "ffn" and cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size) == (
        36, 128, 131072)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_parameters_against_the_issue_s_count(config):
    """53.75 M a layer outside the routed experts, 25.17 M an expert,
    1,154.5 M held here, 118.97 G published."""
    attention = (4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144
                 + 4096 * 4096)
    shared = expert = 3 * 4096 * 2048
    router, norms = 4096 * 128, 2 * 4096 + 1024 + 256
    outside = attention + shared + router + norms
    assert round(attention / 1e6, 2) == 28.05 and round(expert / 1e6, 2) == 25.17
    assert round(outside / 1e6, 2) == 53.75
    held = 4 * (outside + 8 * expert) + 2 * 16384 * 4096 + 4096
    assert round(held / 1e6, 1) == 1154.5 and round(8 * held / 1e9, 2) == 9.24
    whole = 36 * (outside + 128 * expert) + 2 * 131072 * 4096 + 4096
    assert round(whole / 1e9, 2) == 118.97


def test_required_flops_against_a_hand_count(config):
    E, S, V = 4096, 16384, 16384
    chains = 2 * (E * 1024 + 1024 * 4096 + E * 320 + 256 * 6144 + 4096 * E)
    pairs = 4 * 4096 * (S + 1) / 2                      # QK^T and PV, 32 x 128
    shared = 6 * E * 2048
    experts = 0.25 * 6 * E * 2048                       # 4 x 8 / 128 held
    router, head = 2 * E * 128, 2 * E * V
    assert (chains, shared, experts, router, head) == (
        56_098_816, 50_331_648, 12_582_912, 1_048_576, 134_217_728)
    assert round(pairs / 1e6, 1) == 134.2
    forward = 4 * (chains + pairs + shared + experts + router) + head
    assert round(forward / 1e6) == 1151                 # ISSUE 39's
    got = mistral4_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 3.45
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    # the issue's shares of the forward pass
    for part, share in ((4 * pairs, 0.466), (4 * chains, 0.195),
                        (4 * shared, 0.175), (4 * experts, 0.044),
                        (head, 0.117), (4 * router, 0.004)):
        assert round(part / forward, 3) == share
    # the whole model's head is 6.3 %: the price of the depth cut
    whole = 36 * (chains + pairs + shared + 4 * 6 * E * 2048 + router) \
        + 2 * E * 131072
    assert round(2 * E * 131072 / whole, 3) == 0.063


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S = 16384
    assert mistral4_train.head_dim(model) == 128
    need = flash_attention_gqa.required(1, S, 32, 32, 128)
    assert need["fwd"]["flops"] == 4.0 * (S * (S + 1) // 2) * 4096
    tile = S * 4096 * 2                 # q, o, k and v alike: 32 heads each
    assert need["fwd"]["bytes"] == 4 * tile and need["bwd"]["bytes"] == 8 * tile
    sec, binds = flops.least_seconds(need["fwd"]["flops"],
                                     need["fwd"]["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 11.16
    experts = mistral4_train.expert_matmuls(model, S)
    assert mistral4_train.held_experts_per_token(model) == 0.25
    assert experts["flops"] == 3 * 12_582_912 * S
    weights = 8 * 3 * 4096 * 2048 * 2
    rows = 4096 * 4096 * 2                  # a sixteenth of 65,536 pairs
    assert experts["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(experts["flops"], experts["bytes"],
                                     peaks)
    # 512 rows an expert: the weights' bytes bind, 1.72 ms against 3.14
    assert binds == "compute" and round(sec * 1e3, 2) == 3.14
    assert round(experts["bytes"] / peaks["hbm_bytes_per_s"] * 1e3, 2) == 1.72


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of the cell's
# four layers (8 tgmm = 2 a layer x 4 layers)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                      # control flow
    ["fusion.1", 0, 2_000_000],                      # chains, forward
    ["fusion.2", 2_000_000, 2_000_000],              # chains, recomputed
    ["fusion.3", 4_000_000, 4_000_000],              # chains, backward
    ["fusion.4", 8_000_000, 1_000_000],              # router
    ["fusion.5", 9_000_000, 5_000_000],              # shared expert
] + [["flash_fwd.%d" % i, 14_000_000 + 500_000 * i, 500_000]
     for i in range(8)] + [                          # 4 layers, recomputed
    ["flash_bwd_dq.%d" % i, 18_000_000 + 1_000_000 * i, 1_000_000]
    for i in range(4)] + [
    ["flash_bwd_dkv.%d" % i, 22_000_000 + 1_000_000 * i, 1_000_000]
    for i in range(4)] + [
    ["gmm.%d" % i, 26_000_000 + 200_000 * i, 200_000] for i in range(16)] + [
    ["tgmm.%d" % i, 29_200_000 + 100_000 * i, 100_000] for i in range(8)] + [
    ["fusion.9", 30_000_000, 6_000_000],             # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"mistral4.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/latent_attention/"
                    "dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "latent_attention/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/latent_attention/"
                    "dot_general",
    "fusion.4": P + "jvp()/while/body/closed_call/moe/moe/router/dot_general",
    "fusion.5": P + "jvp()/while/body/closed_call/shared_expert/dot_general",
    **{"flash_fwd.%d" % i: P + "jvp()/latent_attention/flash_fwd"
       for i in range(8)},
    **{"flash_bwd_dq.%d" % i: P + "transpose(jvp())/checkpoint/"
                                  "latent_attention/flash_bwd_dq"
       for i in range(4)},
    **{"flash_bwd_dkv.%d" % i: P + "transpose(jvp())/checkpoint/"
                                   "latent_attention/flash_bwd_dkv"
       for i in range(4)},
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


def test_the_six_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # the scope latent_attention: 2 + 2 + 4 ms of chains, 12 ms of kernels
    assert read["mla_time_share"] == pytest.approx(100 * 20 / 36)
    assert read["mla_outside_flash_share"] == pytest.approx(100 * 8 / 36)
    assert read["shared_expert_time_share"] == pytest.approx(100 * 5 / 36)
    # moe + router scopes: 1 + 3.2 + 0.8 ms
    assert read["moe_held8_time_share"] == pytest.approx(100 * 5 / 36)
    # 8 tgmm events = 2 a layer and step x 4 layers: one step
    experts = 3 * 12_582_912 * 16384 / 197e12
    assert read["moe_held8_roofline"] == pytest.approx(
        100 * 4 * experts / 4e-3)
    need = flash_attention_gqa.required(1, 16384, 32, 32, 128)
    least = (8 * need["fwd"]["flops"] + 4 * need["bwd"]["flops"]) / 197e12
    assert read["mla_flash_roofline"] == pytest.approx(100 * least / 12e-3)
    for head, words in (
            ("moe_held8_roofline: least", ("1.000 steps traced",
                                           "16 gmm and 8 tgmm")),
            ("mla_flash_roofline: least", ("fwd 8 calls", "bwd 4 calls")),
            ("mla_time_share: 0.020000 s", ()),
            ("mla_outside_flash_share: 0.020000 s", ("0.012000 s",)),
            ("shared_expert_time_share: 0.005000 s", ())):
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
    lost = dict(MAPS["mistral4.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(16)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"mistral4.run_steps": lost})
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
        NAME, "s16384_scan", 1) and len(cell["why"]) <= 200
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
    # the first capacity's tiles: 5,120 rows compiled, 4,096 expected
    from paddle_tpu.parallel import moe

    assert moe._held_capacities(65536, 8, 128) == (5120, 65536)


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "mistral4_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
              "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
              "v_head_dim": 128, "rms_norm_eps": 1e-6,
              "rope_interleave": True,
              "rope_parameters": dict(
                  ROPE, beta_fast=4, beta_slow=0.5, factor=8,
                  original_max_position_embeddings=16),
              "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
              "num_experts_per_tok": 2, "n_routed_experts": 4,
              "moe_router_width": 8, "moe_first_expert_held": 4,
              "n_shared_experts": 1, "moe_intermediate_size": 32,
              "routed_scaling_factor": 1, "num_hidden_layers": 2,
              "vocab_size": 256},
    "config_factory": {
        "path": "paddle_tpu.models.mistral4.mistral4_tiny_config",
        "kwargs": {"remat": True, "experts_held": 4, "first_expert": 4}},
    "trainer_builder": {
        "path": "paddle_tpu.models.mistral4.build_mistral4_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "mistral4_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "mistral4_tiny.scan"
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


@pytest.mark.parametrize("fault", ["rotate_half_pairs",
                                   "rotary_key_of_head_0_only",
                                   "shared_expert_dropped"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import mistral_small_4_119b as reference

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
