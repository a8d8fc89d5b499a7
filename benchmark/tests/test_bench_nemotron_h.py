"""What PR 52 adds to the benchmark: the ``nemotron3_nano_30b_a3b``
configuration file against the program's factory and the catalog's keys, the
required FLOPs of its step against a hand count, the chunked scan's and the
ungated experts' needs, the seven new readers on a synthetic reduced trace
(and reading nothing without their scope or kernels), the new cell's files,
a tiny copy of the configuration through the harness on the CPU (and one
with a fault in its reference), and the new entries, looked up BY NAME."""

import importlib
import json
import os
import subprocess
import time

import pytest

from benchmark.flops import nemotron_h_train
from benchmark.harness import build, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "nemotron3_nano_30b_a3b", "nemotron3_nano_30b_a3b.s8192_scan"
NEW = {"mamba2_time_share": ("lower", "model code"),
       "ssd_scan_time_share": ("lower", "kernels"),
       "ssd_scan_roofline": ("higher", "kernels"),
       "mamba2_outside_scan_share": ("lower", "model code"),
       "moe_relu2_time_share": ("lower", "model code"),
       "moe_relu2_roofline": ("higher", "kernels"),
       "flash_gqa16_roofline": ("higher", "kernels")}
ADDED = {"configs": [NAME], "workloads": [CELL], "per_layer": list(NEW)}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the catalog's config of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, as published
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 9, "n_routed_experts": 16,
           "vocab_size": 16384}
ASSUMED = {"first_layer": 34, "router_experts": 128, "first_expert": 0}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_three_reduced(config,
                                                              manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert entry["source"] == config["source"] and len(entry["why"]) <= 200
    assert sum(c["file"] == entry["file"] for c in manifest["configs"]) == 1
    want = dict(PUBLISHED, **REDUCED)
    assert {k: config[k] for k in want} == want
    assert config["model"] == dict(want, **ASSUMED)
    for key in REDUCED:
        assert key in config["changed"]
    for words in ("eight v5e chips", "16 of 128", "16,384 of 131,072",
                  "layers 34 to 42", "7.89 GB"):
        assert words in config["deployment"], words
    assert "986.3 M" in config["changed"]["arithmetic"]
    assert "31.58 G" in config["changed"]["arithmetic"]
    assert "4,096 = mamba_num_heads x mamba_head_dim" \
        in config["assumed"]["a_d_inner"]


def test_the_arithmetic_of_the_cut(config):
    m = config["model"]
    E, d = m["hidden_size"], m["mamba_num_heads"] * m["mamba_head_dim"]
    W = d + 2 * m["n_groups"] * m["ssm_state_size"]
    mamba = E * (d + W + m["mamba_num_heads"]) + W * (m["conv_kernel"] + 1) \
        + 3 * m["mamba_num_heads"] + d + d * E + E
    heads = m["head_dim"] * (m["num_attention_heads"]
                             + m["num_key_value_heads"])
    attention = 2 * E * heads + E
    expert = 2 * E * m["moe_intermediate_size"]
    shared = 2 * E * m["moe_shared_expert_intermediate_size"]
    sparse = E * m["router_experts"] + shared \
        + m["n_routed_experts"] * expert + E
    assert round(mamba / 1e6, 2) == 38.74 and round(attention / 1e6, 2) \
        == 23.40 and round(sparse / 1e6, 2) == 179.95
    n_m, n_e, n_a = nemotron_h_train.layer_counts(m)
    assert (n_m, n_e, n_a) == (4, 4, 1)
    total = n_m * mamba + n_e * sparse + n_a * attention \
        + 2 * m["vocab_size"] * E + E
    assert round(total / 1e6, 1) == 986.3
    whole = 23 * mamba + 6 * attention + 23 * (
        E * 128 + shared + 128 * expert + E) + 2 * 131072 * E
    assert round(whole / 1e9, 2) == 31.58


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.kernels import ssd_scan as ssd
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.parallel import moe, transformer as T

    cfg = build._call(config["config_factory"])
    letters = {T.MAMBA2: "M", T.FFN: "E", (None, False): "*"}
    first = config["config_factory"]["kwargs"]["first_layer"]
    held = "".join(letters[k] for k in cfg.layer_kinds)
    assert held == "EMEMEMEM*" == PATTERN[first:first + cfg.n_layers]
    got = dict(PUBLISHED, **{
        "chunk_size": cfg.scan_chunk, "conv_kernel": cfg.d_conv,
        "head_dim": cfg.head_dim, "hidden_size": cfg.hidden,
        "hybrid_override_pattern": nemotron_h.PATTERN,
        "intermediate_size": cfg.ffn_hidden,
        "layer_norm_epsilon": cfg.norm_eps,
        "mamba_head_dim": cfg.d_inner // cfg.ssm_heads,
        "mamba_num_heads": cfg.ssm_heads, "mamba_proj_bias": cfg.bias,
        "max_position_embeddings": cfg.max_seq,
        "mlp_hidden_act": cfg.expert_act,
        "moe_intermediate_size": cfg.ffn_hidden,
        "moe_shared_expert_intermediate_size": cfg.shared_ffn_hidden,
        "n_groups": cfg.ssm_groups, "n_routed_experts": cfg.experts_here,
        "norm_eps": cfg.norm_eps, "num_attention_heads": cfg.n_heads,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.kv_heads,
        "routed_scaling_factor": cfg.route_scale,
        "ssm_state_size": cfg.d_state, "tie_word_embeddings": cfg.tie_head,
        "use_mamba_kernels": ssd.supported(
            (2, 8192, cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state),
            cfg.ssm_heads, cfg.ssm_groups, cfg.d_state, cfg.scan_chunk),
        "vocab_size": cfg.vocab_size, "first_layer": first,
        "router_experts": cfg.n_experts, "first_expert": cfg.first_expert})
    assert got == config["model"]
    assert cfg.single_branch and not cfg.expert_gated and not cfg.bias
    assert cfg.routing == moe.SIGMOID_BIASED and cfg.router_bias_rate == 5e-3
    assert cfg.n_periods == 1 and cfg.moe_layers == 4 and cfg.positions is None
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.tp == cfg.pp == 1 and cfg.d_inner == 4096
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.vocab_size, full.experts_here,
            full.moe_layers) == (52, 131072, 128, 23)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_required_flops_against_a_hand_count(config):
    model, dims = config["model"], {"B": 2, "S": 8192}
    parts = nemotron_h_train.parts(model, dims)
    # a Mamba-2 layer: in_proj, out_proj, the filter, and the four products
    scan = 2 * 8 * 128 * 64.5 + 64 * (2 * 64 * 64.5 + 4 * 64 * 128)
    assert nemotron_h_train.scan_flops_per_token(model) == scan
    mixer = 2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 4 * 6144 + scan
    assert parts["mamba2"] == 4 * mixer
    assert parts["attention"] == 2 * 2688 * 128 * (64 + 4) \
        + 4 * 128 * 32 * 8193 / 2
    assert parts["shared_experts"] == 4 * 4 * 2688 * 3712
    assert nemotron_h_train.held_experts_per_token(model) == 0.75
    assert parts["routed_experts"] == 4 * 0.75 * 4 * 2688 * 1856
    assert parts["routers"] == 4 * 2 * 2688 * 128
    assert parts["head"] == 2 * 2688 * 16384
    total = sum(parts.values())
    assert nemotron_h_train.per_unit(model, dims) == 3 * total
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"mamba2": 43, "attention": 15, "shared_experts": 21,
                      "routed_experts": 8, "routers": 0, "head": 12}


def test_the_scan_s_and_the_experts_required_flops_and_bytes(config):
    model, tokens = config["model"], 16384
    need = nemotron_h_train.ssd_scan(model, tokens)
    assert need["flops"] == 3 * nemotron_h_train.scan_flops_per_token(
        model) * tokens
    # x, B, C in and y out, then x, B, C, dy in and dx, dB, dC out, bf16;
    # three float32 scalars a head in, and again, and their gradients out
    assert need["bytes"] == tokens * (
        (6144 + 4096) * 2 + (6144 + 4096 + 6144) * 2 + 3 * 3 * 64 * 4)
    # HBM binds the scan: 0.91 GB against 0.14 TFLOP
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    need = nemotron_h_train.expert_matmuls(model, tokens)
    rows = 12288
    assert need["flops"] == 3 * rows * 4 * 2688 * 1856
    assert need["bytes"] == 3 * (16 * 2 * 2688 * 1856 * 2
                                 + 2 * rows * 2688 * 2)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9      # the MXU's


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 800_000_000]]}]}


# one device, a traced stretch of 800 ms, busy 760 ms: ONE step of the cell's
# nine layers (4 backward scans, 8 forward: remat runs it twice; 4 sparse
# layers' 5 gmm and 2 tgmm each; one attention layer's flash forward twice
# and its backward once)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 800_000_000],                     # control flow
    ["fusion.1", 0, 30_000_000],                     # projections, forward
    ["fusion.2", 30_000_000, 30_000_000],            # projections, recomputed
    ["fusion.3", 60_000_000, 60_000_000],            # projections, backward
] + [["ssd_scan_fwd.%d" % i, 120_000_000 + 5_000_000 * i, 5_000_000]
     for i in range(8)] + [
    ["ssd_scan_bwd.%d" % i, 160_000_000 + 15_000_000 * i, 15_000_000]
    for i in range(4)] + [
    ["gmm.%d" % i, 220_000_000 + 4_000_000 * i, 4_000_000]
    for i in range(20)] + [
    ["tgmm.%d" % i, 300_000_000 + 5_000_000 * i, 5_000_000]
    for i in range(8)] + [
    ["flash_fwd", 340_000_000, 12_000_000],
    ["flash_fwd.1", 352_000_000, 12_000_000],
    ["flash_bwd_fused", 364_000_000, 36_000_000],
    ["fusion.7", 400_000_000, 20_000_000],           # the moe's dispatch
    ["fusion.8", 420_000_000, 280_000_000],          # shared expert
    ["fusion.9", 700_000_000, 60_000_000],           # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"nemotron_h.run_steps": {
    "fusion.1": P + "jvp()/mamba2/mamba2/dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "mamba2/mamba2/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/mamba2/mamba2/dot_general",
    **{"ssd_scan_fwd.%d" % i: P + "jvp()/mamba2/mamba2/ssd_scan/"
       "ssd_scan_fwd" for i in range(8)},
    **{"ssd_scan_bwd.%d" % i: P + "transpose(jvp())/checkpoint/mamba2/"
       "mamba2/ssd_scan/ssd_scan_bwd" for i in range(4)},
    **{"gmm.%d" % i: P + "jvp()/moe/moe/gmm" for i in range(20)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/moe/tgmm"
       for i in range(8)},
    "flash_fwd": P + "jvp()/attention/flash_fwd",
    "flash_fwd.1": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "attention/flash_fwd",
    "flash_bwd_fused": P + "transpose(jvp())/checkpoint/attention/"
                           "flash_bwd_fused",
    "fusion.7": P + "jvp()/moe/router/top_k",
    "fusion.8": P + "jvp()/shared_expert/dot_general",
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
    assert trace.busy_s == pytest.approx(760e-3)
    cell = _cell(config, lines, throughput=10000.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # the scopes mamba2 + ssd_scan: 30 + 30 + 60 + 40 + 60 of 760 busy
    assert read["mamba2_time_share"] == pytest.approx(100 * 220 / 760)
    assert read["ssd_scan_time_share"] == pytest.approx(100 * 100 / 760)
    assert read["mamba2_outside_scan_share"] == pytest.approx(100 * 120 / 760)
    # 4 backward kernels = one a Mamba-2 layer and step: one step; HBM binds
    model = config["model"]
    need = nemotron_h_train.ssd_scan(model, 16384)
    assert read["ssd_scan_roofline"] == pytest.approx(
        100 * 4 * need["bytes"] / 819e9 / 100e-3)
    # moe + router: 80 + 40 + 20 of 760; 8 tgmm = two a sparse layer and step
    assert read["moe_relu2_time_share"] == pytest.approx(100 * 140 / 760)
    need = nemotron_h_train.expert_matmuls(model, 16384)
    assert read["moe_relu2_roofline"] == pytest.approx(
        100 * 4 * need["flops"] / 197e12 / 120e-3)
    # two forward calls and one backward of 32 heads on two key/value heads
    pairs = 2 * 8192 * 8193 / 2 * 32 * 128
    assert read["flash_gqa16_roofline"] == pytest.approx(
        100 * (2 * 4 * pairs + 8 * pairs) / 197e12 / 60e-3)
    for name in ("ssd_scan_roofline", "moe_relu2_roofline",
                 "flash_gqa16_roofline"):
        assert 0 < read[name] < 100, name
    for head, words in (
            ("ssd_scan_roofline: least", (
                "memory binds", "4 layers", "1.000 steps traced",
                "ssd_scan_fwd 0.040000 s in 8 calls",
                "ssd_scan_bwd 0.060000 s in 4 calls",
                "0.220000 s under mamba2 + ssd_scan")),
            ("mamba2_time_share: 0.220000 s", ("0.100000 s of it",)),
            ("mamba2_outside_scan_share: 0.220000 s", (
                "the projections' least", "1.000 steps traced")),
            ("ssd_scan_time_share: ssd_scan_fwd", ()),
            ("moe_relu2_roofline: least", (
                "compute binds", "1.000 steps traced", "20 gmm and 8 tgmm",
                "0.140000 s under scopes moe + router")),
            ("flash_gqa16_roofline: least", ("fwd 2 calls", "bwd 1 calls"))):
        assert any(l.startswith(head) and all(w in l for w in words)
                   for l in lines), (head, lines)
    # model_mfu reads the configuration's own count
    mfu = mf.module("layer_metrics", "model_mfu").read(trace, None, {}, cell)
    assert mfu == pytest.approx(
        100 * 10000.0 * nemotron_h_train.per_unit(model, cell["dims"])
        / 197e12)


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
    # lost scopes: over 5 % unattributed, the shares of a scope are not
    # reported; the kernels' own, by name, are
    lost = dict(MAPS["nemotron_h.run_steps"], **{"fusion.8": "copy-fusion"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"nemotron_h.run_steps": lost})
    for name in ("mamba2_time_share", "mamba2_outside_scan_share",
                 "moe_relu2_time_share"):
        assert mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell) is None
    assert mf.module("layer_metrics", "ssd_scan_time_share").read(
        tr.Reduced(TRACE), None, {}, cell) == pytest.approx(100 * 100 / 760)


def test_new_entries_are_additions_found_by_name(manifest):
    """The configuration, the cell and the seven metrics, each looked up by
    its NAME (a later PR's additions come after these), and nothing that the
    parent commit's file holds changed (read off git where the checkout has
    the parent)."""
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
        assert callable(mf.module("layer_metrics", name).read)
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_scan", 1) and len(cell["why"]) <= 200
    assert "balanced routing only" in cell["why"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics that list no cells report in the new cell by themselves
    got = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) < got and {"model_mfu", "step_ms_p50",
                               "device_idle_share", "step_need_gb"} < got
    assert all("workloads" not in entries[n] for n in got - set(NEW))
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
            ["git", "show", "16980ad:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        return          # a checkout without the parent commit
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert manifest[key] == before[key], key
    for key in ("configs", "workloads", "per_layer"):
        assert manifest[key][:len(before[key])] == before[key], key
        added = [e["name"] for e in manifest[key][len(before[key]):]]
        assert added[:len(ADDED[key])] == ADDED[key], key


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert {k: traffic[k] for k in ("driver", "mesh", "batch", "dims",
                                    "staged_batches", "trace_dispatches")} == {
        "driver": "train_scan_witnessed", "mesh": {"dp": 1, "pp": 1, "tp": 1},
        "batch": 2, "dims": {"S": 8192}, "staged_batches": 2,
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
    "name": "nemotron_h_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hybrid_override_pattern": "EM*EM", "first_layer": 0,
              "num_hidden_layers": 5, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 128, "mamba_num_heads": 16, "mamba_head_dim": 16,
              "n_groups": 2, "ssm_state_size": 128, "conv_kernel": 4,
              "chunk_size": 16, "n_routed_experts": 4, "router_experts": 8,
              "first_expert": 0, "num_experts_per_tok": 2,
              "n_shared_experts": 1, "moe_intermediate_size": 192,
              "moe_shared_expert_intermediate_size": 128,
              "routed_scaling_factor": 2.5, "norm_topk_prob": True,
              "n_group": 1, "topk_group": 1, "norm_eps": 1e-5,
              "tie_word_embeddings": False, "vocab_size": 256},
    "config_factory": {
        "path": "paddle_tpu.models.nemotron_h.nemotron_h_tiny_config",
        "kwargs": {"remat": True}},
    "trainer_builder": {
        "path": "paddle_tpu.models.nemotron_h.build_nemotron_h_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "nemotron_h_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "nemotron_h_tiny.scan"
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
    the timed path's own first loss and of its logits in both groups of
    both sequences, and the new readers finding no device plane."""
    out, said, lines = _run_tiny(tmp_path, manifest, trace)
    assert out["correct"] is True, lines
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert said("reference: ")["relative_error"] < 1e-5
    witness = said("witness: ")
    assert witness["ok"] and witness["logits_relative_error"] < 3e-5
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert not set(NEW) & set(out["metrics"])       # no device plane
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_dropped_at_chunk_edges",
                                   "gate_after_norm", "relu_for_relu2"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import nemotron3_nano_30b_a3b as reference

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
