"""What PR 27 adds to the benchmark: the ``olmoe_1b_7b`` configuration file
against the program's factory and the catalog's keys, the required FLOPs of
a MoE decoder against a hand count, ``moe_roofline``'s required FLOPs and
bytes, the three new readers on a synthetic reduced trace, the new cells'
files, and PR 24's five entries left as they were."""

import importlib
import json
import os

import pytest

from benchmark.flops import flash_attention, moe_decoder_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmoe_1b_7b.s4096_scan"
NEW = {"moe_time_share": ("lower", "model code"),
       "moe_roofline": ("higher", "kernels"),
       "flash_causal_roofline": ("higher", "kernels")}
# the catalog's config of OLMoE-1B-7B-0125-Instruct, as published
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", "olmoe_1b_7b.json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_depth(config, manifest):
    entry = mf.config_entry(manifest, "olmoe_1b_7b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/olmoe_1b_7b.json"
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == {"num_hidden_layers"} and config["num_hidden_layers"] == 3
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert set(config["model"]) - set(PUBLISHED) == {
        "router_aux_loss_coef", "router_z_loss_coef"}
    assert set(config["changed"]) == {"num_hidden_layers"}
    for key in ("router_aux_loss_coef", "router_z_loss_coef", "optimizer",
                "state_bytes", "ids", "documents", "intermediate_size"):
        assert key in config["assumed"], key
    assert config["deployment"] and config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, depth included, so that file and factory cannot drift."""
    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = {
        "attention_bias": cfg.bias, "clip_qkv": None,
        "hidden_act": "silu" if cfg.n_experts else "gelu",
        "hidden_size": cfg.hidden, "intermediate_size": cfg.ffn_hidden,
        "max_position_embeddings": cfg.max_seq,
        "model_type": "olmoe" if (cfg.qk_norm and cfg.n_experts) else "?",
        # the one routing parallel/moe.py has: a configuration file that
        # renormalises must fail here until the layer can
        "norm_topk_prob": False,
        "num_attention_heads": cfg.n_heads, "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.n_heads,         # full MHA: no GQA knob
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_scaling": None,
        "rope_theta": cfg.rope_theta if cfg.positions == "rotary" else None,
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "router_aux_loss_coef": cfg.router_aux_coef,
        "router_z_loss_coef": cfg.router_z_coef}
    assert got == model
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.head_dim == 128 and cfg.tp == cfg.pp == 1
    # the published model is the factory's default: only the depth is cut
    full = build.resolve(config["config_factory"]["path"])()
    assert full.n_layers == PUBLISHED["num_hidden_layers"]
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 4e-4


def test_required_flops_against_a_hand_count(config):
    model = config["model"]
    E, F, S, V = 2048, 1024, 4096, 50304
    attention = 8 * E * E + 2 * S * E            # projections, causal scores
    experts = 8 * 6 * E * F                      # top 8, gate + up + down
    layer = attention + 2 * E * 64 + experts
    assert experts == 100_663_296 and layer == 151_257_088
    forward = 3 * layer + 2 * E * V
    assert forward == 659_816_448
    got = moe_decoder_train.per_unit(model, {"S": S, "B": 4})
    assert got == 3.0 * forward
    assert round(got / 1e9, 2) == 1.98
    # the head's and the experts' share of the cut model, and of the whole
    assert round(2 * E * V / forward, 2) == 0.31
    assert round(3 * experts / forward, 2) == 0.46
    whole = 16 * layer + 2 * E * V
    assert round(2 * E * V / whole, 2) == 0.08
    assert flops.per_unit(config, {"S": S, "B": 4}) == got


def test_expert_matmuls_required_flops_and_bytes(config):
    need = moe_decoder_train.expert_matmuls(config["model"], 16384)
    assert need["flops"] == 3 * 100_663_296 * 16384
    weights = 64 * 3 * 2048 * 1024 * 2           # 805 MB of bf16
    rows = 16384 * 8 * 2048 * 2                  # 537 MB of sorted rows
    assert need["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(need["flops"], need["bytes"],
                                     PEAKS["TPU v5 lite"])
    assert binds == "compute" and round(sec * 1e3, 1) == 25.1
    # a token's rows scale, the weights do not
    half = moe_decoder_train.expert_matmuls(config["model"], 8192)
    assert half["flops"] == need["flops"] / 2
    assert half["bytes"] == 3 * (weights + rows)


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 10_000_000]]}]}


# one device, a traced stretch of 10 ms, busy 9 ms
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 10_000_000],                      # control flow
    ["gmm.1", 0, 3_000_000],                         # moe forward
    ["fusion.5", 3_000_000, 1_000_000],              # router forward
    ["gmm.2", 4_000_000, 500_000],                   # moe backward, dX
] + [["tgmm.%d" % i, 4_500_000 + 250_000 * i, 250_000]   # dW: 2 a layer
     for i in range(6)] + [
    ["flash_fwd.3", 6_000_000, 500_000],
    ["flash_fwd.4", 6_500_000, 500_000],             # the recomputed forward
    ["flash_bwd_dq.3", 7_000_000, 500_000],
    ["flash_bwd_dkv.3", 7_500_000, 500_000],
    ["fusion.9", 8_000_000, 1_000_000],              # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"olmoe.run_steps": {
    "gmm.1": P + "jvp()/while/body/closed_call/moe/moe/gmm",
    "fusion.5": P + "jvp()/while/body/closed_call/moe/moe/router/top_k",
    "gmm.2": P + "transpose(jvp())/while/body/closed_call/checkpoint/moe/gmm",
    **{"tgmm.%d" % i: P + "transpose(jvp())/while/body/closed_call/"
                          "checkpoint/moe/tgmm" for i in range(6)},
    "flash_fwd.3": P + "jvp()/while/body/closed_call/attention/flash_fwd",
    "flash_fwd.4": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "attention/flash_fwd",
    "flash_bwd_dq.3": P + "transpose(jvp())/checkpoint/attention/flash_bwd_dq",
    "flash_bwd_dkv.3": P + "transpose(jvp())/checkpoint/attention/flash_bwd_dkv",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_three_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(9e-3)
    assert trace.window_s == pytest.approx(10e-3)
    # the host clock's rate is NOT what counts the traced steps: the six
    # tgmm events are (two a layer and step, three layers: one step)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    assert read["moe_time_share"] == pytest.approx(100 * 6 / 9)
    # three layers' expert matmuls need 3 x 25.1 ms; gmm + tgmm "took" 5 ms
    # of the 6 ms under the scopes
    per_layer = 3 * 100_663_296 * 16384 / 197e12
    assert read["moe_roofline"] == pytest.approx(100 * 3 * per_layer / 5e-3)
    need = flash_attention.required(4, 4096, 2048, causal=True)
    assert need["fwd"]["flops"] == 4.0 * 4 * 4096 * 4096 * 2048 / 2
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) / 197e12
    assert read["flash_causal_roofline"] == pytest.approx(100 * least / 2e-3)
    assert any(l.startswith("moe_roofline: least") and "compute binds" in l
               and "1.000 steps traced" in l and "0.005000 s in gmm / tgmm" in l
               and "0.006000 s under scopes" in l for l in lines)


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    cell = _cell(config, [], throughput=1e4)
    for name in NEW:
        read = mf.module("layer_metrics", name).read
        assert read(None, None, {}, cell) is None
        assert read(tr.Reduced({"planes": []}), None, {}, cell) is None
    # a program without the scopes (an earlier commit's): no moe metric
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"bert.run_steps": {
        "fusion.9": P + "jvp(lm_head)/lm_head/dot_general"}})
    trace = tr.Reduced(TRACE)
    assert mf.module("layer_metrics", "moe_time_share").read(
        trace, None, {}, cell) is None
    # the kernels are read by name, scopes or none; another primitive's
    # (XLA's ragged-dot calls) or a forward-only stretch read nothing
    got = mf.module("layer_metrics", "moe_roofline").read(
        trace, None, {}, cell)
    assert got == pytest.approx(100 * 3 * 3 * 100_663_296 * 16384 / 197e12
                                / 5e-3)
    for drop in ("tgmm", "gmm"):
        plane = TRACE["planes"][0]
        ops = [[n.replace(drop + ".", "ragged-dot-none."), a, b] if
               n.startswith(drop + ".") else [n, a, b]
               for n, a, b in plane["lines"][0]["events"]]
        other = tr.Reduced({"planes": [_plane(plane["name"], ops)]})
        got = mf.module("layer_metrics", "moe_roofline").read(
            other, None, {}, cell)
        assert (got is None) == (drop == "tgmm")


def test_moe_time_share_reads_nothing_where_the_scopes_are_lost(
        config, monkeypatch):
    """A primitive whose instructions lose the program's path (XLA's own
    ragged-dot calls did): over 5 % of busy without a scope, a wrong share
    is not reported."""
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    lost = dict(MAPS["olmoe.run_steps"], **{"gmm.1": "ragged-dot-none"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"olmoe.run_steps": lost})
    lines = []
    cell = _cell(config, lines, throughput=1e4)
    assert mf.module("layer_metrics", "moe_time_share").read(
        tr.Reduced(TRACE), None, {}, cell) is None
    assert any(l.startswith("moe_time_share: 33.333 % of the busy time "
                            "carries no scope") for l in lines)


def test_new_entries_are_additions_and_list_the_one_cell(manifest):
    """The three stand at the end of the list, after PR 24's five: an entry
    put anywhere else reads to the driver as a change to what was there."""
    entries = [e["name"] for e in manifest["per_layer"]]
    assert entries[-3:] == list(NEW)
    for e in manifest["per_layer"][-3:]:
        better, layer = NEW[e["name"]]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
    assert [w["name"] for w in manifest["workloads"]][-2:] == [
        CELL, "resnet50.b256_scan"]
    assert [c["name"] for c in manifest["configs"]][-1] == "olmoe_1b_7b"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics that list no cells report in both new cells by themselves
    for cell in (CELL, "resnet50.b256_scan"):
        names = {e["name"] for e in mf.metrics_of(manifest, "per_layer", cell)}
        assert {"step_ms_p50", "window_lost_share", "recompiles_in_window",
                "model_mfu", "device_idle_share"} <= names
        assert len(mf.cell(manifest, cell)["why"]) <= 200
    # no existing metric took a new cell
    for e in manifest["per_layer"]:
        if e["name"] not in NEW:
            assert not {CELL, "resnet50.b256_scan"} & set(
                e.get("workloads", ()))


def test_pr24_s_five_entries_are_still_there_by_name(manifest):
    """Everything ``test_bench_scope_time.py`` holds them to but their place:
    that file pins them to the LAST five places, where this PR's three now
    stand, and is not this PR's to edit."""
    from benchmark.tests.test_bench_scope_time import ENTRIES

    entries = {e["name"]: e for e in manifest["per_layer"]}
    assert list(entries)[-8:-3] == list(ENTRIES)    # next to each other, in order
    for name, (cells, layer) in ENTRIES.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "lower", "device_trace", "train_throughput")
        assert e["workloads"] == cells and e["layer"] == layer
        assert callable(mf.module("layer_metrics", name).read)
    assert not set(ENTRIES) & {e["name"] for e in mf.metrics_of(
        manifest, "per_layer", "bert_base.s512_dp4")}


def test_new_traffic_files(manifest):
    moe = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert {k: moe[k] for k in ("driver", "mesh", "batch", "dims",
                                "staged_batches", "trace_dispatches")} == {
        "driver": "train_scan", "mesh": {"dp": 1, "pp": 1, "tp": 1},
        "batch": 4, "dims": {"S": 4096}, "staged_batches": 4,
        "trace_dispatches": 2}
    old = mf.read_json(ROOT, "benchmark", "traffic", "resnet50.b128_scan.json")
    new = mf.read_json(ROOT, "benchmark", "traffic", "resnet50.b256_scan.json")
    differs = {k for k in old if old[k] != new[k]}
    assert differs == {"batch", "about"} and new["batch"] == 256
    assert set(old) == set(new)


def test_benchmark_json_is_small_and_parses():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) < 64 * 1024
    with open(path) as f:
        json.load(f)
