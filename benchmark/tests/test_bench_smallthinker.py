"""What PR 31 adds to the benchmark: the ``smallthinker_21b_a3b``
configuration file against the program's factory and the catalog's keys, the
required FLOPs of its step against a hand count, the kernels' needs, the four
new readers on a synthetic reduced trace, the new cell's files, a tiny copy
of the configuration through the harness on the CPU, and the new entries
looked up BY NAME (their place in the lists is the next PR's to move)."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import flash_attention_gqa, smallthinker_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "smallthinker_21b_a3b", "smallthinker_21b_a3b.s16384_scan"
NEW = {"swa_flash_roofline": ("higher", "kernels"),
       "swa_flash_time_share": ("lower", "kernels"),
       "moe_held_time_share": ("lower", "model code"),
       "moe_held_roofline": ("higher", "kernels")}
PERIOD = [0, 1, 1, 1]
# the catalog's config of SmallThinker-21BA3B-Instruct, as published
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "moe_num_primary_experts": 16,
           "vocab_size": 37984}


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
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("head_dim", "hidden_size", "moe_ffn_hidden_size",
                "moe_num_active_primary_experts", "num_attention_heads",
                "num_key_value_heads", "sliding_window_size"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: a whole period and >= 4 layers, >= 8 experts, >= 1/8 vocabulary
    assert config["num_hidden_layers"] % len(PERIOD) == 0
    assert config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "moe_router_width": PUBLISHED["moe_num_primary_experts"],
        "moe_first_expert_held": 0}
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for key in ("attention_bias", "window_edge", "router_input", "routing",
                "auxiliary_loss", "optimizer", "state_bytes", "remat",
                "documents", "ids", "embedding_init"):
        assert key in config["assumed"], key
    assert "four v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.models import smallthinker

    cfg = build._call(config["config_factory"])
    model = config["model"]
    n = cfg.n_layers
    got = {
        "head_dim": cfg.head_dim, "hidden_size": cfg.hidden,
        "max_position_embeddings": cfg.max_seq,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": cfg.ffn_hidden,
        "moe_num_active_primary_experts": cfg.experts_per_token,
        "moe_num_primary_experts": cfg.experts_here,
        "moe_router_width": cfg.n_experts,
        "moe_first_expert_held": cfg.first_expert,
        "moe_primary_router_apply_softmax":
            cfg.routing in ("top_k_softmax", "softmax_top_k"),
        "norm_topk_prob": cfg.routing == "top_k_softmax",
        "num_attention_heads": cfg.n_heads,
        "num_hidden_layers": n, "num_key_value_heads": cfg.kv_heads,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_scaling": None,
        "rope_theta": cfg.rope_theta if cfg.positions == "rotary" else None,
        "sliding_window_size": max(w or 0 for w, _ in cfg.layer_kinds),
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size}
    layouts = ("rope_layout", "sliding_window_layout")
    assert got == {k: v for k, v in model.items() if k not in layouts}
    # the layouts stand whole; the program's period is their first entries
    kinds = [cfg.layer_kinds[i % len(cfg.layer_kinds)] for i in range(n)]
    assert [int(r) for _, r in kinds] == model["rope_layout"][:n]
    assert [int(bool(w)) for w, _ in kinds] == \
        model["sliding_window_layout"][:n]
    assert model["rope_layout"] == PERIOD * 13 == model["sliding_window_layout"]
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert not cfg.bias and not cfg.qk_norm and cfg.expert_act == "relu"
    assert cfg.router_input == "block" and cfg.tp == cfg.pp == 1
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    # the published model is the factory's default: only the share is cut
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size) == (
        52, 64, 151936)
    assert smallthinker.PERIOD == tuple(
        (4096 * w, bool(r)) for w, r in zip(PERIOD, PERIOD))
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5         # the long-context stage's decayed rate


def test_required_flops_against_a_hand_count(config):
    E, S, V = 2560, 16384, 37984
    projections = 2 * (2 * E * 3584 + 2 * E * 512)         # q, o, k, v
    assert projections == 41_943_040
    full = 4 * 3584 * (S * (S + 1) // 2) / S               # QK^T and PV
    banded = 4 * 3584 * (4096 * 4097 // 2 + (S - 4096) * 4096) / S
    assert round(full / 1e6, 1) == 117.4 and round(banded / 1e6, 1) == 51.4
    assert round(banded / full, 3) == 0.437
    experts = 1.5 * 6 * E * 768                            # 6 x 16 / 64 held
    router = 2 * E * 64
    assert experts == 17_694_720 and router == 327_680
    head = 2 * E * V
    assert round(head / 1e6, 1) == 194.5

    def forward(periods):
        return (periods * (4 * (projections + experts + router)
                           + full + 3 * banded) + head)

    # ISSUE 31's count, two periods: 1.217 G forward, 3.652 G a token
    model8 = dict(config["model"], num_hidden_layers=8)
    got8 = smallthinker_train.per_unit(model8, {"S": S, "B": 1})
    assert got8 == pytest.approx(3.0 * forward(2), rel=1e-12)
    assert round(got8 / 1e9, 3) == 3.652
    # the cell's, one period
    got = smallthinker_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward(1), rel=1e-12)
    assert round(got / 1e9, 3) == 2.118
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    # shares of the cut model: the flash kernels' pairs, with their
    # projections, the head
    assert round(3 * (full + 3 * banded) / got, 2) == 0.38
    assert round(3 * (full + 3 * banded + 4 * projections) / got, 2) == 0.62
    assert round(3 * head / got, 2) == 0.28
    assert smallthinker_train.seen_pairs(4096, 4096) == 4096 * 4097 // 2
    assert smallthinker_train.seen_pairs(100, 4096) == 100 * 101 // 2


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    peaks = PEAKS["TPU v5 lite"]
    S = 16384
    full = flash_attention_gqa.required(1, S, 28, 4, 128)
    band = flash_attention_gqa.required(1, S, 28, 4, 128, window=4096)
    assert full["fwd"]["flops"] == 4.0 * (S * (S + 1) // 2) * 3584
    assert band["bwd"]["flops"] == 8.0 * 58_722_304 * 3584
    q, kv = S * 3584 * 2, S * 512 * 2          # q, o at 28 heads; k, v at 4
    assert full["fwd"]["bytes"] == band["fwd"]["bytes"] == 2 * q + 2 * kv
    assert full["bwd"]["bytes"] == 4 * q + 4 * kv
    for need in (full, band):
        for part in ("fwd", "bwd"):
            assert flops.least_seconds(need[part]["flops"],
                                       need[part]["bytes"], peaks)[1] == "compute"
    sec = flops.least_seconds(band["fwd"]["flops"], band["fwd"]["bytes"],
                              peaks)[0]
    assert round(sec * 1e3, 2) == 4.27
    # the expert matmuls over the rows held: 24,576 of 98,304 at 16,384 tokens
    need = smallthinker_train.expert_matmuls(model, S)
    assert smallthinker_train.held_experts_per_token(model) == 1.5
    assert need["flops"] == 3 * 17_694_720 * S
    weights = 16 * 3 * 2560 * 768 * 2          # 189 MB of bf16
    rows = 24576 * 2560 * 2                    # 126 MB of sorted rows
    assert need["bytes"] == 3 * (weights + 2 * rows)
    sec, binds = flops.least_seconds(need["flops"], need["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 4.41


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 20_000_000]]}]}


# one device, a traced stretch of 20 ms, busy 18 ms: ONE step of the cell's
# four layers (a full one and three windowed), remat's second forwards in it
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 20_000_000],                      # control flow
    ["fusion.1", 0, 500_000],                        # router logits, forward
    ["flash_fwd.1", 500_000, 1_000_000],
    ["flash_fwd.2", 1_500_000, 1_000_000],           # the recomputed forward
    ["flash_bwd_dq.1", 2_500_000, 1_000_000],
    ["flash_bwd_dkv.1", 3_500_000, 1_000_000],
] + [["flash_swa_fwd.%d" % i, 4_500_000 + 500_000 * i, 500_000]
     for i in range(6)] + [                          # 3 layers, twice each
    ["flash_swa_bwd_dq.%d" % i, 7_500_000 + 500_000 * i, 500_000]
    for i in range(3)] + [
    ["flash_swa_bwd_dkv.%d" % i, 9_000_000 + 500_000 * i, 500_000]
    for i in range(3)] + [
    ["gmm.%d" % i, 10_500_000 + 250_000 * i, 250_000] for i in range(20)] + [
    ["tgmm.%d" % i, 15_500_000 + 125_000 * i, 125_000] for i in range(8)] + [
    ["fusion.9", 16_500_000, 1_500_000],             # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"smallthinker.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/router/dot_general",
    "flash_fwd.1": P + "jvp()/while/body/closed_call/attention/flash_fwd",
    "flash_fwd.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "attention/flash_fwd",
    "flash_bwd_dq.1": P + "transpose(jvp())/checkpoint/attention/flash_bwd_dq",
    "flash_bwd_dkv.1": P + "transpose(jvp())/checkpoint/attention/"
                           "flash_bwd_dkv",
    **{"flash_swa_fwd.%d" % i: P + "jvp()/attention/flash_swa_fwd"
       for i in range(6)},
    **{"flash_swa_bwd_dq.%d" % i: P + "transpose(jvp())/checkpoint/attention/"
                                      "flash_swa_bwd_dq" for i in range(3)},
    **{"flash_swa_bwd_dkv.%d" % i: P + "transpose(jvp())/checkpoint/attention"
                                       "/flash_swa_bwd_dkv" for i in range(3)},
    **{"gmm.%d" % i: P + "jvp()/moe/moe/branch_0_fun/gmm" for i in range(20)},
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


def test_the_four_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(18e-3)
    cell = _cell(config, lines, throughput=7.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # flash kernels: 4 ms full + 6 ms windowed of 18 busy
    assert read["swa_flash_time_share"] == pytest.approx(100 * 10 / 18)
    full = flash_attention_gqa.required(1, 16384, 28, 4, 128)
    band = flash_attention_gqa.required(1, 16384, 28, 4, 128, window=4096)
    least = (2 * full["fwd"]["flops"] + full["bwd"]["flops"]
             + 6 * band["fwd"]["flops"] + 3 * band["bwd"]["flops"]) / 197e12
    assert read["swa_flash_roofline"] == pytest.approx(100 * least / 10e-3)
    # moe + router scopes: 0.5 + 5 + 1 ms of 18
    assert read["moe_held_time_share"] == pytest.approx(100 * 6.5 / 18)
    # 8 tgmm events = 2 a layer and step x 4 layers: one step traced
    per_layer = 3 * 17_694_720 * 16384 / 197e12
    assert read["moe_held_roofline"] == pytest.approx(
        100 * 4 * per_layer / 6e-3)
    assert any(l.startswith("moe_held_roofline: least") and "compute binds"
               in l and "1.000 steps traced" in l and "20 gmm and 8 tgmm" in l
               for l in lines)
    assert any(l.startswith("swa_flash_roofline: least") and
               "0.006000 s in the windowed kernels" in l and
               "windowed fwd 6 calls" in l and "full bwd 1 calls" in l
               for l in lines)
    assert any(l.startswith("moe_held_time_share: 36.111 % under moe + router;"
                            " 0.000 % of the busy time carries no scope")
               for l in lines)


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """No trace, an empty trace, a program without the windowed kernels or
    without the scopes (the parent commit's): no number and no error."""
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
    # lost scopes: over 5 % unattributed, the share is not reported
    lost = dict(MAPS["smallthinker.run_steps"],
                **{"gmm.%d" % i: "ragged-dot-none" for i in range(20)})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"smallthinker.run_steps": lost})
    assert mf.module("layer_metrics", "moe_held_time_share").read(
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
    # appended behind everything the benchmark had, in the issue's order
    names = list(entries)
    assert [names.index(n) for n in NEW] == sorted(names.index(n) for n in NEW)
    assert min(names.index(n) for n in NEW) > names.index(
        "flash_short_roofline")
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s16384_scan", 1) and len(cell["why"]) <= 200
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
        "batch": 1, "dims": {"S": 16384}, "staged_batches": 2,
        "trace_dispatches": 2}
    assert traffic["dims"]["S"] == config["max_position_embeddings"]
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
    "name": "smallthinker_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"head_dim": 128, "hidden_size": 64, "num_attention_heads": 6,
              "num_key_value_heads": 2, "num_hidden_layers": 4,
              "rms_norm_eps": 1e-6, "rope_theta": 1500000,
              "rope_layout": PERIOD, "sliding_window_layout": PERIOD,
              "sliding_window_size": 24, "moe_ffn_hidden_size": 32,
              "moe_num_active_primary_experts": 2,
              "moe_num_primary_experts": 2, "moe_router_width": 8,
              "moe_first_expert_held": 2, "vocab_size": 256},
    "config_factory": {
        "path": "paddle_tpu.models.smallthinker.smallthinker_tiny_config",
        "kwargs": {"remat": True}},
    "trainer_builder": {
        "path": "paddle_tpu.models.smallthinker.build_smallthinker_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 4e-4,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "smallthinker_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "smallthinker_tiny.scan"
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


def test_a_run_whose_logits_miss_the_reference_s_is_not_correct(
        tmp_path, manifest, monkeypatch):
    """The witness alone decides: with its limit under what a sound float32
    program reads, the loss passes its check and the run is not
    ``correct``."""
    from benchmark.reference import smallthinker_21b_a3b as reference

    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 1e-9)
    out, said, lines = _run_tiny(tmp_path, manifest, 0)
    assert said("reference: ")["ok"], lines
    witness = said("witness: ")
    assert not witness["ok"] and witness["logits_relative_error"] > 1e-9
    assert out["correct"] is False and out["failed"] == 0
