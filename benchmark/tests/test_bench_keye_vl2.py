"""What PR 61 adds to the benchmark: the ``keye_vl2_30b_a3b`` configuration
file against the program's factory and the catalog's keys, the arithmetic of
its ``changed`` against the program's own tree, the required FLOPs against
the issue's numbers, the six new readers on a synthetic reduced trace, the
new cell's files, a tiny copy of the configuration through the harness on
the CPU (and with each fault in its reference), and the new entries looked up
BY NAME."""

import copy
import importlib
import json
import os
import time

import pytest

from benchmark.flops import keye_vl2_train
from benchmark.harness import build, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "keye_vl2_30b_a3b", "keye_vl2_30b_a3b.s16384_scan"
NEW = {"dsa_time_share": ("lower", "model code"),
       "indexer_time_share": ("lower", "model code"),
       "indexer_select_time_share": ("lower", "model code"),
       "indexer_scores_roofline": ("higher", "kernels"),
       "sparse_attn_roofline": ("higher", "kernels"),
       "moe_held16of128_roofline": ("higher", "kernels")}
SA = {"indexer_head_dim": 64, "indexer_num_heads": 16,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": 2048}
ROPE = {"mrope_section": [16, 24, 24], "rope_type": "default",
        "type": "default"}
# the catalog's config of Keye-VL-2.0-30B-A3B, as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06, "rope_scaling": ROPE,
    "rope_theta": 10000000, "sa_config": SA, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6, "num_experts": 16,
           "num_local_experts": 16, "vocab_size": 18992}
S = 16384


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_the_catalog_s_row_is_the_published_config_here():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    row, = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert row["config"] == PUBLISHED
    assert row["source_url"] == mf.config_entry(mf.load(ROOT), NAME)["source"]


def test_file_holds_every_published_key_but_the_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's, the groups whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "sa_config", "rope_scaling",
                "rope_theta"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == {
        "router_width": PUBLISHED["num_experts"], "first_expert_held": 0}
    assert set(config["changed"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size", "arithmetic"}
    for text in ("48 -> 6", "128 -> 16", "151,936 -> 18,992"):
        assert any(text in v for v in config["changed"].values()), text
    assert [k[0] for k in list(config["assumed"])] == list("abcdefghi")
    assert "eight v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = {
        "hidden_size": cfg.hidden, "head_dim": cfg.head_dim,
        "hidden_act": cfg.expert_act, "attention_bias": cfg.bias,
        "moe_intermediate_size": cfg.ffn_hidden,
        "norm_topk_prob": cfg.routing == "top_k_softmax",
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "num_experts": cfg.experts_here, "num_local_experts": cfg.experts_here,
        "router_width": cfg.n_experts, "first_expert_held": cfg.first_expert,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(ROPE, mrope_section=list(cfg.mrope_sections)),
        "sa_config": dict(SA, indexer_head_dim=cfg.indexer_dim,
                          indexer_num_heads=cfg.indexer_heads,
                          topk=cfg.indexer_topk,
                          q_chunk_size=cfg.flash_block_q,
                          kv_chunk_size=cfg.flash_block_k),
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "use_sliding_window": bool(cfg.layer_pattern), "sliding_window": None,
        "mlp_only_layers": [], "decoder_sparse_step": 1}
    assert got == {k: model[k] for k in got}
    # keys no layer reads, as published
    assert {k: model[k] for k in set(model) - set(got)} == {
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "model_type": "KeyeVL2"}
    assert cfg.qk_norm == "head" and cfg.causal and cfg.remat \
        and cfg.dtype == "bfloat16" and not cfg.shared_ffn_hidden
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    assert cfg.residual_out_gain == 48 ** -0.5
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size) == (
        48, 128, 151936)
    assert config["lr"] == 1e-5


def test_the_arithmetic_of_changed_against_the_program_s_tree(config):
    """The counts the file states, from the shapes the program seeds."""
    import jax

    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    tree = jax.eval_shape(lambda: T.init_transformer_params(
        jax.random.PRNGKey(0), cfg))
    sizes = {k: int(v.size) for k, v in tree["params_layers"].items()}
    layers = cfg.n_layers
    attention = sum(sizes[k] for k in ("wq", "wk", "wv", "wo")) / layers
    indexer = sum(sizes[k] for k in ("wq_idx", "wk_idx", "w_idx",
                                     "idx_k_norm_scale",
                                     "idx_k_norm_bias")) / layers
    experts = (sizes["we_gate_up"] + sizes["we_down"]) / layers
    norms = sum(sizes[k] for k in ("ln1_scale", "ln2_scale", "q_norm",
                                   "k_norm")) / layers
    layer = sum(sizes.values()) / layers
    vocabulary = tree["tok_emb"].size + tree["lm_head"].size
    total = sum(x.size for x in jax.tree.leaves(tree))
    assert (round(attention / 1e6, 2), round(indexer / 1e6, 2),
            round(sizes["router"] / layers / 1e6, 2),
            round(experts / 1e6, 2), round(layer / 1e6, 1),
            round(vocabulary / 1e6, 1), round(total / 1e6, 1)) == (
        18.87, 2.26, 0.26, 75.50, 96.9, 77.8, 659.2)
    assert norms == 4352 and norms + 128 == 4480
    text = config["changed"]["arithmetic"]
    for count in ("18.87 M", "2.26 M", "0.26 M", "75.50", "96.9 M", "77.8 M",
                  "659.2 M", "5.27 GB", "4,480", "31.46 M", "23.4 %",
                  "1.90 T", "34.6 T", "59 %"):
        assert count in text, count
    assert round(total * 8 / 1e9, 2) == 5.27
    # whole: 128 experts a layer, the whole vocabulary, 48 layers
    whole = layer - experts + 128 * experts / 16
    assert round(whole / 1e6, 1) == 625.4
    assert round((48 * whole + 2 * 151936 * 2048) / 1e9, 1) == 30.6


def test_required_flops_against_the_issue_s_numbers(config):
    model = config["model"]
    parts = {k: round(v / 1e12, 3) for k, v in
             keye_vl2_train.layer_forward(model, S).items()}
    assert parts == {"projections": 0.618, "indexer_projections": 0.074,
                     "indexer_scores": 0.275, "attention": 0.515,
                     "kl_target": 0.258, "experts": 0.155, "router": 0.009}
    assert keye_vl2_train.causal_pairs(S) == 134225920
    assert keye_vl2_train.selected_pairs(S, 2048) \
        == 2048 * 2049 // 2 + 14336 * 2048 == 31458304
    assert round(100 * keye_vl2_train.selected_share(S, 2048), 1) == 23.4
    assert round(100 * keye_vl2_train.mechanism_share(model, S)) == 59
    assert keye_vl2_train.held_experts_per_token(model) == 1.0
    # a trained step: 3 forwards of every part but the two the loss stops
    # a gradient at (the indexer's input: 2; the KL's target: 1), where
    # ISSUE 61's 38 T took a flat 3
    trained = keye_vl2_train.layer_trained(model, S)
    forward = keye_vl2_train.layer_forward(model, S)
    assert {k: trained[k] / forward[k] for k in forward} == dict(
        {k: 3.0 for k in forward}, indexer_projections=2.0, kl_target=1.0)
    step = keye_vl2_train.per_unit(model, {"S": S}) * S
    head = 3 * 2.0 * 2048 * 18992 * S
    assert step == 6 * sum(trained.values()) + head
    assert round(step / 1e12, 1) == 34.6
    assert round((3 * (6 * sum(forward.values())) + head) / 1e12, 1) == 38.1
    assert round(100 * head / step) == 11
    mechanism = 6 * sum(trained[k] for k in (
        "indexer_projections", "indexer_scores", "attention", "kl_target"))
    assert round(100 * mechanism / step) == 48
    # a short sequence selects everything
    assert keye_vl2_train.selected_share(2048, 2048) == 1.0


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    idx = keye_vl2_train.indexer_scores(model, 1, S)
    assert idx["fwd"]["flops"] == 2.0 * 134225920 * 1024
    assert idx["bwd"]["flops"] == 2 * idx["fwd"]["flops"]
    assert idx["fwd"]["bytes"] == S * (1088 * 2 + 64) + 4.0 * 134225920
    att = keye_vl2_train.sparse_attention(model, 1, S)
    assert att["fwd"]["flops"] == 4.0 * 31458304 * 4096
    assert att["bwd"]["flops"] == 2 * att["fwd"]["flops"]
    assert att["fwd"]["bytes"] == 2.0 * S * 4096 * 2 + 2.0 * S * 512 * 2
    moe = keye_vl2_train.expert_matmuls(model, S)
    assert moe["flops"] == 3 * 6.0 * 2048 * 768 * S
    assert moe["bytes"] == 3 * (16 * 3 * 2048 * 768 * 2 + 2 * S * 2048 * 2)


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of ONE layer
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                          # control flow
    ["fusion.1", 0, 2_000_000],                          # indexer projections
    ["indexer_scores_fwd.1", 2_000_000, 1_000_000],
    ["indexer_scores_fwd.2", 3_000_000, 1_000_000],      # recomputed
    ["indexer_scores_bwd.1", 4_000_000, 2_000_000],
    ["fusion.2", 6_000_000, 3_000_000],                  # the select
    ["flash_dsa_fwd.1", 9_000_000, 4_000_000],
    ["flash_dsa_fwd.2", 13_000_000, 4_000_000],          # recomputed
    ["flash_dsa_bwd_fused.1", 17_000_000, 6_000_000],
    ["indexer_kl_fwd.1", 23_000_000, 2_000_000],
    ["indexer_kl_fwd.2", 25_000_000, 2_000_000],         # recomputed
    ["fusion.3", 27_000_000, 1_000_000],                 # attention's wq
] + [["gmm.%d" % i, 28_000_000 + 500_000 * i, 500_000] for i in range(4)]
  + [["tgmm.%d" % i, 30_000_000 + 500_000 * i, 500_000] for i in range(2)]
  + [["fusion.9", 31_000_000, 5_000_000]])]}             # lm_head
P = "jit(multi)/while/body/closed_call/"
FWD, RE, BWD = ("jvp()/attention/", "transpose(jvp())/checkpoint/"
                "rematted_computation/attention/",
                "transpose(jvp())/checkpoint/attention/")
MAPS = {"keye_vl2.run_steps": {
    "fusion.1": P + FWD + "indexer/dot_general",
    "indexer_scores_fwd.1": P + FWD + "indexer/indexer_scores_fwd",
    "indexer_scores_fwd.2": P + RE + "indexer/indexer_scores_fwd",
    "indexer_scores_bwd.1": P + BWD + "indexer/indexer_scores_bwd",
    "fusion.2": P + FWD + "indexer_select/while/body/reduce_sum",
    "flash_dsa_fwd.1": P + FWD + "sparse_attn/flash_dsa_fwd",
    "flash_dsa_fwd.2": P + RE + "sparse_attn/flash_dsa_fwd",
    "flash_dsa_bwd_fused.1": P + BWD + "sparse_attn/flash_dsa_bwd_fused",
    "indexer_kl_fwd.1": P + FWD + "indexer_kl/indexer_kl_fwd",
    "indexer_kl_fwd.2": P + RE + "indexer_kl/indexer_kl_fwd",
    "fusion.3": P + FWD + "dot_general",
    **{"gmm.%d" % i: P + "jvp()/moe/moe/branch_0_fun/gmm" for i in range(4)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/branch_0_fun/tgmm"
       for i in range(2)},
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput=7.0):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def _read(name, trace, cell):
    return mf.module("layer_metrics", name).read(trace, None, {}, cell)


def test_the_six_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    one = copy.deepcopy(config)
    one["model"]["num_hidden_layers"] = 1
    cell = _cell(one, lines)
    peaks = cell["peaks"]
    assert _read("dsa_time_share", trace, cell) == pytest.approx(
        100 * 27 / 36)
    assert _read("indexer_time_share", trace, cell) == pytest.approx(
        100 * 13 / 36)
    assert _read("indexer_select_time_share", trace, cell) == pytest.approx(
        100 * 3 / 36)
    need = keye_vl2_train.indexer_scores(one["model"], 1, S)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    assert _read("indexer_scores_roofline", trace, cell) == pytest.approx(
        100 * least / 4e-3)
    need = keye_vl2_train.sparse_attention(one["model"], 1, S)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    got = _read("sparse_attn_roofline", trace, cell)
    assert got == pytest.approx(100 * least / 14e-3) and got < 100
    need = keye_vl2_train.expert_matmuls(one["model"], S)
    assert _read("moe_held16of128_roofline", trace, cell) == pytest.approx(
        100 * need["flops"] / peaks["bf16_flops"] / 3e-3)
    assert any(l.startswith("sparse_attn_roofline: least") for l in lines)


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """The parent commit's program: no scope, no kernel of these names."""
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    bare = {"planes": [_plane("/device:TPU:0", [
        ["fusion.1", 0, 30_000_000], ["flash_fwd.1", 30_000_000, 6_000_000]])]}
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"x.run_steps": {
        "fusion.1": P + "jvp()/attention/dot_general",
        "flash_fwd.1": P + "jvp()/attention/flash_fwd"}})
    trace, lines = tr.Reduced(bare), []
    for name in NEW:
        assert _read(name, trace, _cell(config, lines)) is None, name
        assert _read(name, None, _cell(config, lines)) is None, name


def test_new_entries_by_name(manifest):
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s16384_scan", 1) and len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = by_name[name]
        assert (e["unit"], e["better"], e["layer"], e["source"], e["moves"],
                e["workloads"]) == ("%", better, layer, "device_trace",
                                    "train_throughput", [CELL]), name
    assert len(manifest["workloads"]) == 17 and len(manifest["configs"]) == 13
    reported = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) | {"model_mfu", "device_idle_share"} <= reported


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert traffic["driver"] == "train_scan_witnessed"
    assert (traffic["batch"], traffic["dims"], traffic["staged_batches"],
            traffic["mesh"]) == (1, {"S": S}, 2, {"dp": 1, "pp": 1, "tp": 1})
    from benchmark.reference import keye_vl2_30b_a3b as reference

    groups = reference.witness_groups(S)
    assert len(reference.witness_positions(S)) == 1046
    assert "1,046 positions" in traffic["about"]
    assert list(groups["before_topk"]) == list(range(2040, 2048))
    assert list(groups["past_topk"]) == list(range(2048, 2056))
    assert list(groups["end"]) == list(range(S - 8, S))
    field, = config["batch_fields"]
    assert field["gen"] == {"kind": "randint", "low": 0, "high": 18992}


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "keye_vl2_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": dict(
        PUBLISHED, hidden_size=64, num_attention_heads=16,
        num_key_value_heads=2, moe_intermediate_size=32,
        num_experts_per_tok=2, num_experts=2, num_local_experts=2,
        router_width=8, first_expert_held=2, num_hidden_layers=2,
        vocab_size=256,
        sa_config=dict(SA, indexer_head_dim=16, indexer_num_heads=4, topk=8)),
    "config_factory": {
        "path": "paddle_tpu.models.keye_vl2.keye_vl2_tiny_config",
        "kwargs": {"remat": True}},
    "trainer_builder": {
        "path": "paddle_tpu.models.keye_vl2.build_keye_vl2_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "keye_vl2_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "keye_vl2_tiny.scan"
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


def _run_with_fault(tmp_path, manifest, monkeypatch, fault):
    """A tiny run whose reference throws ``fault`` (one of its own
    ``FAULTS``) at every call, beside a sound program."""
    from benchmark.reference import keye_vl2_30b_a3b as reference

    assert fault in reference.FAULTS
    terms = reference.forward_terms
    monkeypatch.setattr(
        reference, "forward_terms",
        lambda params, batch, model, faults=(), *a, **kw: terms(
            params, batch, model, tuple(faults) + (fault,), *a, **kw))
    monkeypatch.setattr(reference, "_last", {})
    return _run_tiny(tmp_path, manifest, 0)


@pytest.mark.parametrize("fault", [
    "no_selection", "half_topk", "w_dropped", "no_relu",
    "unrotated_indexer_keys", "selection_of_previous_row", "wrong_kv_head",
    "top_k_minus_one"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else and a sound program: the
    witness misses its limit and the run is not ``correct``."""
    out, said, lines = _run_with_fault(tmp_path, manifest, monkeypatch, fault)
    assert out["correct"] is False
    assert not said("witness: ")["ok"]


@pytest.mark.parametrize("fault", ["bfloat16_throughout", "bfloat16_layers",
                                   "float8_layers"])
def test_a_lower_precision_in_the_reference_fails_the_run(
        tmp_path, manifest, monkeypatch, fault):
    """The reference in bfloat16, throughout or in the layers alone (head
    and loss in float32), or those layers on float8 weights, beside the tiny
    float32 program: not ``correct``
    by one limit or the other (which one at the published sizes is the
    chip's to say: the reference file has the readings)."""
    out, said, lines = _run_with_fault(tmp_path, manifest, monkeypatch, fault)
    assert out["correct"] is False
