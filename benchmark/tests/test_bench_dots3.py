"""What PR 63 adds to the benchmark: the ``dots3_note_prev`` configuration
file against the program's factory and the catalog's keys, the arithmetic of
its ``changed`` against the program's own tree, the required FLOPs against
the issue's numbers, the seven new readers on a synthetic reduced trace, the
new cell's files, a tiny copy of the configuration through the harness on
the CPU (and with each fault in its reference), and the new entries looked up
BY NAME."""

import copy
import importlib
import json
import os
import time

import pytest

from benchmark.flops import dots3_train
from benchmark.harness import build, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "dots3_note_prev", "dots3_note_prev.s8192_scan"
NEW = {"mla_dsa_time_share": ("lower", "model code"),
       "mla_swa_time_share": ("lower", "model code"),
       "indexer64_time_share": ("lower", "model code"),
       "dense_ffn_time_share": ("lower", "model code"),
       "mla_dsa_flash_roofline": ("higher", "kernels"),
       "mla_swa_flash_roofline": ("higher", "kernels"),
       "indexer64_scores_roofline": ("higher", "kernels")}
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8,
           "num_attention_heads": 32, "swa_num_attention_heads": 16,
           "vocab_size": 19008}
OWN = {"router_width": 256, "first_expert_held": 0, "first_head_held": 0,
       "swa_first_head_held": 0, "attention_heads_published": 128,
       "swa_attention_heads_published": 64}
S = 8192


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


@pytest.fixture(scope="module")
def published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    row, = [r for r in rows if r["name"] == "dots3-note-prev"]
    return row


def test_file_holds_every_published_key_but_the_reduced(config, manifest,
                                                        published):
    entry = mf.config_entry(manifest, NAME)
    pub = published["config"]
    assert published["source_url"] == entry["source"] == config["source"]
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    differs = {k: config[k] for k, v in pub.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's, the groups whole
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "swa_q_lora_rank",
                "swa_kv_lora_rank", "swa_qk_nope_head_dim",
                "swa_qk_rope_head_dim", "swa_v_head_dim", "index_head_dim",
                "index_n_heads", "index_topk", "sliding_window_size",
                "num_experts_per_tok", "layer_types", "rope_theta",
                "swa_rope_theta"):
        assert config[key] == pub[key] and key not in entry["reduced"]
    assert config["num_hidden_layers"] == 1 + 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == pub["vocab_size"]
    assert config["num_attention_heads"] * 4 == pub["num_attention_heads"]
    assert config["swa_num_attention_heads"] * 4 \
        == pub["swa_num_attention_heads"]
    assert {k: config["model"][k] for k in pub} == {k: config[k] for k in pub}
    assert {k: config["model"][k] for k in set(config["model"]) - set(pub)} \
        == OWN
    assert set(config["changed"]) == {
        "num_hidden_layers", "n_routed_experts", "num_attention_heads",
        "vocab_size", "arithmetic"}
    for text in ("46 -> 5", "256 -> 8", "128 -> 32", "64 -> 16",
                 "152,064 -> 19,008"):
        assert any(text in v for v in config["changed"].values()), text
    assert [k[0] for k in list(config["assumed"])] == list("abcdefghijkl")
    assert "32 v5e chips" in config["deployment"]


def test_model_block_equals_what_the_factory_returns(config):
    from benchmark.reference import dots3_note_prev as reference

    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = dict(reference.model_of(cfg), **{
        "hidden_size": cfg.hidden, "hidden_act": cfg.expert_act,
        "attention_bias": cfg.bias, "intermediate_size": cfg.dense_ffn_hidden,
        "moe_intermediate_size": cfg.ffn_hidden,
        "n_routed_experts": cfg.experts_here, "router_width": cfg.n_experts,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "apply_mla_qkv_lora_rescale": cfg.latent_rescale,
        "attention_gate_type": "headwise",
        "swa_attention_gate_type": "headwise",
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq, "rope_scaling": None})
    full, sliding = (cfg.position(k)[0] for k in cfg.layer_kinds[:2])
    got.update({"q_lora_rank": full.q_lora_rank,
                "kv_lora_rank": full.kv_lora_rank,
                "swa_q_lora_rank": sliding.q_lora_rank,
                "swa_kv_lora_rank": sliding.kv_lora_rank,
                "attention_heads_published": full.n_heads,
                "swa_attention_heads_published": sliding.n_heads})
    # the published depth's layer_types stand whole in the file
    got["layer_types"] = model["layer_types"]
    assert model["layer_types"][:5] == reference.model_of(cfg)["layer_types"]
    assert got == {k: model[k] for k in got}
    assert {k: model[k] for k in set(model) - set(got)} == {
        "model_type": "dots3_note", "moe_layer_freq": 1,
        "num_key_value_heads": 128, "swa_num_key_value_heads": 64}
    assert cfg.shared_ffn_hidden == cfg.ffn_hidden == 1536
    assert cfg.attn_gate == "head" and cfg.causal and cfg.remat \
        and cfg.dtype == "bfloat16" and cfg.indexer_query == "latent" \
        and cfg.indexer_rope_dim == 64 and cfg.run_scan
    assert cfg.residual_out_gain == 46 ** -0.5
    assert (cfg.router_bias_rate, cfg.router_bias_std) == (5e-5, 0.01)
    whole = build.resolve(config["config_factory"]["path"])()
    assert (whole.n_layers, whole.experts_here, whole.vocab_size) == (
        45, 256, 152064)
    assert [whole.position(k)[0].heads_here for k in whole.layer_kinds[:2]] \
        == [128, 64]
    assert config["lr"] == 1e-5


def test_the_arithmetic_of_changed_against_the_program_s_tree(config):
    """The counts the file states, from the shapes the program seeds."""
    import jax

    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    tree = jax.eval_shape(lambda: T._init_params(jax.random.PRNGKey(0), cfg))

    def size(t):
        return sum(int(x.size) for x in jax.tree.leaves(t))

    l0 = tree["prefix_layers"]["l0"]
    r0, r1 = (tree["params_layers"][r] for r in ("r0", "r1"))
    attention = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wz")
    indexer = ("wq_idx", "wk_idx", "w_idx", "idx_k_norm_scale",
               "idx_k_norm_bias")
    counts = (sum(l0[k].size for k in attention),
              sum(l0[k].size for k in indexer),
              sum(r1[k].size for k in attention) / 3, size(l0), size(r0),
              size(r1) / 3, tree["tok_emb"].size + tree["lm_head"].size,
              size(tree))
    assert tuple(round(c / 1e6, 2) for c in counts) == (
        40.30, 9.37, 31.06, 262.02, 263.34, 244.72, 194.64, 1454.18)
    assert round(size(tree) * 8 / 1e9, 2) == 11.63
    text = config["changed"]["arithmetic"]
    for count in ("40.30 M", "9.37 M", "31.06 M", "262.0 M", "263.3 M",
                  "244.7 M", "194.6 M", "1,454.2 M", "11.63 GB", "14.68 M",
                  "43.7 %", "4.07 M", "12.43 T", "36.3 T", "43 %", "28 %",
                  "13 %"):
        assert count in text, count
    # with every head held: the floors leave no step
    whole = build._call(dict(config["config_factory"], kwargs=dict(
        config["config_factory"]["kwargs"], heads_held_share=1)))
    every = size(jax.eval_shape(lambda: T._init_params(
        jax.random.PRNGKey(0), whole)))
    assert round(every * 8 / 1e9, 1) == 14.6 and "14.6 GB" in text


def test_required_flops_against_the_issue_s_numbers(config):
    model = config["model"]
    parts = {k: round(v / 1e12, 2) for k, v in
             dots3_train.forward(model, S).items()}
    # two full layers, three sliding, one dense FFN, four sparse
    assert parts == {
        "head": 1.59, "full.projections": 1.30,
        "full.indexer_projections": 0.31, "full.indexer_scores": 1.10,
        "full.attention": 0.60, "full.kl_target": 0.36,
        "sliding.projections": 1.51, "sliding.attention": 0.15,
        "dense_ffn": 3.48, "shared_expert": 1.55, "experts": 0.39,
        "router": 0.09}
    from benchmark.flops.keye_vl2_train import causal_pairs, selected_pairs
    from benchmark.flops.smallthinker_train import seen_pairs

    assert causal_pairs(S) == 33558528
    assert selected_pairs(S, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14681088
    assert round(100 * selected_pairs(S, 2048) / causal_pairs(S), 1) == 43.7
    assert seen_pairs(S, 513) == 513 * 514 // 2 + (S - 513) * 513 == 4071168
    assert dots3_train.held_experts_per_token(model) == 0.25
    forward = sum(dots3_train.forward(model, S).values())
    step = dots3_train.per_unit(model, {"S": S}) * S
    assert round(forward / 1e12, 2) == 12.43 and round(step / 1e12, 1) == 36.3
    assert round(100 * dots3_train.share(model, S, ("full.", "sliding."))) \
        == 43
    assert round(100 * dots3_train.share(model, S, ("dense_ffn",))) == 28
    assert round(100 * dots3_train.share(model, S, ("head",))) == 13
    trained, once = dots3_train.trained(model, S), dots3_train.forward(model,
                                                                      S)
    assert {k: trained[k] / once[k] for k in once} == dict(
        {k: 3.0 for k in once}, **{"full.indexer_projections": 2.0,
                                   "full.kl_target": 1.0})


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    idx = dots3_train.indexer_scores(model, 1, S)
    assert idx["fwd"]["flops"] == 2.0 * 33558528 * 8192
    assert idx["bwd"]["flops"] == 2 * idx["fwd"]["flops"]
    assert idx["fwd"]["bytes"] == S * (8320 * 2 + 256) + 4.0 * 33558528
    full = dots3_train.flash(model, 1, S, sliding=False)
    assert full["fwd"]["flops"] == 14681088 * 32 * (2 * 192 + 2 * 128)
    assert full["bwd"]["flops"] == 2 * full["fwd"]["flops"]
    assert full["fwd"]["bytes"] == 2.0 * S * 32 * (192 + 128) * 2
    band = dots3_train.flash(model, 1, S, sliding=True)
    assert band["fwd"]["flops"] == 4071168 * 16 * (2 * 256 + 2 * 128)
    assert band["bwd"]["bytes"] == 4.0 * S * 16 * (256 + 128) * 2


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE full layer over a
# dense FFN and ONE sliding layer, one step
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                          # control flow
    ["fusion.1", 0, 2_000_000],                          # full: projections
    ["indexer_scores_fwd.1", 2_000_000, 1_000_000],
    ["indexer_scores_fwd.2", 3_000_000, 1_000_000],      # recomputed
    ["indexer_scores_bwd.1", 4_000_000, 2_000_000],
    ["fusion.2", 6_000_000, 1_000_000],                  # the select
    ["flash_dsa_fwd.1", 7_000_000, 2_000_000],
    ["dsa_attend_kl_fwd.1", 9_000_000, 2_000_000],
    ["dsa_attend_kl_fwd.2", 11_000_000, 2_000_000],      # recomputed
    ["flash_dsa_bwd_fused.1", 13_000_000, 4_000_000],
    ["fusion.3", 17_000_000, 1_000_000],                 # the gate
    ["fusion.4", 18_000_000, 6_000_000],                 # the dense FFN
    ["fusion.5", 24_000_000, 3_000_000],                 # sliding: projections
    ["flash_swa_fwd.1", 27_000_000, 1_000_000],
    ["flash_swa_fwd.2", 28_000_000, 1_000_000],          # recomputed
    ["flash_swa_bwd_fused.1", 29_000_000, 2_000_000],
    ["fusion.9", 31_000_000, 5_000_000]])]}              # lm_head
P = "jit(multi)/while/body/closed_call/"
FWD, RE, BWD = ("jvp()/%s/", "transpose(jvp())/checkpoint/"
                "rematted_computation/%s/", "transpose(jvp())/checkpoint/%s/")
DSA, SWA = "mla_dsa", "mla_swa"
MAPS = {"dots3.run_steps": {
    "fusion.1": P + FWD % DSA + "dot_general",
    "indexer_scores_fwd.1": P + FWD % DSA + "indexer/indexer_scores_fwd",
    "indexer_scores_fwd.2": P + RE % DSA + "indexer/indexer_scores_fwd",
    "indexer_scores_bwd.1": P + BWD % DSA
    + "sparse_attn/indexer/indexer_scores_bwd",
    "fusion.2": P + FWD % DSA + "indexer_select/while/body/reduce_sum",
    "flash_dsa_fwd.1": P + FWD % DSA + "sparse_attn/flash_dsa_fwd",
    "dsa_attend_kl_fwd.1": P + FWD % DSA + "sparse_attn/dsa_attend_kl_fwd",
    "dsa_attend_kl_fwd.2": P + RE % DSA + "sparse_attn/dsa_attend_kl_fwd",
    "flash_dsa_bwd_fused.1": P + BWD % DSA + "sparse_attn/flash_dsa_bwd_fused",
    "fusion.3": P + FWD % DSA + "attn_gate/mul",
    "fusion.4": P + "jvp()/mlp/dot_general",
    "fusion.5": P + FWD % SWA + "dot_general",
    "flash_swa_fwd.1": P + FWD % SWA + "flash_swa_fwd",
    "flash_swa_fwd.2": P + RE % SWA + "flash_swa_fwd",
    "flash_swa_bwd_fused.1": P + BWD % SWA + "flash_swa_bwd_fused",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput=11.0):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def _read(name, trace, cell):
    return mf.module("layer_metrics", name).read(trace, None, {}, cell)


def test_the_seven_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    cell = _cell(copy.deepcopy(config), lines)
    peaks = cell["peaks"]
    # the full layer's own scope and the flash calls inside it
    assert _read("mla_dsa_time_share", trace, cell) == pytest.approx(
        100 * (2 + 2 + 2 + 2 + 4) / 36)
    assert _read("mla_swa_time_share", trace, cell) == pytest.approx(
        100 * 7 / 36)
    assert _read("indexer64_time_share", trace, cell) == pytest.approx(
        100 * 5 / 36)
    assert _read("dense_ffn_time_share", trace, cell) == pytest.approx(
        100 * 6 / 36)
    need = dots3_train.indexer_scores(config["model"], 1, S)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    assert _read("indexer64_scores_roofline", trace, cell) == pytest.approx(
        100 * least / 4e-3)
    need = dots3_train.flash(config["model"], 1, S, sliding=False)
    least = (3 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    got = _read("mla_dsa_flash_roofline", trace, cell)
    assert got == pytest.approx(100 * least / 10e-3) and got < 100
    need = dots3_train.flash(config["model"], 1, S, sliding=True)
    least = sum(max(n["flops"] / peaks["bf16_flops"],
                    n["bytes"] / peaks["hbm_bytes_per_s"]) * calls
                for n, calls in ((need["fwd"], 2), (need["bwd"], 1)))
    got = _read("mla_swa_flash_roofline", trace, cell)
    assert got == pytest.approx(100 * least / 4e-3) and got < 100
    assert any(l.startswith("mla_dsa_flash_roofline: least") for l in lines)


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """The parent commit's program: no scope, no kernel of these names
    (Keye's, which has the indexer's scopes and kernels, reads nothing of
    the two shares that lean on them)."""
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    bare = {"planes": [_plane("/device:TPU:0", [
        ["fusion.1", 0, 30_000_000], ["flash_fwd.1", 30_000_000, 4_000_000],
        ["fusion.2", 34_000_000, 2_000_000]])]}
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"x.run_steps": {
        "fusion.1": P + "jvp()/attention/dot_general",
        "flash_fwd.1": P + "jvp()/attention/flash_fwd",
        "fusion.2": P + "jvp()/attention/indexer/dot_general"}})
    trace, lines = tr.Reduced(bare), []
    for name in NEW:
        assert _read(name, trace, _cell(config, lines)) is None, name
        assert _read(name, None, _cell(config, lines)) is None, name


def test_new_entries_by_name(manifest):
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_scan", 1) and len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = by_name[name]
        assert (e["unit"], e["better"], e["layer"], e["source"], e["moves"],
                e["workloads"]) == ("%", better, layer, "device_trace",
                                    "train_throughput", [CELL]), name
    assert len(manifest["workloads"]) == 18 and len(manifest["configs"]) == 14
    assert [e["name"] for e in manifest["per_layer"]][-7:] == list(NEW)
    reported = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) | {"model_mfu", "device_idle_share"} <= reported


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert traffic["driver"] == "train_scan_witnessed"
    assert (traffic["batch"], traffic["dims"], traffic["staged_batches"],
            traffic["mesh"]) == (1, {"S": S}, 2, {"dp": 1, "pp": 1, "tp": 1})
    from benchmark.reference import dots3_note_prev as reference

    groups = reference.witness_groups(S)
    assert len(reference.witness_positions(S)) == 1059
    assert "1,059 positions" in traffic["about"]
    assert list(groups["before_window"]) == list(range(505, 513))
    assert list(groups["past_window"]) == list(range(513, 521))
    assert list(groups["before_topk"]) == list(range(2040, 2048))
    assert list(groups["past_topk"]) == list(range(2048, 2056))
    assert list(groups["end"]) == list(range(S - 8, S))
    field, = config["batch_fields"]
    assert field["gen"] == {"kind": "randint", "low": 0, "high": 19008}


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


def _tiny():
    from benchmark.reference import dots3_note_prev as reference
    from paddle_tpu.models import dots3

    return {
        "name": "dots3_tiny", "unit_of_work": "token",
        "units_per_step": ["B", "S"],
        "model": reference.model_of(dots3.dots3_tiny_config()),
        "config_factory": {
            "path": "paddle_tpu.models.dots3.dots3_tiny_config",
            "kwargs": {"remat": True}},
        "trainer_builder": {
            "path": "paddle_tpu.models.dots3.build_dots3_trainer",
            "kwargs": {}},
        "optimizer": {"path": "paddle_tpu.parallel.optim.adamw",
                      "kwargs": {}},
        "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
        "lr": 1e-5,
        "batch_fields": [{"name": "ids", "shape": ["B", "S"],
                          "dtype": "int32",
                          "gen": {"kind": "randint", "low": 0, "high": 256}}],
        "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "dots3_tiny.scan"
    traffic = {"driver": "train_scan_witnessed", "batch": 1,
               "staged_batches": 2, "trace_dispatches": 1,
               "mesh": {"dp": 1, "pp": 1, "tp": 1}, "dims": {"S": 64}}
    root, m = write_tree(tmp_path, manifest, {cell: (_tiny(), traffic, 1)})
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
    assert witness["ok"] and witness["logits_relative_error"] < 2e-5
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        assert not set(NEW) & set(out["metrics"])       # no device plane
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


def _run_with_fault(tmp_path, manifest, monkeypatch, fault):
    """A tiny run whose reference throws ``fault`` (one of its own
    ``FAULTS``) at every call, beside a sound program."""
    from benchmark.reference import dots3_note_prev as reference

    assert fault in reference.FAULTS
    # the tiny program is float32 and stands 2e-5 from the sound reference:
    # its witness is held to 1e-3, not to the chip's bf16 limit
    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 1e-3)
    terms = reference.forward_terms
    monkeypatch.setattr(
        reference, "forward_terms",
        lambda params, batch, model, faults=(), *a, **kw: terms(
            params, batch, model, tuple(faults) + (fault,), *a, **kw))
    monkeypatch.setattr(reference, "_last", {})
    return _run_tiny(tmp_path, manifest, 0)


@pytest.mark.parametrize("fault", [
    "window_minus_one", "window_plus_one",
    "top_k_minus_one_key", "no_rescale", "gate_dropped", "gate_elementwise",
    "swa_theta_of_full", "wrong_first_head", "no_selection",
    "unrotated_indexer_keys", "w_dropped", "top_k_minus_one"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else and a sound program: the
    witness misses its limit and the run is not ``correct``."""
    out, said, lines = _run_with_fault(tmp_path, manifest, monkeypatch, fault)
    assert out["correct"] is False
    assert not said("witness: ")["ok"]


def test_a_lower_precision_in_the_reference_fails_the_run(
        tmp_path, manifest, monkeypatch):
    """The reference in bfloat16 throughout beside the tiny float32 program:
    not ``correct``, by one limit or the other (which one at the published
    sizes is the chip's to say: PERF.md section 6, PR 63)."""
    out, said, lines = _run_with_fault(tmp_path, manifest, monkeypatch,
                                       "bfloat16_throughout")
    assert out["correct"] is False
