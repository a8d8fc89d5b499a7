"""What PR 73 adds to the benchmark: the ``kanana_2_30b_a3b`` configuration
file against the program's factory and the catalog's keys (ONE key reduced),
the arithmetic of its ``changed`` against the program's own tree and specs
(a chip's count and the host's), the required FLOPs and the exchange's bytes
against the issue's numbers, the twelve new readers on a synthetic reduced
trace of four devices, the new cell's files, a tiny copy of the configuration
through the harness and the new driver on FOUR CPU devices (and with faults
in its reference), and the new entries looked up BY NAME."""

import copy
import importlib
import json
import os
import time

import pytest

from benchmark.flops import kanana2_train
from benchmark.harness import build, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "kanana_2_30b_a3b", "kanana_2_30b_a3b.s8192_ep4"
NEW = {"ep_exchange_time_share": ("%", "lower", "model code", "device_trace"),
       "ep_all_to_all_share": ("%", "lower", "collectives", "device_trace"),
       "ep_collective_exposed_share": ("%", "lower", "collectives",
                                       "device_trace"),
       "ep_all_to_all_roofline": ("%", "higher", "collectives",
                                  "device_trace"),
       "moe_ep32of128_time_share": ("%", "lower", "model code",
                                    "device_trace"),
       "moe_ep32of128_roofline": ("%", "higher", "kernels", "device_trace"),
       "ep_mla_time_share": ("%", "lower", "model code", "device_trace"),
       "ep_mla_flash_roofline": ("%", "higher", "kernels", "device_trace"),
       "ep_head_time_share": ("%", "lower", "model code", "device_trace"),
       "ep_tier_max": ("rounds", "lower", "model code", "program_counter"),
       "ep_collective_share": ("%", "lower", "collectives", "device_trace"),
       "ep_optimizer_time_share": ("%", "lower", "model code",
                                   "device_trace")}
# the catalog's config of kanana-2-30b-a3b-instruct-2601, as published
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
REDUCED = {"num_hidden_layers": 5}
ADDED = {"expert_parallel_size": 4}
S = 8192


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
    row, = [r for r in rows if r["name"] == "kanana-2-30b-a3b-instruct-2601"]
    assert row["config"] == PUBLISHED and row["not_given"] == []
    assert row["source_url"] == mf.config_entry(mf.load(ROOT), NAME)["source"]


def test_file_holds_every_published_key_but_the_depth(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == ADDED
    assert set(config["changed"]) == {"num_hidden_layers", "arithmetic"}
    assert "48 -> 5" in config["changed"]["num_hidden_layers"]
    assert [k[0] for k in list(config["assumed"])] == list("abcdef")
    assert "ONE v5e host of FOUR chips" in config["deployment"]
    assert config["source"] == entry["source"]
    assert "GB" in config["described_chip"]


def test_model_block_equals_what_the_factory_returns(config):
    cfg = build._call(config["config_factory"])
    model = config["model"]
    got = {
        "hidden_size": cfg.hidden, "qk_head_dim": cfg.head_dim,
        "hidden_act": cfg.expert_act, "attention_bias": cfg.bias,
        "intermediate_size": cfg.dense_ffn_hidden,
        "moe_intermediate_size": cfg.ffn_hidden,
        "n_shared_experts": cfg.shared_ffn_hidden // cfg.ffn_hidden,
        "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank or None,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.kv_heads,
        "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.experts_per_token,
        "routed_scaling_factor": cfg.route_scale,
        "scoring_func": {"sigmoid_biased_top_k": "sigmoid"}[cfg.routing],
        "first_k_dense_replace": len(cfg.prefix_pattern),
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "rope_scaling": None,
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_seq}
    assert got == {k: model[k] for k in got}
    # keys no layer reads, or that say HOW a layer reads another key
    assert {k: model[k] for k in set(model) - set(got)} == {
        "head_dim": 64, "model_type": "deepseek_v3", "moe_layer_freq": 1,
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "rope_interleave": True,
        "expert_parallel_size": 4}
    assert cfg.expert_parallel and not cfg.experts_held and cfg.remat \
        and cfg.dtype == "bfloat16" and cfg.experts_here == 128
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    assert cfg.residual_out_gain == 48 ** -0.5
    assert (cfg.router_bias_rate, cfg.router_bias_std) == (1e-3, 0.01)
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.n_periods, full.vocab_size) == (
        48, 47, 128256)
    assert config["lr"] == 1e-5


def test_the_arithmetic_of_changed_against_the_program_s_tree(config):
    """The counts the file states, from the shapes the program seeds and the
    specs it places them by: a chip's and the host's."""
    import jax

    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    tree = jax.eval_shape(lambda: T.init_transformer_params(
        jax.random.PRNGKey(0), cfg))
    specs = T.transformer_param_specs(cfg)
    sparse = {k: int(v.size) // 4 for k, v in
              tree["params_layers"]["p0"].items()}
    dense = {k: int(v.size) for k, v in tree["prefix_layers"]["l0"].items()}
    attention = sum(dense[k] for k in ("wq", "wkv_a", "wkv_b", "wo"))
    experts = sparse["we_gate_up"] + sparse["we_down"]
    shared = sparse["ws_gate_up"] + sparse["ws_down"]
    host = sum(x.size for x in jax.tree.leaves(tree))
    chip = sum(x.size // (4 if "dp" in tuple(spec) else 1)
               for x, spec in zip(jax.tree.leaves(tree), jax.tree.leaves(
                   specs, is_leaf=lambda s: isinstance(s, T.P))))
    assert (round(attention / 1e6, 2),
            round((dense["w_gate_up"] + dense["w_down"]) / 1e6, 2),
            round(experts / 128 / 1e6, 2), round(experts / 4 / 1e6, 1),
            round(shared / 1e6, 2), round(sparse["router"] / 1e6, 2),
            round((sum(sparse.values()) - experts * 3 / 4) / 1e6, 1),
            round(sum(sparse.values()) / 1e6, 1),
            round(sum(dense.values()) / 1e6, 1),
            round((tree["tok_emb"].size + tree["lm_head"].size) / 1e6, 1),
            chip, host) == (
        26.35, 37.75, 4.72, 151.0, 9.44, 0.26, 187.0, 640.0, 64.1, 525.3,
        1337615360, 3149554688)
    text = config["changed"]["arithmetic"]
    for count in ("26.35 M", "37.75 M", "4.72 M", "151.0 M", "9.44 M",
                  "0.26 M", "640.0 M", "187.0 M", "64.1 M", "525.3 M",
                  "1,337,615,360", "3,149,554,688", "10.70 GB", "1,587.9 M",
                  "39.0 T", "43.0 %", "33.1 %", "14.3 %", "604 MB",
                  "2.42 GB"):
        assert count in text, count
    assert round(chip * 8 / 1e9, 2) == 10.70


def test_required_flops_and_bytes_against_the_issue_s_numbers(config):
    model = config["model"]
    parts = kanana2_train.parts_per_token(model, S)
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 1587.9
    assert {k: round(100 * v / total, 1) for k, v in parts.items()} == {
        "latent": 43.0, "dense": 4.8, "routed": 14.3, "shared": 4.8,
        "router": 0.1, "head": 33.1}
    assert round(kanana2_train.chain_flops_per_token(model) / 1e6, 1) == 52.7
    assert round(kanana2_train.pair_flops_per_token(model, S) / 1e6, 1) \
        == 83.9
    assert kanana2_train.per_unit(model, {"S": S}) == 3 * total
    assert round(3 * total * S / 1e12, 1) == 39.0
    # the exchange: three quarters of 49,152 rows of 4 KB, four times
    assert kanana2_train.exchange_bytes(model, S, 4) \
        == 4 * 36864 * 2048 * 2 == 603979776
    assert kanana2_train.exchange_bytes(model, S, 1) == 0
    moe = kanana2_train.expert_matmuls(model, S)
    assert moe["flops"] == 3 * 6 * 6.0 * 2048 * 768 * S
    assert moe["bytes"] == 3 * (32 * 3 * 2048 * 768 * 2
                                + 2 * S * 6 * 2048 * 2)
    att = kanana2_train.latent_attention(model, 1, S)
    assert att["fwd"]["flops"] == 2.0 * S * (S + 1) / 2 * 32 * 320
    assert att["bwd"]["flops"] == 2 * att["fwd"]["flops"]


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# four devices, a traced stretch of 40 ms, busy 38 ms: ONE step of ONE
# sparse layer (the synthetic times are made up: a share is the chip's)
OPS = [
    ["while.4", 0, 40_000_000],                          # control flow
    ["fusion.2", 0, 4_000_000],                          # wq, wkv
    ["flash_fwd.1", 4_000_000, 3_000_000],
    ["flash_fwd.2", 7_000_000, 3_000_000],               # recomputed
    ["flash_bwd_fused.1", 10_000_000, 6_000_000],
    ["fusion.3", 16_000_000, 2_000_000],                 # wo
    ["fusion.5", 18_000_000, 1_000_000],                 # the pack
    ["all_to_all.1", 19_000_000, 1_000_000],      # as the chip names it
    ["all-to-all.2", 20_000_000, 1_000_000],
] + [["gmm.%d" % i, 21_000_000 + 500_000 * i, 500_000] for i in range(4)] \
  + [["tgmm.%d" % i, 23_000_000 + 500_000 * i, 500_000] for i in range(2)] \
  + [["fusion.4", 24_000_000, 3_000_000],                # router
     ["all-reduce.7", 27_000_000, 2_000_000],            # the gradients
     ["fusion.8", 28_500_000, 500_000],   # ... an update behind them
     ["fusion.9", 31_000_000, 9_000_000]]                # lm_head
TRACE = {"planes": [_plane("/device:TPU:%d" % i, OPS) for i in range(4)]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"kanana2.run_steps": {
    "fusion.2": P + "jvp()/latent_attention/dot_general",
    "flash_fwd.1": P + "jvp()/latent_attention/flash_fwd",
    "flash_fwd.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
    "latent_attention/flash_fwd",
    "flash_bwd_fused.1": P + "transpose(jvp())/checkpoint/latent_attention/"
    "flash_bwd_fused",
    "fusion.3": P + "transpose(jvp())/checkpoint/latent_attention/dot_general",
    "fusion.5": P + "jvp()/moe/moe/exchange/gather",
    "all_to_all.1": P + "jvp()/moe/moe/exchange/all_to_all",
    "all-to-all.2": P + "transpose(jvp())/checkpoint/moe/moe/exchange/"
    "all_to_all",
    **{"gmm.%d" % i: P + "jvp()/moe/moe/gmm" for i in range(4)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/moe/tgmm"
       for i in range(2)},
    "fusion.4": P + "jvp()/moe/router/dot_general",
    "all-reduce.7": "jit(multi)/while/body/grad_sync/psum",
    "fusion.8": "jit(multi)/while/body/optimizer/mul",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}
COUNTERS = {"monitor.train.moe_exchange_tier": 0.0,
            "monitor.train.moe_exchange_tier.end": 1.0,
            "monitor.train.moe_exchange_fullest": 12500.0,
            "monitor.train.moe_exchange_capacity": 15360.0}


def _cell(config, lines, throughput=7.0):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 4,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def _read(name, trace, cell, counters=None):
    return mf.module("layer_metrics", name).read(trace, None, counters or {},
                                                 cell)


def _trace_file(monkeypatch, trace):
    """The two collective readers take the run's trace FILE once more: hand
    them this neutral form as what the file holds."""
    exposed = mf.module("layer_metrics", "ep_collective_exposed_share")
    monkeypatch.setattr(exposed, "newest_trace", lambda cell: "a.xplane.pb")
    monkeypatch.setattr(tr, "load_xplane", lambda path: copy.deepcopy(trace))
    exposed._kept.clear()


def test_the_twelve_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    _trace_file(monkeypatch, TRACE)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(38e-3)
    one = copy.deepcopy(config)
    one["model"]["num_hidden_layers"] = 2       # the dense one and ONE sparse
    cell = _cell(one, lines)
    peaks = cell["peaks"]
    assert _read("ep_exchange_time_share", trace, cell) == pytest.approx(
        100 * 3 / 38)
    assert _read("ep_all_to_all_share", trace, cell) == pytest.approx(
        100 * 2 / 40)
    # the harness's pattern sees the opcode's spelling and the all-reduce
    assert trace.collective_s == pytest.approx(3e-3)
    # ... these two BOTH spellings of the exchange beside the all-reduce, of
    # which a fusion hides a quarter (``fusion.8``, inside it)
    assert _read("ep_collective_share", trace, cell) == pytest.approx(
        100 * 4 / 40)
    assert _read("ep_collective_exposed_share", trace, cell) \
        == pytest.approx(100 * 3.5 / 40)
    assert any(l.startswith("ep_collective_exposed_share: 0.003500 s of the "
                            "0.004000 s") for l in lines)
    assert _read("ep_optimizer_time_share", trace, cell) == pytest.approx(
        100 * 0.5 / 38)
    assert _read("moe_ep32of128_time_share", trace, cell) == pytest.approx(
        100 * 6 / 38)
    assert _read("ep_mla_time_share", trace, cell) == pytest.approx(
        100 * 18 / 38)
    assert _read("ep_head_time_share", trace, cell) == pytest.approx(
        100 * 9 / 38)
    assert _read("ep_tier_max", trace, cell, COUNTERS) == 1.0
    need = kanana2_train.exchange_bytes(one["model"], S, 4)
    assert _read("ep_all_to_all_roofline", trace, cell) == pytest.approx(
        100 * need * 8 / peaks["ici_bits_per_s"] / 2e-3)
    need = kanana2_train.expert_matmuls(one["model"], S)
    assert _read("moe_ep32of128_roofline", trace, cell) == pytest.approx(
        100 * need["flops"] / peaks["bf16_flops"] / 3e-3)
    need = kanana2_train.latent_attention(one["model"], 1, S)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    assert _read("ep_mla_flash_roofline", trace, cell) == pytest.approx(
        100 * least / 12e-3)
    assert any(l.startswith("ep_all_to_all_roofline: least") for l in lines)
    # the whole step's share reads this cell from its own FLOP file, a chip
    assert _read("model_mfu", trace, cell) == pytest.approx(
        100 * 7.0 * kanana2_train.per_unit(one["model"], cell["dims"])
        / (4 * peaks["bf16_flops"]))


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """The parent commit's program: no such scope, no kernel of these names,
    no collective, no counters."""
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    bare = {"planes": [_plane("/device:TPU:0", [
        ["fusion.1", 0, 30_000_000], ["fusion.2", 30_000_000, 6_000_000]])]}
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"x.run_steps": {
        "fusion.1": P + "jvp()/mlp/dot_general",
        "fusion.2": P + "jvp()/mlp/dot_general"}})
    trace, lines = tr.Reduced(bare), []
    _trace_file(monkeypatch, bare)
    for name in NEW:
        assert not _read(name, trace, _cell(config, lines)), name
        assert _read(name, None, _cell(config, lines)) is None, name


def test_new_entries_by_name(manifest):
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_ep4", 4) and len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name, (unit, better, layer, source) in NEW.items():
        e = by_name[name]
        assert (e["unit"], e["better"], e["layer"], e["source"], e["moves"],
                e["workloads"]) == (unit, better, layer, source,
                                    "train_throughput", [CELL]), name
    # appended at the end of their lists
    assert [e["name"] for e in manifest["per_layer"]][-12:] == list(NEW)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == NAME
    reported = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) | {"model_mfu", "device_idle_share", "step_ms_p50"} \
        <= reported
    # the other collective metrics keep their lists
    assert by_name["collective_share"]["workloads"] == ["bert_base.s512_dp4"]
    # two of twenty-one cells ask for four chips; a quarter may
    four = [w["name"] for w in manifest["workloads"] if w["chips"] == 4]
    assert four == ["bert_base.s512_dp4", CELL]
    assert len(manifest["workloads"]) == 21 and len(manifest["configs"]) == 17


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert traffic["driver"] == "train_scan_witnessed_mesh"
    assert (traffic["batch"], traffic["dims"], traffic["staged_batches"],
            traffic["mesh"], traffic["trace_dispatches"]) == (
        4, {"S": S}, 2, {"dp": 4, "pp": 1, "tp": 1}, 1)
    from benchmark.reference import kanana_2_30b_a3b as reference

    at = reference.witness_positions(S)
    assert len(at) == 64 and at[0] == 0 and at[-1] == S - 1
    assert "64 positions" in traffic["about"]
    ids, = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0, "high": 128256}
    from paddle_tpu.parallel import moe

    assert moe._exchange_capacity(S * 6, 4) == 15360
    assert "15,360" in traffic["about"] and "12,288" in traffic["about"]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "kanana2_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": dict(
        PUBLISHED, hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
        moe_intermediate_size=32, intermediate_size=96, n_shared_experts=2,
        num_experts_per_tok=2, n_routed_experts=8, num_hidden_layers=3,
        vocab_size=256, expert_parallel_size=4),
    "config_factory": {"path": "paddle_tpu.models.kanana2.kanana2_tiny_config",
                       "kwargs": {"remat": True, "shared_ffn_hidden": 64}},
    "trainer_builder": {
        "path": "paddle_tpu.models.kanana2.build_kanana2_trainer",
        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [
        {"name": "ids", "shape": ["B", "S"], "dtype": "int32",
         "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "kanana2_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "kanana2_tiny.ep4"
    traffic = {"driver": "train_scan_witnessed_mesh", "batch": 4,
               "staged_batches": 2, "trace_dispatches": 1,
               "mesh": {"dp": 4, "pp": 1, "tp": 1}, "dims": {"S": 64}}
    root, m = write_tree(tmp_path, manifest, {cell: (TINY, traffic, 4)})
    lines = []
    out = run_cell(root, m, cell, seed=2147483659, seconds=0.3, trace=trace,
                   t_start=time.perf_counter(), devices=jax.devices()[:4],
                   say=lines.append)

    def said(head):
        return json.loads([l for l in lines if l.startswith(head)][0]
                          [len(head):])

    return out, said, lines


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_copy_runs_through_the_harness_on_four_devices(
        tmp_path, manifest, trace):
    out, said, lines = _run_tiny(tmp_path, manifest, trace)
    assert out["correct"] is True, lines
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert said("reference: ")["relative_error"] < 1e-5
    witness = said("witness: ")
    assert witness["ok"] and witness["logits_relative_error"] < 1e-5
    assert len(witness["by_sequence"]) == 4
    counters = said("counters: ")
    assert counters["monitor.train.moe_exchange_tier"] == 0
    assert counters["monitor.train.moe_exchange_capacity"] == 128
    assert 0 < counters["monitor.train.moe_rows_sent"] < 2 * 4 * 128
    assert counters["monitor.train.moe_load_max_over_mean"] >= 1
    assert counters["monitor.kernels.moe_rows_sum_calls{fused=1,k=2}"] >= 1
    after = said("counters at the end: ")
    assert after["monitor.train.moe_exchange_tier.end"] == 0
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        # no device plane; the counter's metric is read all the same
        assert set(NEW) & set(out["metrics"]) == {"ep_tier_max"}
        assert out["metrics"]["ep_tier_max"]["value"] == 0
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "holder_offset_dropped", "combine_permuted", "overflow_dropped",
    "shared_expert_summed_over_chips", "route_scale_one",
    "bias_in_the_weights", "rotate_half", "shared_key_rotated_twice",
    "softmax_scale_of_nope_alone", "bfloat16_throughout"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (or in bfloat16) and a sound
    program on four devices: the witness misses its limit and the run is not
    ``correct``."""
    from benchmark.reference import kanana_2_30b_a3b as reference

    assert fault in reference.FAULTS
    forward = reference.forward
    monkeypatch.setattr(
        reference, "forward",
        lambda params, batch, model, faults=(), *a, **kw: forward(
            params, batch, model, tuple(faults) + (fault,), *a, **kw))
    monkeypatch.setattr(reference, "_last", {})
    # the tiny program is float32 (its sound reading is 1e-6): the limit a
    # float32 program allows
    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 1e-4)
    out, said, lines = _run_tiny(tmp_path, manifest, 0)
    assert out["correct"] is False
    assert not said("witness: ")["ok"]


def test_the_collective_readers_find_the_newest_trace_of_the_cell(tmp_path):
    exposed = mf.module("layer_metrics", "ep_collective_exposed_share")
    cell = {"config": {"name": NAME}}
    assert exposed.newest_trace(cell, str(tmp_path)) is None
    for i, name in enumerate((NAME + ".other", CELL, "bert_base.s512_dp4")):
        run = tmp_path / "out" / name / "trace" / "plugins" / "profile" / "t"
        run.mkdir(parents=True)
        (run / "host.xplane.pb").write_bytes(b"")
        os.utime(run / "host.xplane.pb", (100 + i, 100 + i))
    assert exposed.newest_trace(cell, str(tmp_path)) == str(
        tmp_path / "out" / CELL / "trace" / "plugins" / "profile" / "t"
        / "host.xplane.pb")
