"""What PR 71 adds to the benchmark: the ``sdar_30b_a3b_chat`` configuration
file against the program's factory and the catalog's keys, the arithmetic of
its ``changed`` against the program's own tree, the required FLOPs against
the issue's numbers, the two new generators, the seven new readers on a
synthetic reduced trace, the new cell's files, a tiny copy of the
configuration through the harness and the new driver on the CPU (and with
each fault in its reference), and the new entries looked up BY NAME."""

import copy
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark.flops import sdar_train
from benchmark.harness import batches, build, manifest as mf, \
    trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "sdar_30b_a3b_chat", "sdar_30b_a3b_chat.s8192_scan"
NEW = {"bd_attn_time_share": ("lower", "model code", "device_trace"),
       "flash_blockdiff_roofline": ("higher", "kernels", "device_trace"),
       "bd_noise_time_share": ("lower", "model code", "device_trace"),
       "bd_head_time_share": ("lower", "model code", "device_trace"),
       "bd_head_rows_share": ("lower", "model code", "program_counter"),
       "moe_held32of128_time_share": ("lower", "model code", "device_trace"),
       "moe_held32of128_roofline": ("higher", "kernels", "device_trace")}
# the catalog's config of SDAR-30B-A3B-Chat, as published
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6, "num_experts": 32, "vocab_size": 37984}
ADDED = {"router_width": 128, "first_expert_held": 0, "block_length": 4,
         "mask_token_id": 37983, "noise_low": 0.45, "noise_high": 0.95}
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
    row, = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
    assert row["config"] == PUBLISHED
    assert row["not_given"] == ["block length", "noise schedule"]
    assert row["source_url"] == mf.config_entry(mf.load(ROOT), NAME)["source"]


def test_file_holds_every_published_key_but_the_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_experts_per_tok", "num_attention_heads",
                "num_key_value_heads", "rope_theta"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == ADDED
    assert set(config["changed"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size", "arithmetic"}
    for text in ("48 -> 6", "128 -> 32", "151,936 -> 37,984"):
        assert any(text in v for v in config["changed"].values()), text
    assert [k[0] for k in list(config["assumed"])] == list("abcdefgh")
    # what the catalog says the config does not give is under ``assumed``
    assert "block_length" in " ".join(config["assumed"]) \
        and "c_noise" in config["assumed"]
    assert "FOUR v5e chips" in config["deployment"]
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
        "num_experts": cfg.experts_here, "router_width": cfg.n_experts,
        "first_expert_held": cfg.first_expert,
        "num_experts_per_tok": cfg.experts_per_token,
        "num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "rope_scaling": None,
        "tie_word_embeddings": cfg.tie_head, "vocab_size": cfg.vocab_size,
        "use_sliding_window": bool(cfg.layer_pattern), "sliding_window": None,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "max_position_embeddings": cfg.max_seq,
        "block_length": cfg.block_diffusion,
        "mask_token_id": cfg.mask_token_id}
    assert got == {k: model[k] for k in got}
    # keys no layer reads, as published, and the traffic's noise interval
    assert {k: model[k] for k in set(model) - set(got)} == {
        "intermediate_size": 6144, "max_window_layers": 48,
        "model_type": "sdar_moe", "noise_low": 0.45, "noise_high": 0.95}
    assert cfg.qk_norm == "head" and not cfg.causal and cfg.remat \
        and cfg.dtype == "bfloat16" and not cfg.shared_ffn_hidden
    assert cfg.router_aux_coef == cfg.router_z_coef == 0.0
    assert cfg.residual_out_gain == 48 ** -0.5
    assert cfg.mask_embed_gain == 2.0 ** -10        # assumed f: balance
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.experts_here, full.vocab_size,
            full.mask_token_id) == (48, 128, 151936, 151935)
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
    attention = sum(sizes[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm")) / layers
    experts = (sizes["we_gate_up"] + sizes["we_down"]) / layers
    norms = sum(sizes[k] for k in ("ln1_scale", "ln2_scale", "q_norm",
                                   "k_norm")) / layers
    layer = sum(sizes.values()) / layers
    vocabulary = tree["tok_emb"].size + tree["lm_head"].size
    total = sum(x.size for x in jax.tree.leaves(tree))
    assert (round(attention / 1e6, 2),
            round(sizes["router"] / layers / 1e6, 2),
            round(experts / 1e6, 1), round(layer / 1e6, 1),
            round(vocabulary / 1e6, 1), total) == (
        18.87, 0.26, 151.0, 170.1, 155.6, 1176399360)
    assert norms == 4352
    text = config["changed"]["arithmetic"]
    for count in ("18.87 M", "0.26 M", "151.0", "170.1 M", "155.6 M",
                  "1,176,399,360", "9.41 GB", "7.06 GB", "4,352", "67.1 M",
                  "288", "39.3 T", "50.3 %", "14.2 %", "6.8 %"):
        assert count in text, count
    assert round(total * 8 / 1e9, 2) == 9.41
    # whole: 128 experts a layer, the whole vocabulary, 48 layers
    whole = layer - experts + 128 * experts / 32
    assert round(whole / 1e6, 1) == 623.1
    assert round((48 * whole + 2 * 151936 * 2048) / 1e9, 1) == 30.5


def test_required_flops_against_the_issue_s_numbers(config):
    model = config["model"]
    parts = {k: round(v / S / 1e6, 1) for k, v in
             sdar_train.layer_forward(model, S).items()}
    assert parts == {"projections": 75.5, "attention": 134.3,
                     "experts": 37.7, "router": 1.0}
    assert sdar_train.blockdiff_pairs(S, 4) == S * (S + 4) == 67141632
    # twice a causal sequence's pairs, half a causal 2 S's
    assert sdar_train.blockdiff_pairs(S, 1) == 2 * (S * (S + 1) // 2)
    assert sdar_train.held_experts_per_row(model) == 2.0
    assert sdar_train.masked_share(model) == pytest.approx(0.7)
    assert round(sdar_train.head_forward(model, S) / S / 1e6, 1) == 108.9
    step = sdar_train.per_unit(model, {"S": S}) * S
    assert round(step / 1e12, 1) == 39.3
    shares = {k: round(100 * v, 1) for k, v in
              sdar_train.shares(model, S).items()}
    assert shares == {"projections": 28.3, "attention": 50.3,
                      "experts": 14.2, "router": 0.4, "head": 6.8}


def test_kernels_required_flops_and_bytes(config):
    model = config["model"]
    att = sdar_train.blockdiff_attention(model, 1, S)
    assert att["fwd"]["flops"] == 4.0 * 67141632 * 4096
    assert att["bwd"]["flops"] == 2 * att["fwd"]["flops"]
    assert att["fwd"]["bytes"] == 2.0 * 2 * S * 4096 * 2 \
        + 2.0 * 2 * S * 512 * 2
    moe = sdar_train.expert_matmuls(model, S)
    assert moe["flops"] == 3 * 2 * 6.0 * 2048 * 768 * 2 * S
    assert moe["bytes"] == 3 * (32 * 3 * 2048 * 768 * 2
                                + 2 * 2 * S * 2 * 2048 * 2)


def test_the_noise_generators_and_the_head_s_blocks(config):
    """``stratified_uniform`` deals one level to each of a row's equal parts
    of the interval; at the cell's shape the masked tokens fill 12 blocks of
    512 at every seed tried (the traffic file's ``about`` says which)."""
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    dims = build.cell_dims(config, traffic)
    t = config["batch_fields"][1]["gen"]
    assert (t["low"], t["high"]) == (config["model"]["noise_low"],
                                     config["model"]["noise_high"])
    for seed in (0, 2147483653, 1987654321):
        for index in range(2):
            b = batches.host_batch(config["batch_fields"], dims, seed, index)
            assert b["t"].shape == (1, 2048) and b["u"].shape == (1, S)
            assert b["t"].dtype == b["u"].dtype == np.float32
            assert b["ids"].max() < 37983       # the mask token is not drawn
            # one level in each of the 2,048 equal parts of the interval
            middles = 0.45 + 0.5 * (np.arange(2048) + 0.5) / 2048
            assert np.abs(np.sort(b["t"][0]) - middles).max() \
                <= 0.25 / 2048 + 1e-6
            assert not np.array_equal(np.sort(b["t"][0]), b["t"][0])
            assert abs(b["t"].mean() - 0.7) < 1e-4
            assert 0 <= b["u"].min() and b["u"].max() < 1
            masked = int((b["u"] < np.repeat(b["t"], 4, -1)).sum())
            assert -(-masked // 512) == 12, (seed, index, masked)
    again = batches.host_batch(config["batch_fields"], dims, 0, 0)
    other = batches.host_batch(config["batch_fields"], dims, 1, 0)
    assert np.array_equal(again["t"], batches.host_batch(
        config["batch_fields"], dims, 0, 0)["t"])
    assert not np.array_equal(again["t"], other["t"])


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 40_000_000]]}]}


# one device, a traced stretch of 40 ms, busy 36 ms: ONE step of ONE layer
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 40_000_000],                          # control flow
    ["fusion.1", 0, 1_000_000],                          # the noising
    ["fusion.2", 1_000_000, 4_000_000],                  # wq, wk, wv
    ["flash_bd_fwd.1", 5_000_000, 4_000_000],
    ["flash_bd_fwd.2", 9_000_000, 4_000_000],            # recomputed
    ["flash_bd_bwd_fused.1", 13_000_000, 8_000_000],
    ["fusion.3", 21_000_000, 3_000_000],                 # wo
] + [["gmm.%d" % i, 24_000_000 + 500_000 * i, 500_000] for i in range(4)]
  + [["tgmm.%d" % i, 26_000_000 + 500_000 * i, 500_000] for i in range(2)]
  + [["fusion.4", 27_000_000, 4_000_000],                # router, sort
     ["fusion.9", 31_000_000, 5_000_000]])]}             # lm_head
P = "jit(multi)/while/body/closed_call/"
FWD, RE, BWD = ("jvp()/attention/", "transpose(jvp())/checkpoint/"
                "rematted_computation/attention/",
                "transpose(jvp())/checkpoint/attention/")
MAPS = {"sdar.run_steps": {
    "fusion.1": P + "jvp(noise)/noise/select_n",
    "fusion.2": P + FWD + "dot_general",
    "flash_bd_fwd.1": P + FWD + "flash_bd_fwd",
    "flash_bd_fwd.2": P + RE + "flash_bd_fwd",
    "flash_bd_bwd_fused.1": P + BWD + "flash_bd_bwd_fused",
    "fusion.3": P + BWD + "dot_general",
    **{"gmm.%d" % i: P + "jvp()/moe/moe/branch_0_fun/gmm" for i in range(4)},
    **{"tgmm.%d" % i: P + "transpose(jvp())/checkpoint/moe/branch_0_fun/tgmm"
       for i in range(2)},
    "fusion.4": P + "jvp()/moe/router/dot_general",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}
COUNTERS = {"monitor.train.lm_head_rows_share": 0.75,
            "monitor.train.bd_masked_share": 0.7}


def _cell(config, lines, throughput=7.0):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def _read(name, trace, cell, counters=None):
    return mf.module("layer_metrics", name).read(trace, None, counters or {},
                                                 cell)


def test_the_seven_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(36e-3)
    one = copy.deepcopy(config)
    one["model"]["num_hidden_layers"] = 1
    cell = _cell(one, lines)
    peaks = cell["peaks"]
    assert _read("bd_attn_time_share", trace, cell) == pytest.approx(
        100 * 23 / 36)
    assert _read("bd_noise_time_share", trace, cell) == pytest.approx(
        100 * 1 / 36)
    assert _read("bd_head_time_share", trace, cell) == pytest.approx(
        100 * 5 / 36)
    assert _read("moe_held32of128_time_share", trace, cell) == pytest.approx(
        100 * 7 / 36)
    assert _read("bd_head_rows_share", trace, cell, COUNTERS) == 75.0
    need = sdar_train.blockdiff_attention(one["model"], 1, S)
    least = (2 * need["fwd"]["flops"] + need["bwd"]["flops"]) \
        / peaks["bf16_flops"]
    got = _read("flash_blockdiff_roofline", trace, cell)
    # (the synthetic times are made up: a share under 100 is the chip's)
    assert got == pytest.approx(100 * least / 16e-3)
    need = sdar_train.expert_matmuls(one["model"], S)
    got = _read("moe_held32of128_roofline", trace, cell)
    assert got == pytest.approx(
        100 * need["flops"] / peaks["bf16_flops"] / 3e-3)
    assert any(l.startswith("flash_blockdiff_roofline: least") for l in lines)
    # the whole step's share reads this cell from its own FLOP file
    assert _read("model_mfu", trace, cell) == pytest.approx(
        100 * 7.0 * sdar_train.per_unit(one["model"], cell["dims"])
        / peaks["bf16_flops"])


def test_the_readers_read_nothing_where_there_is_nothing(config, monkeypatch):
    """The parent commit's program: no such scope, no kernel of these names,
    no counters."""
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    bare = {"planes": [_plane("/device:TPU:0", [
        ["fusion.1", 0, 30_000_000], ["flash_fwd.1", 30_000_000, 6_000_000]])]}
    monkeypatch.setattr(devscope, "scope_maps", lambda: {"x.run_steps": {
        "fusion.1": P + "jvp()/mlp/dot_general",
        "flash_fwd.1": P + "jvp()/mlp/flash_fwd"}})
    trace, lines = tr.Reduced(bare), []
    for name in NEW:
        if name not in ("bd_attn_time_share", "bd_head_time_share"):
            assert not _read(name, trace, _cell(config, lines)), name
        assert _read(name, None, _cell(config, lines)) is None, name


def test_new_entries_by_name(manifest):
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_scan", 1) and len(cell["why"]) <= 200
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer, source) in NEW.items():
        e = by_name[name]
        assert (e["unit"], e["better"], e["layer"], e["source"], e["moves"],
                e["workloads"]) == ("%", better, layer, source,
                                    "train_throughput", [CELL]), name
    # appended at the end of their lists
    assert [e["name"] for e in manifest["per_layer"]][-7:] == list(NEW)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == NAME
    reported = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) | {"model_mfu", "device_idle_share"} <= reported
    # at most a quarter of the cells ask for four chips
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert traffic["driver"] == "train_scan_witnessed_batch"
    assert (traffic["batch"], traffic["dims"], traffic["staged_batches"],
            traffic["mesh"], traffic["trace_dispatches"]) == (
        1, {"S": S, "NB": 2048}, 2, {"dp": 1, "pp": 1, "tp": 1}, 1)
    from benchmark.reference import sdar_30b_a3b_chat as reference

    groups = reference.witness_groups(S)
    assert len(reference.witness_positions(S)) == 272
    assert "272 rows" in traffic["about"] and "12 blocks" in traffic["about"]
    assert list(groups["block_0"]) == [0, 1, 2, 3]
    assert list(groups["tile_edge"]) == list(range(508, 516))
    assert list(groups["last"]) == list(range(S - 4, S))
    ids, t, u = config["batch_fields"]
    assert ids["gen"] == {"kind": "randint", "low": 0, "high": 37983}
    assert t["gen"] == {"kind": "stratified_uniform", "low": 0.45,
                        "high": 0.95} and t["shape"] == ["B", "NB"]
    assert u["gen"] == {"kind": "uniform"} and u["shape"] == ["B", "S"]


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "sdar_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": dict(
        PUBLISHED, hidden_size=64, num_attention_heads=16,
        num_key_value_heads=2, moe_intermediate_size=32,
        num_experts_per_tok=2, num_experts=2, router_width=8,
        first_expert_held=2, num_hidden_layers=2, vocab_size=256,
        block_length=4, mask_token_id=255, noise_low=0.45, noise_high=0.95),
    "config_factory": {"path": "paddle_tpu.models.sdar.sdar_tiny_config",
                       "kwargs": {"remat": True}},
    "trainer_builder": {"path": "paddle_tpu.models.sdar.build_sdar_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [
        {"name": "ids", "shape": ["B", "S"], "dtype": "int32",
         "gen": {"kind": "randint", "low": 0, "high": 255}},
        {"name": "t", "shape": ["B", "NB"], "dtype": "float32",
         "gen": {"kind": "stratified_uniform", "low": 0.45, "high": 0.95}},
        {"name": "u", "shape": ["B", "S"], "dtype": "float32",
         "gen": {"kind": "uniform"}}],
    "flops": "sdar_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "sdar_tiny.scan"
    traffic = {"driver": "train_scan_witnessed_batch", "batch": 1,
               "staged_batches": 2, "trace_dispatches": 1,
               "mesh": {"dp": 1, "pp": 1, "tp": 1},
               "dims": {"S": 64, "NB": 16}}
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
    counters = said("counters: ")
    assert 0.3 < counters["monitor.train.bd_masked_share"] < 0.95
    assert counters["monitor.train.bd_tiles_a_layer"] == 24
    assert counters["monitor.kernels.flash_blockdiff_calls"
                    "{blocks=4,fused=1,part=fwd}"] >= 1
    assert counters["monitor.train.moe_rows_held"] > 0
    if trace:
        assert out["metrics"]["recompiles_in_window"]["value"] == 0
        # no device plane; the counter's metric is read all the same
        assert set(NEW) & set(out["metrics"]) == {"bd_head_rows_share"}
        assert out["metrics"]["bd_head_rows_share"]["value"] == pytest.approx(
            100 * counters["monitor.train.lm_head_rows_share"])
    else:
        assert out["metrics"]["train_throughput"]["value"] > 0


@pytest.mark.parametrize("fault", [
    "causal_inside_a_noised_block", "noised_reads_its_own_clean_block",
    "clean_reads_noised", "positions_not_repeated", "no_qk_norm",
    "wrong_kv_head", "nothing_masked", "bfloat16_throughout"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (or in bfloat16) and a sound
    program: the witness misses its limit and the run is not ``correct``."""
    from benchmark.reference import sdar_30b_a3b_chat as reference

    assert fault in reference.FAULTS
    forward = reference.forward
    monkeypatch.setattr(
        reference, "forward",
        lambda params, batch, model, faults=(), *a, **kw: forward(
            params, batch, model, tuple(faults) + (fault,), *a, **kw))
    monkeypatch.setattr(reference, "_last", {})
    # the tiny program is float32 (its sound reading is 1e-6): the limit a
    # float32 program allows.  Clean queries that read noised keys reach
    # the witnessed NOISED rows through the next layer's clean keys alone,
    # and two tiny layers move them by 2e-3, under the chip's limit
    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 1e-4)
    out, said, lines = _run_tiny(tmp_path, manifest, 0)
    assert out["correct"] is False
    assert not said("witness: ")["ok"]
