"""What PR 48 adds to the benchmark: the ``jamba2_3b`` configuration file
against the program's factory and the catalog's keys, the required FLOPs of
its step against a hand count, the selective scan's needs, the five new
readers on a synthetic reduced trace (and reading nothing without their
scope or kernels), the new cell's files, a tiny copy of the configuration
through the harness on the CPU (and one with a fault in its reference), and
the new entries: additions after the existing ones, nothing else changed."""

import importlib
import json
import os
import subprocess
import time

import pytest

from benchmark.flops import jamba_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "jamba2_3b", "jamba2_3b.s8192_scan"
NEW = {"mamba_time_share": ("lower", "model code"),
       "selective_scan_time_share": ("lower", "kernels"),
       "selective_scan_roofline": ("higher", "kernels"),
       "mamba_outside_scan_share": ("lower", "model code"),
       "flash_mqa20_roofline": ("higher", "kernels")}
# the catalog's config of AI21-Jamba2-3B, as published
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 14}
ASSUMED = {"scan_chunk": 128}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_one_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "mamba_d_state",
                "mamba_d_conv", "mamba_dt_rank", "mamba_expand",
                "num_attention_heads", "num_key_value_heads", "vocab_size"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # the floor: ONE whole period, the attention layer at its published place
    assert config["num_hidden_layers"] == config["attn_layer_period"]
    assert jamba_train.layer_counts(config["model"]) == (13, 1)
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == ASSUMED
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for key in ("layer_order", "head_dim", "ffn", "inner_norms", "positions",
                "mixer_seeding", "init", "optimizer", "compute_dtype",
                "state_bytes", "remat", "run_scan", "scan_chunk", "documents",
                "labels", "ids"):
        assert key in config["assumed"], key
    assert "arXiv:2312.00752" in config["assumed"]["mixer_seeding"]
    assert "DEPARTURE" in config["assumed"]["optimizer"]
    assert "two v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.kernels import selective_scan as ss
    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    attention = [i for i, k in enumerate(cfg.layer_kinds) if k != T.MAMBA]
    got = {
        "attn_layer_offset": attention[0],
        "attn_layer_period": len(cfg.layer_kinds),
        "expert_layer_offset": PUBLISHED["expert_layer_offset"],
        "expert_layer_period": PUBLISHED["expert_layer_period"],
        "hidden_act": cfg.expert_act, "hidden_size": cfg.hidden,
        "intermediate_size": cfg.dense_ffn_hidden,
        "mamba_conv_bias": True, "mamba_d_conv": cfg.d_conv,
        "mamba_d_state": cfg.d_state, "mamba_dt_rank": cfg.dt_rank,
        "mamba_expand": cfg.d_inner // cfg.hidden,
        "mamba_proj_bias": cfg.bias,
        "max_position_embeddings": cfg.max_seq, "model_type": "jamba",
        "num_attention_heads": cfg.n_heads,
        "num_experts": cfg.n_experts or 1, "num_experts_per_tok": 1,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.kv_heads, "num_logits_to_keep": 1,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "sliding_window": None, "tie_word_embeddings": cfg.tie_head,
        "use_mamba_kernels": ss.supported((1, 8192, cfg.d_inner),
                                          cfg.d_state, cfg.scan_chunk),
        "vocab_size": cfg.vocab_size, "scan_chunk": cfg.scan_chunk}
    assert got == config["model"]
    assert attention == [7] and cfg.layer_kinds[7] == (None, False)
    assert [r[2] for r in cfg.runs] == [7, 1, 6] and cfg.run_scan
    assert cfg.n_periods == 1 and not cfg.n_experts and not cfg.prefix_kinds
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.positions is None and cfg.head_dim == 128
    assert cfg.tp == cfg.pp == 1
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.vocab_size, full.n_periods) == (28, 65536, 2)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_required_flops_against_a_hand_count(config):
    E, d, S, V, F = 2560, 5120, 8192, 65536, 8192
    in_proj, out_proj = 2 * E * 2 * d, 2 * d * E
    x_proj, dt_proj = 2 * d * (160 + 32), 2 * 160 * d
    scan = 9 * d * 16 + 2 * 4 * d
    assert [round(v / 1e6, 1) for v in (in_proj, out_proj, x_proj, dt_proj,
                                        scan)] == [52.4, 26.2, 2.0, 1.6, 0.8]
    mixer = in_proj + out_proj + x_proj + dt_proj + scan
    assert mixer == jamba_train.mixer_flops_per_token(config["model"])
    assert round(mixer / 1e6, 1) == 83.0                    # ISSUE 48's
    ffn, head = 6 * E * F, 2 * E * V
    assert (ffn, head) == (125_829_120, 335_544_320)
    attention = 2 * E * (2 * 2560 + 2 * 128) + 4 * 128 * 20 * (S + 1) / 2
    assert round(attention / 1e6, 1) == 69.5
    forward = 13 * mixer + attention + 14 * ffn + head
    got = jamba_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 9.74
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    # the issue's shares of the forward pass
    for part, share in ((13 * mixer, 0.33), (14 * ffn, 0.54),
                        (attention, 0.02), (head, 0.10)):
        assert round(part / forward, 2) == share
    # the parameters by the same widths: 1,599 M, 12.79 GB at 8 bytes
    mamba = (E * 2 * d + d * 4 + d + d * 192 + 160 * d + d + d * 16 + d + 192
             + d * E)
    params = 13 * (mamba + 3 * E * F + 2 * E) + (
        2 * E * 2560 + 2 * E * 128 + 3 * E * F + 2 * E) + V * E + E
    assert round(params / 1e6) == 1599
    assert round(params * 8 / 1e9, 2) == 12.79


def test_the_scan_s_required_flops_and_bytes(config):
    model, peaks = config["model"], PEAKS["TPU v5 lite"]
    T = 8192
    need = jamba_train.selective_scan(model, T)
    assert need["flops"] == 3.0 * 9 * 5120 * 16 * T
    # forward x, z, out at 2 B and dt at 4 B a channel, B and C at 4 B a
    # cell; backward x, z, dout, dx, dz at 2 B, dt and ddt at 4 B, B, C, dB,
    # dC: 28 B a channel and 24 a cell a token
    assert need["bytes"] == (28 * 5120 + 24 * 16) * T
    sec, binds = flops.least_seconds(need["flops"], need["bytes"], peaks)
    assert binds == "memory" and round(sec * 1e3, 2) == 1.44


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 800_000_000]]}]}


# one device, a traced stretch of 800 ms, busy 760 ms: ONE step of the
# cell's period (13 backward scans, 26 forward: remat runs it twice; one
# attention layer's flash forward twice and its backward once)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 800_000_000],                     # control flow
    ["fusion.1", 0, 30_000_000],                     # projections, forward
    ["fusion.2", 30_000_000, 30_000_000],            # projections, recomputed
    ["fusion.3", 60_000_000, 60_000_000],            # projections, backward
] + [["selective_scan_fwd.%d" % i, 120_000_000 + 4_000_000 * i, 4_000_000]
     for i in range(26)] + [
    ["selective_scan_bwd.%d" % i, 224_000_000 + 8_000_000 * i, 8_000_000]
    for i in range(13)] + [
    ["flash_fwd", 328_000_000, 2_000_000],
    ["flash_fwd.1", 330_000_000, 2_000_000],
    ["flash_bwd_fused", 332_000_000, 8_000_000],
    ["fusion.7", 340_000_000, 6_000_000],            # attention projections
    ["fusion.8", 346_000_000, 354_000_000],          # mlp
    ["fusion.9", 700_000_000, 60_000_000],           # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"jamba.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/mamba/mamba/dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "mamba/mamba/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/mamba/mamba/dot_general",
    **{"selective_scan_fwd.%d" % i: P + "jvp()/mamba/mamba/selective_scan/"
       "selective_scan_fwd" for i in range(26)},
    **{"selective_scan_bwd.%d" % i: P + "transpose(jvp())/checkpoint/mamba/"
       "mamba/selective_scan/selective_scan_bwd" for i in range(13)},
    "flash_fwd": P + "jvp()/attention/flash_fwd",
    "flash_fwd.1": P + "transpose(jvp())/checkpoint/rematted_computation/"
                       "attention/flash_fwd",
    "flash_bwd_fused": P + "transpose(jvp())/checkpoint/attention/"
                           "flash_bwd_fused",
    "fusion.7": P + "jvp()/attention/dot_general",
    "fusion.8": P + "jvp()/mlp/dot_general",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_five_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(760e-3)
    cell = _cell(config, lines, throughput=10000.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # the scopes mamba + selective_scan: 30 + 30 + 60 + 104 + 104 of 760 busy
    assert read["mamba_time_share"] == pytest.approx(100 * 328 / 760)
    assert read["selective_scan_time_share"] == pytest.approx(100 * 208 / 760)
    assert read["mamba_outside_scan_share"] == pytest.approx(100 * 120 / 760)
    # 13 backward kernels = one a Mamba layer and step: one step; HBM binds
    least = 13 * (28 * 5120 + 24 * 16) * 8192 / 819e9
    assert read["selective_scan_roofline"] == pytest.approx(
        100 * least / 208e-3)
    assert read["selective_scan_roofline"] < 100
    # two forward calls and one backward of 20 heads on one key/value head
    pairs = 8192 * 8193 / 2 * 20 * 128
    assert read["flash_mqa20_roofline"] == pytest.approx(
        100 * (2 * 4 * pairs + 8 * pairs) / 197e12 / 12e-3)
    assert read["flash_mqa20_roofline"] < 100
    for head, words in (
            ("selective_scan_roofline: least", (
                "memory binds", "13 layers", "1.000 steps traced",
                "selective_scan_fwd 0.104000 s in 26 calls",
                "selective_scan_bwd 0.104000 s in 13 calls",
                "0.328000 s under mamba + selective_scan")),
            ("mamba_time_share: 0.328000 s", ("0.208000 s of it",)),
            ("mamba_outside_scan_share: 0.328000 s", (
                "the projections' least", "1.000 steps traced")),
            ("selective_scan_time_share: selective_scan_fwd", ()),
            ("flash_mqa20_roofline: least", ("fwd 2 calls", "bwd 1 calls"))):
        assert any(l.startswith(head) and all(w in l for w in words)
                   for l in lines), (head, lines)
    assert not any(l.startswith("flash_gqa64_roofline") for l in lines)
    # model_mfu reads the configuration's own count
    mfu = mf.module("layer_metrics", "model_mfu").read(trace, None, {}, cell)
    assert mfu == pytest.approx(100 * 10000.0 * 9.7392e9 / 197e12, rel=1e-3)


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
    # lost scopes: over 5 % unattributed, the shares of the scope are not
    # reported; the kernels' own, by name, are
    lost = dict(MAPS["jamba.run_steps"], **{"fusion.8": "copy-fusion"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"jamba.run_steps": lost})
    for name in ("mamba_time_share", "mamba_outside_scan_share"):
        assert mf.module("layer_metrics", name).read(
            tr.Reduced(TRACE), None, {}, cell) is None
    assert mf.module("layer_metrics", "selective_scan_time_share").read(
        tr.Reduced(TRACE), None, {}, cell) == pytest.approx(100 * 208 / 760)


def test_new_entries_are_additions_at_the_end(manifest):
    """The configuration, the cell and the five metrics stand after
    everything the parent commit's file holds, and nothing that was there
    changed (read off git where the checkout has the parent)."""
    entries = {e["name"]: e for e in manifest["per_layer"]}
    for name, (better, layer) in NEW.items():
        e = entries[name]
        assert (e["unit"], e["better"], e["source"], e["moves"], e["layer"]) \
            == ("%", better, "device_trace", "train_throughput", layer)
        assert e["workloads"] == [CELL]
        assert callable(mf.module("layer_metrics", name).read)
    # after what PR 47 left last; a later PR's additions come after these
    names = {key: [e["name"] for e in manifest[key]]
             for key in ("configs", "workloads", "per_layer")}
    at = names["per_layer"].index("mamba_time_share")
    assert names["per_layer"][at:at + 5] == list(NEW)
    assert names["per_layer"][at - 1] == "moe_held8of256_roofline"
    assert names["configs"].index(NAME) == 1 + names["configs"].index(
        "trinity_large_preview")
    assert names["workloads"].index(CELL) == 1 + names["workloads"].index(
        "trinity_large_preview.s6144_scan")
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s8192_scan", 1) and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # the metrics that list no cells report in the new cell by themselves
    got = {e["name"] for e in mf.metrics_of(manifest, "per_layer", CELL)}
    assert got == set(NEW) | {
        "step_ms_p50", "window_lost_share", "recompiles_in_window",
        "model_mfu", "device_idle_share", "setup_init_s",
        "setup_trace_lower_s", "setup_compile_s", "setup_cache_misses",
        "setup_unattributed_share"}
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
            ["git", "show", "3ea462c:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
    except (OSError, subprocess.CalledProcessError):
        return          # a checkout without the parent commit
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert manifest[key] == before[key], key
    for key in ("configs", "workloads", "per_layer"):
        assert manifest[key][:len(before[key])] == before[key], key


def test_new_traffic_file(manifest, config):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    assert {k: traffic[k] for k in ("driver", "mesh", "batch", "dims",
                                    "staged_batches", "trace_dispatches")} == {
        "driver": "train_scan_witnessed", "mesh": {"dp": 1, "pp": 1, "tp": 1},
        "batch": 1, "dims": {"S": 8192}, "staged_batches": 2,
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
    "name": "jamba_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "intermediate_size": 96,
              "num_attention_heads": 5, "num_key_value_heads": 1,
              "num_hidden_layers": 4, "attn_layer_period": 4,
              "attn_layer_offset": 2, "num_experts": 1, "rms_norm_eps": 1e-6,
              "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_d_conv": 4,
              "mamba_expand": 2, "tie_word_embeddings": True,
              "vocab_size": 256, "scan_chunk": 16},
    "config_factory": {"path": "paddle_tpu.models.jamba.jamba_tiny_config",
                       "kwargs": {"remat": True, "n_layers": 4}},
    "trainer_builder": {"path": "paddle_tpu.models.jamba.build_jamba_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "jamba_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "jamba_tiny.scan"
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
    the timed path's own first loss and of its logits in both groups, and
    the new readers finding no device plane."""
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
                                   "no_dt_norm", "second_kv_head"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import jamba2_3b as reference

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
