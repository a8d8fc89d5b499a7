"""What PR 37 adds to the benchmark: the ``brumby_14b`` configuration file
against the program's factory and the catalog's keys, the required FLOPs of
its step against a hand count, the retention's needs, the two new readers on
a synthetic reduced trace, the new cell's files, a tiny copy of the
configuration through the harness on the CPU (and one with a fault in its
reference), and the new entries looked up BY NAME (their place in the lists
is the next PR's to move: PERF.md section 7 (k))."""

import importlib
import json
import os
import time

import pytest

from benchmark.flops import brumby_train
from benchmark.harness import build, flops, manifest as mf, trace_reduce as tr
from benchmark.harness.peaks import PEAKS
from benchmark.tests.test_bench_harness import write_tree

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME, CELL = "brumby_14b", "brumby_14b.s16384_scan"
NEW = {"retention_time_share": ("lower", "model code"),
       "retention_roofline": ("higher", "kernels")}
# the catalog's config of Brumby-14B-Base, as published
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 4, "vocab_size": 18992}
ASSUMED = {"retention_degree": 2, "retention_eps": 1e-06,
           "retention_chunk": 1024}


@pytest.fixture(scope="module")
def config():
    return mf.read_json(ROOT, "benchmark", "configs", NAME + ".json")


@pytest.fixture(scope="module")
def manifest():
    return mf.load(ROOT)


def test_file_holds_every_published_key_but_the_two_reduced(config, manifest):
    entry = mf.config_entry(manifest, NAME)
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == "benchmark/configs/%s.json" % NAME
    assert len(entry["why"]) <= 200
    differs = {k: config[k] for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == REDUCED
    # no width among them: every width is the catalog's
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads"):
        assert config[key] == PUBLISHED[key] and key not in entry["reduced"]
    # floors: four layers (the period is one layer), an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the copy the harness hands to the reference and the FLOP count
    assert {k: config["model"][k] for k in PUBLISHED} == \
        {k: config[k] for k in PUBLISHED}
    assert {k: config["model"][k] for k in
            set(config["model"]) - set(PUBLISHED)} == ASSUMED
    assert set(config["changed"]) == set(REDUCED) | {"arithmetic"}
    for key in ("operator", "degree", "gate", "qk_norm_and_rotary", "scale",
                "eps", "normaliser", "chunk", "optimizer", "init",
                "state_bytes", "remat", "documents", "labels", "ids"):
        assert key in config["assumed"], key
    assert "arXiv:2507.04239" in config["assumed"]["operator"]
    assert "eight v5e chips" in config["deployment"]
    assert config["source"] == entry["source"]


def test_model_block_equals_what_the_factory_returns(config):
    """Key by key, the cut included, so that file and factory cannot
    drift."""
    from paddle_tpu.kernels import power_retention as pr
    from paddle_tpu.parallel import transformer as T

    cfg = build._call(config["config_factory"])
    got = {
        "attention_bias": cfg.bias, "head_dim": cfg.head_dim,
        "hidden_act": cfg.expert_act, "hidden_size": cfg.hidden,
        "intermediate_size": cfg.dense_ffn_hidden,
        "max_position_embeddings": cfg.max_seq,
        "max_window_layers": PUBLISHED["max_window_layers"],
        "model_type": "brumby", "num_attention_heads": cfg.n_heads,
        "num_hidden_layers": cfg.n_layers,
        "num_key_value_heads": cfg.kv_heads,
        "rms_norm_eps": cfg.norm_eps if cfg.norm == "rms" else None,
        "rope_scaling": None,
        "rope_theta": cfg.rope_theta if cfg.positions == "rotary" else None,
        "sliding_window": None, "tie_word_embeddings": cfg.tie_head,
        "use_sliding_window": False, "vocab_size": cfg.vocab_size,
        "retention_degree": 2, "retention_eps": pr.EPS,
        "retention_chunk": cfg.retention_chunk}
    assert got == config["model"]
    assert cfg.layer_kinds == (T.RETENTION,) and not cfg.prefix_kinds
    assert cfg.n_periods == 4 and not cfg.n_experts
    assert cfg.causal and cfg.remat and cfg.dtype == "bfloat16"
    assert cfg.qk_norm == "head" and cfg.tp == cfg.pp == 1
    assert pr.supported(cfg.head_dim, 16384, cfg.retention_chunk)
    # the published model is the factory's default
    full = build.resolve(config["config_factory"]["path"])()
    assert (full.n_layers, full.vocab_size) == (40, 151936)
    assert config["optimizer"]["path"].endswith(".adamw")
    assert config["lr"] == 1e-5


def test_required_flops_against_a_hand_count(config):
    E, S, V, F = 5120, 16384, 18992, 17408
    projections = 2 * E * (2 * 5120 + 2 * 1024)                # q, o, k, v
    gate = 2 * E * 8
    columns = 128 * 129 // 2
    assert columns == 8256 == brumby_train.state_columns(config["model"])
    state = (40 + 8) * 2 * columns * 128 + (40 + 8) * 2 * columns
    scores = 4 * 128 * 40 * (S + 1) / 2
    assert round(state / 1e6, 1) == 102.2 and state < scores   # ISSUE 37's
    ffn, head = 6 * E * F, 2 * E * V
    assert (projections, ffn, head) == (125_829_120, 534_773_760,
                                        194_478_080)
    forward = 4 * (projections + gate + state + ffn) + head
    got = brumby_train.per_unit(config["model"], {"S": S, "B": 1})
    assert got == pytest.approx(3.0 * forward, rel=1e-12)
    assert round(got / 1e9, 2) == 9.74
    assert flops.per_unit(config, {"S": S, "B": 1}) == got
    # the issue's shares of the forward pass
    for part, share in ((4 * ffn, 0.659), (4 * projections, 0.155),
                        (4 * state, 0.126), (head, 0.060)):
        assert round(part / forward, 3) == share
    # a query head and token: 2.54 M by the state, 4.19 M by the scores
    assert round((2 * columns * 128 * 1.2) / 1e6, 2) == 2.54
    assert round(4 * 128 * S / 2 / 1e6, 2) == 4.19
    # below the crossing the score matrix is the cheaper form, and counted
    short = brumby_train.retention_flops_per_token(config["model"], 8192)
    assert short == 4 * 128 * 40 * 8193 / 2 < state


def test_retention_s_required_flops_and_bytes(config):
    model, peaks = config["model"], PEAKS["TPU v5 lite"]
    T = 16384
    need = brumby_train.retention(model, T, 16384)
    assert need["flops"] == 3.0 * 102_242_304 * T
    assert need["bytes"] == (96 + 96 + 56) * 128 * T * 2
    sec, binds = flops.least_seconds(need["flops"], need["bytes"], peaks)
    assert binds == "compute" and round(sec * 1e3, 2) == 25.51


def _plane(name, ops):
    return {"name": name, "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_multi(1)", 0, 400_000_000]]}]}


# one device, a traced stretch of 400 ms, busy 360 ms: ONE step of the
# cell's four layers (4 backward kernels, 8 forward: remat runs it twice)
TRACE = {"planes": [_plane("/device:TPU:0", [
    ["while.4", 0, 400_000_000],                     # control flow
    ["fusion.1", 0, 20_000_000],                     # projections, forward
    ["fusion.2", 20_000_000, 20_000_000],            # projections, recomputed
    ["fusion.3", 40_000_000, 40_000_000],            # projections, backward
] + [["power_retention_fwd.%d" % i, 80_000_000 + 10_000_000 * i, 10_000_000]
     for i in range(8)] + [
    ["power_retention_bwd.%d" % i, 160_000_000 + 25_000_000 * i, 25_000_000]
    for i in range(4)] + [
    ["fusion.8", 260_000_000, 80_000_000],           # mlp
    ["fusion.9", 340_000_000, 20_000_000],           # lm_head
])]}
P = "jit(multi)/while/body/closed_call/"
MAPS = {"brumby.run_steps": {
    "fusion.1": P + "jvp()/while/body/closed_call/retention/retention/"
                    "dot_general",
    "fusion.2": P + "transpose(jvp())/checkpoint/rematted_computation/"
                    "retention/retention/dot_general",
    "fusion.3": P + "transpose(jvp())/checkpoint/retention/retention/"
                    "dot_general",
    **{"power_retention_fwd.%d" % i: P + "jvp()/retention/retention/"
       "power_retention_fwd" for i in range(8)},
    **{"power_retention_bwd.%d" % i: P + "transpose(jvp())/checkpoint/"
       "retention/retention/power_retention_bwd" for i in range(4)},
    "fusion.8": P + "jvp()/mlp/dot_general",
    "fusion.9": P + "jvp(lm_head)/lm_head/dot_general",
}}


def _cell(config, lines, throughput):
    traffic = mf.read_json(ROOT, "benchmark", "traffic", CELL + ".json")
    return {"say": lines.append, "peaks": PEAKS["TPU v5 lite"], "chips": 1,
            "config": config, "traffic": traffic,
            "dims": build.cell_dims(config, traffic),
            "throughput": throughput}


def test_the_two_readers_on_a_synthetic_trace(config, monkeypatch):
    devscope = importlib.import_module("paddle_tpu.monitor.devscope")
    monkeypatch.setattr(devscope, "scope_maps", lambda: MAPS)
    trace, lines = tr.Reduced(TRACE), []
    assert trace.busy_s == pytest.approx(360e-3)
    cell = _cell(config, lines, throughput=8000.0)
    read = {n: mf.module("layer_metrics", n).read(trace, None, {}, cell)
            for n in NEW}
    # the scope retention: 20 + 20 + 40 + 80 + 100 ms of 360 busy
    assert read["retention_time_share"] == pytest.approx(100 * 260 / 360)
    # 4 backward kernels = one a layer and step: one step
    least = 4 * 3 * 102_242_304 * 16384 / 197e12
    assert read["retention_roofline"] == pytest.approx(100 * least / 260e-3)
    assert read["retention_roofline"] < 100
    for head, words in (
            ("retention_roofline: least", (
                "compute binds", "4 layers", "1.000 steps traced",
                "power_retention_fwd 0.080000 s in 8 calls",
                "power_retention_bwd 0.100000 s in 4 calls")),
            ("retention_time_share: 0.260000 s", ())):
        assert any(l.startswith(head) and all(w in l for w in words)
                   for l in lines), (head, lines)
    # model_mfu reads the configuration's own count
    mfu = mf.module("layer_metrics", "model_mfu").read(trace, None, {}, cell)
    assert mfu == pytest.approx(100 * 8000.0 * 9.7387e9 / 197e12, rel=1e-3)


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
    # lost scopes: over 5 % unattributed, the share is not reported
    lost = dict(MAPS["brumby.run_steps"], **{"fusion.8": "copy-fusion"})
    monkeypatch.setattr(devscope, "scope_maps",
                        lambda: {"brumby.run_steps": lost})
    assert mf.module("layer_metrics", "retention_time_share").read(
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
    names = list(entries)
    assert min(names.index(n) for n in NEW) > names.index(
        "setup_unattributed_share")
    cell = mf.cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "s16384_scan", 1) and len(cell["why"]) <= 200
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


def test_the_reference_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", NAME + ".py")
    with open(path) as f:
        imports = [l for l in f if l.startswith(("import ", "from "))]
    assert imports and not any("paddle_tpu" in l or "benchmark" in l
                               for l in imports)


TINY = {
    "name": "brumby_tiny", "unit_of_work": "token",
    "units_per_step": ["B", "S"],
    "model": {"hidden_size": 64, "intermediate_size": 96, "head_dim": 128,
              "num_attention_heads": 10, "num_key_value_heads": 2,
              "num_hidden_layers": 1, "rms_norm_eps": 1e-6,
              "rope_theta": 1000000, "vocab_size": 256,
              "retention_degree": 2, "retention_eps": 1e-6,
              "retention_chunk": 16},
    "config_factory": {"path": "paddle_tpu.models.brumby.brumby_tiny_config",
                       "kwargs": {"remat": True, "n_layers": 1}},
    "trainer_builder": {"path": "paddle_tpu.models.brumby.build_brumby_trainer",
                        "kwargs": {}},
    "optimizer": {"path": "paddle_tpu.parallel.optim.adamw", "kwargs": {}},
    "mesh_spec": "paddle_tpu.parallel.mesh.MeshSpec", "batch_axis": "dp",
    "lr": 1e-5,
    "batch_fields": [{"name": "ids", "shape": ["B", "S"], "dtype": "int32",
                      "gen": {"kind": "randint", "low": 0, "high": 256}}],
    "flops": "brumby_train", "reference": NAME}


def _run_tiny(tmp_path, manifest, trace):
    import jax

    from benchmark.harness.cellrun import run_cell

    cell = "brumby_tiny.scan"
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
                                   "wrong_kv_head"])
def test_a_fault_in_the_reference_fails_the_run(tmp_path, manifest,
                                                monkeypatch, fault):
    """A reference that computes something else (one of its own ``FAULTS``,
    thrown for every call) and a sound program: the witness misses its
    limit and the run is not ``correct``."""
    from benchmark.reference import brumby_14b as reference

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
