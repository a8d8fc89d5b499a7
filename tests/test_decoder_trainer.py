"""The causal decoders' ONE trainer (``parallel/decoder.py``): every model's
builder returns it with the model's label, what it observes under a monitor
session follows from the configuration (of the names the five trainer
classes of the commit before it, ad87b08, wrote in one ``run_steps`` each,
those that are readings of the batch and the weights, from ONE probe a
trainer), and the seam holds: nothing beside or beneath the block imports a
model, and a model's file defines no class."""

import ast
import functools
import importlib
import pathlib
import tempfile

import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.parallel import decoder, moe, optim
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.train import stack_batches

PACKAGE = pathlib.Path(decoder.__file__).resolve().parents[1]
MODELS = ("olmoe", "smallthinker", "lfm2", "brumby", "mistral4")

# the twenty-two names the configuration and the batch's shape fix, which a
# trainer wrote until PR 56: each republished a function (``packed_grid``,
# ``bwd_sweeps``, ``kv_blocks``, ``moe._held_capacities``,
# ``power_retention.state_sweeps``, ``transformer.yarn_blend_range`` or
# plain arithmetic on ``cfg``), and its test now calls the function
FIXED = {"monitor.kernels." + g for g in (
    "flash_pairs_per_grid_step", "flash_grid_steps", "flash_heads_stacked",
    "flash_bwd_sweeps_full", "flash_bwd_sweeps_windowed",
    "flash_kv_blocks_visited_full", "flash_kv_blocks_visited_windowed",
    "flash_kv_blocks_skipped_full", "flash_kv_blocks_skipped_windowed",
    "moe_pair_slots", "moe_rows_fetch_bound")} | {
        "monitor.train." + g for g in (
            "moe_assignments", "retention_chunks", "retention_state_mb",
            "retention_state_sweeps", "mla_latent_bytes_per_token",
            "mla_expanded_kv_bytes_per_token", "yarn_first_interpolated_pair",
            "yarn_last_interpolated_pair", "q_scaled_positions",
            "loop_passes", "layer_applications")}
MOE = {"monitor.train.moe_load_max_over_mean",
       # since PR 46: a count a compiled gmm / tgmm call, by its tiles
       "monitor.kernels.moe_grouped_matmul_calls",
       # since PR 71: a count a traced sum back of the experts' rows (the row
       # kernel, ``fused`` 1: it has no other path)
       "monitor.kernels.moe_rows_sum_calls"}
HELD = {"monitor.train.moe_held_rows_share", "monitor.train.moe_rows_held"}
# since PR 47: a count a traced q or k of ``_qkv`` with a q/k norm or rotary
# positions, by whether the row kernel took it (since PR 57 Mistral's latent
# q and k too, ``convention`` "pairs")
QK = {"monitor.kernels.qk_rope_calls"}
# since PR 55: a count a traced several-block flash backward, by whether the
# row kernel made its ``delta`` (OLMoE's heads of 16: no flash call)
DELTA = {"monitor.kernels.flash_delta_calls"}
# since PR 68: a count a traced several-block sweep, forward and one-sweep
# backward, by the query head-blocks of a group that ride one grid step
SWEEP = {"monitor.kernels.flash_sweep_calls"}
# tiny model -> the names one run_steps writes, by its observation and by
# its trace: of those the five trainer
# classes of ad87b08 wrote, the readings of the batch and the weights, and
# PR 46's counter of compiled grouped-matmul calls, PR 47's of q/k passes,
# PR 55's of several-block flash backwards, PR 68's of several-block sweeps
WRITTEN = {
    "olmoe": MOE | QK,
    "smallthinker": MOE | HELD | QK | DELTA | SWEEP,
    "lfm2": MOE | HELD | QK | DELTA | SWEEP
    | {"monitor.train.router_bias_abs_max"},
    "brumby": QK | {"monitor.train.retention_gate_mean"},
    "mistral4": MOE | HELD | QK | DELTA | SWEEP,
}
# tiny model -> (sequence, what ``decoder.probe``'s ONE program reads of
# ``_staged``'s first batch at seed 3 (``moe_rows_held``: of both batches)):
# the values the six programs of PR 56's parent (ca40cf3) wrote, each an
# expression of its own closure then
READ = {
    "olmoe": (32, {"moe_load_max_over_mean": 1.9375}),
    "smallthinker": (64, {"moe_load_max_over_mean": 1.5, "moe_rows_held": 569,
                          "moe_held_rows_share": 0.27783203125}),
    "lfm2": (64, {"moe_load_max_over_mean": 1.625, "moe_rows_held": 518,
                  "moe_held_rows_share": 0.2529296875,
                  "router_bias_abs_max": 0.13578346371650696}),
    "brumby": (64, {"retention_gate_mean": 0.5010311603546143}),
    "mistral4": (64, {"moe_load_max_over_mean": 1.40625, "moe_rows_held": 336,
                      "moe_held_rows_share": 0.328125}),
    "trinity": (64, {"moe_load_max_over_mean": 1.75, "moe_rows_held": 524,
                     "moe_held_rows_share": 0.255859375,
                     "router_bias_abs_max": 0.13578346371650696,
                     "attn_gate_mean": 0.49989986419677734}),
    "jamba": (64, {"mamba_dt_mean": 0.03273104131221771,
                   "mamba_decay_min": 9.094620889715799e-12}),
    "nemotron_h": (64, {"moe_load_max_over_mean": 1.3125,
                        "moe_rows_held": 521,
                        "moe_held_rows_share": 0.5087890625,
                        "router_bias_abs_max": 0.013578345067799091,
                        "mamba2_dt_mean": 0.020154699683189392,
                        "mamba2_decay_min": 0.07360795885324478}),
    "ouro": (64, {"exit_prob_mean{exit=1}": 0.30535072088241577,
                  "exit_prob_mean{exit=2}": 0.17375758290290833,
                  "exit_prob_mean{exit=3}": 0.5208917856216431,
                  "exit_entropy_mean": 0.8631321787834167}),
}


def _trainer(model, **cfg):
    module = importlib.import_module("paddle_tpu.models." + model)
    return getattr(module, "build_%s_trainer" % model)(
        getattr(module, model + "_tiny_config")(**cfg), MeshSpec(dp=1),
        optimizer=optim.adamw(), seed=3)


def _staged(tr, seq, n=2):
    rng = np.random.RandomState(5)
    return stack_batches(tr.mesh, decoder.BATCH_SPECS, [
        {"ids": rng.randint(0, 256, (2, seq)).astype(np.int32)}
        for _ in range(n)])


def _written(registry):
    """``name{label=value}`` -> value of what the train path wrote."""
    return {row["name"] + "".join(
        "{%s=%s}" % kv for kv in sorted((row["labels"] or {}).items())):
        row.get("value") for row in registry.snapshot()
        if row["name"].startswith(("monitor.train.", "monitor.kernels."))}


@functools.lru_cache(maxsize=None)
def _observed(model):
    """A model's tiny trainer after one call's observation under a monitor
    session and, where ``WRITTEN`` says what a ``run_steps`` writes, the
    trace of its scan (a kernel's call counts when it is traced; compiling
    and running the step is each model's own reference file's), and the
    same observation once more: ``(trainer, what the first call wrote)``.
    One a model for the two tests below; the registry is the process's, so
    other tests' names go first."""
    tr = _trainer(model)
    staged = _staged(tr, READ[model][0])
    assert monitor.active() is None
    tr._observe(staged)                 # off a session: nothing is built
    assert tr._probe_fn is None
    mon = monitor.enable(tempfile.mkdtemp(), flight=False)
    try:
        mon.registry.reset()
        tr._observe(staged)
        if model in WRITTEN:
            tr.multi_fn.lower(tr.state, staged, 1e-3)
        probe, written = tr._probe_fn, _written(mon.registry)
        tr._observe(staged)
        assert tr._probe_fn is probe
    finally:
        monitor.disable()
    return tr, written


@pytest.mark.parametrize("model", MODELS)
def test_a_model_s_trainer_is_the_decoder_s_and_writes_what_its_class_did(
        model):
    names = WRITTEN[model]
    tr, written = _observed(model)
    assert type(tr) is decoder.DecoderTrainer and tr.label == model
    assert {name.split("{")[0] for name in written} == names


@pytest.mark.parametrize("model", list(READ))
def test_a_trainer_s_readings_come_from_one_probe(model):
    """Off a session a call's observation builds nothing; under one it
    builds ONE program, which every later call runs, and each reading is
    the value its own program wrote on the parent."""
    tr, written = _observed(model)
    assert tr._probe_fn is not None
    # what the configuration and the shapes fix is no gauge
    assert not {name.split("{")[0] for name in written} & FIXED
    assert not {"_routing_fn", "_gate_fn", "_attn_gate_fn", "_mamba_fn",
                "_mamba2_fn", "_exits_fn"} & set(dir(tr))
    got = {name[len("monitor.train."):]: value
           for name, value in written.items()
           if name.startswith("monitor.train.")}
    assert got == pytest.approx(READ[model][1], rel=1e-4)


def test_a_full_set_of_experts_holds_every_pair(tmp_path):
    """``experts_held`` = ``n_experts``: the layer counts no pair (none can
    miss), so rows held are the assignments and their share is 1."""
    tr = _trainer("smallthinker", experts_held=8, first_expert=0)
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()
        tr._observe(_staged(tr, 64))
        got = _written(mon.registry)
    finally:
        monitor.disable()
    cfg = tr.cfg
    # batches x tokens x top-2 x L
    pairs = 2 * 2 * 64 * cfg.experts_per_token * cfg.moe_layers
    assert pairs == 2 * 2 * 64 * 2 * 4
    assert got["monitor.train.moe_rows_held"] == pairs
    assert got["monitor.train.moe_held_rows_share"] == 1.0
    # with every expert held the rows are the slots
    assert moe._held_capacities(2 * 64 * 2, cfg.experts_here,
                                cfg.n_experts) == (2 * 64 * 2,)


def _imports(path):
    """Absolute dotted names a module's import statements name."""
    package = ("paddle_tpu",) + path.relative_to(PACKAGE).parts[:-1]
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = list(package[:len(package) - node.level + 1]
                        if node.level else ())
            base += node.module.split(".") if node.module else []
            for alias in node.names:
                yield ".".join(base + [alias.name])


def test_nothing_beside_or_beneath_the_block_imports_a_model():
    seen = 0
    for folder in ("parallel", "kernels"):
        for path in sorted((PACKAGE / folder).rglob("*.py")):
            seen += 1
            named = [n for n in _imports(path)
                     if (n + ".").startswith("paddle_tpu.models.")]
            assert not named, (path, named)
    assert seen > 20
    # the walk sees a model where there is one: the models import the block
    assert "paddle_tpu.parallel.decoder" in set(
        _imports(PACKAGE / "models" / "olmoe.py"))


@pytest.mark.parametrize("model", MODELS)
def test_a_model_s_file_is_configuration_only(model):
    tree = ast.parse((PACKAGE / "models" / (model + ".py")).read_text())
    assert not [n.name for n in ast.walk(tree)
                if isinstance(n, ast.ClassDef)]
    module = importlib.import_module("paddle_tpu.models." + model)
    build = getattr(module, "build_%s_trainer" % model)
    assert build.func is decoder.build_decoder_trainer \
        and build.keywords == {"label": model} and not build.args
