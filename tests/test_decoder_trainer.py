"""The causal decoders' ONE trainer (``parallel/decoder.py``): every model's
builder returns it with the model's label, what it observes under a monitor
session follows from the configuration (the names below were written by the
five trainer classes of the commit before it, ad87b08, one ``run_steps``
each), and the seam holds: nothing beside or beneath the block imports a
model, and a model's file defines no class."""

import ast
import importlib
import pathlib

import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.parallel import decoder, optim
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.train import stack_batches

PACKAGE = pathlib.Path(decoder.__file__).resolve().parents[1]
MODELS = ("olmoe", "smallthinker", "lfm2", "brumby", "mistral4")

FLASH = {"monitor.kernels.flash_" + g for g in (
    "bwd_sweeps_full", "grid_steps", "heads_stacked", "pairs_per_grid_step")}
KINDS = {"monitor.kernels.flash_" + g for g in (
    "bwd_sweeps_windowed", "kv_blocks_skipped_full",
    "kv_blocks_skipped_windowed", "kv_blocks_visited_full",
    "kv_blocks_visited_windowed")}
MOE = {"monitor.kernels.moe_pair_slots", "monitor.kernels.moe_rows_fetch_bound",
       "monitor.train.moe_assignments", "monitor.train.moe_load_max_over_mean",
       # since PR 46: a count a compiled gmm / tgmm call, by its tiles
       "monitor.kernels.moe_grouped_matmul_calls"}
HELD = {"monitor.train.moe_held_rows_share", "monitor.train.moe_rows_held"}
# since PR 47: a count a traced q or k of ``_qkv`` with a q/k norm or rotary
# positions, by whether the row kernel took it (Mistral's latent chain: none)
QK = {"monitor.kernels.qk_rope_calls"}
# since PR 55: a count a traced several-block flash backward, by whether the
# row kernel made its ``delta`` (OLMoE's heads of 16: no flash call)
DELTA = {"monitor.kernels.flash_delta_calls"}
# tiny model -> (sequence, the names one run_steps wrote on ad87b08, and
# PR 46's counter of compiled grouped-matmul calls, PR 47's of q/k passes,
# PR 55's of several-block flash backwards)
WRITTEN = {
    # 4 heads of 16: no packed layout, so no flash gauge
    "olmoe": (32, MOE | QK),
    "smallthinker": (64, FLASH | KINDS | MOE | HELD | QK | DELTA),
    "lfm2": (64, FLASH | KINDS | MOE | HELD | QK | DELTA
             | {"monitor.train.router_bias_abs_max"}),
    "brumby": (64, QK | {"monitor.train.retention_" + g for g in (
        "chunks", "gate_mean", "state_mb", "state_sweeps")}),
    "mistral4": (64, FLASH | MOE | HELD | DELTA | {
        "monitor.train." + g for g in (
            "mla_expanded_kv_bytes_per_token", "mla_latent_bytes_per_token",
            "q_scaled_positions", "yarn_first_interpolated_pair",
            "yarn_last_interpolated_pair")}),
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
    return {row["name"]: row.get("value") for row in registry.snapshot()
            if row["name"].startswith(("monitor.train.", "monitor.kernels."))}


@pytest.mark.parametrize("model", MODELS)
def test_a_model_s_trainer_is_the_decoder_s_and_writes_what_its_class_did(
        tmp_path, model):
    seq, names = WRITTEN[model]
    tr = _trainer(model)
    assert type(tr) is decoder.DecoderTrainer and tr.label == model
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        # the registry is the process's: other tests' names go first
        mon.registry.reset()
        tr.run_steps(_staged(tr, seq), 1e-3)
        assert set(_written(mon.registry)) == names
    finally:
        monitor.disable()


def test_a_full_set_of_experts_holds_every_pair(tmp_path):
    """``experts_held`` = ``n_experts``: the layer counts no pair (none can
    miss), so rows held are the assignments and their share is 1."""
    tr = _trainer("smallthinker", experts_held=8, first_expert=0)
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()
        tr.run_steps(_staged(tr, 64), 1e-3)
        got = _written(mon.registry)
    finally:
        monitor.disable()
    pairs = 2 * 2 * 64 * 2 * 4              # batches x tokens x top-2 x L
    assert got["monitor.train.moe_assignments"] == pairs
    assert got["monitor.train.moe_rows_held"] == pairs
    assert got["monitor.train.moe_held_rows_share"] == 1.0
    # with every expert held the rows are the slots
    assert got["monitor.kernels.moe_rows_fetch_bound"] == \
        got["monitor.kernels.moe_pair_slots"] == 2 * 64 * 2


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
