"""The Kanana-2 TRAINER on four forced host devices, its experts riding
``dp`` (``models/kanana2.py`` through ``parallel/decoder.py``'s
``build_decoder_trainer`` at ``MeshSpec(dp=4)``: seeded on the mesh, two
experts a device, moments beside them), against the SAME configuration on ONE
device with the field off, from the same weights over the same batches:

- three AdamW steps: losses, every leaf and the selection bias after them
  (the exchange changes nothing but where rows are computed).  On the mesh
  they are ONE ``run_steps`` (the program the benchmark's cell runs, and the
  ONE program this file compiles for four devices), on the one device a
  ``step`` a batch;
- the same ``run_steps`` over three more batches = the one-device trainer's
  next three steps; under a monitor session, what a call says of its
  exchange, and the scopes its instructions carry (``exchange``, forward and
  backward);
- a checkpoint of the four-device state loads on two devices and on one with
  every expert where the rules say.

The program's own granule (512): at the tiny size a round holds every pair,
so the trainers run ONE round; rounds past the first are
``test_kanana2_expert_parallel.py``'s."""

import jax
import numpy as np
import pytest

import decoder_reference as H
import kanana2_case as K
from paddle_tpu import monitor
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.checkpoint import (
    latest_checkpoint, restore_checkpoint, save_checkpoint)
from paddle_tpu.parallel.train import shard_pytree

B, S = 4, 64
CASE = K.case(B=B, S=S)
PAIRS = B // 4 * S * 2          # a device's (token, expert) pairs a layer


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """``{dp: (trainer, the three steps' losses, the next three batches'
    losses, the state after the first three)}`` and the monitor's rows of
    the mesh's first ``run_steps`` call."""
    tr = H.trainer(CASE, expert_parallel=False)
    weights = H.moved(CASE, jax.tree.map(np.asarray, tr.state["params"]))
    batches = [{"ids": i} for i in H.ids(CASE, seed=5, n=6)]
    out = {}
    for dp, field in ((4, True), (1, False)):
        tr = H.trainer(CASE, dp=dp, expert_parallel=field)
        with tr.mesh:
            tr.state = shard_pytree(
                dict(jax.tree.map(np.asarray, tr.state), params=weights),
                tr.specs, tr.mesh)
        if dp == 1:
            def run(some):
                return [float(tr.step(b, 1e-3)) for b in some]
        else:
            def run(some):
                return np.asarray(tr.run_steps(H.staged(tr, some),
                                               1e-3)).tolist()
            mon = monitor.enable(str(tmp_path_factory.mktemp("monitor")),
                                 flight=False)
            mon.registry.reset()    # the process's: another file's rows
        try:        # both calls inside the session: outside it the same
            losses = run(batches[:3])   # trainer compiles a second program
            after = jax.tree.map(np.asarray, tr.state)
            if dp > 1:
                out["rows"] = {r["name"][len("monitor.train."):]: r["value"]
                               for r in mon.registry.snapshot()
                               if r["name"].startswith("monitor.train.")}
            out[dp] = (tr, losses, run(batches[3:]), after)
        finally:
            monitor.disable()
    return out


def test_three_adamw_steps_on_four_devices_equal_one_device_s(trained):
    (four, got, _, a), (_, want, _, b) = trained[4], trained[1]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[0] != got[1]
    np.testing.assert_array_equal(a["params"]["router_bias"],
                                  b["params"]["router_bias"])
    H.leaves_agree(a["params"], b["params"], 1e-4, 1e-5)
    H.leaves_agree(a["opt"]["m"], b["opt"]["m"], 1e-3, 1e-4)
    held = four.state["params"]["params_layers"]["p0"]["we_gate_up"]
    assert held.sharding.shard_shape(held.shape) == (2, 2, 64, 64)
    moment = four.state["opt"]["m"]["params_layers"]["p0"]["we_down"]
    assert moment.sharding.shard_shape(moment.shape) == (2, 2, 32, 64)


def test_run_steps_on_the_mesh_equals_the_one_device_trainer_s_steps(
        trained):
    np.testing.assert_allclose(trained[4][2], trained[1][2], rtol=1e-5)
    assert trained[4][2][0] != trained[4][2][1]
    for a, b in zip(jax.tree.leaves(
            jax.tree.map(np.asarray, trained[4][0].state["params"])),
            jax.tree.leaves(jax.tree.map(
                np.asarray, trained[1][0].state["params"]))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_under_a_monitor_session_a_call_says_what_the_exchange_did(trained):
    """``monitor.train.moe_*``: the rows sent and received, the fullest
    destination beside a round's capacity, the rounds past the first, the
    GLOBAL load's unevenness and the largest bias."""
    rows = trained["rows"]
    assert rows["moe_exchange_capacity"] == moe._exchange_capacity(PAIRS, 4) \
        == PAIRS
    assert rows["moe_exchange_tier"] == 0
    assert 0 < rows["moe_exchange_fullest"] <= PAIRS
    assert 0 < rows["moe_rows_sent"] < 2 * 4 * PAIRS    # two sparse layers
    assert PAIRS / 4 < rows["moe_rows_received"] < 4 * PAIRS
    assert rows["moe_load_max_over_mean"] >= 1
    assert rows["router_bias_abs_max"] > 0
    assert "moe_rows_held" not in rows      # no share: every expert is here


def test_the_exchange_s_scope_holds_its_instructions(trained):
    got = {H.devscope.classify(op)
           for op in H.scope_map(trained[4][0]).values()}
    for scope in ("exchange", "latent_attention", "shared_expert", "moe",
                  "router", "mlp", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("grad_sync", "grad_sync") in got
    assert "attention" not in {s for _, s in got}
    text = trained[4][0].multi_fn.lower(
        trained[4][0].state, H.staged(trained[4][0], [
            {"ids": i} for i in H.ids(CASE, seed=5, n=3)]), 1e-3).as_text()
    assert "all_to_all" in text
    # ... and the one-device program has none
    one = trained[1][0]
    assert "all_to_all" not in one.step_fn.lower(
        one.state, {"ids": H.ids(CASE, seed=5)[0]}, 1e-3).as_text()


def test_a_checkpoint_of_four_devices_loads_on_two_and_on_one(trained,
                                                              tmp_path):
    """The re-sharder reads the same rule tree: the state saved at dp 4
    comes back at dp 2 and at dp 1 with every expert where the rules say,
    and the values it was saved with."""
    four = trained[4][0]
    save_checkpoint(str(tmp_path), four.state, step=5)
    saved = jax.tree.map(np.asarray, four.state)
    for dp in (2, 1):
        tr = H.trainer(CASE, dp=dp)
        state, step = restore_checkpoint(latest_checkpoint(str(tmp_path)),
                                         tr.state)
        assert step == 5
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(saved)):
            np.testing.assert_array_equal(np.asarray(a), b)
        for tree in (state["params"], state["opt"]["m"], state["opt"]["v"]):
            held = tree["params_layers"]["p0"]["we_gate_up"]
            assert held.sharding.shard_shape(held.shape) == (
                2, 8 // dp, 64, 64)
            assert len({s.index for s in held.addressable_shards}) == dp
            whole = tree["params_layers"]["p0"]["router"]
            assert whole.sharding.shard_shape(whole.shape) == whole.shape
