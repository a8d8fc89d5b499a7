"""Expert parallelism FOR REAL on the forced host devices: the Kanana-2 tiny
configuration with its routed experts riding ``dp`` (``TransformerConfig.
expert_parallel``; two experts a device on four, four on two), each device
routing its own sequences over all eight, exchanging rows with
``lax.all_to_all`` (``parallel/moe.py:_exchange_ffn``) and summing what comes
home, against

- the plain float32 reference on the GLOBAL batch
  (``benchmark/reference/kanana_2_30b_a3b.py``: one device's view of the
  whole model): loss, witness logits and EVERY leaf's gradient, an expert's
  gathered from its holder: the guide's shares test with every share
  present;
- (``test_kanana2_ep_trainer.py``: the same configuration on ONE device with
  the field off, three AdamW steps and the selection bias after them;)
- itself under a routing that sends EVERY pair of every device to one
  device's experts: no pair is dropped, the rounds counter says how many ran.

ONE traced program a mesh, shared by a module fixture (the mesh of two
devices under ``-m slow``).  ``moe.HELD_GRANULE``
is 16 here (512 in the program: the tiny size's 128 pairs a device would be
one round whatever the routing), so a destination's round holds 48 rows on
four devices and the all-to-one routing needs three.

float32 throughout: the tolerances are the reference tests' (1e-5 of the
loss, 1e-5 of an array's largest element), the two differing by
accumulation order only."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import decoder_reference as H
import kanana2_case as K
from benchmark.reference import kanana_2_30b_a3b as reference
from paddle_tpu.models import kanana2
from paddle_tpu.parallel import decoder, moe, optim, transformer as T
from paddle_tpu.parallel.mesh import DP, MeshSpec, local_shard_map
from paddle_tpu.parallel.train import shard_pytree, state_specs

B, S, TOL = 4, 64, 1e-5
# of an array's largest element, by routing.  All-to-one: every token meets
# the SAME two experts in both layers, so the branch's rows are alike, the
# sums that follow cancel, and program and reference, which differ by
# accumulation order, stand 1.1e-4 apart at the worst logit, with ONE round
# (a granule of 512) as with three: the rounds add nothing to it.  A fault
# stands at 1e-2 and more
EACH = {"sound": TOL, "one_chip": 30 * TOL}
CASE = K.case(B=B, S=S)
EXCHANGED = ("rows_sent", "rows_received", "exchange_fullest",
             "exchange_capacity", "exchange_tier", "load_max_over_mean")


@pytest.fixture(scope="module", autouse=True)
def small_rounds():
    kept, moe.HELD_GRANULE = moe.HELD_GRANULE, 16
    yield
    moe.HELD_GRANULE = kept


@dataclasses.dataclass
class Mesh:
    """The ONE program of a mesh of ``dp`` devices and what it gave on the
    seeded weights (``sound``) and on the all-to-one routing (``one_chip``):
    ``(loss, gradients, the next selection biases, witness logits, what the
    exchange did a layer)``."""
    dp: int
    run: object
    params: dict
    ids: np.ndarray
    sound: tuple
    one_chip_params: dict
    one_chip: tuple


def _program(dp):
    cfg = CASE.config()
    mesh = MeshSpec(dp=dp).build(devices=jax.devices()[:dp])
    specs = T.transformer_param_specs(cfg)
    syncs = T.grad_sync_axes(cfg)
    loss_fn = decoder.make_loss_fn(cfg)
    at = jnp.asarray(reference.witness_positions(S))

    def device(params, ids):
        (loss, stepped), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {"ids": ids})
        grads = jax.tree.map(
            lambda g, axes: jax.lax.psum(g, axes) if axes else g, grads,
            jax.tree.map(lambda a: tuple(x for x in a if x == DP), syncs,
                         is_leaf=lambda x: isinstance(x, tuple)))
        x, aux = decoder.forward(params, ids, cfg)
        return (loss, grads, stepped["router_bias"],
                T.head_logits(params, x[:, at], cfg),
                {k: aux[k] for k in EXCHANGED})

    fn = jax.jit(local_shard_map(
        device, mesh, in_specs=(specs, P(DP)),
        out_specs=(P(), specs, P(), P(DP), P())))

    def run(params, ids):
        with mesh:
            placed = shard_pytree(params, specs, mesh)
        return jax.tree.map(np.asarray, fn(placed, jnp.asarray(ids)))

    return run


def _to_one_chip(params):
    """Selection biases that send EVERY token to experts 0 and 1, which the
    first device holds (the weights are still the scores': the bias decides
    who is chosen and nothing else)."""
    bias = np.array(params["router_bias"])
    bias[:, :2] += 10.0
    return dict(params, router_bias=bias)


# two devices (four experts a device, a round of 160 rows) run with ``-m
# slow``: the second mesh's program is a third of this file's tier-1 seconds
# and differs from the first in sizes alone; tier-1 keeps the four of the
# cell (``test_kanana2_ep_trainer.py`` loads a four-device state on two)
@pytest.fixture(scope="module",
                params=[4, pytest.param(2, marks=pytest.mark.slow)])
def mesh(request, seeded):
    params, ids = seeded
    run = _program(request.param)
    crowded = _to_one_chip(params)
    return Mesh(request.param, run, params, ids, run(params, ids), crowded,
                run(crowded, ids))


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights, moved as the reference tests move them, from a
    ONE-device trainer with the field off, and the global batch."""
    tr = H.trainer(CASE, expert_parallel=False)
    return (H.moved(CASE, jax.tree.map(np.asarray, tr.state["params"])),
            H.ids(CASE)[0])


@pytest.fixture(scope="module")
def want(seeded):
    """The reference on the global batch: loss, gradients and witness
    logits, on the seeded weights and on the all-to-one routing."""
    def of(params):
        (loss, _), grads = jax.value_and_grad(
            lambda p: reference.forward(p, seeded[1], K.MODEL,
                                        keep_logits=False), has_aux=True)(
            jax.tree.map(jnp.asarray, params))
        logits = np.stack(reference.forward(
            params, seeded[1], K.MODEL,
            positions=reference.witness_positions(S))[1])
        return float(loss), jax.tree.map(np.asarray, grads), logits
    return {"sound": of(seeded[0]), "one_chip": of(_to_one_chip(seeded[0]))}


@pytest.mark.parametrize("routing", ["sound", "one_chip"])
def test_loss_and_witness_logits_equal_the_reference(mesh, want, routing):
    loss, _, _, logits, _ = getattr(mesh, routing)
    H.loss_agrees(loss, want[routing][0], TOL)
    ref = want[routing][2]
    assert logits.shape == ref.shape == (B, S, 256)
    np.testing.assert_allclose(logits, ref, rtol=1e-4,
                               atol=EACH[routing] * np.abs(ref).max())
    # each sequence lives on another device: every one's rows are held
    each = reference.position_errors(
        logits, mesh.params if routing == "sound" else mesh.one_chip_params,
        {"ids": mesh.ids}, K.MODEL)
    assert each.reshape(B, -1).max(axis=1).max() < 10 * EACH[routing]


@pytest.mark.parametrize("routing", ["sound", "one_chip"])
@pytest.mark.parametrize("path", K.LEAVES)
def test_gradient_of_every_leaf_equals_the_reference(mesh, want, routing,
                                                     path):
    """An expert's gradient is gathered from its holder (``out_specs``): no
    device summed it over dp, and it carries the global batch's 1 / tokens
    like every other leaf's."""
    g = H.leaf(getattr(mesh, routing)[1], path)
    w = H.leaf(want[routing][1], path)
    assert g.shape == H.leaf(mesh.params, path).shape, path
    assert np.abs(w).max() > 0, path
    np.testing.assert_allclose(g, w, rtol=1e-4,
                               atol=EACH[routing] * np.abs(w).max())


def test_no_pair_is_dropped_and_the_counters_say_what_ran(mesh):
    dp, pairs = mesh.dp, B // mesh.dp * S * 2       # a device's T k
    cap = moe._exchange_capacity(pairs, dp)
    assert cap == {4: 48, 2: 160}[dp] and cap < pairs
    sound, crowded = mesh.sound[4], mesh.one_chip[4]
    for aux in (sound, crowded):
        assert aux["exchange_capacity"].tolist() == [cap, cap]
        assert (aux["exchange_tier"]
                == -(-aux["exchange_fullest"] // cap) - 1).all()
    # seeded: fewer rounds than all-to-one needs (at this granule a layer's
    # fullest destination may pass one round's 48 rows; the trainers of
    # ``test_kanana2_ep_trainer.py`` run the program's own granule, ONE
    # round); every pair somewhere, most of them off their device
    assert (sound["exchange_tier"] < crowded["exchange_tier"]).all()
    assert (sound["rows_sent"] > 0).all() and (
        sound["rows_sent"] < dp * pairs).all()
    assert (sound["rows_received"] < dp * pairs).all()
    # all to the first device: it receives EVERY pair of the batch, the
    # others send all of theirs, and the rounds are what that takes
    assert crowded["rows_received"].tolist() == [dp * pairs] * 2
    assert crowded["rows_sent"].tolist() == [(dp - 1) * pairs] * 2
    assert crowded["exchange_fullest"].tolist() == [pairs] * 2
    assert crowded["exchange_tier"].tolist() == [-(-pairs // cap) - 1] * 2 \
        == [{4: 2, 2: 1}[dp]] * 2
    assert (crowded["load_max_over_mean"] == 4.0).all()     # 8 experts, 2 used
    # the next biases are moved by the GLOBAL load: the same on every device
    # (``out_specs`` P() would not say), and against the crowd
    moved = mesh.one_chip[2] - mesh.one_chip_params["router_bias"]
    assert (moved[:, :2] < 0).all() and (moved[:, 2:] > 0).all()


def test_dropping_past_the_first_capacity_would_show(mesh, want):
    """The fault the rounds exist to exclude, at the program's OWN capacity:
    under the all-to-one routing a reference that drops what the first round
    does not carry stands far from the program."""
    if mesh.dp != 4:
        pytest.skip("the reference's fault is written for four chips")
    cap = moe._exchange_capacity(B // 4 * S * 2, 4)
    model = dict(K.MODEL)
    kept, reference.OVERFLOW_SHARE = reference.OVERFLOW_SHARE, cap / (S * 2 / 4)
    try:
        bad = reference.logits_error(
            mesh.one_chip[3], mesh.one_chip_params, {"ids": mesh.ids}, model,
            faults=("overflow_dropped",))
    finally:
        reference.OVERFLOW_SHARE = kept
    assert bad > 1e3 * TOL


def test_experts_ride_dp_and_nothing_else_does():
    cfg = CASE.config()
    specs, syncs = T.transformer_param_specs(cfg), T.grad_sync_axes(cfg)
    paths, _, _ = H.leaf_paths(jax.eval_shape(
        lambda: T.init_transformer_params(jax.random.PRNGKey(0), cfg)))
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    sync_leaves = jax.tree.leaves(syncs,
                                  is_leaf=lambda x: isinstance(x, tuple))
    assert len(paths) == len(spec_leaves) == len(sync_leaves)
    riding = {p for p in paths if p.split("/")[-1] in ("we_gate_up",
                                                       "we_down")}
    assert riding == {"params_layers/p0/we_gate_up",
                      "params_layers/p0/we_down"}
    for path, spec, axes in zip(paths, spec_leaves, sync_leaves):
        if path in riding:
            assert spec == P(None, DP) and DP not in axes, path
        else:
            assert DP not in tuple(spec) and DP in axes, path
    # the moments follow the parameters
    state = jax.eval_shape(lambda: {"params": T.init_transformer_params(
        jax.random.PRNGKey(0), cfg), "opt": optim.adamw()[0](
            T.init_transformer_params(jax.random.PRNGKey(0), cfg))})
    sspecs = state_specs(specs, state)
    assert sspecs["opt"]["m"] == sspecs["opt"]["v"] == specs
    # off, the same leaves are whole on every device and summed over dp
    off = CASE.config(expert_parallel=False)
    assert T.transformer_param_specs(off)["params_layers"]["p0"][
        "we_gate_up"] == P(None, None, None, None)
    assert DP in T.grad_sync_axes(off)["params_layers"]["p0"]["we_down"]
    with pytest.raises(AssertionError):     # 8 experts on 3 devices
        kanana2.build_kanana2_trainer(cfg, MeshSpec(dp=3))
