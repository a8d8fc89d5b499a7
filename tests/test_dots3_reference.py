"""The dots3-note-prev decoder on the program's normal path against the plain
reference (``benchmark/reference/dots3_note_prev.py``) at the tiny size:
logits, both loss terms and every leaf's gradient; which leaves hear which
term; the selected sets.  ONE traced program of the model for the file's
comparisons (the shares' parts: ``tests/test_dots3_shares.py``; each fault in
the reference: ``benchmark/tests/test_bench_dots3.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dots3_note_prev as ref
from paddle_tpu.kernels import indexer as ix
from paddle_tpu.models import dots3
from paddle_tpu.parallel import decoder, transformer as T

CFG = dots3.dots3_tiny_config(remat=True)
B, S, TOPK = 1, 64, CFG.indexer_topk


def leaves_of(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def off_their_seeds(params, r):
    """The norms' weights, the indexer key norm's bias and the selection
    biases off their seeds, so that each shows."""
    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name:
            return leaf + jnp.asarray(0.1 * r.randn(*leaf.shape), leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def case():
    r = np.random.RandomState(1)
    params = off_their_seeds(
        T.init_transformer_params(jax.random.PRNGKey(0), CFG), r)
    ids = r.randint(0, CFG.vocab_size, (B, S)).astype(np.int32)

    def terms(p):
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to((jnp.arange(S) < S - 1).astype(jnp.float32),
                                ids.shape)
        x, aux = decoder.forward(p, ids, CFG)
        return (T.final_logits_loss(p, x, labels, mask, CFG),
                decoder._dsa_kl_mean(aux, CFG)), (
                    T.head_logits(p, x, CFG), aux["dsa_kl"])

    def program(p):
        (ce, kl), pull, (logits, kl_layers) = jax.vjp(terms, p, has_aux=True)
        one, zero = jnp.ones(()), jnp.zeros(())
        pl, h = decoder._first_layer_input(p, ids, CFG)
        scores, tau = T.indexer_selection(
            pl, h, CFG.position(CFG.prefix_kinds[0])[0])
        loss, stepped = decoder.make_loss_fn(CFG)(p, {"ids": ids})
        return dict(ce=ce, kl=kl, logits=logits, kl_layers=kl_layers,
                    d_ce=pull((one, zero))[0], d_kl=pull((zero, one))[0],
                    selected=ix.selected(scores, tau), loss=loss)

    with jax.default_matmul_precision("highest"):
        got = jax.device_get(jax.jit(program)(params))
        model = ref.model_of(CFG)
        selections = []
        want = ref.forward_terms(params, {"ids": ids}, model,
                                 selections=selections)
        want["grad"] = jax.grad(lambda p: ref.forward(
            p, {"ids": ids}, model, keep_logits=False)[0])(params)
    want["selected"] = selections[0]
    return params, ids, got, jax.device_get(want)


def close(got, want, tolerance=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) <= tolerance * max(
        1e-3, np.max(np.abs(want)))


def test_logits_and_both_loss_terms_agree_with_the_reference(case):
    _, _, got, want = case
    assert close(got["logits"], np.stack(want["logits"]), 2e-5)
    assert close(got["ce"], want["ce"], 1e-6)
    assert close(got["kl"], want["kl"], 1e-5) and want["kl"] > 0.05
    assert close(got["loss"], want["ce"] + want["kl"], 1e-6)


def test_only_the_layers_with_an_indexer_add_to_the_kl_term(case):
    _, _, got, _ = case
    # layer 0 and the period's first: full; the three sliding ones zero
    assert np.all(got["kl_layers"][:2] > 0.01)
    assert not np.any(got["kl_layers"][2:]) and CFG.indexer_layers == 2
    assert close(got["kl"], np.sum(got["kl_layers"]) / 2, 1e-6)


LEAVES = sorted(leaves_of(jax.eval_shape(
    lambda: T.init_transformer_params(jax.random.PRNGKey(0), CFG))))
# stepped by the load, not by a gradient
TRAINED = [leaf for leaf in LEAVES if "router_bias" not in leaf]


@pytest.mark.parametrize("leaf", TRAINED)
def test_a_leaf_s_gradient_agrees_with_the_reference(case, leaf):
    """float32 both sides; 5e-5 of the leaf's largest entry: the kernels
    sum a tile's products in another order than ``jnp`` does."""
    _, _, got, want = case
    total = leaves_of(got["d_ce"])[leaf] + leaves_of(got["d_kl"])[leaf]
    wanted = leaves_of(want["grad"])[leaf]
    assert np.max(np.abs(wanted)) > 0
    if "wz" in leaf:
        # the gate's columns of the absent heads hear nothing
        at = CFG.position(CFG.layer_kinds[0 if "r0" in leaf or "l0" in leaf
                                          else 1])[0]
        held = np.zeros(wanted.shape[-1], bool)
        held[at.first_head:at.first_head + at.heads_here] = True
        assert not np.any(total[..., ~held]) and np.all(
            np.any(total[..., held] != 0, axis=-2))
    assert close(total, wanted, 5e-5), leaf


@pytest.mark.parametrize("leaf", TRAINED)
def test_a_leaf_hears_one_term_alone(case, leaf):
    """The indexer's five leaves get EXACTLY nothing from the cross entropy
    and every other leaf exactly nothing from the KL term."""
    _, _, got, _ = case
    indexer = leaf.split("'")[-2] in ref.INDEXER_LEAVES
    silent, heard = ("d_ce", "d_kl") if indexer else ("d_kl", "d_ce")
    assert not np.any(leaves_of(got[silent])[leaf]), leaf
    assert np.any(leaves_of(got[heard])[leaf]), leaf


def test_the_selected_sets_are_the_reference_s(case):
    _, _, got, want = case
    assert np.array_equal(got["selected"], want["selected"])
    kept = got["selected"].sum(-1)
    assert np.array_equal(kept[:, :TOPK],
                          np.broadcast_to(np.arange(1, TOPK + 1), (B, TOPK)))
    assert np.all(kept[:, TOPK:] >= TOPK)
    assert np.mean(kept[:, TOPK:] == TOPK) > 0.9


def _counted(tmp_path, trace):
    """{(dh, convention, rotary, fused): calls} that ``trace()`` counts in
    ``monitor.kernels.qk_rope_calls`` under a monitor session."""
    from paddle_tpu import monitor

    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()
        trace()
        return {tuple(r["labels"][k] for k in (
            "dh", "convention", "rotary", "fused")): r["value"]
                for r in mon.registry.snapshot()
                if r["name"] == "monitor.kernels.qk_rope_calls"}
    finally:
        monitor.disable()


def test_every_latent_layer_counts_both_projections_fused(case, tmp_path):
    """Both shapes' q and k go through ``kernels/qk_rope.py`` at a head of
    two lane blocks (``_latent_qkv_lanes``; the indexer's queries and key
    through the same kernel at a head of one), counted ``fused`` 1."""
    params, ids, _, _ = case
    counted = _counted(tmp_path, lambda: jax.eval_shape(
        lambda p: decoder.forward(p, ids, CFG)[0], params))
    assert set(counted) == {(256, "pairs", 1, 1), (128, "half", 1, 1)}
    # layer 0, the period's full layer and its run of sliding ones: q and k
    assert counted[(256, "pairs", 1, 1)] == 3 * 2


@pytest.mark.parametrize("layer", ["full", "sliding"])
def test_the_row_kernel_s_q_k_v_and_gradients_equal_the_lines(
        case, monkeypatch, layer):
    """A tiny layer of each shape takes the row kernel; with the kernel
    refused the same call runs the ``rope_pairs`` lines and the broadcast
    add: the same q, k, v and the same gradients of the chain's leaves and
    of the rows."""
    from paddle_tpu.kernels import qk_rope

    params = case[0]
    pl, kind = (params["prefix_layers"]["l0"], CFG.prefix_kinds[0]) \
        if layer == "full" else (jax.tree.map(
            lambda a: a[0, 0], params["params_layers"]["r1"]),
            CFG.layer_kinds[1])
    at, (_, rotary) = CFG.position(kind)
    lanes, H = 256, at.heads_here
    assert rotary and T._latent_head_lanes(at) == lanes
    h = jax.random.normal(jax.random.PRNGKey(8), (2, S, CFG.hidden))
    w = [jax.random.normal(jax.random.PRNGKey(9 + i), (2, S, H * n))
         for i, n in enumerate((lanes, lanes, 128))]

    def run(pl, h):
        out = T._qkv(pl, h, at, rotary)
        return sum(jnp.sum(a * b) for a, b in zip(out, w)), out

    assert qk_rope.supported((2, S, H * lanes), lanes, 4)
    (_, got), got_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(pl, h)
    monkeypatch.setattr(qk_rope, "supported", lambda *a: False)
    (_, want), want_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(
        pl, h)
    for a, r in zip(got, want):
        assert a.shape == r.shape
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "q_a_norm", "kv_a_norm"):
        a, r = got_grads[0][name], want_grads[0][name]
        assert a.shape == pl[name].shape and np.abs(r).max() > 0
        np.testing.assert_allclose(a, r, rtol=1e-4,
                                   atol=1e-5 * np.abs(r).max(), err_msg=name)
    np.testing.assert_allclose(got_grads[1], want_grads[1], rtol=1e-4,
                               atol=1e-4)
