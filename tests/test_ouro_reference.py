"""The Ouro LOOPED decoder through the normal path (``models/ouro.py`` over
``parallel/transformer.py``'s one-tree stack with the dense gated FFN and
sandwich norms, ``run_passes`` and ``exit_weighted_loss``; trainer and loss
``parallel/decoder.py``'s) against the benchmark's plain float32 reference
(``benchmark/reference/ouro_2_6b.py``, a Python loop over passes and
layers), on seeded weights at ``ouro_tiny_config``: two layers, hidden 64, 4
heads of 16, a gated FFN of width 96, THREE passes over the same leaves (a
first, a middle and a last exit, which differ), vocab 256, an untied head.

What the tiny configuration keeps of the published one: every leaf and every
line of the layer, the final norm inside the loop, the gate with its bias on
the normed state, the survival product with the last exit taking what is
left, the entropy term.  What it drops: the widths and the fourth pass.

The tiny configuration computes in float32, so the tolerance is 1e-5 on the
loss (the two differ by accumulation order only) and three times that on a
single logit row or gradient element, against the largest of its leaf.  A
shared leaf's gradient is the SUM of its passes' contributions: the program
sums them in the carry of the passes' scan, in the leaf's own type (float32
here; bf16 at the benchmark's sizes, one rounding a pass more than a plain
stack has, which only a chip run sees), the reference by ``jax.grad``
through its Python loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import ouro_2_6b as reference
from paddle_tpu import monitor
from paddle_tpu.models import bert, brumby, ouro
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, transformer as T

B, S, TOL = 2, 32, 1e-5
EACH = 3 * TOL         # one logit row, one gradient element
PASSES = 3
# the reference reads the published keys
MODEL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
         "rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_hidden_layers": 2,
         "total_ut_steps": PASSES, "exit_entropy_coef": 0.1,
         "vocab_size": 256, "tie_word_embeddings": False,
         "rope_scaling": None, "sliding_window": None,
         "layer_types": ["full_attention"] * 48}
LEAVES = ["tok_emb", "lm_head", "lnf_scale", "exit_gate_w", "exit_gate_b"] \
    + ["params_layers/" + n for n in reference.MATRICES + reference.NORMS]
# which of the two limits a fault has to pass at the tiny sizes
CAUGHT_BY_LOSS = ("last_exit_takes_lambda", "entropy_term_dropped",
                  "uniform_exit_weights", "one_pass")


def _mechanism():
    cfg = ouro.ouro_tiny_config()
    assert cfg.loop_passes == PASSES and cfg.exit_entropy_coef == 0.1
    assert cfg.post_norm and cfg.dense_stack and not cfg.per_position \
        and not cfg.tie_head and cfg.layer_kinds == ((None, True),)
    big = ouro.ouro_2_6b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads, big.head_dim,
            big.dense_ffn_hidden, big.vocab_size, big.rope_theta,
            big.norm_eps, big.max_seq, big.loop_passes,
            big.exit_entropy_coef, big.expert_act) == (
        48, 2048, 16, 16, 128, 5632, 49152, 1e6, 1e-6, 65536, 4, 0.1, "silu")

    def count(c):
        shapes = jax.eval_shape(
            lambda: T._init_params(jax.random.PRNGKey(0), c))
        return sum(a.size for a in jax.tree.leaves(shapes))

    # the published 2.6B, and the benchmark's cut
    assert count(big) == 2_667_974_657
    assert count(ouro.ouro_2_6b_config(n_layers=12)) == 817_991_681


CASE = H.Case(
    "ouro", reference, MODEL, tuple(LEAVES), S=S, each=EACH,
    off_one=("scale",), mechanism=_mechanism, logits=False, grad_rtol=1e-3,
    # the reference's view of the exits beside its loss
    forward=lambda p, ids: reference.forward(p, ids, MODEL)[::2],
    # the layers' leaves among them: each one's gradient is the sum over the
    # three passes that read it
    grad_test="test_every_leaf_s_gradient_equals_the_reference")
globals().update(H.common(CASE))


def test_the_exit_distribution_sums_to_one_and_logits_at_weights_by_it(both):
    cfg, params, ids, _, _ = both
    p = np.asarray(both.seen["p"])                              # [B, T, S]
    assert p.shape == (B, PASSES, S)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-6)
    # every exit weighs in, and no two alike
    means = p.mean(axis=(0, 2))
    assert means.min() > 0.05 and len(set(np.round(means, 3))) == PASSES
    # the program's own distribution, from its gates
    gates = jax.jit(lambda q: decoder.forward(q, jnp.asarray(ids), cfg)[1])(
        params)
    mine = np.exp(np.asarray(T.exit_log_probs(gates)))      # [T, B, S]
    np.testing.assert_allclose(mine.swapaxes(0, 1), p, atol=EACH)
    at = reference.witness_positions(S)
    assert len(at) and set(range(8, 12)) <= set(at.tolist())
    # the witness's unit: this reference in bfloat16 against itself
    unit = reference.precision_unit(params, {"ids": ids}, MODEL)
    assert unit.shape == (B * len(at),) and 1e-3 < np.median(unit) < 0.1
    got = np.asarray(H.at_weights(both.tr, params).logits_at(ids, at))
    want = reference.logits(params, {"ids": ids}, MODEL)
    assert got.shape == want.shape == (B, len(at), 256)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=EACH * np.abs(want).max())
    # float32 against float32, in units of a bfloat16 rounding
    assert reference.logits_error(got, params, {"ids": ids}, MODEL) < 1e-3


def test_the_precision_below_in_the_program_s_place_is_not_correct(both):
    """The control of the witness's limit: the reference in bfloat16
    throughout, every operation rounded, as the program's logits through the
    run's own comparison.  It is the unit, so it reads 1, over the limit;
    the chip's readings of the sound program are under it
    (``benchmark/tools/ouro_ref_sensitivity.py`` prints both)."""
    _, params, ids, _, _ = both
    batch = {"ids": ids}
    below = reference.logits(params, batch, MODEL, reference.PRECISION)
    err = reference.logits_error(below, params, batch, MODEL)
    assert err == pytest.approx(1.0) and err > reference.LOGITS_TOLERANCE


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_fault_of_the_reference_moves_a_reading_past_its_limit(
        both, fault):
    _, params, ids, _, (want, _) = both
    batch = {"ids": ids}
    sound = reference.logits(params, batch, MODEL)
    moved = abs(reference.loss(params, batch, MODEL, faults=(fault,))
                - float(want)) / float(want)
    err = reference.logits_error(sound, params, batch, MODEL,
                                 faults=(fault,))
    if fault in CAUGHT_BY_LOSS:
        assert moved > reference.TOLERANCE, (fault, moved)
    if fault != "entropy_term_dropped":     # which the logits do not hold
        assert err > reference.LOGITS_TOLERANCE, (fault, err)


def test_with_one_pass_it_is_today_s_decoder():
    """The same configuration at ``loop_passes`` 1: the leaves a looped
    stack shares with it are seeded alike, the gate's are gone, and the loss
    lowers to the text of ``final_logits_loss`` on ONE ``run_layers``."""
    looped = ouro.ouro_tiny_config()
    plain = ouro.ouro_tiny_config(loop_passes=1, exit_entropy_coef=0.0)
    key = jax.random.PRNGKey(5)
    mine, theirs = T._init_params(key, looped), T._init_params(key, plain)
    assert set(mine) - set(theirs) == {"exit_gate_w", "exit_gate_b"}
    mine = {k: v for k, v in mine.items() if k in theirs}
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), mine, theirs))
    ids = jnp.asarray(H.ids(CASE)[0])

    def loss(params, batch):
        """What ``make_loss_fn`` was before the passes."""
        ids = batch["ids"]
        labels = jnp.roll(ids, -1, axis=1)
        mask = jnp.broadcast_to(
            (jnp.arange(ids.shape[1]) < ids.shape[1] - 1).astype(jnp.float32),
            ids.shape)
        x, _ = T.run_layers(params["params_layers"],
                            T.embed(params, ids, plain), plain, with_aux=True,
                            prefix=None, router_bias=None)
        return T.final_logits_loss(params, x, labels, mask, plain)

    got, want = (jax.jit(fn).lower(theirs, {"ids": ids}).as_text()
                 for fn in (decoder.make_loss_fn(plain), loss))
    assert got.replace("loss_fn", "loss") == want
    assert "while" in want


def test_the_one_tree_gated_ffn_is_the_per_position_stacks():
    """A one-tree stack with ``dense_ffn_hidden`` and no experts holds the
    leaves a per-position stack's layers hold for their FFN, and its scan is
    the layer those stacks run (``transformer_layer(..., dense=True)``:
    ``gated_ffn``) applied layer by layer; BERT's tree keeps ``w1`` / ``w2``."""
    cfg = ouro.ouro_tiny_config(loop_passes=1, exit_entropy_coef=0.0)
    params = T._init_params(jax.random.PRNGKey(5), cfg)
    tree = params["params_layers"]
    theirs = T._init_params(jax.random.PRNGKey(5),
                            brumby.brumby_tiny_config())["params_layers"]["p0"]
    assert {"w_gate_up", "w_down"} <= set(tree) & set(theirs) \
        and not {"w1", "w2"} & set(tree)
    assert tree["w_gate_up"].shape == (2, 64, 192) == theirs["w_gate_up"].shape
    assert {"w1", "w2"} <= set(T._init_params(
        jax.random.PRNGKey(5), bert.bert_tiny_config())["params_layers"])
    x = T.embed(params, jnp.asarray(H.ids(CASE)[0]), cfg)
    got = T.run_layers(tree, x, cfg)
    want = x
    for i in range(cfg.n_layers):
        pl = jax.tree.map(lambda a: a[i], tree)
        want, aux = T.transformer_layer(pl, want, cfg, (None, True), True)
        assert aux is None
        # the FFN branch alone, written out
        h = T.rms_norm(want, pl["ln2_scale"], cfg.norm_eps)
        gate, up = jnp.split(h @ pl["w_gate_up"], 2, axis=-1)
        np.testing.assert_allclose(
            T.gated_ffn(pl, h, cfg), (jax.nn.silu(gate) * up) @ pl["w_down"],
            atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.fixture(scope="module")
def ran():
    """One trainer under remat, a scan of six steps under a monitor session:
    the losses, the session's registry and the program's scopes."""
    tr = H.trainer(CASE, remat=True)
    batches = H.staged(
        tr, [{"ids": H.ids(CASE, seed)[0]} for seed in (0, 1)] * 3)
    mon = monitor.enable()
    try:
        losses = np.asarray(tr.run_steps(batches, 1e-3))
        names = devscope.scope_maps()["ouro.run_steps"]
        return losses, mon.registry, names
    finally:
        monitor.disable()


def test_the_trainer_steps_under_remat_and_its_loss_falls(ran):
    assert np.isfinite(ran[0]).all() and ran[0][-1] < ran[0][0]


def test_the_gauges_and_the_counter_of_a_call(ran):
    reg = ran[1]
    cfg = ouro.ouro_tiny_config()
    # layer applications: passes x layers x the call's six steps
    assert cfg.loop_passes * cfg.n_layers * 6 == PASSES * 2 * 6
    probs = [reg.gauge("monitor.train.exit_prob_mean", exit=t).value
             for t in range(1, PASSES + 1)]
    assert abs(sum(probs) - 1.0) < 1e-5 and min(probs) > 0.05
    # seeded gates: no exit dead, none alone
    assert 0.5 < reg.gauge("monitor.train.exit_entropy_mean").value \
        <= np.log(PASSES)


def test_the_loop_s_instructions_are_under_their_scopes(ran):
    got = {devscope.classify(op) for op in ran[2].values()}
    for scope in ("loop_scan", "exit_gate", "layer_scan", "attention", "mlp",
                  "post_norm", "layer_norm"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("recompute", "mlp") in got and ("forward", "embed") in got
