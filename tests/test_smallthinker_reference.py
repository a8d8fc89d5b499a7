"""The SmallThinker decoder through the normal path (``models/smallthinker.py``
over ``parallel/transformer.py``, ``parallel/moe.py`` and the flash kernels'
grouped and windowed modes) against the benchmark's plain float32 reference
(``benchmark/reference/smallthinker_21b_a3b.py``), on seeded weights at
``smallthinker_tiny_config``: one period of 4 layers (a position-free full
layer, three rotary ones with a window of 24), hidden 64, 6 query heads on 2
key/value heads of 128, 8 experts of width 32 of which this share holds 2,
top-2, vocab 256, S = 64 (above the window).

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only): computing in bfloat16 moves the
loss by more and fails it, as ``test_a_bfloat16_shortcut_...`` shows."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import smallthinker_21b_a3b as reference
from paddle_tpu.kernels.flash_attention import kv_blocks
from paddle_tpu.models import bert, olmoe, smallthinker
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, moe, transformer as T

B, S, TOL = 2, 64, 1e-5
# the reference reads the published keys
MODEL = {"num_attention_heads": 6, "num_key_value_heads": 2,
         "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "rope_theta": 1500000,
         "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
         "sliding_window_size": 24, "moe_num_active_primary_experts": 2,
         "moe_num_primary_experts": 2, "moe_router_width": 8,
         "moe_first_expert_held": 2}
LEAVES = ("tok_emb", "lm_head", "lnf_scale", "ln1_scale", "ln2_scale", "wq",
          "wk", "wv", "wo", "router", "we_gate_up", "we_down")


def _mechanism():
    cfg = smallthinker.smallthinker_tiny_config()
    assert cfg.n_heads // cfg.kv_heads == 3
    assert cfg.n_heads * cfg.head_dim != cfg.hidden
    assert cfg.layer_kinds == ((None, False),) + ((24, True),) * 3
    assert 24 < S and 24 % cfg.flash_block_k           # edge blocks masked
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert) == (8, 2, 2)
    assert T._packed_flash_blocks(cfg, 6, S, 2) == (16, 16)   # the kernels run
    big = smallthinker.smallthinker_21b_a3b_config()
    assert big.layer_kinds == ((None, False),) + ((4096, True),) * 3
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.n_experts,
            big.experts_per_token, big.experts_here, big.vocab_size) == (
        52, 2560, 28, 4, 128, 768, 64, 6, 64, 151936)


def test_a_bfloat16_shortcut_would_fail_the_tolerance(both):
    """The same program in bfloat16 against the float32 one: its logits miss
    the tolerance the logits test holds by orders (the scalar loss, a mean
    over 126 positions, hides most of it: these tests hold more than the
    loss for that reason)."""
    cfg, params, ids, _, _ = both
    low_cfg = smallthinker.smallthinker_tiny_config(dtype="bfloat16")
    bf16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a,
        jax.tree.map(jnp.asarray, params))

    def logits(c, p):
        x, _ = decoder.forward(p, jnp.asarray(ids), c)
        return (T.rms_norm(x, p["lnf_scale"], c.norm_eps).astype(jnp.float32)
                @ p["lm_head"].T.astype(jnp.float32))

    off = np.abs(np.asarray(logits(low_cfg, bf16))
                 - np.asarray(logits(cfg, jax.tree.map(jnp.asarray, params))))
    assert off.max() > 100 * TOL and np.median(off) > 10 * TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_the_reference_s_faults_move_its_loss(both, fault):
    """The switches ``benchmark/tools/smallthinker_ref_sensitivity.py``
    throws at the published sizes do something at the tiny one too."""
    _, params, ids, _, (want, _) = both
    bad = reference.loss(params, {"ids": ids}, MODEL, faults=(fault,))
    assert abs(bad - float(want)) / float(want) > 2 * TOL


def test_the_witness_holds_the_program_s_logits(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    ``StepTrainer``'s own forward at the witness's positions against the
    reference's logits, as one relative error, on the weights and ids of
    ``both``."""
    params, ids, program_logits, model = witnessed
    at = reference.witness_positions(S)
    assert len(at) == S and (at == np.arange(S)).all()      # S < WITNESS_ROWS
    assert program_logits.shape == (B, S, 256)
    assert reference.logits_error(program_logits, params, {"ids": ids},
                                  model) < TOL
    assert reference.witness_positions(16384)[[0, 1, -1]].tolist() == [
        32, 96, 16352]


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The PROGRAM's expert layer on each of the four shares of 2 experts,
    summed, is the REFERENCE's layer with all 8 experts held: what a share
    leaves out is exactly what the other three compute."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    h1 = jax.random.normal(ks[1], (S, 64))
    x = jax.random.normal(ks[2], (S, 64))              # the block's input
    scale = jax.random.uniform(ks[3], (64,), minval=0.5, maxval=1.5)
    r = x @ whole["router"]
    want = reference.moe_part(h1, r, scale, whole["we_gate_up"],
                              whole["we_down"], 0, 2, 1e-6)
    parts = []
    for first in range(0, 8, 2):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(
            share, T.rms_norm(h1, scale, 1e-6), 2, rule=moe.TOP_K_SOFTMAX,
            act="relu", logits=moe.router_logits(share["router"], x),
            first_held=first)
        parts.append(y)
        # and the reference given the same share gives the same part
        np.testing.assert_allclose(y, reference.moe_part(
            h1, r, scale, share["we_gate_up"], share["we_down"], first, 2,
            1e-6), rtol=1e-5, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)


def test_two_periods_scanned_equal_the_reference():
    """8 layers are two periods: the scan's second turn runs the same four
    kinds on the second half of the stacked leaves."""
    tr = H.trainer(CASE, n_layers=8)
    params = H.moved(CASE, tr.state["params"])
    ids = H.ids(CASE, seed=2)[0]
    got = jax.jit(decoder.make_loss_fn(tr.cfg))(params, {"ids": jnp.asarray(ids)})
    model = dict(MODEL, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2,
                 sliding_window_layout=[0, 1, 1, 1] * 2)
    want = reference.loss(params, {"ids": ids}, model)
    assert abs(float(got) - want) / want < TOL


def test_heads_the_kernel_cannot_tile_are_refused():
    """Heads of 16 (no 128-lane block holds one): grouped and windowed
    attention has no path but the packed kernel, and says so."""
    tr = H.trainer(CASE, head_width=16)
    assert T._packed_flash_blocks(tr.cfg, 6, S, 2) is None
    with pytest.raises(AssertionError, match="packed flash kernel"):
        decoder.make_loss_fn(tr.cfg)(tr.state["params"],
                                   {"ids": jnp.asarray(H.ids(CASE, seed=4)[0])})


def _lowered(stack, layers, x):
    """StableHLO of the gradient of ``stack``'s summed output, private
    function numbering aside."""
    def loss(layers, x):
        return jnp.sum(stack(layers, x).astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(layers, x).as_text()
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)


@pytest.mark.parametrize("model", ["bert", "olmoe"])
def test_a_period_of_one_layer_is_the_scan_over_layers_it_was(model):
    """BERT's and OLMoE's stacks: ``run_layers`` lowers to the program a
    plain scan of the (rematerialised) block over the stacked leaves gives,
    which is what it was before layer patterns; and a pattern that names
    the one kind in full gives the same program again."""
    cfg = (bert.bert_tiny_config(remat=True) if model == "bert"
           else olmoe.olmoe_tiny_config(remat=True, scan_unroll=2))
    assert cfg.layer_pattern == () and len(cfg.layer_kinds) == 1
    layers = T.init_transformer_params(jax.random.PRNGKey(0),
                                       cfg)["params_layers"]
    x = jnp.ones((2, 32, cfg.hidden), cfg.jdtype)

    def as_it_was(layers, x):
        body = jax.checkpoint(T.transformer_layer, static_argnums=(2,))
        return jax.lax.scan(lambda x, pl: body(pl, x, cfg), x, layers,
                            unroll=cfg.scan_unroll)[0]

    was = _lowered(as_it_was, layers, x)
    assert "stablehlo.while" in was
    assert _lowered(lambda l, x: T.run_layers(l, x, cfg), layers, x) == was
    if model == "olmoe":
        named = olmoe.olmoe_tiny_config(remat=True, scan_unroll=2,
                                        layer_pattern=((0, True),))
        assert _lowered(lambda l, x: T.run_layers(l, x, named), layers,
                        x) == was


def _counters(trained):
    cfg = trained.scan.cfg
    # batches x tokens x top-2 x L
    pairs = 3 * trained.batches[0]["ids"].size * cfg.experts_per_token \
        * cfg.moe_layers
    assert pairs == 3 * B * S * 2 * 4
    got = trained.value("monitor.train.moe_rows_held")
    assert 0 < got < pairs
    share = trained.value("monitor.train.moe_held_rows_share")
    np.testing.assert_allclose(share, got / pairs)
    assert 0.1 < share < 0.5                # 2 of 8 experts held
    # what the sum back's row kernel is sized by: a layer's pair slots,
    # and the rows of its first capacity (at this size one 512-row tile
    # would pass the slots, so they are the only capacity)
    slots = B * S * cfg.experts_per_token
    assert moe._held_capacities(slots, cfg.experts_here,
                                cfg.n_experts)[0] == slots == B * S * 2
    # the flash kernels' grids by layer kind: S = 64 in 16-blocks, a
    # window of 24 visits 2 or 3 kv blocks a q block (the grid is the
    # table of visited blocks: it holds no other step)
    blocks = T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads)
    window = max(k[0] or 0 for k in cfg.layer_kinds) or None
    assert (blocks, window) == ((16, 16), 24)
    assert kv_blocks(S, *blocks, True, None) == 10
    assert kv_blocks(S, *blocks, True, window) == 9


CASE = H.Case(
    "smallthinker", reference, MODEL, LEAVES, off_one=("scale",),
    # a router steep enough that the top-2 weights are not all one half
    gain=H.steep("router"),
    mechanism=_mechanism,
    # 4 row blocks of 64; chunks of 100, 100, 56; one expert at a time
    pieces={"QUERY_BLOCK": 16, "VOCAB_CHUNK": 100, "EXPERT_GROUP": 1},
    pieces_hold=("loss", "logits", "grads"),
    # the trainer's own logits on the weights and ids of ``both``
    witness=H.Witness(seed=None, faults=reference.FAULTS),
    steps=3, counters=True, also={"counters": _counters})
globals().update(H.common(CASE))


def test_the_pre_attention_router_s_instructions_are_under_router(trained):
    """The router's logits are computed before the attention scope, from
    the block's input: in the compiled step they carry the scope ``router``
    (forward and backward), and every scope of the block is there."""
    names, got = trained.names, trained.scopes()
    for scope in ("moe", "router", "attention", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    # the logits' matmul sits under ``router`` and under no ``moe``
    before = [op for op in names.values()
              if "/router/dot_general" in op and "/moe/" not in op]
    assert {devscope.classify(op) for op in before} >= {
        ("forward", "router"), ("backward", "router")}
