"""The SDAR block-diffusion decoder through the normal path (``models/sdar.py``
over ``parallel/transformer.py``, ``parallel/decoder.py``'s denoising loss and
the flash kernels under the block rule) against the benchmark's plain float32
reference (``benchmark/reference/sdar_30b_a3b_chat.py``), on seeded weights at
``sdar_tiny_config``: 2 layers, hidden 64, 16 query heads on 2 key/value heads
of 128 (a group of 8), blocks of 4 at S = 64 (128 rows a layer) in 16-row
tiles, 8 experts of width 32 of which this share holds 2, top-2, vocab 256
(row 255 the mask token).

The tiny configuration computes in float32, so the tolerance is 1e-5 (the two
differ by accumulation order only; 2e-5 of a gradient's largest entry: the
kernels sum a tile's products in another order than ``jnp`` does)."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import sdar_30b_a3b_chat as reference
from paddle_tpu.kernels.flash_attention import kv_blocks
from paddle_tpu.models import sdar
from paddle_tpu.parallel import decoder, moe, transformer as T

B, S, TOL, BD = 2, 64, 1e-5, 4
# the reference reads the published keys, and the two the cell assumes
MODEL = {"num_attention_heads": 16, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
         "num_experts_per_tok": 2, "first_expert_held": 2,
         "block_length": BD, "mask_token_id": 255}
LEAVES = ("tok_emb", "lm_head", "lnf_scale", "ln1_scale", "ln2_scale", "wq",
          "wk", "wv", "wo", "q_norm", "k_norm", "router", "we_gate_up",
          "we_down")


def noise(ids):
    """A batch's noise, seeded by its ids: a level a block in [0.45, 0.95]
    and a draw a token."""
    r = np.random.RandomState(zlib.crc32(np.asarray(ids).tobytes()))
    return {"t": r.uniform(0.45, 0.95, (len(ids), S // BD)).astype("f4"),
            "u": r.rand(*np.shape(ids)).astype("f4")}


def _mechanism():
    cfg = sdar.sdar_tiny_config()
    assert (cfg.n_heads // cfg.kv_heads, cfg.head_dim) == (8, 128)
    assert (cfg.block_diffusion, cfg.mask_token_id, cfg.causal) == (
        BD, 255, False)
    assert cfg.qk_norm == "head" and cfg.positions == "rotary"
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert) == (8, 2, 2)
    # the kernels run, on both copies' rows
    assert T._packed_flash_blocks(cfg, 16, 2 * S, 2) == (16, 16)
    big = sdar.sdar_30b_a3b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads,
            big.head_dim, big.ffn_hidden, big.n_experts,
            big.experts_per_token, big.experts_here, big.vocab_size,
            big.rope_theta, big.block_diffusion, big.mask_token_id) == (
        48, 2048, 32, 4, 128, 768, 128, 8, 128, 151936, 1e6, 4, 151935)


def _forward(params, ids):
    return reference.forward(params, H.batch_of(CASE, ids), MODEL,
                             keep_logits=False)[0], None


def _counters(trained):
    cfg = trained.scan.cfg
    # every scope of the step is there, and the noising has its own
    got = {H.devscope.classify(op)
           for op in H.scope_map(trained.scan).values()}
    for scope in ("moe", "router", "attention", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("forward", "noise") in got
    masked = np.mean([b["u"] < np.repeat(b["t"], BD, -1)
                      for b in trained.batches])
    np.testing.assert_allclose(
        trained.value("monitor.train.bd_masked_share"), masked)
    # the head computes whole blocks of the masked rows alone
    block = T.head_row_block(B * S)
    rows = sum(-(-int((b["u"] < np.repeat(b["t"], BD, -1)).sum()) // block)
               * block for b in trained.batches)
    assert trained.value("monitor.train.lm_head_rows") == rows
    assert 0 < rows < len(trained.batches) * B * S
    # 128 rows in 16-row tiles: nq (nq + 1) + nq at nq = 4
    assert trained.value("monitor.train.bd_tiles_a_layer") == 24 == kv_blocks(
        2 * S, 16, 16, False, blocks=BD)
    # every pair of BOTH copies' rows is routed: batches x rows x top-2 x L
    pairs = len(trained.batches) * B * 2 * S * cfg.experts_per_token \
        * cfg.moe_layers
    held = trained.value("monitor.train.moe_rows_held")
    np.testing.assert_allclose(
        trained.value("monitor.train.moe_held_rows_share"), held / pairs)
    assert 0.1 < held / pairs < 0.5                 # 2 of 8 experts held
    slots = B * 2 * S * cfg.experts_per_token
    assert trained.value("monitor.train.moe_capacity_rows") == \
        moe._held_capacities(slots, 2, 8)[0]
    assert trained.value("monitor.kernels.flash_blockdiff_calls",
                         part="fwd", fused=1) >= 1
    assert trained.value("monitor.kernels.flash_blockdiff_calls",
                         part="bwd", fused=1, sweeps=1) >= 1
    assert trained.value("monitor.kernels.moe_rows_sum_calls", fused=1,
                         k=2) >= 1


CASE = H.Case(
    "sdar", reference, MODEL, LEAVES, B=B, S=S, tol=TOL, each=2e-5,
    # a router steep enough that the top-2 weights are not all one half
    gain=H.steep("router"), mechanism=_mechanism, forward=_forward,
    fields=noise, logits=False, bfloat16=True,
    # 8 row blocks of 128; chunks of 100, 100, 56; one expert at a time
    pieces={"QUERY_BLOCK": 16, "VOCAB_CHUNK": 100, "EXPERT_GROUP": 1},
    pieces_hold=("loss", "logits", "grads"),
    # the trainer's own logits on the weights and ids of ``both``; the
    # faults move a softmax's support: a hundred times the sound distance
    # at the least (a wrong key/value head: 16 of 16 heads moved)
    witness=H.Witness(seed=None, faults=reference.FAULTS[:-1],
                      floors={f: 1e2 for f in reference.FAULTS}),
    # ONE trainer's ``run_steps`` under a session of its own (that a scan
    # equals its steps is the trainer's, held by thirteen files already)
    counters={"remat": True}, also={"counters": _counters})
globals().update(H.common(CASE))


def test_the_witness_holds_the_program_s_logits(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed_batch.py`` checks on the
    chip: the trainer's own forward on the batch WITH its noise, the noised
    rows' logits at the witness's positions, against the reference's."""
    params, ids, program_logits, model = witnessed
    at = reference.witness_positions(S)
    assert len(at) == S and (at == np.arange(S)).all()      # S < WITNESS_ROWS
    assert program_logits.shape == (B, S, 256)
    batch = H.batch_of(CASE, ids)
    assert reference.logits_error(program_logits, params, batch, model) < TOL
    assert reference.position_errors(program_logits, params, batch,
                                     model).max() < 10 * TOL
    groups = reference.witness_groups(8192)
    assert groups["block_0"].tolist() == [0, 1, 2, 3]
    assert groups["tile_edge"].tolist() == list(range(508, 516))
    assert groups["last"].tolist() == list(range(8188, 8192))
    assert len(reference.witness_positions(8192)) == 256 + 16


def test_masked_and_unmasked_rows_are_both_witnessed_and_weighted(both):
    batch = H.batch_of(CASE, both.ids)
    rows, masked, level = reference.noise_of(batch, MODEL)
    assert rows.shape == (B, 2 * S) and 0.3 < masked.mean() < 0.9
    assert (rows[:, :S][masked] == 255).all()
    assert (rows[:, :S][~masked] == both.ids[~masked]).all()
    assert (rows[:, S:] == both.ids).all()
    got = jax.jit(lambda b: decoder.noised_rows(b, both.cfg))(
        jax.tree.map(jnp.asarray, batch))
    for g, w in zip(got, (rows, masked, level)):
        assert np.array_equal(np.asarray(g), w)
    # the divisor is the sequence's length: doubling every weight (half the
    # noise level, the same mask) doubles the loss
    loss_fn = jax.jit(decoder.make_loss_fn(both.cfg))
    params = jax.tree.map(jnp.asarray, both.params)
    keep = dict(batch, u=np.where(masked, 0.0, 1.0).astype("f4"))
    half = dict(keep, t=batch["t"] / 2)
    np.testing.assert_allclose(loss_fn(params, half),
                               2 * loss_fn(params, keep), rtol=1e-6)


def test_both_copies_carry_the_sequence_s_positions():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 2 * S, 256), jnp.float32)
    twice = T.rope(x, 2, 1e6, period=S)
    np.testing.assert_allclose(twice[:, :S], T.rope(x[:, :S], 2, 1e6),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(twice[:, S:], T.rope(x[:, S:], 2, 1e6),
                               rtol=1e-6, atol=1e-6)
    assert not np.allclose(twice[:, S:], T.rope(x, 2, 1e6)[:, S:], atol=1e-3)


def test_the_two_shares_routed_parts_add_up_to_the_uncut_layer():
    """The PROGRAM's expert layer on each of two shares of 4 of the 8
    experts, summed, is the REFERENCE's layer with all 8 held (there is no
    shared expert to count once): what a share leaves out is exactly what
    the other computes."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    h1 = jax.random.normal(ks[1], (2 * S, 64))
    scale = jax.random.uniform(ks[2], (64,), minval=0.5, maxval=1.5)
    want = reference.moe_part(h1, scale, whole["router"], whole["we_gate_up"],
                              whole["we_down"], 0, 2, 1e-6)
    parts = []
    for first in (0, 4):
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 4],
                     we_down=whole["we_down"][first:first + 4])
        y, aux = moe.dropless_moe_ffn(
            share, T.rms_norm(h1, scale, 1e-6), 2, rule=moe.TOP_K_SOFTMAX,
            act="silu", first_held=first)
        parts.append(y)
        # and the reference given the same share gives the same part
        np.testing.assert_allclose(y, reference.moe_part(
            h1, scale, share["router"], share["we_gate_up"],
            share["we_down"], first, 2, 1e-6), rtol=1e-5, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)
