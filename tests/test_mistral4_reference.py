"""The Mistral-Small-4 decoder through the normal path (``models/mistral4.py``
over ``parallel/transformer.py``'s latent attention and shared expert,
``parallel/moe.py``'s held-experts path and the flash kernels' packed causal
mode, in interpret mode) against the benchmark's plain float32 reference
(``benchmark/reference/mistral_small_4_119b.py``), on seeded weights at
``mistral4_tiny_config``: two layers, hidden 64, 4 heads of 96 + 32 = 128
with values of 128, latents of 32 and 16, YaRN by 8 over 16 original
positions under S = 64, 8 routed experts of width 32 of which this share
holds 2, top-2, a shared expert of width 48, vocab 256.

The tiny configuration computes in float32, so the tolerance is 1e-5 (the
two differ by accumulation order only)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import mistral_small_4_119b as reference
from paddle_tpu.kernels.flash_attention import packed_grid
from paddle_tpu.models import brumby, mistral4
from paddle_tpu.parallel import moe, transformer as T

B, S, TOL = 2, 64, 1e-5
ROPE = {"beta_fast": 4, "beta_slow": 0.5, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}
PUBLISHED_ROPE = dict(ROPE, beta_fast=32, beta_slow=1, factor=128,
                      original_max_position_embeddings=8192)
# the reference reads the published keys
MODEL = {"num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
         "qk_nope_head_dim": 96, "qk_rope_head_dim": 32, "v_head_dim": 128,
         "rms_norm_eps": 1e-6, "rope_interleave": True, "rope_parameters": ROPE,
         "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "num_experts_per_tok": 2, "n_routed_experts": 2,
         "moe_router_width": 8, "moe_first_expert_held": 2,
         "n_shared_experts": 1, "routed_scaling_factor": 1,
         "num_hidden_layers": 2}
NAMES = ("ln1_scale", "ln2_scale", "wq_a", "q_a_norm", "wq_b", "wkv_a",
         "kv_a_norm", "wkv_b", "wo", "router", "we_gate_up", "we_down",
         "ws_gate_up", "ws_down")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["params_layers/" + n for n in NAMES]


def _mechanism():
    cfg = mistral4.mistral4_tiny_config()
    assert cfg.latent and not cfg.per_position and cfg.layer_kinds == (
        (None, True),)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim) == (4, 4, 128, 96, 32, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (32, 16)
    assert cfg.rope_original_max < S and cfg.q_scale_beta == 0.1
    assert (cfg.n_experts, cfg.experts_here, cfg.first_expert,
            cfg.experts_per_token, cfg.shared_ffn_hidden) == (8, 2, 2, 2, 48)
    assert cfg.routing == moe.TOP_K_SOFTMAX and not cfg.tie_head
    assert T._packed_flash_blocks(cfg, 4, S, 4) == (16, 16)   # the kernels run
    # the blend is live: a plain pair, blended ones, wholly interpolated ones
    f = T.yarn_frequencies(cfg)
    plain = 10000.0 ** (-np.arange(16) / 16)
    assert f[0] == plain[0] and (f[3:] == plain[3:] / 8).all()
    assert (plain[1:3] / 8 < f[1:3]).all() and (f[1:3] < plain[1:3]).all()
    # the pairs wholly interpolated, first and last; the positions whose
    # query is scaled by more than 1: those from ``rope_original_max`` on
    assert (T.yarn_blend_range(cfg)[1], cfg.qk_rope_dim // 2 - 1) == (3, 15)
    assert max(S - cfg.rope_original_max, 0) == 48
    big = mistral4.mistral_small_4_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.head_dim,
            big.q_lora_rank, big.kv_lora_rank, big.qk_nope_dim,
            big.qk_rope_dim, big.v_head_dim, big.ffn_hidden,
            big.shared_ffn_hidden, big.n_experts, big.experts_here,
            big.experts_per_token, big.vocab_size, big.norm_eps) == (
        36, 4096, 32, 128, 1024, 256, 64, 64, 128, 2048, 2048, 128, 128, 4,
        131072, 1e-6)
    assert (T.yarn_blend_range(big)[1], big.qk_rope_dim // 2 - 1) == (25, 31)
    assert max(16384 - big.rope_original_max, 0) == 8192
    np.testing.assert_allclose(T.yarn_softmax_scale(big), 1.4852 ** 2,
                               rtol=1e-4)
    assert T.yarn_rotary_factor(big) == 1.0
    with pytest.raises(AssertionError):     # q.k width != v width: not yet
        mistral4.mistral4_tiny_config(v_head_dim=64)


def _shapes(both):
    layers = both.params["params_layers"]
    assert layers["wq_a"].shape == (2, 64, 32)
    assert layers["wq_b"].shape == (2, 32, 4 * 128)
    assert layers["wkv_a"].shape == (2, 64, 16 + 32)
    assert layers["wkv_b"].shape == (2, 16, 4 * (96 + 128))
    assert layers["wo"].shape == (2, 4 * 128, 64)
    assert layers["ws_gate_up"].shape == (2, 64, 96)
    assert layers["we_gate_up"].shape == (2, 2, 64, 64)
    assert layers["router"].shape == (2, 64, 8)


def _layer0(seed=4):
    cfg = mistral4.mistral4_tiny_config()
    params = T.init_transformer_params(jax.random.PRNGKey(seed), cfg)
    pl = jax.tree.map(lambda a: a[0], params["params_layers"])
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, 64))
    return cfg, pl, h


def test_the_rotated_key_is_one_vector_for_all_heads():
    """Each head's key ends in the same rotated 32 columns, which are
    ``wkv_a``'s last 32 rotated and nothing a head owns; its first 96 are
    the head's own and carry no position."""
    cfg, pl, h = _layer0()
    q, k, v = T._latent_qkv(pl, h, cfg)
    assert q.shape == k.shape == v.shape == (B, S, 4 * 128)
    k = np.asarray(k).reshape(B, S, 4, 128)
    for head in range(1, 4):
        np.testing.assert_array_equal(k[:, :, head, 96:], k[:, :, 0, 96:])
        assert np.abs(k[:, :, head, :96] - k[:, :, 0, :96]).max() > 1e-2
    # the position-free part does not move with the position
    moved = np.asarray(T._latent_qkv(pl, h, cfg, first=5)[1]).reshape(
        B, S, 4, 128)
    np.testing.assert_allclose(moved[..., :96], k[..., :96], atol=1e-6)
    assert np.abs(moved[..., 96:] - k[..., 96:]).max() > 1e-2
    # a norm of the pair is kept: a rotation
    kr = np.asarray(h @ pl["wkv_a"])[..., 16:]
    np.testing.assert_allclose(
        np.hypot(k[:, :, 0, 96::2], k[:, :, 0, 97::2]),
        np.hypot(kr[..., 0::2], kr[..., 1::2]), rtol=1e-4, atol=1e-6)


def test_the_columns_taken_apart_are_wkv_b_s_own_numbers():
    """``_latent_columns``: head i of the published ``[k_nope_i | v_i]``
    layout as the keys' matrix ``[k_nope_i | 0]`` a head and the values'
    ``v_i`` a head; the leaf keeps its layout."""
    cfg, pl, _ = _layer0()
    k_columns, v_columns = T._latent_columns(pl["wkv_b"], cfg)
    assert pl["wkv_b"].shape == (16, 4 * (96 + 128))
    assert k_columns.shape == v_columns.shape == (16, 4 * 128)
    w = np.asarray(pl["wkv_b"]).reshape(16, 4, 224)
    k_columns = np.asarray(k_columns).reshape(16, 4, 128)
    np.testing.assert_array_equal(k_columns[..., :96], w[..., :96])
    assert not k_columns[..., 96:].any()
    np.testing.assert_array_equal(
        np.asarray(v_columns).reshape(16, 4, 128), w[..., 96:])


def test_the_row_kernel_s_q_k_v_and_gradients_equal_the_lines(monkeypatch):
    """The tiny layer takes the row kernel (``tests/test_qk_rope_kernel.py::
    ENGAGED``); with the kernel refused the same call runs the ``rope_pairs``
    lines: the same q, k, v and the same gradients of the four chain leaves
    in their PUBLISHED column order, and of the rows."""
    from paddle_tpu.kernels import qk_rope

    cfg, pl, h = _layer0()
    w = jax.random.normal(jax.random.PRNGKey(9), (3, B, S, 512))

    def run(pl, h):
        out = T._latent_qkv(pl, h, cfg, 7)
        return sum(jnp.sum(a * b) for a, b in zip(out, w)), out

    assert qk_rope.supported((B, S, 512), 128, 4)
    (_, got), got_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(pl, h)
    monkeypatch.setattr(qk_rope, "supported", lambda *a: False)
    (_, want), want_grads = jax.value_and_grad(run, (0, 1), has_aux=True)(
        pl, h)
    for a, r in zip(got, want):
        np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "q_a_norm", "kv_a_norm"):
        a, r = got_grads[0][name], want_grads[0][name]
        assert a.shape == pl[name].shape and np.abs(r).max() > 0
        np.testing.assert_allclose(a, r, rtol=1e-4,
                                   atol=1e-5 * np.abs(r).max(), err_msg=name)
    np.testing.assert_allclose(got_grads[1], want_grads[1], rtol=1e-4,
                               atol=1e-4)


def test_rotation_is_of_adjacent_pairs():
    """``rope_pairs`` against complex multiplication of the pairs (2j, 2j +
    1) by e^(i pos f_j), and NOT the rotate-half convention's."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, S, 3, 32).astype("f4")
    f = T.yarn_frequencies(mistral4.mistral4_tiny_config())
    ang = np.arange(S)[:, None] * f[None]
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(1j * ang)[None, :, None]
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    got = np.asarray(T.rope_pairs(jnp.asarray(x), jnp.asarray(ang, "f4")))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    halves = (x[..., :16] + 1j * x[..., 16:]) * np.exp(1j * ang)[None, :, None]
    assert np.abs(got - np.concatenate([halves.real, halves.imag], -1)
                  ).max() > 0.1
    # position 0 is left as it is, and cos / sin take the factor
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    np.testing.assert_allclose(
        T.rope_pairs(jnp.asarray(x), jnp.asarray(ang, "f4"), 1.5),
        1.5 * want, rtol=1e-4, atol=1e-5)


def test_yarn_frequencies_against_the_closed_form():
    """At the published sizes: pairs 0..12 keep ``theta^(-2j/64)`` (they
    turn more than 32 times over 8,192 positions), pairs 25..31 are divided
    by the factor, a linear ramp between; program and reference agree."""
    big = mistral4.mistral_small_4_config()
    f = T.yarn_frequencies(big)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)

    def pair_of(rotations):
        return 64 * np.log(8192 / (rotations * 2 * np.pi)) / (
            2 * np.log(10000.0))

    lo, hi = int(np.floor(pair_of(32))), int(np.ceil(pair_of(1)))
    assert (lo, hi) == (12, 25)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(
        f, ramp * plain / 128 + (1 - ramp) * plain, rtol=1e-12)
    np.testing.assert_array_equal(f[:13], plain[:13])
    np.testing.assert_allclose(f[25:], plain[25:] / 128, rtol=1e-12)
    assert (np.diff(f) < 0).all()
    np.testing.assert_allclose(
        f, reference.yarn_frequencies(PUBLISHED_ROPE, 64), rtol=1e-12)
    np.testing.assert_array_equal(
        reference.yarn_frequencies(PUBLISHED_ROPE, 64, plain=True), plain)
    # without a factor: plain rotary positions
    np.testing.assert_array_equal(
        T.yarn_frequencies(mistral4.mistral_small_4_config(rope_factor=0.0)),
        plain)


def test_the_query_scale_steps_at_the_original_length():
    """``q`` of position p is ``mscale^2 * (1 + 0.1 ln(1 + p // 16))`` times
    the query without either; keys and values carry neither."""
    cfg, pl, h = _layer0()
    bare = mistral4.mistral4_tiny_config(
        q_scale_beta=0.0, rope_mscale=0.0, rope_mscale_all_dim=0.0)
    q, k, v = (np.asarray(a) for a in T._latent_qkv(pl, h, cfg))
    q0, k0, v0 = (np.asarray(a) for a in T._latent_qkv(pl, h, bare))
    np.testing.assert_array_equal(k, k0)
    np.testing.assert_array_equal(v, v0)
    m2 = (0.1 * np.log(8.0) + 1) ** 2
    want = m2 * (1 + 0.1 * np.log1p(np.arange(S) // 16))
    assert len(np.unique(want)) == 4
    np.testing.assert_allclose(q, q0 * want[None, :, None], rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("kernel,elements,width", [
    (True, 1 << 13, 32 + 16 + 32), (False, 1 << 18, 2 * 3 * 512)])
def test_a_block_of_positions_at_a_time_gives_the_same_numbers(
        monkeypatch, kernel, elements, width):
    """Past ROW_BLOCK_ELEMENTS the chains, the rotation and the scale run a
    block of positions at a time with the block's first position (traced,
    under ``lax.map`` and a checkpoint of the block's own): the same q, k, v
    and the same gradients, through the row kernel (whose backward holds
    the latents alone: a block is counted by their width) and through the
    ``rope_pairs`` lines (by q's and k's float32 width)."""
    from paddle_tpu.kernels import qk_rope

    cfg, pl, h = _layer0()
    w = jax.random.normal(jax.random.PRNGKey(9), (3, B, S, 512))
    if not kernel:
        monkeypatch.setattr(qk_rope, "supported", lambda *a: False)
    assert T._latent_fused(cfg, (B, S), 4) == kernel

    def run(pl, h):
        return sum(jnp.sum(a * b) for a, b in zip(T._qkv(pl, h, cfg, True),
                                                  w))

    assert T.row_block(S, B * width) == S
    whole = jax.value_and_grad(run, (0, 1))(pl, h)
    monkeypatch.setattr(T, "ROW_BLOCK_ELEMENTS", elements)
    assert T.row_block(S, B * width) == 8
    blocked = jax.value_and_grad(run, (0, 1))(pl, h)
    for a, b in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _layer_inputs():
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    whole = moe.init_dropless_moe_params(ks[0], 8, 64, 32)
    whole["router"] = whole["router"] * 3.0
    whole["ws_gate_up"] = jax.random.normal(ks[2], (64, 96)) / 8
    whole["ws_down"] = jax.random.normal(ks[3], (48, 64)) / 7
    return whole, jax.random.normal(ks[1], (S, 64))


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """The PROGRAM's FFN half of a layer on each of the four shares of 2
    routed experts: every share computes the shared expert, so the four
    routed parts summed, plus the shared expert counted ONCE, is the
    REFERENCE's layer with all 8 experts held."""
    whole, m = _layer_inputs()
    cfg = mistral4.mistral4_tiny_config()
    routed_want, _ = reference.moe_part(
        m, whole["router"], whole["we_gate_up"], whole["we_down"], 0, 2)
    shared_want = reference.shared_part(m, whole["ws_gate_up"],
                                        whole["ws_down"])
    def ffn_half(first):
        """``transformer_layer``'s second half on normed rows ``m``, for the
        share that holds experts [first, first + 2)."""
        share = dict(whole, we_gate_up=whole["we_gate_up"][first:first + 2],
                     we_down=whole["we_down"][first:first + 2])
        y, aux = moe.dropless_moe_ffn(share, m, 2, rule=moe.TOP_K_SOFTMAX,
                                      first_held=first)
        shared = T.gated_ffn({"w_gate_up": share["ws_gate_up"],
                              "w_down": share["ws_down"]}, m[None], cfg)[0]
        return y, shared, aux

    parts = [ffn_half(first) for first in range(0, 8, 2)]
    for first, (y, shared, aux) in zip(range(0, 8, 2), parts):
        np.testing.assert_allclose(shared, shared_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y, reference.moe_part(
            m, whole["router"], whole["we_gate_up"][first:first + 2],
            whole["we_down"][first:first + 2], first, 2)[0],
            rtol=1e-5, atol=1e-5)
    assert sum(int(p[2]["rows_held"]) for p in parts) == 2 * S
    assert all(float(jnp.abs(p[0]).max()) > 0 for p in parts)
    np.testing.assert_allclose(sum(p[0] for p in parts) + parts[0][1],
                               routed_want + shared_want, rtol=1e-5,
                               atol=1e-5)
    # ... and counted four times it is not
    assert np.abs(sum(p[0] + p[1] for p in parts)
                  - (routed_want + shared_want)).max() > 0.1


def test_the_layer_adds_the_shared_expert_to_the_routed_result():
    """``transformer_layer`` itself: with the shared expert's down
    projection zeroed the layer gives the routed result alone, and the
    difference is the shared expert on the same normed rows."""
    cfg, pl, h = _layer0()
    x = h
    out, aux = T.transformer_layer(pl, x, cfg)
    bare, _ = T.transformer_layer(
        dict(pl, ws_down=jnp.zeros_like(pl["ws_down"])), x, cfg)
    assert "rows_held" in aux
    # the rows the FFN half reads: the stream after attention, normed
    attn_out, _ = T.transformer_layer(
        dict(pl, ws_down=jnp.zeros_like(pl["ws_down"]),
             we_down=jnp.zeros_like(pl["we_down"])), x, cfg)
    m = T.rms_norm(attn_out, pl["ln2_scale"], cfg.norm_eps)
    want = reference.shared_part(m[0], pl["ws_gate_up"], pl["ws_down"])
    np.testing.assert_allclose((out - bare)[0], want, rtol=1e-4, atol=1e-5)


def test_the_witness_reads_both_sides_of_the_original_length(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    the trainer's own forward at the witness's positions against the
    reference's logits; the statistic is the larger group's third
    quartile."""
    params, ids, program, model = witnessed
    groups = reference.witness_groups(S)
    assert groups["edge"].tolist() == [
        at + i for at in (16, 32, 48) for i in range(-4, 4)] + [60, 61, 62, 63]
    assert not set(groups["edge"]) & set(groups["spread"])
    big = reference.witness_groups(16384)
    assert big["edge"].tolist() == list(range(8184, 8200)) + list(
        range(16376, 16384))
    assert len(big["spread"]) == 256 and (big["spread"] < 8192).sum() == 128
    each = reference.position_errors(program, params, {"ids": ids}, model)
    assert each.shape == (S,) and each.max() < TOL
    parts = reference.group_errors(program, params, {"ids": ids}, model)
    assert reference.logits_error(program, params, {"ids": ids}, model) \
        == max(parts.values())
    assert parts["edge"] == np.quantile(each[:28], 0.75)


def _counters(trained):
    cfg, ids = trained.scan.cfg, trained.batches[0]["ids"]
    # batches x tokens x top-2 x layers
    pairs = 2 * ids.size * cfg.experts_per_token * cfg.moe_layers
    assert pairs == 2 * B * S * 2 * 2
    held = trained.value("monitor.train.moe_rows_held")
    assert 0 < held < pairs
    assert trained.value("monitor.train.moe_held_rows_share") == held / pairs
    # what a layer's keys and values come from (the latent and the
    # shared rotary key) beside what the flash kernels read (every
    # head's key and value), bytes a token
    itemsize = cfg.jdtype.itemsize
    assert (cfg.kv_lora_rank + cfg.qk_rope_dim) * itemsize == (16 + 32) * 4
    assert cfg.n_heads * (cfg.head_dim + cfg.v_head_dim) * itemsize == \
        4 * 256 * 4
    assert (T.yarn_blend_range(cfg)[1], cfg.qk_rope_dim // 2 - 1) == (3, 15)
    assert max(S - cfg.rope_original_max, 0) == 48
    # a layer's grid: the causal triangle's 10 blocks a sequence, its four
    # heads in one step (PR 70)
    assert packed_grid(
        B, S, cfg.n_heads, cfg.head_dim,
        *T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads),
        itemsize=itemsize, n_kv_heads=cfg.kv_heads,
        causal=True) == (4, 20)


def _specs(specs):
    specs = specs["params_layers"]
    assert specs["wkv_b"] == specs["ws_down"] == T.P(None, None, None)
    assert specs["kv_a_norm"] == T.P(None, None)


CASE = H.Case(
    "mistral4", reference, MODEL, tuple(LEAVES),
    # a router steep enough that the weights are not all one half
    gain=H.steep("router"),
    mechanism=_mechanism, spec_configs=({},), bfloat16=True,
    # 4 row blocks of 64; a head at a time; chunks of 100, 100, 56; an expert
    # at a time; the shared expert's columns as 20, 20, 8
    pieces={"QUERY_BLOCK": 16, "HEAD_GROUP": 1, "VOCAB_CHUNK": 100,
            "EXPERT_GROUP": 1, "DENSE_CHUNK": 20},
    # a trainer that holds HALF the experts (4 of 8, the second half): with 2
    # of 8 held, many positions meet no held expert in either layer and a
    # routing fault does not touch them; a bf16 router moves the witness by a
    # hundred times the sound difference (it changes a weight's last bits,
    # and who is chosen at few tokens)
    witness=H.Witness(cfg={"experts_held": 4, "first_expert": 4},
                      model={"n_routed_experts": 4,
                             "moe_first_expert_held": 4},
                      floors={"bfloat16_router": 1e2}),
    steps=2, counters=True,
    also={"leaves": _shapes, "specs": _specs, "counters": _counters})
globals().update(H.common(CASE))


def test_the_new_scopes_hold_their_instructions_and_attention_none(trained):
    got = trained.scopes()
    for scope in ("latent_attention", "shared_expert", "moe", "router",
                  "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    for scope in ("latent_attention", "shared_expert"):
        assert ("recompute", scope) in got, scope
    assert not {s for _, s in got} & {"attention", "mlp"}


def test_brumby_s_tree_and_seeds_are_unchanged():
    """``tests/test_brumby_reference.py`` holds the four older trees' seeded
    leaves to the commit before Brumby; this is Brumby's own, taken on the
    parent commit: the new leaves took no fold of the others' keys."""
    params = T.init_transformer_params(jax.random.PRNGKey(7),
                                       brumby.brumby_tiny_config())
    flat = jax.tree_util.tree_leaves_with_path(params)
    got = {jax.tree_util.keystr(p): float(np.float64(
        np.abs(np.asarray(a, np.float64)).sum())) for p, a in flat}
    assert got == pytest.approx({
        "['lm_head']": 1632.9334373973475,
        "['lnf_scale']": 64.0,
        "['params_layers']['p0']['k_norm']": 256.0,
        "['params_layers']['p0']['ln1_scale']": 128.0,
        "['params_layers']['p0']['ln2_scale']": 128.0,
        "['params_layers']['p0']['q_norm']": 256.0,
        "['params_layers']['p0']['w_down']": 997.1294860520356,
        "['params_layers']['p0']['w_gate_up']": 2438.6383084782965,
        "['params_layers']['p0']['wg']": 25.373491836420726,
        "['params_layers']['p0']['wk']": 3280.5871752061794,
        "['params_layers']['p0']['wo']": 3664.777847672638,
        "['params_layers']['p0']['wq']": 16305.01196900601,
        "['params_layers']['p0']['wv']": 3271.7729206716544,
        "['tok_emb']": 1633.9137341165888}, rel=1e-6)
