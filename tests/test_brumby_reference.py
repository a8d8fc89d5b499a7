"""The Brumby decoder through the normal path (``models/brumby.py`` over
``parallel/transformer.py``'s RETENTION position, the gated FFN of a stack
without experts and ``kernels/power_retention.py``'s carried-state kernels,
in interpret mode) against the benchmark's plain float32 reference
(``benchmark/reference/brumby_14b.py``, the score-matrix form), on seeded
weights at ``brumby_tiny_config``: two layers, hidden 64, 10 query heads on 2
key/value heads of 128 (a group of 5), chunks of 16 under S = 64 (4 chunks),
FFN width 96, vocab 256.

The tiny configuration computes in float32, so the tolerance is 1e-5 on the
loss (the two differ by accumulation order only) and three times that on a
single logit row or gradient element, against the largest of its leaf: the
quotient of two sums of up to 64 squared products rounds more than a
softmax does."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import brumby_14b as reference
from paddle_tpu.kernels import power_retention as pr
from paddle_tpu.models import bert, brumby, lfm2, olmoe, smallthinker
from paddle_tpu.parallel import decoder, transformer as T

B, S, TOL = 2, 64, 1e-5
EACH = 3 * TOL         # one logit row, one gradient element
# the reference reads the published keys and the operator's assumed sizes
MODEL = {"num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 128,
         "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
         "retention_degree": 2, "retention_eps": 1e-6, "retention_chunk": 16}
NAMES = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
         "wg", "w_gate_up", "w_down")
LEAVES = ["tok_emb", "lm_head", "lnf_scale"] \
    + ["params_layers/p0/" + n for n in NAMES]


def _mechanism():
    cfg = brumby.brumby_tiny_config()
    assert cfg.layer_kinds == (T.RETENTION,) and cfg.prefix_kinds == ()
    assert cfg.per_position and cfg.n_periods == 2 and cfg.moe_layers == 0
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (10, 2, 128)
    assert cfg.qk_norm == "head" and not cfg.tie_head and not cfg.n_experts
    assert S // min(cfg.retention_chunk, S) == 4     # chunks a sequence
    assert pr.supported(cfg.head_dim, S, cfg.retention_chunk)
    big = brumby.brumby_14b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads, big.head_dim,
            big.dense_ffn_hidden, big.vocab_size, big.rope_theta,
            big.norm_eps, big.retention_chunk) == (
        40, 5120, 40, 8, 128, 17408, 151936, 1e6, 1e-6, 1024)
    assert 16384 // min(big.retention_chunk, 16384) == 16
    # the state a layer carries: 8 heads x 8,320 x 128 float32, in MB
    np.testing.assert_allclose(
        big.kv_heads * pr.STATE_COLUMNS * big.head_dim * 4 / 1e6, 34.08,
        rtol=1e-3)
    assert pr.STATE_COLUMNS == 8320 and pr.DIAGONALS == 65


def _shapes(both):
    params = both.params
    assert params["params_layers"]["p0"]["wg"].shape == (2, 64, 2)
    assert params["params_layers"]["p0"]["wg"].dtype == np.float32
    assert params["params_layers"]["p0"]["q_norm"].shape == (2, 128)


# ---------------------------------------------------------------------------
# the operator alone, against the score-matrix form
# ---------------------------------------------------------------------------

def _score_matrix_form(q, k, v, g, n_heads, n_kv):
    """``reference._retain`` a key/value head at a time: o [B, S, H * dh]."""
    b, s, _ = q.shape
    group = n_heads // n_kv
    q, k, v = (a.reshape(b, s, n, 128) for a, n in
               ((q, n_heads), (k, n_kv), (v, n_kv)))
    with jax.default_matmul_precision("highest"):
        return jnp.stack([jnp.concatenate([reference._retain(
            q[i, :, h * group:(h + 1) * group], k[i, :, h], v[i, :, h],
            g[i, :, h], 1e-6, s, None) for h in range(n_kv)], axis=1)
            for i in range(b)]).reshape(b, s, -1)


def _operands(gates, seq=S, group=5):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    q = jax.random.normal(ks[0], (1, seq, 2 * group * 128))
    k = jax.random.normal(ks[1], (1, seq, 2 * 128))
    v = jax.random.normal(ks[2], (1, seq, 2 * 128))
    g = -0.01 * jax.random.uniform(ks[3], (1, seq, 2)) if gates == "near one" \
        else jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, seq, 2)))
    return q, k, v, g, jax.random.normal(ks[4], q.shape)


def _program_and_plain(chunk, group):
    def program(*a):
        return pr.power_retention(*a, chunk=chunk)

    def plain(*a):
        return _score_matrix_form(*a, 2 * group, 2)

    return program, plain


def _value_and_grads(f, q, k, v, g, w):
    return (f(q, k, v, g),) + jax.grad(
        lambda *a: jnp.sum(f(*a) * w), (0, 1, 2, 3))(q, k, v, g)


@pytest.mark.parametrize("group", [1, 5])
@pytest.mark.parametrize("chunk", [8, 32])
@pytest.mark.parametrize("gates", ["near one", "seeded"])
def test_the_kernels_equal_the_score_matrix_form(gates, chunk, group):
    """Output and the gradients of q, k, v and the log-decay, with decays
    in [-0.01, 0) (a state that reaches across every chunk of the sequence)
    and from a seeded gate (mean one half), at two chunk lengths (8 and 2
    chunks) and with one and five query heads a key/value head (the five
    stacked along rows in one grid step): the same numbers beyond
    rounding."""
    q, k, v, g, w = _operands(gates, group=group)
    program, plain = _program_and_plain(chunk, group)

    got, want = program(q, k, v, g), plain(q, k, v, g)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=TOL * float(jnp.abs(want).max()))
    got = jax.grad(lambda *a: jnp.sum(program(*a) * w), (0, 1, 2, 3))(
        q, k, v, g)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), (0, 1, 2, 3))(
        q, k, v, g)
    for name, a, b in zip("qkvg", got, want):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=TOL * float(jnp.abs(b).max()),
            err_msg=name)


def test_a_group_that_does_not_fit_goes_in_parts_to_the_same_numbers(
        monkeypatch):
    """The kernels' VMEM rule: a group whose stacked rows would pass
    ``VMEM_LIMIT`` is swept in the largest divisor of its heads that fits,
    on a fourth grid axis; with the limit patched under the five stacked
    heads' need the group goes a head at a time, to the whole group's
    numbers, and ``state_sweeps`` (the trainer's gauge) counts a sweep a
    query head and chunk where it counted one a key/value head and chunk."""
    q, k, v, g, w = _operands("near one")
    program, _ = _program_and_plain(16, 5)
    assert pr.sweep_heads(5, 16, 4) == 5
    assert pr.state_sweeps(10, 2, S, 16, 4) == 2 * 4
    whole = _value_and_grads(program, q, k, v, g, w)
    monkeypatch.setattr(pr, "VMEM_LIMIT", pr._step_vmem_bytes(5, 16, 4) - 1)
    assert pr.sweep_heads(5, 16, 4) == 1
    assert pr.state_sweeps(10, 2, S, 16, 4) == 2 * 4 * 5
    grids = [tuple(int(n) for n in grid.split(",")) for grid in re.findall(
        r"grid=\(([\d, ]*)\)", str(jax.make_jaxpr(
            lambda *a: _value_and_grads(program, *a))(q, k, v, g, w)))]
    assert grids and set(grids) == {(1, 2, 4, 5)}
    in_parts = _value_and_grads(program, q, k, v, g, w)
    for name, a, b in zip("oqkvg", in_parts, whole):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=name)
    cfg = brumby.brumby_tiny_config()
    assert pr.state_sweeps(cfg.n_heads, cfg.kv_heads, S,
                           min(cfg.retention_chunk, S),
                           cfg.jdtype.itemsize) == 2 * 4 * 5
    # six heads a group go in threes where two such steps fit and six do not
    monkeypatch.setattr(pr, "VMEM_LIMIT", pr._step_vmem_bytes(3, 16, 4))
    assert pr.sweep_heads(6, 16, 4) == 3


def test_the_in_chunk_block_goes_in_row_blocks_to_the_same_numbers(
        monkeypatch):
    """At the cell's shape a chunk's own block goes 256 query rows of every
    head at a time, the stacked arrays holding one row block of every head
    after another; at these sizes a chunk is one row block.  With
    ``ROW_BLOCK`` patched to 8 the chunk of 32 goes in four row blocks, to
    the one block's numbers."""
    q, k, v, g, w = _operands("seeded")
    program, _ = _program_and_plain(32, 5)
    whole = _value_and_grads(program, q, k, v, g, w)
    monkeypatch.setattr(pr, "ROW_BLOCK", 8)
    in_blocks = _value_and_grads(program, q, k, v, g, w)
    for name, a, b in zip("oqkvg", in_blocks, whole):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.abs(b).max()),
            err_msg=name)


def test_the_state_carries_across_every_chunk_where_the_gates_are_near_one():
    """With decays near one the first token still weighs at the last: moving
    v_0 moves the last chunk's output, through three folds of the state."""
    q, k, v, g, _ = _operands("near one")
    out = pr.power_retention(q, k, v, g, chunk=16)
    moved = pr.power_retention(q, k, v.at[:, 0].add(1.0), g, chunk=16)
    assert float(jnp.abs(moved - out)[:, 48:].max()) > 1e-3
    # and retention is causal: nothing before a change moves
    late = pr.power_retention(q, k, v.at[:, 40].add(1.0), g, chunk=16)
    np.testing.assert_array_equal(late[:, :40], out[:, :40])


def test_the_feature_tiles_multiply_out_to_the_squared_product():
    """65 wrapped diagonals, weighted 1, 2, ..., 2, 1, are the 8,256
    distinct products with the off-diagonal ones twice."""
    x, y = np.random.RandomState(0).randn(2, 128)
    total = sum((1.0 if d in (0, 64) else 2.0)
                * np.dot(x * np.roll(x, d), y * np.roll(y, d))
                for d in range(pr.DIAGONALS))
    np.testing.assert_allclose(total, np.dot(x, y) ** 2, rtol=1e-10)


def test_the_row_blocked_ffn_equals_the_unblocked_one(monkeypatch):
    cfg = brumby.brumby_tiny_config()
    pl = jax.tree.map(lambda a: a[0], T._position_leaves(
        jax.random.PRNGKey(2), cfg, T.RETENTION, 1, True))
    h = jax.random.normal(jax.random.PRNGKey(3), (B, S, 64))
    w = jax.random.normal(jax.random.PRNGKey(4), h.shape)
    assert T.row_block(S, B * 96) == S                      # one block
    # from the shapes: the cell's FFN runs 1,024 rows at a time, and no
    # FFN of another configuration's cell is blocked
    assert T.row_block(16384, 17408) == 1024
    assert T.row_block(16384, 2 * 7168) == 2048             # its projections
    assert T.row_block(8192, 2 * 7168) == 8192              # lfm2's prefix
    whole = jax.value_and_grad(
        lambda pl, h: jnp.sum(T.gated_ffn(pl, h, cfg) * w), (0, 1))(pl, h)
    monkeypatch.setattr(T, "ROW_BLOCK_ELEMENTS", 96 * 64)
    assert T.row_block(S, B * 96) == 8                      # eight blocks
    blocked = jax.value_and_grad(
        lambda pl, h: jnp.sum(T.gated_ffn(pl, h, cfg) * w), (0, 1))(pl, h)
    for a, b in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_eight_vocabulary_slices_logits_are_the_uncut_model_s_columns():
    """A chip's slice of the vocabulary is a smaller vocabulary: with the
    head's rows [lo, lo + V/8) the logits are those columns of the uncut
    model's, for ids inside the slice."""
    ids = jnp.asarray(H.ids(CASE, seed=4)[0] % 32)

    def logits(cfg):
        return jax.jit(lambda p: T.head_logits(
            p, decoder.forward(p, ids, cfg)[0], cfg))

    uncut, cut = (brumby.brumby_tiny_config(vocab_size=v) for v in (256, 32))
    params = T.init_transformer_params(jax.random.PRNGKey(3), uncut)
    whole, share = np.asarray(logits(uncut)(params)), logits(cut)
    for lo in range(0, 256, 32):
        got = share(dict(params, lm_head=params["lm_head"][lo:lo + 32],
                         tok_emb=params["tok_emb"][:32]))
        np.testing.assert_allclose(got, whole[..., lo:lo + 32], rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("config", [
    bert.bert_tiny_config, olmoe.olmoe_tiny_config,
    smallthinker.smallthinker_tiny_config, lfm2.lfm2_tiny_config])
def test_the_other_configurations_trees_and_seeds_are_unchanged(config):
    """The RETENTION kind and the gated FFN of a stack without experts took
    nothing from the four transformers the benchmark holds: the same
    leaves, and the same seeded numbers (a digest of every leaf held in
    ``SEEDED``, taken on the parent commit)."""
    params = T.init_transformer_params(jax.random.PRNGKey(7), config())
    flat = jax.tree_util.tree_leaves_with_path(params)
    got = {jax.tree_util.keystr(p): float(np.float64(
        np.abs(np.asarray(a, np.float64)).sum())) for p, a in flat}
    assert got == pytest.approx(SEEDED[config.__name__], rel=1e-6)


# every leaf's sum of magnitudes at PRNGKey(7), taken on the parent commit
SEEDED = {'bert_tiny_config': {"['lnf_bias']": 0.0,
                      "['lnf_scale']": 32.0,
                      "['params_layers']['b1']": 0.0,
                      "['params_layers']['b2']": 0.0,
                      "['params_layers']['bo']": 0.0,
                      "['params_layers']['bqkv']": 0.0,
                      "['params_layers']['ln1_bias']": 0.0,
                      "['params_layers']['ln1_scale']": 128.0,
                      "['params_layers']['ln2_bias']": 0.0,
                      "['params_layers']['ln2_scale']": 128.0,
                      "['params_layers']['w1']": 1152.8355756563942,
                      "['params_layers']['w2']": 808.2289385568729,
                      "['params_layers']['wk']": 592.1522415721083,
                      "['params_layers']['wo']": 574.7196429537144,
                      "['params_layers']['wq']": 588.4103693639227,
                      "['params_layers']['wv']": 579.7487703325514,
                      "['pos_emb']": 143.66492584527805,
                      "['tok_emb']": 569.3203954972032},
 'lfm2_tiny_config': {"['lnf_scale']": 64.0,
                      "['params_layers']['p0']['k_norm']": 64.0,
                      "['params_layers']['p0']['ln1_scale']": 64.0,
                      "['params_layers']['p0']['ln2_scale']": 64.0,
                      "['params_layers']['p0']['q_norm']": 64.0,
                      "['params_layers']['p0']['router']": 54.1657983098703,
                      "['params_layers']['p0']['we_down']": 587.6754246161472,
                      "['params_layers']['p0']['we_gate_up']": 823.1441512249457,
                      "['params_layers']['p0']['wk']": 816.5909004715668,
                      "['params_layers']['p0']['wo']": 822.264226873272,
                      "['params_layers']['p0']['wq']": 1635.71336963069,
                      "['params_layers']['p0']['wv']": 822.4447295245268,
                      "['params_layers']['p1']['conv_in']": 1227.8499064550597,
                      "['params_layers']['p1']['conv_out']": 405.60675256537706,
                      "['params_layers']['p1']['conv_w']": 92.46371063939296,
                      "['params_layers']['p1']['ln1_scale']": 64.0,
                      "['params_layers']['p1']['ln2_scale']": 64.0,
                      "['params_layers']['p1']['router']": 52.07485518039903,
                      "['params_layers']['p1']['we_down']": 588.8123617785568,
                      "['params_layers']['p1']['we_gate_up']": 827.2239650608913,
                      "['params_layers']['p2']['conv_in']": 1215.3631965017703,
                      "['params_layers']['p2']['conv_out']": 401.857793078394,
                      "['params_layers']['p2']['conv_w']": 83.68283341638744,
                      "['params_layers']['p2']['ln1_scale']": 64.0,
                      "['params_layers']['p2']['ln2_scale']": 64.0,
                      "['params_layers']['p2']['router']": 52.60504949082315,
                      "['params_layers']['p2']['we_down']": 572.210527533156,
                      "['params_layers']['p2']['we_gate_up']": 824.212584609777,
                      "['params_layers']['p3']['conv_in']": 1214.78922535883,
                      "['params_layers']['p3']['conv_out']": 414.4740955226516,
                      "['params_layers']['p3']['conv_w']": 95.52531716157682,
                      "['params_layers']['p3']['ln1_scale']": 64.0,
                      "['params_layers']['p3']['ln2_scale']": 64.0,
                      "['params_layers']['p3']['router']": 52.70977442455478,
                      "['params_layers']['p3']['we_down']": 570.0817819327187,
                      "['params_layers']['p3']['we_gate_up']": 806.7620937885613,
                      "['prefix_layers']['l0']['conv_in']": 1224.633957261458,
                      "['prefix_layers']['l0']['conv_out']": 418.40880030640847,
                      "['prefix_layers']['l0']['conv_w']": 82.72618536796654,
                      "['prefix_layers']['l0']['ln1_scale']": 64.0,
                      "['prefix_layers']['l0']['ln2_scale']": 64.0,
                      "['prefix_layers']['l0']['w_down']": 499.64167449623346,
                      "['prefix_layers']['l0']['w_gate_up']": 1232.4543042174964,
                      "['router_bias']": 1.8799528190866113,
                      "['tok_emb']": 1633.9137341165888},
 'olmoe_tiny_config': {"['lm_head']": 1632.9334373973475,
                       "['lnf_scale']": 64.0,
                       "['params_layers']['k_norm']": 128.0,
                       "['params_layers']['ln1_scale']": 128.0,
                       "['params_layers']['ln2_scale']": 128.0,
                       "['params_layers']['q_norm']": 128.0,
                       "['params_layers']['router']": 103.56748182419688,
                       "['params_layers']['we_down']": 4618.478713639468,
                       "['params_layers']['we_gate_up']": 6546.293675803162,
                       "['params_layers']['wk']": 818.7573912261068,
                       "['params_layers']['wo']": 809.5457860952924,
                       "['params_layers']['wq']": 823.2149566229631,
                       "['params_layers']['wv']": 823.1931161046712,
                       "['tok_emb']": 1633.9137341165888},
 'smallthinker_tiny_config': {"['lm_head']": 1632.9334373973475,
                              "['lnf_scale']": 64.0,
                              "['params_layers']['ln1_scale']": 256.0,
                              "['params_layers']['ln2_scale']": 256.0,
                              "['params_layers']['router']": 204.00814175308915,
                              "['params_layers']['we_down']": 2296.916480960748,
                              "['params_layers']['we_gate_up']": 3284.987979393266,
                              "['params_layers']['wk']": 6560.700681847619,
                              "['params_layers']['wo']": 5651.161562121702,
                              "['params_layers']['wq']": 19613.929149552085,
                              "['params_layers']['wv']": 6524.984493576052,
                              "['tok_emb']": 13071.30987293271}}


def test_the_witness_reads_both_groups(witnessed):
    """What ``benchmark/drivers/train_scan_witnessed.py`` checks on the chip:
    the trainer's own forward at the witness's positions against the
    reference's logits; the statistic is the larger group's third
    quartile."""
    params, ids, program, _ = witnessed
    groups = reference.witness_groups(S)
    assert groups["edge"].tolist() == [
        at + i for at in (16, 32, 48) for i in range(8)]
    assert not set(groups["edge"]) & set(groups["spread"])
    big = reference.witness_groups(16384)
    assert big["edge"][:9].tolist() == list(range(2048, 2056)) + [4096]
    assert len(big["edge"]) == 56 and len(big["spread"]) == 256
    each = reference.position_errors(program, params, {"ids": ids}, MODEL)
    assert each.shape == (S,) and each.max() < EACH
    parts = reference.group_errors(program, params, {"ids": ids}, MODEL)
    assert reference.logits_error(program, params, {"ids": ids}, MODEL) \
        == max(parts.values())
    per = each.reshape(1, -1)
    assert parts["edge"] == np.quantile(per[:, :24], 0.75)


def _edge(args, fault):
    """The three faults of the carried state show in the ``edge`` group."""
    if fault in ("state_dropped_at_chunk_edges", "state_read_undecayed",
                 "sqrt2_left_out_of_state"):
        assert reference.group_errors(*args, faults=(fault,))["edge"] \
            > 1e3 * TOL


def _specs(specs):
    assert specs["params_layers"]["p0"]["wg"] == T.P(None, None, None)


CASE = H.Case(
    "brumby", reference, MODEL, tuple(LEAVES), each=EACH,
    # a gate projection steep enough that the decays differ from token to
    # token
    gain=H.steep("wg"),
    mechanism=_mechanism, spec_configs=({},), bfloat16=True,
    # 4 row blocks of 64; chunks of 100, 100, 56; the FFN's 40, 40, 16
    pieces={"QUERY_BLOCK": 16, "VOCAB_CHUNK": 100, "DENSE_CHUNK": 40},
    # ``both``'s trainer and weights, on ONE sequence (the cell's batch)
    witness=H.Witness(), steps=2,
    trained_cfg={"remat": True, "n_layers": 1},
    also={"leaves": _shapes, "specs": _specs, "fault": _edge})
globals().update(H.common(CASE))


def test_gauges_only_under_a_monitor_session(trained):
    cfg, chunk = trained.scan.cfg, min(trained.scan.cfg.retention_chunk, S)
    assert S // chunk == 4
    # a sweep of the state's tiles a key/value head and chunk: the
    # five heads of a group ride one grid step
    assert pr.state_sweeps(cfg.n_heads, cfg.kv_heads, S, chunk,
                           cfg.jdtype.itemsize) == 8
    np.testing.assert_allclose(
        cfg.kv_heads * pr.STATE_COLUMNS * cfg.head_dim * 4 / 1e6,
        2 * 8320 * 128 * 4 / 1e6)
    # seeded gates: sigmoid of a unit-scale projection, mean one half
    assert 0.4 < trained.value("monitor.train.retention_gate_mean") < 0.6


def test_the_retention_s_instructions_are_under_their_scope(trained):
    got = trained.scopes()
    for scope in ("retention", "mlp", "layer_norm", "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
    assert ("recompute", "retention") in got
