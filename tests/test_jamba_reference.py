"""The Jamba decoder through the normal path (``models/jamba.py`` over
``parallel/transformer.py``'s MAMBA position beside position-free
multi-query attention, the run-scan of a period's runs, the gated FFN of a
stack without experts and ``kernels/selective_scan.py``'s kernels, in
interpret mode) against the benchmark's plain float32 reference
(``benchmark/reference/jamba2_3b.py``, the per-token scan), on seeded
weights at ``jamba_tiny_config``: two periods of four layers, attention at
offset 2 (runs of 2, 1 and 1 layers), hidden 64, 5 query heads on ONE
key/value head of 128, an inner width of 128, 16 state cells, 4 taps, rank
8, chunks of 16 under S = 64 (4 chunks), FFN width 96, vocab 256, tied head.

What the tiny configuration keeps of the published one: every leaf and
every line of the mixer, the inner norms, a period with Mamba runs of
different lengths on both sides of the attention layer, multi-query
attention without positions, the tied head, the state carried over three
chunk edges.  What it drops: the period's length (14, attention at 7) and
the widths.

The tiny configuration computes in float32, so the tolerance is 1e-5 on the
loss (the two differ by accumulation order only) and three times that on a
single logit row or gradient element, against the largest of its leaf."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_reference as H
from benchmark.reference import jamba2_3b as reference
from paddle_tpu import monitor
from paddle_tpu.kernels import selective_scan as ss
from paddle_tpu.kernels.flash_attention import packed_grid
from paddle_tpu.models import jamba
from paddle_tpu.monitor import devscope
from paddle_tpu.parallel import decoder, transformer as T

B, S, TOL = 2, 64, 1e-5
EACH = 3 * TOL         # one logit row, one gradient element
# the reference reads the published keys and the scan's chunk
MODEL = {"num_attention_heads": 5, "num_key_value_heads": 1,
         "num_hidden_layers": 8, "attn_layer_period": 4,
         "attn_layer_offset": 2, "num_experts": 1, "rms_norm_eps": 1e-6,
         "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_d_conv": 4,
         "mamba_expand": 2, "tie_word_embeddings": True, "scan_chunk": 16}
FFN = ("ln1_scale", "ln2_scale", "w_gate_up", "w_down")
LEAVES = ["tok_emb", "lnf_scale"] \
    + ["params_layers/%s/%s" % (run, n) for run in ("r0", "r2")
       for n in FFN + reference.MAMBA_LEAVES] \
    + ["params_layers/r1/" + n for n in FFN + reference.ATTENTION_LEAVES]


def _mechanism():
    cfg = jamba.jamba_tiny_config()
    attention = (None, False)
    assert cfg.layer_kinds == (T.MAMBA, T.MAMBA, attention, T.MAMBA)
    assert cfg.runs == ((0, T.MAMBA, 2), (2, attention, 1), (3, T.MAMBA, 1))
    assert cfg.per_position and cfg.n_periods == 2 and cfg.positions is None
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (5, 1, 128)
    assert cfg.tie_head and not cfg.n_experts and not cfg.qk_norm
    assert ss.supported((B, S, cfg.d_inner), cfg.d_state, cfg.scan_chunk)
    big = jamba.jamba2_3b_config()
    assert (big.n_layers, big.hidden, big.n_heads, big.kv_heads, big.head_dim,
            big.dense_ffn_hidden, big.vocab_size, big.norm_eps, big.d_inner,
            big.d_state, big.d_conv, big.dt_rank) == (
        28, 2560, 20, 1, 128, 8192, 65536, 1e-6, 5120, 16, 4, 160)
    assert big.n_periods == 2 and [r[2] for r in big.runs] == [7, 1, 6]
    assert big.layer_kinds[7] == attention and big.layer_kinds.count(
        T.MAMBA) == 13
    assert ss.supported((1, 8192, big.d_inner), big.d_state, big.scan_chunk)


def _floats(both):
    floats = {"a_log", "d_skip", "b_dt", "dt_norm", "b_norm", "c_norm"}
    for p, a in jax.tree_util.tree_leaves_with_path(both.params):
        if p[-1].key in floats:
            assert a.dtype == np.float32, p


CASE = H.Case(
    "jamba", reference, MODEL, tuple(LEAVES), each=EACH,
    # the norm scales, the skip and the rates off their seeds
    off_one=("scale", "_norm", "d_skip", "a_log"), mechanism=_mechanism,
    also={"leaves": _floats})
globals().update(H.common(CASE))


def test_logits_at_reads_the_step_s_own_forward(both):
    _, params, ids, _, _ = both
    tr = H.at_weights(both.tr, params)
    at = reference.witness_positions(S)
    got = np.asarray(tr.logits_at(ids, at))
    assert reference.logits_error(got, params, {"ids": ids}, MODEL) < EACH
    groups = reference.witness_groups(S)
    # the eight tokens past each multiple of the tiny chunk, then the spread
    assert list(groups["edge"][:9]) == list(range(16, 24)) + [32]
    assert not set(groups["edge"]) & set(groups["spread"])


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_fault_moves_the_reference_s_logits(both, fault):
    """At the tiny size, in float32: a fault put into the reference moves
    the witnessed logits by far more than the comparison's tolerance (the
    precision faults by more than float32 rounds)."""
    _, params, ids, _, _ = both
    # the first period alone: half the layers to walk a fault
    model = dict(MODEL, num_hidden_layers=4)
    params = dict(params, params_layers=jax.tree.map(
        lambda a: a[:1], params["params_layers"]))
    batch = {"ids": ids[:1]}
    sound = reference.logits(params, batch, model)
    err = reference.logits_error(sound, params, batch, model,
                                 faults=(fault,))
    # without its softplus a step size is negative and the state overflows:
    # no number is a failed comparison too
    assert not err <= (100 if "bfloat16" in fault else 1000) * TOL, (fault,
                                                                     err)


# --- the kernels against the per-token scan --------------------------------

def _operands(b, s, d, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x, z, w = (jax.random.normal(k, (b, s, d)) for k in (ks[0], ks[1], ks[7]))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, s, d)) - 2.0)
    bmat, cmat = (jax.random.normal(k, (b, s, n)) for k in (ks[3], ks[4]))
    a = -jnp.exp(0.5 * jax.random.normal(ks[5], (d, n)))
    return (x, dt, bmat, cmat, z, a, jax.random.normal(ks[6], (d,))), w


@pytest.fixture(scope="module")
def scan_reference():
    args, w = _operands(2, 48, 256, 16)
    out = ss.selective_scan_reference(*args)
    grads = jax.grad(lambda *a: jnp.sum(ss.selective_scan_reference(*a) * w),
                     argnums=tuple(range(7)))(*args)
    return args, w, out, grads


@pytest.mark.parametrize("chunk", (8, 16, 48))
def test_the_kernels_equal_the_per_token_scan(scan_reference, chunk):
    """Output and all seven gradients, at two chunk lengths under a sequence
    of several chunks and at one chunk the sequence: the result does not
    depend on the chunk beyond rounding."""
    args, w, want, want_g = scan_reference
    got = ss.selective_scan(*args, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=TOL * np.abs(want).max())
    got_g = jax.grad(lambda *a: jnp.sum(ss.selective_scan(
        *a, chunk=chunk, interpret=True) * w), argnums=tuple(range(7)))(*args)
    for name, g, wg in zip("x dt B C z a D".split(), got_g, want_g):
        np.testing.assert_allclose(g, wg, rtol=1e-4, err_msg=name,
                                   atol=TOL * np.abs(wg).max())


def test_the_kernels_take_whole_tiles_of_channels_and_bfloat16():
    """1,024 channels are one float32 tile a state cell and two groups of
    rows at 2,048; bf16 x and z round once on the way out."""
    args, w = _operands(1, 32, 2048, 4, seed=1)
    want = ss.selective_scan_reference(*args)
    got = ss.selective_scan(*args, chunk=16, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=TOL * np.abs(want).max())
    low = tuple(t.astype(jnp.bfloat16) if i in (0, 4) else t
                for i, t in enumerate(args))
    got = ss.selective_scan(*low, chunk=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(jnp.float32), ss.selective_scan_reference(*low).astype(
            jnp.float32), rtol=2e-2, atol=2e-2 * np.abs(want).max())
    assert not ss.supported((1, 32, 192), 16, 16)
    assert not ss.supported((1, 40, 256), 16, 16)
    assert ss.group_rows(5120) == 8 and ss.group_rows(256) == 2


@pytest.mark.parametrize("b,s,d,chunk,dtype", [
    # a token group of eight that is a whole chunk, one and two groups of rows
    (1, 16, 1024, 8, "bfloat16"), (1, 16, 2048, 8, "bfloat16"),
    # a bfloat16 tile's sixteen tokens split by a chunk edge
    (1, 48, 256, 8, "bfloat16"), (1, 48, 256, 24, "bfloat16"),
    # one row of channels; two sequences
    (1, 32, 128, 16, "float32"), (2, 32, 128, 8, "bfloat16"),
])
def test_the_kernels_read_the_projections_own_tiles(b, s, d, chunk, dtype):
    """The door's addressing (token ``t`` of a chunk at ``[t // 8, rows, t %
    8]``; x, z and the gradients beside them as plain ``[chunk, d]`` blocks,
    cast a lane tile at a time): output and all seven gradients against the
    per-token scan on the same operands, where bfloat16 rounds x, z, the
    output and their gradients once."""
    args, w = _operands(b, s, d, 4, seed=2)
    args = tuple(t.astype(dtype) if i in (0, 4) else t
                 for i, t in enumerate(args))
    tol = 2e-2 if dtype == "bfloat16" else TOL

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=tuple(range(7)))(*args)

    (got, got_g), (want, want_g) = (
        both(lambda *a: ss.selective_scan(*a, chunk=chunk, interpret=True)),
        both(ss.selective_scan_reference))
    assert abs(float(got) - float(want)) <= tol * float(jnp.sum(jnp.abs(
        ss.selective_scan_reference(*args).astype(jnp.float32) * w)))
    for name, g, wg in zip("x dt B C z a D".split(), got_g, want_g):
        assert g.dtype == wg.dtype and g.shape == wg.shape, name
        g, wg = (np.asarray(t, np.float32) for t in (g, wg))
        np.testing.assert_allclose(g, wg, rtol=max(tol, 1e-4), err_msg=name,
                                   atol=tol * np.abs(wg).max())


@pytest.mark.parametrize("b,s,d,chunk,dtype,z_at,blocks", [
    (2, 32, 128, 8, "bfloat16", 1, 2),      # in_proj's [x | z]
    (1, 48, 256, 24, "bfloat16", 1, 2), (1, 32, 128, 16, "float32", 0, 3),
])
def test_the_kernels_read_z_in_place_in_a_wider_array(b, s, d, chunk, dtype,
                                                      z_at, blocks):
    """z handed over as block ``z_at`` of ``blocks`` blocks of d lanes (the
    packed projection, no copy of its z half): the output and the six other
    gradients to the bit those of the same z alone, and z's gradient that
    one in its own lanes and zero in the others."""
    args, w = _operands(b, s, d, 4, seed=3)
    args = tuple(t.astype(dtype) if i in (0, 4) else t
                 for i, t in enumerate(args))
    others = jax.random.normal(jax.random.PRNGKey(9), (b, s, blocks * d)) \
        .astype(dtype)
    wide = others.at[..., z_at * d:(z_at + 1) * d].set(args[4])

    def both(z, **kw):
        return jax.value_and_grad(
            lambda *a: jnp.sum(ss.selective_scan(
                *a, chunk=chunk, interpret=True, **kw).astype(jnp.float32)
                * w), argnums=tuple(range(7)))(*args[:4], z, *args[5:])

    (got, got_g), (want, want_g) = both(wide, z_at=z_at), both(args[4])
    assert float(got) == float(want)
    for i, (g, wg) in enumerate(zip(got_g, want_g)):
        if i == 4:
            assert g.shape == wide.shape and g.dtype == wide.dtype
            mine = g[..., z_at * d:(z_at + 1) * d]
            assert float(jnp.abs(g.astype(jnp.float32)).sum()) == float(
                jnp.abs(mine.astype(jnp.float32)).sum())    # zero elsewhere
            g = mine
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(wg, np.float32))


def test_the_door_s_view_is_the_array_s_own_tiles():
    """``_tiles``: row 8 r + s of group g of a sequence's view is token 8 g
    + s, channels 128 r ..; ``_of_tiles`` puts it back."""
    t = jnp.arange(2 * 24 * 384, dtype=jnp.float32).reshape(2, 24, 384)
    view = ss._tiles(t)
    assert view.shape == (2, 3, 24, 128)
    np.testing.assert_array_equal(view[1, 2, 8 * 1 + 5], t[1, 21, 128:256])
    np.testing.assert_array_equal(ss._of_tiles(view), t)


@pytest.mark.parametrize("shape,chunk", [
    ((1, 44, 256), 44), ((1, 36, 256), 12), ((1, 20, 128), 4)])
def test_supported_refuses_sequences_off_whole_sublane_tiles(shape, chunk):
    """The view ``[S / 8, d / 128, 8, 128]`` needs S in whole groups of 8
    tokens: such shapes run the per-token scan."""
    assert shape[1] % chunk == 0 and not ss.supported(shape, 16, chunk)
    assert ss.supported((1, 48, 256), 16, 8)


# --- the block ---------------------------------------------------------------

def test_the_run_scan_equals_the_inlined_period(both):
    """The same leaves read a position at a time (``p<i>``, the period's
    body inlining every layer) give the run-scan's activations."""
    cfg, params, ids, _, _ = both
    inlined = jamba.jamba_tiny_config(run_scan=False)
    assert [r[2] for r in inlined.runs] == [1, 1, 1, 1]
    by_position = {}
    for at, (first, _, length) in enumerate(cfg.runs):
        for i in range(length):
            by_position["p%d" % (first + i)] = jax.tree.map(
                lambda a: a[:, i], params["params_layers"]["r%d" % at])
    # seeded alone, a position's leaves are the run's
    alone = T.init_transformer_params(jax.random.PRNGKey(3), inlined)
    np.testing.assert_array_equal(
        alone["params_layers"]["p1"]["w_in"],
        both.tr.state["params"]["params_layers"]["r0"]["w_in"][:, 1])
    got = jax.jit(lambda p, i: decoder.forward(p, i, cfg)[0])(params, ids)
    want = jax.jit(lambda p, i: decoder.forward(p, i, inlined)[0])(
        dict(params, params_layers=by_position), ids)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_filter_s_halo_crosses_a_row_block_edge(both, monkeypatch):
    """Past ROW_BLOCK_ELEMENTS the mixer's operands are made a block of
    positions at a time, each block projecting the three rows before it
    again for the filter: the same numbers, and gradients."""
    cfg, params, ids, _, _ = both
    pl = jax.tree.map(lambda a: jnp.asarray(a[0, 0]),
                      params["params_layers"]["r0"])
    h = jax.random.normal(jax.random.PRNGKey(5), (B, S, cfg.hidden))

    def run(pl, h):
        return jnp.sum(T.mamba_mixer(pl, h, cfg) ** 2)

    whole = jax.value_and_grad(run, argnums=(0, 1))(pl, h)
    monkeypatch.setattr(T, "ROW_BLOCK_ELEMENTS", 4 * 8 * B * 2 * cfg.d_inner)
    assert T.row_block(S, B * 2 * cfg.d_inner) == 8
    blocked = jax.value_and_grad(run, argnums=(0, 1))(pl, h)
    for got, want in zip(jax.tree.leaves(blocked), jax.tree.leaves(whole)):
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=EACH * np.abs(want).max())


def test_a_per_position_stack_may_carry_no_positions():
    """``positions`` None: no table, no rotation; a per-position stack with
    rotary positions still builds; learned positions are refused."""
    params = T.init_transformer_params(jax.random.PRNGKey(0),
                                       jamba.jamba_tiny_config())
    assert "pos_emb" not in params
    rotary = jamba.jamba_tiny_config(
        positions="rotary", layer_pattern=(T.MAMBA, (0, True)), n_layers=2)
    assert rotary.layer_kinds == (T.MAMBA, (None, True))
    with pytest.raises(AssertionError):
        jamba.jamba_tiny_config(positions="learned")
    with pytest.raises(AssertionError):     # None, and a layer that rotates
        jamba.jamba_tiny_config(layer_pattern=(T.MAMBA, (0, True)),
                                n_layers=2)


@pytest.fixture(scope="module")
def ran():
    """One trainer under remat, a step and a scan of six under a monitor
    session: the losses, the session's registry and the program's scopes."""
    tr = H.trainer(CASE, remat=True)
    batches = [{"ids": i} for i in H.ids(CASE, n=2)]
    mon = monitor.enable()
    try:
        first = float(tr.step(batches[0], 1e-3))
        many = np.asarray(tr.run_steps(
            H.staged(tr, batches * 3), 1e-3))
        names = devscope.scope_maps()["jamba.run_steps"]
        return first, many, mon.registry, names
    finally:
        monitor.disable()


def test_the_trainer_steps_and_its_loss_falls(ran):
    first, many, _, _ = ran
    assert np.isfinite(many).all() and many[-1] < first


def test_the_gauges_of_a_call(ran):
    reg = ran[2]
    # step sizes seeded log-uniform in [1e-3, 1e-1] under unit noise
    assert 1e-3 < reg.gauge("monitor.train.mamba_dt_mean").value < 0.2
    # the fastest cell (rate 16) under the largest step of the batch
    assert 0.0 < reg.gauge("monitor.train.mamba_decay_min").value < 0.5
    # the kernels ran, on views of the projections' own tiles
    assert reg.counter("monitor.kernels.selective_scan_calls",
                       fused=1, door="tiles").value > 0
    assert reg.counter("monitor.kernels.selective_scan_calls",
                       fused=0, door="copied").value == 0
    # the one attention layer's grid: 5 heads on one key/value head, a
    # grid step each (the function the kernels take their grid from)
    cfg = jamba.jamba_tiny_config()
    assert packed_grid(
        B, S, cfg.n_heads, cfg.head_dim,
        *T._packed_flash_blocks(cfg, cfg.n_heads, S, cfg.kv_heads),
        itemsize=cfg.jdtype.itemsize, n_kv_heads=cfg.kv_heads,
        causal=True) == (1, 10)


def test_the_mixer_s_instructions_are_under_their_scopes(ran):
    got = {devscope.classify(op) for op in ran[3].values()}
    for scope in ("mamba", "selective_scan", "attention", "mlp", "layer_norm",
                  "embed"):
        assert ("forward", scope) in got and ("backward", scope) in got, scope
    # the head makes its gradient in its forward rule (PR 74): its backward
    # rule is a multiply by a cotangent of 1, which folds away
    assert ("forward", "lm_head") in got
