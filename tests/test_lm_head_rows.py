"""The tp=1 LM head computes only the rows the mask counts
(``transformer._weighted_vocab_nll``): live rows compacted to the front, the
chunked-vocab head run over ``ceil(count / R)`` row blocks.  Held here
against the plain formula, under ``jit(scan)``, across a dp=4 mesh with a
different count on every device, through the tiny trainer, and in the
lowered text of the step."""

import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.models import bert
from paddle_tpu.parallel import optim, transformer as T
from paddle_tpu.parallel.mesh import MeshSpec
from paddle_tpu.parallel.train import (TrainState, make_train_step,
                                       shard_pytree, stack_batches,
                                       state_specs)

# the package's attribute of that name is the function
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

N, E, V = 256, 16, 50
R = T.head_row_block(N)                                         # 16


LAYER_NORM = ("layer", 1e-6)


def _plain_loss(x, scale, bias, emb, labels, mask, norm=LAYER_NORM):
    h = T._head_norm(norm, x, scale, bias)
    logits = (h @ emb.T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _compact_loss(x, scale, bias, emb, labels, mask, norm=LAYER_NORM):
    return T._weighted_vocab_nll(
        x, scale, bias, emb, labels, mask / jnp.maximum(jnp.sum(mask), 1.0),
        norm=norm)[0]


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(N, E), jnp.float32),
            jnp.asarray(1 + 0.1 * rng.randn(E), jnp.float32),
            jnp.asarray(0.1 * rng.randn(E), jnp.float32),
            jnp.asarray(0.3 * rng.randn(V, E), jnp.float32),
            jnp.asarray(rng.randint(0, V, N), jnp.int32))


def _mask(kind, seed=1):
    rng = np.random.RandomState(seed)
    m = np.zeros(N, np.float32)
    if kind == "hot15":
        m[rng.permutation(N)[:int(0.15 * N)]] = 1
    elif kind == "ones":
        m[:] = 1
    elif kind == "one_row":
        m[N - 7] = 1
    elif kind == "ragged":              # a count that is no multiple of R
        m[rng.permutation(N)[:3 * R + 5]] = 1
    elif kind == "weights":             # non-binary: mask stays a weight
        m[:] = (rng.rand(N) < 0.4) * (0.25 + rng.rand(N))
    else:
        assert kind == "zeros"
    return jnp.asarray(m)


# the backward feeds the MXU bf16 softmax gradients (as the dense head
# did): each term of a gradient's sum is off by up to half an ulp of bf16, so
# one ulp of the largest entry bounds the sum
GRAD_TOL = 2.0 ** -8


def _close(got, want, tol):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("kind", ["hot15", "ones", "zeros", "one_row",
                                  "ragged", "weights"])
def test_compact_head_matches_the_plain_formula(kind):
    args, mask = _inputs(), _mask(kind)
    want, dwant = jax.value_and_grad(_plain_loss, argnums=(0, 1, 2, 3))(
        *args, mask)
    got, dgot = jax.jit(jax.value_and_grad(
        _compact_loss, argnums=(0, 1, 2, 3)))(*args, mask)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6, atol=1e-7)
    for g, w in zip(dgot, dwant):
        _close(g, w, GRAD_TOL)
    if kind == "zeros":
        assert float(got) == 0.0
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in dgot)
    # a dead row gets exactly no gradient, whatever block it sat in
    assert float(jnp.max(jnp.abs(dgot[0][np.asarray(mask) == 0]),
                         initial=0.0)) == 0.0


def test_nll_is_zero_on_dead_rows_and_exact_on_live_ones():
    args, mask = _inputs(), _mask("ragged")
    _, nll = T._weighted_vocab_nll(*args, mask)
    h = T.layer_norm(args[0], args[1], args[2], fused=False)
    logits = h @ args[3].T
    want = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, args[4][:, None], -1)[:, 0]
    live = np.asarray(mask) != 0
    assert (np.asarray(nll)[~live] == 0).all()
    np.testing.assert_allclose(np.asarray(nll)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)


def test_under_jit_scan_every_step_has_its_own_count():
    """As ``run_steps`` uses it: one compiled loop body, a trip count per
    step from that step's mask."""
    args = _inputs()
    masks = jnp.stack([_mask(k) for k in
                       ("hot15", "ones", "zeros", "ragged", "weights")])

    def step(carry, mask):
        loss, grads = jax.value_and_grad(_compact_loss, argnums=(0, 3))(
            *args, mask)
        return carry + loss, (loss, grads)

    total, (losses, grads) = jax.jit(
        lambda m: jax.lax.scan(step, 0.0, m))(masks)
    for i, mask in enumerate(masks):
        want, dwant = jax.value_and_grad(_plain_loss, argnums=(0, 3))(
            *args, mask)
        np.testing.assert_allclose(float(losses[i]), float(want), rtol=2e-6,
                                   atol=1e-7)
        _close(grads[0][i], dwant[0], GRAD_TOL)
        _close(grads[1][i], dwant[1], GRAD_TOL)
    np.testing.assert_allclose(float(total), float(jnp.sum(losses)),
                               rtol=1e-6)


@pytest.mark.parametrize("count,rows", [(0, 0), (1, R), (R, R), (R + 1, 2 * R),
                                        (3 * R + 5, 4 * R), (N, N)])
def test_rows_computed_is_whole_blocks(count, rows):
    assert T.head_rows_computed(count, N) == rows
    # the device code's own trip count, traced
    assert int(jax.jit(lambda c: T.head_rows_computed(c, N))(
        jnp.int32(count))) == rows


def test_row_block_comes_from_the_shape_alone():
    assert T.head_row_block(64 * 512) == T.head_row_block(256 * 128) == 1024
    assert T.head_row_block(4 * 32) == 8 and T.head_row_block(3) == 8
    assert all(T.head_row_block(n) % 8 == 0 for n in (100, 1000, 5000, 10**6))


# ---------------------------------------------------------------------------
# where the vocabulary is cut (``transformer._vocab_chunks``)
# ---------------------------------------------------------------------------

# vocabularies the rule treats differently; ``rows`` is the cut it makes
CUTS = {
    "four_times_8_times_a_prime": (4 * 8 * 37, [360, 360, 360, 104]),
    "below_one_granule": (5, [5]),
    "an_exact_multiple": (1024, [256] * 4),
    "a_remainder_under_128_rows": (1064, [320, 320, 320, 104]),
}


@pytest.mark.parametrize("mask_kind", ["zero_one", "ones"])
@pytest.mark.parametrize("norm", ["rms", "layer"])
@pytest.mark.parametrize("vocab", sorted(CUTS))
def test_any_cut_of_the_vocabulary_gives_the_dense_formula(vocab, norm,
                                                           mask_kind):
    V_, rows = CUTS[vocab]
    n, e = 64, 16
    rng = np.random.RandomState(V_)
    emb = jnp.asarray(0.3 * rng.randn(V_, e), jnp.float32)
    assert [r for _, r in T._vocab_chunks(emb)] == rows
    x = jnp.asarray(rng.randn(n, e), jnp.float32)
    scale = jnp.asarray(1 + 0.1 * rng.randn(e), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(e), jnp.float32)
    labels = rng.randint(0, V_, n)
    labels[:4] = [0, V_ - 1, rows[0] - 1, min(rows[0], V_ - 1)]   # the edges
    labels = jnp.asarray(labels, jnp.int32)
    mask = jnp.asarray(np.ones(n) if mask_kind == "ones"
                       else rng.rand(n) < 0.4, jnp.float32)
    wrt = (0, 1, 3) if norm == "rms" else (0, 1, 2, 3)
    args = (x, scale, None if norm == "rms" else bias, emb, labels, mask)
    want, dwant = jax.value_and_grad(functools.partial(
        _plain_loss, norm=(norm, 1e-5)), argnums=wrt)(*args)
    got, dgot = jax.jit(jax.value_and_grad(functools.partial(
        _compact_loss, norm=(norm, 1e-5)), argnums=wrt))(*args)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for g, w in zip(dgot, dwant):                # dx, norm gradients, demb
        assert g.shape == w.shape
        _close(g, w, GRAD_TOL)


def test_the_partition_covers_the_vocabulary_once_and_in_order():
    sweep = list(range(1, 2200)) + [
        30522, 30528, 32000, 37984, 50257, 50304, 128256, 151936, 262144]
    for V_ in sweep:
        chunks = T._vocab_chunks(jax.ShapeDtypeStruct((V_, 8), jnp.bfloat16))
        offsets, rows = zip(*chunks)
        assert offsets[0] == 0 and min(rows) >= 1, V_
        assert all(o + r == nxt for (o, r), nxt in zip(
            chunks, offsets[1:] + (V_,))), V_
        granule = T.head_row_block(V_)
        assert all(r % granule == 0 for r in rows[:-1]), V_
        assert rows[-1] <= rows[0] and len(rows) <= T._VOCAB_CHUNKS, V_
        # the float32 [R, rows] logits tile is a quarter of the vocabulary's,
        # to a granule
        assert rows[0] < V_ / T._VOCAB_CHUNKS + granule, V_


@pytest.mark.parametrize("what,V_,rows", [
    ("smallthinker_21b_a3b", 37984, [10240, 10240, 10240, 7264]),
    ("olmoe_1b_7b", 50304, [13312, 13312, 13312, 10368]),
    ("bert_base", 30528, [8192, 8192, 8192, 5952]),
])
def test_the_cells_vocabularies_are_cut_on_whole_kilorows(what, V_, rows):
    """37,984 / 4 = 8 x 1,187, a prime: the equal cut the head made until
    PR 32 left the TPU compiler one 8-row window to tile a chunk's gradient
    with (``tests/test_chip_compile_steps.py`` holds the compiled head to
    it)."""
    chunks = T._vocab_chunks(jax.ShapeDtypeStruct((V_, 64), jnp.bfloat16))
    assert [r for _, r in chunks] == rows, what


# ---------------------------------------------------------------------------
# through the trainer
# ---------------------------------------------------------------------------

B, S = 8, 32


def _batch(rng, counts=None):
    """``counts``: predicted positions in each of the batch's rows."""
    labels = rng.randint(0, 128, (B, S)).astype("int32")
    mask = np.zeros((B, S), np.float32)
    for r, c in enumerate(counts if counts is not None
                          else rng.randint(1, 9, B)):
        mask[r, rng.permutation(S)[:c]] = 1
    return {"ids": np.where(mask != 0, 3, labels).astype("int32"),
            "labels": labels, "mask": mask}


def _trainer(dp=1, loss_fn=None):
    """SGD with momentum, whose step is linear in the gradient: LAMB's first
    step is lr * sign(g), which turns rounding noise on a near-zero gradient
    into a whole step."""
    cfg, opt = bert.bert_tiny_config(), optim.momentum(0.9)
    if loss_fn is None:
        return bert.build_bert_trainer(cfg, MeshSpec(dp=dp), optimizer=opt,
                                       devices=jax.devices()[:dp])
    # the same step around another loss: what build_bert_trainer does
    mesh = MeshSpec(dp=dp).build(devices=jax.devices()[:dp])
    pspecs = T.transformer_param_specs(cfg)
    state = TrainState.create(
        T.init_transformer_params(jax.random.PRNGKey(0), cfg), opt)
    step = make_train_step(loss_fn(cfg), mesh, pspecs, T.grad_sync_axes(cfg),
                           opt, bert.batch_specs(), donate=False)
    with mesh:
        state = shard_pytree(state, state_specs(pspecs, state), mesh)
    return step(state), state


def _dense_loss_fn(cfg):
    """The parent's formula: final layer norm and head on every row."""
    def loss_fn(params, batch):
        x = T.run_layers(params["params_layers"],
                         T.embed(params, batch["ids"], cfg), cfg)
        return _plain_loss(x, params["lnf_scale"], params["lnf_bias"],
                           params["tok_emb"], batch["labels"],
                           batch["mask"].astype(jnp.float32))
    return loss_fn


def _flat(params):
    return np.concatenate([np.asarray(p, np.float32).ravel()
                           for p in jax.tree.leaves(params)])


def test_tiny_trainer_steps_as_the_dense_formula_does():
    batch = _batch(np.random.RandomState(3))
    tr = _trainer()
    before = _flat(tr.state["params"])
    loss = float(tr.step(batch, 0.1))
    ref_step, ref_state = _trainer(loss_fn=_dense_loss_fn)
    ref_state, ref_loss = ref_step(ref_state, batch, 0.1)
    np.testing.assert_allclose(loss, float(ref_loss), rtol=1e-6)
    got, want = _flat(tr.state["params"]), _flat(ref_state["params"])
    moved = np.abs(want - before).max()
    assert moved > 1e-3                       # the step did something
    assert np.abs(got - want).max() <= GRAD_TOL * moved


def test_dp4_every_device_runs_its_own_count():
    """Two rows a device: 2, 9, 24 and 64 live rows on the four devices, so
    1, 2, 3 and 8 blocks of 8.  No collective sits inside the loop, so the
    trip counts may differ; the result is the one-device step's."""
    rng = np.random.RandomState(5)
    batch = _batch(rng, counts=[1, 1, 4, 5, 12, 12, 32, 32])
    assert T.head_row_block(2 * S) == 8
    one, four = _trainer(dp=1), _trainer(dp=4)
    # the SAME learning rate makes the same step (since PR 73: the loss's
    # sum over dp hands each shard its own cotangent,
    # ``collectives.psum_forward``; before it a dp=4 gradient was 4 times the
    # one-device one and this test took a quarter of the rate)
    before = _flat(one.state["params"])
    l1, l4 = float(one.step(batch, 0.1)), float(four.step(batch, 0.1))
    np.testing.assert_allclose(l4, l1, rtol=2e-6)
    got, want = _flat(four.state["params"]), _flat(one.state["params"])
    assert np.abs(got - want).max() <= GRAD_TOL * np.abs(want - before).max()
    # and scanned, as the four-chip cell runs it
    staged = [_batch(rng, counts=rng.permutation([0, 1, 2, 3, 8, 16, 31, 32]))
              for _ in range(3)]
    s1 = np.asarray(one.run_steps(
        stack_batches(one.mesh, bert.batch_specs(), staged), 0.1))
    s4 = np.asarray(four.run_steps(
        stack_batches(four.mesh, bert.batch_specs(), staged), 0.1))
    np.testing.assert_allclose(s4, s1, rtol=1e-4)


class _Unreadable:
    shape = (B, S)

    def __array__(self, *a, **k):
        raise AssertionError("the mask was read back with no monitor on")


def test_rows_gauge_and_counter_only_under_a_monitor_session(tmp_path):
    tr = _trainer()
    rng = np.random.RandomState(7)
    assert monitor.active() is None
    tr._count_head_rows(_Unreadable())          # off: nothing is read back
    counts = [1, 2, 3, 4, 5, 6, 7, 8]           # 36 live rows of 256
    batch = _batch(rng, counts=counts)
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        share = mon.registry.gauge("monitor.train.lm_head_rows_share")
        rows = mon.registry.counter("monitor.train.lm_head_rows")
        start = rows.value              # the registry outlives a session
        tr.step(batch, 1e-3)
        assert rows.value - start == T.head_rows_computed(36, B * S) == 48
        assert share.value == 48 / 256
        ones = dict(batch, mask=np.ones((B, S), np.float32))
        tr.run_steps(stack_batches(tr.mesh, bert.batch_specs(),
                                   [batch, ones]), 1e-3)
        assert rows.value - start == 48 + 48 + 256
        assert share.value == (48 + 256) / 512
        # four devices, two rows each: every device rounds its own count
        # (3, 7, 11, 15) up to whole blocks of 8
        _trainer(dp=4)._count_head_rows(batch["mask"])
        assert rows.value - start == 48 + 48 + 256 + 8 + 8 + 16 + 16
        assert share.value == 48 / 256
    finally:
        monitor.disable()


def _flash_shapes(cfg, S):
    """A configuration's local heads and blocks as its attention hands them
    to the packed kernel: ``(heads, key/value heads, block_q, block_k)``."""
    heads, kv = cfg.n_heads // cfg.tp, cfg.kv_heads // cfg.tp
    return (heads, kv) + T._packed_flash_blocks(cfg, heads, S, kv)


def _flash_grid(cfg, b, S):
    """``packed_grid`` of ``b`` local sequences of S positions and the query
    heads a kv step computes as one tile (``_Geom.halves``)."""
    heads, kv, bq, bk = _flash_shapes(cfg, S)
    return fa.packed_grid(
        b, S, heads, cfg.head_dim, bq, bk, itemsize=cfg.jdtype.itemsize,
        n_kv_heads=kv, causal=cfg.causal) + (
            fa._heads_per_block(cfg.head_dim) if kv != heads else 1,)


def test_flash_grid_is_a_function_of_the_shapes_and_no_gauge(tmp_path):
    """(pairs a grid step, grid steps a layer and pass) are what
    ``kernels.flash_attention.packed_grid`` says of a call's shapes, the
    function the kernels take their grid from (a grouped several-block
    step: the query head-blocks of a group that ride it, PR 68), and the
    heads one kv step
    computes as one tile 2 where the two heads of a 64-wide lane block read
    one key/value head (LFM2's 32 on 8), 1 in every other cell.  The
    configuration fixes all three, so a trainer writes none of them."""
    import dataclasses

    from paddle_tpu.models import lfm2, olmoe, smallthinker

    # heads the packed layout can tile (two of 64), so the kernel runs
    cfg = bert.bert_tiny_config(hidden=128, n_heads=2)
    tr = bert.build_bert_trainer(cfg, MeshSpec(dp=1),
                                 optimizer=optim.momentum(0.9),
                                 devices=jax.devices()[:1])
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        mon.registry.reset()
        tr.step(_batch(np.random.RandomState(9)), 1e-3)
        assert {row["name"] for row in mon.registry.snapshot()
                if row["name"].startswith(("monitor.train.",
                                           "monitor.kernels."))} == {
                    "monitor.train.lm_head_rows",
                    "monitor.train.lm_head_rows_share"}
    finally:
        monitor.disable()
    assert _flash_shapes(cfg, S) == (2, 2, 32, 32)
    assert _flash_grid(cfg, B, S) == (8, 1, 1)
    assert _flash_grid(cfg, B // 2, S)[:2] == (4, 1)    # two dp shards
    # the cells' shapes, by configuration alone
    base = bert.bert_base_config()
    for c, b, s, want, heads in [
            (base, 256, 128, (6, 256), 1),  # bert_base.s128_scan
            (base, 64, 512, (1, 384), 1),   # bert_base.s512_scan, _dp4
            (dataclasses.replace(base, tp=2), 256, 128, (6, 128), 1),
            # the causal triangle: 36 of 8 x 8 blocks, eight of a row's
            # sixteen heads a step (PR 70)
            (olmoe.olmoe_1b_7b_config(), 4, 4096, (8, 4 * 2 * 36), 1),
            # 28 on 4 heads of 128: a lane block is one head, a group's
            # seven ride one step
            (smallthinker.smallthinker_21b_a3b_config(), 1, 16384,
             (7, 4 * 528), 1),
            # 32 on 8 heads of 64: the two heads of a lane block stacked,
            # the four query blocks of a key/value block in one step
            (lfm2.lfm2_8b_a1b_config(), 2, 8192, (4, 2 * 4 * 136), 2)]:
        assert _flash_grid(c, b, s) == want + (heads,)
    # heads the packed layout cannot tile take another path
    assert T._packed_flash_blocks(bert.bert_tiny_config(), 4, S, 4) is None


def test_flash_backward_sweeps_of_the_cells_shapes():
    """The kernels of a layer kind's backward, from
    ``flash_attention.bwd_sweeps``, which the kernel asks: 1 at the three
    sparse cells' shapes and BERT's (dq, dk and dv off one sweep), 2 where
    dk and dv of the sequence are past VMEM.  A window changes the step
    table, not what VMEM holds: one answer a stack."""
    import dataclasses

    from paddle_tpu.models import lfm2, olmoe, smallthinker

    def sweeps(cfg, S):
        heads, kv, _, bk = _flash_shapes(cfg, S)
        return fa.bwd_sweeps(
            S, bk, cfg.head_dim * fa._heads_per_block(cfg.head_dim),
            cfg.jdtype.itemsize, heads // kv)

    small = smallthinker.smallthinker_21b_a3b_config()
    for cfg, S in ((olmoe.olmoe_1b_7b_config(), 4096),
                   (bert.bert_base_config(), 512), (small, 16384),
                   (lfm2.lfm2_8b_a1b_config(), 8192)):
        assert sweeps(cfg, S) == 1
    # SmallThinker's layers at eight times the sequence: two sweeps
    assert fa.fused_sweep_vmem_bytes(131072, 128, 2) > fa.SWEEP_VMEM
    assert sweeps(dataclasses.replace(small, max_seq=131072), 131072) == 2


# ---------------------------------------------------------------------------
# what compiled
# ---------------------------------------------------------------------------

_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_DOT = re.compile(r"stablehlo\.dot_general .*? : \((.*?)\) -> (tensor<[^>]*>)"
                  r".* loc\((#loc\d+)\)")
_DIMS = re.compile(r"tensor<((?:\d+x)*)")


def test_no_head_matmul_has_all_the_rows():
    """In the lowered step every ``dot_general`` under scope ``lm_head`` has
    R rows, none b*S: a refactor cannot silently restore the dense head."""
    tr = _trainer()
    batch = _batch(np.random.RandomState(0))
    text = tr.step_fn.lower(tr.state, batch, 1e-3).as_text(debug_info=True)
    names = dict(m.groups() for m in map(_LOC.match, text.splitlines()) if m)
    rows = T.head_row_block(B * S)
    assert rows == 16 and B * S == 256
    head_dots = []
    for m in _DOT.finditer(text):
        if "lm_head" in names.get(m.group(3), ""):
            head_dots.append({int(d) for t in _DIMS.findall(
                m.group(1) + m.group(2)) for d in t.split("x") if d})
    # 4 vocab chunks: the logits, dh and demb, each made once (PR 74: the
    # backward made the logits a second time, 16)
    assert len(head_dots) == 12
    assert all(rows in dims and B * S not in dims for dims in head_dots)
