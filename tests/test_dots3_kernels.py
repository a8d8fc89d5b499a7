"""The kernel modes dots3-note-prev's two attention shapes bring, in interpret
mode against the float32 formulas: the MASKED sweeps at a value width of its
own and a head a step (heads of 192 in 256 lanes, values of 128: the
statistic alone, the backward's dq / dk / dv, under the seeded selection and
with every causal key selected, and the pass with the statistic known), the
flash kernels' WINDOW mode at a value width of its own (256 / 128 under a
window a key longer than a block), the
indexer's scores at 64 heads of 128, and the rotation of a head's first
columns by the row kernel.  ONE traced program for the file."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import indexer as ix
from paddle_tpu.kernels.flash_attention import (_fwd as causal_flash_fwd,
                                                flash_attention_packed)
from paddle_tpu.parallel import transformer as T

B, S, H, D, LANES, DV, K, BLOCK, WINDOW = 1, 64, 2, 192, 256, 128, 8, 16, 17
HI, DI = 64, 128
TRI = np.tril(np.ones((S, S), bool))
BAND = TRI & (np.arange(S)[:, None] - np.arange(S)[None] < WINDOW)


def padded(x, width):
    """Heads of ``width`` in LANES lanes, zeros behind them."""
    x = x.reshape(B, S, H, LANES)
    return jnp.where(jnp.arange(LANES) < width, x, 0.0).reshape(B, S, -1)


def dense(q, k, v, keep, width):
    """(o, lse, probabilities) of the dense softmax over ``keep`` [.., S,
    S] at heads of ``width`` (the lanes behind them are zero)."""
    qh, kh = (x.reshape(B, S, H, LANES) for x in (q, k))
    s = jnp.where(keep[:, None], jnp.einsum("bthd,bshd->bhts", qh, kh)
                  * width ** -0.5, -jnp.inf)
    a = jax.nn.softmax(s, -1)
    return (jnp.einsum("bhts,bshd->bthd", a, v.reshape(B, S, H, DV)).reshape(
        B, S, H * DV), jax.nn.logsumexp(s, -1), a)


def ref_scores(q, k, w):
    s = jnp.einsum("bthd,bsd->bhts", q.reshape(B, S, HI, DI), k)
    return jnp.where(TRI, jnp.einsum("bth,bhts->bts", w, jax.nn.relu(s)),
                     -jnp.inf)


def ref_kl(scores, keep, a):
    p = jnp.mean(a, 1)
    log_r = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                         - jnp.where(keep, log_r, 0.0)),
                             0.0)) / (B * S)


@pytest.fixture(scope="module")
def case():
    r = np.random.RandomState(0)
    f32 = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    qi, ki, w = f32(B, S, HI * DI) / 8, f32(B, S, DI), f32(B, S, HI) / 8
    q, k = padded(f32(B, S, H * LANES), D), padded(f32(B, S, H * LANES), D)
    qw, kw = f32(B, S, H * LANES), f32(B, S, H * LANES)     # heads of 256
    v, c_out, c_scores = f32(B, S, H * DV), f32(B, S, H * DV), f32(B, S, S)
    blocks = dict(block_q=BLOCK, block_k=BLOCK)
    masked = dict(blocks, scale=D ** -0.5, v_head_dim=DV)

    def program():
        weigh = lambda fn: lambda *a: jnp.sum(jnp.where(TRI, fn(*a)
                                                        * c_scores, 0.0))
        got, want = (ix.indexer_scores(qi, ki, w, **blocks),
                     ref_scores(qi, ki, w))
        g_got = jax.grad(weigh(lambda *a: ix.indexer_scores(*a, **blocks)),
                         (0, 1, 2))(qi, ki, w)
        g_want = jax.grad(weigh(ref_scores), (0, 1, 2))(qi, ki, w)
        tau = ix.kth_largest(got, K, rows=BLOCK)
        keep = ix.selected(got, tau)
        o_want, lse_want, a = dense(q, k, v, keep, D)
        f_want = jax.grad(lambda *x: jnp.sum(dense(*x, keep, D)[0] * c_out),
                          (0, 1, 2))(q, k, v)
        # every causal key selected: the statistic is the causal flash
        # kernel's, o and its gradients the causal softmax's
        every = jnp.full_like(tau, -jnp.inf)
        every_lse = ix.dsa_lse(q, k, got, every, H, scale=D ** -0.5, **blocks)
        attend = lambda *x: ix.dsa_attend_kl(
            *x, (qi, ki, w), got, every, every_lse,
            ix.selected_lse(got, every, rows=BLOCK), H, **masked)[0]
        o = attend(q, k, v)
        f_got = jax.grad(lambda *x: jnp.sum(attend(*x) * c_out),
                         (0, 1, 2))(q, k, v)
        causal_o, causal_lse = causal_flash_fwd(
            q, k, v, D ** -0.5, True, BLOCK, BLOCK, True, H, H, None, DV)
        causal_d = jax.grad(lambda *x: jnp.sum(dense(
            *x, jnp.asarray(TRI)[None], D)[0] * c_out), (0, 1, 2))(q, k, v)
        known = ix.dsa_lse(q, k, got, tau, H, scale=D ** -0.5, **blocks)
        lse_i = ix.selected_lse(got, tau, rows=BLOCK)
        fused = lambda q, k, v, *indexer: ix.dsa_attend_kl(
            q, k, v, indexer, got, tau, known, lse_i, H, **masked)
        (o2, kl), pull = jax.vjp(fused, q, k, v, qi, ki, w)
        from_o = pull((c_out, jnp.zeros(())))
        from_kl = pull((jnp.zeros_like(c_out), jnp.ones(())))
        kl_want, kl_d_want = jax.value_and_grad(
            lambda *x: ref_kl(jnp.where(TRI, ref_scores(*x), -1e9), keep, a),
            (0, 1, 2))(qi, ki, w)
        # the window mode at 256 / 128
        band = jnp.asarray(BAND)[None]
        window = lambda *x: flash_attention_packed(
            *x, H, causal=True, window=WINDOW, v_head_dim=DV, **blocks)
        w_got = jax.grad(lambda *x: jnp.sum(window(*x) * c_out),
                         (0, 1, 2))(qw, kw, v)
        w_want = jax.grad(lambda *x: jnp.sum(dense(*x, band, LANES)[0]
                                             * c_out), (0, 1, 2))(qw, kw, v)
        # a head's first 64 columns through the row kernel and the lines
        turned = T._rope_first_columns(qi, DI, 64, 8e7)
        heads = qi.reshape(B, S, HI, DI)
        lines = jnp.concatenate([T.rope(
            heads[..., :64].reshape(B, S, -1), HI, 8e7).reshape(
                B, S, HI, 64), heads[..., 64:]], -1).reshape(qi.shape)
        narrow = T._rope_first_columns(qi[..., :HI * 32], 32, 16, 8e7)
        return dict(
            scores=(got, want), o=(o, causal_o), lse=(known, lse_want),
            known_lse=(every_lse, causal_lse[..., 0]), fused_o=(o2, o_want),
            kl=(kl, kl_want), window_o=(window(qw, kw, v),
                                        dense(qw, kw, v, band, LANES)[0]),
            rope_first=(turned, lines),
            rope_first_lines=(narrow.reshape(B, S, -1, 32)[..., 16:],
                              qi[..., :HI * 32].reshape(B, S, -1, 32)[
                                  ..., 16:]),
            **{"kl_d" + n: (x, y) for n, x, y in zip(
                ("q", "k", "w"), from_kl[3:], kl_d_want)},
            **{"fused_d" + n: (x, y) for n, x, y in zip(
                "qkv", from_o, f_want)},
            **{"scores_d" + n: (x, y) for n, x, y in zip(
                ("q", "k", "w"), g_got, g_want)},
            **{"flash_d" + n: (x, y) for n, x, y in zip(
                "qkv", f_got, causal_d)},
            **{"window_d" + n: (x, y) for n, x, y in zip(
                "qkv", w_got, w_want)},
            kept=jnp.sum(keep, -1))

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(program)())


@pytest.mark.parametrize("check,tolerance", [
    ("scores", 1e-5), ("scores_dq", 1e-5), ("scores_dk", 1e-5),
    ("scores_dw", 2e-5), ("o", 1e-5), ("lse", 1e-5), ("flash_dq", 1e-5),
    ("flash_dk", 1e-5), ("flash_dv", 1e-5), ("kl", 1e-6), ("kl_dq", 1e-5),
    ("kl_dk", 1e-5), ("kl_dw", 1e-5), ("known_lse", 1e-6), ("fused_o", 1e-5),
    ("fused_dq", 1e-5), ("fused_dk", 1e-5), ("fused_dv", 1e-5),
    ("window_o", 1e-5), ("window_dq", 1e-5), ("window_dk", 1e-5),
    ("window_dv", 1e-5), ("rope_first", 1e-6), ("rope_first_lines", 0.0)])
def test_a_kernel_agrees_with_its_float32_formula(case, check, tolerance):
    got, want = (np.asarray(x) for x in case[check])
    assert got.shape == want.shape, check
    assert np.array_equal(np.isfinite(got), np.isfinite(want)), check
    ok = np.isfinite(want)
    assert np.max(np.abs(want[ok])) > 0
    assert np.max(np.abs(got[ok] - want[ok])) <= tolerance * max(
        1.0, np.max(np.abs(want[ok]))), check


def test_the_selection_keeps_topk_of_a_row(case):
    kept = case["kept"]
    assert np.array_equal(kept[:, :K], np.broadcast_to(np.arange(1, K + 1),
                                                       (B, K)))
    assert np.all(kept[:, K:] >= K)


def test_the_gradients_stay_out_of_the_lanes_behind_a_head(case):
    """q and k stand in 256 lanes a head of 192: what the masked backward
    gives the 64 zero lanes is what the formula gives (the other operand's
    zeros), so nothing leaks into the weights' zero columns."""
    for name in ("flash_dq", "flash_dk", "fused_dq", "fused_dk"):
        got = np.asarray(case[name][0]).reshape(B, S, H, LANES)
        assert not np.any(got[..., D:]), name


@pytest.mark.parametrize("shape,ok", [
    ((64, 128, 512, 512), True), ((16, 64, 512, 512), True)])
def test_the_scores_kernels_state_what_they_hold(shape, ok):
    """Keye's 16 x 64 stays inside what Mosaic gives unasked (its calls are
    as they were); 64 x 128 asks, forward and backward, within a v5e
    core's 128 MiB."""
    heads, di, bq, bk = shape
    fwd = ix.scores_vmem_bytes(bq, bk, heads * di, heads, 2)
    bwd = ix.scores_vmem_bytes(bq, bk, heads * di, heads, 2, True, 16384)
    assert bwd < 100 * 2 ** 20
    assert (ix._past_scoped(fwd) == {}) == (heads == 16)
    assert (bwd > 48 * 2 ** 20) == (heads == 64)
