"""``kernels/indexer.py`` in interpret mode, against the float32 formulas:
the indexer's scores forward and backward, the k-th largest of a row against
``numpy.partition``, the two masked sweeps (``flash_dsa_fwd``: the statistic
alone; ``flash_dsa_bwd_fused``: dq, dk and dv, a group's query heads looped
inside a (tile, key/value head) step) against a dense masked softmax, under
the seeded selection and with every causal key selected (``tau = -inf``,
where the statistic is the causal flash kernel's), and the pass with the
statistic known (``dsa_attend_kl``: the output, the indexer's KL term, and
the gradients of both).  ONE traced program a geometry (a group of 8, of 4,
and a q block of two kv blocks): every check reads it."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import indexer as ix
from paddle_tpu.kernels.flash_attention import _fwd as causal_flash_fwd

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

B, S, HI, DI, H, D, K, BLOCK = 2, 64, 4, 16, 8, 128, 8, 16
TRI = np.tril(np.ones((S, S), bool))
# (query heads a key/value head, q block): the second q block's last kv
# block is the diagonal's at either
GEOMETRIES = [(8, BLOCK), (4, BLOCK), (8, 2 * BLOCK)]


def ref_scores(q, k, w):
    s = jnp.einsum("bthd,bsd->bhts", q.reshape(B, S, HI, DI), k)
    return jnp.where(TRI, jnp.einsum("bth,bhts->bts", w, jax.nn.relu(s)),
                     -jnp.inf)


def dense(q, k, v, scores, tau):
    """(o, lse, probabilities [B, H, S, S]) of the dense masked softmax."""
    keep = ix.selected(scores, tau)
    qh = q.reshape(B, S, H, D)
    kh, vh = (jnp.repeat(x.reshape(B, S, -1, D), H * D // x.shape[-1], 2)
              for x in (k, v))
    s = jnp.where(keep[:, None], jnp.einsum("bthd,bshd->bhts", qh, kh)
                  / D ** 0.5, -jnp.inf)
    a = jax.nn.softmax(s, -1)
    return (jnp.einsum("bhts,bshd->bthd", a, vh).reshape(B, S, H * D),
            jax.nn.logsumexp(s, -1), a)


def ref_kl(scores, keep, a):
    """``keep`` = ``ix.selected(the kernel's scores, tau)``: the selection
    is a constant, and a formula that selected from its own scores would
    drop the key AT the threshold for a last bit."""
    p = jnp.mean(a, 1)
    log_r = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                         - jnp.where(keep, log_r, 0.0)),
                             0.0)) / (B * S)


@pytest.fixture(scope="module", params=GEOMETRIES,
                ids=lambda g: "group%d_bq%d" % g)
def case(request):
    """``{check: (got, want)}`` of everything the file holds, from one
    jitted program a geometry."""
    group, block_q = request.param
    HKV = H // group
    r = np.random.RandomState(0)
    f32 = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    qi, ki, w = f32(B, S, HI * DI), f32(B, S, DI), f32(B, S, HI)
    q, k, v = f32(B, S, H * D), f32(B, S, HKV * D), f32(B, S, HKV * D)
    c_scores, c_out = f32(B, S, S), f32(B, S, H * D)
    blocks = dict(block_q=block_q, block_k=BLOCK)

    def program():
        weigh = lambda fn: lambda *a: jnp.sum(jnp.where(TRI, fn(*a)
                                                        * c_scores, 0.0))
        got, want = (ix.indexer_scores(qi, ki, w, **blocks),
                     ref_scores(qi, ki, w))
        g_got = jax.grad(weigh(lambda *a: ix.indexer_scores(*a, **blocks)),
                         (0, 1, 2))(qi, ki, w)
        g_want = jax.grad(weigh(ref_scores), (0, 1, 2))(qi, ki, w)
        tau = ix.kth_largest(got, K, rows=BLOCK)
        o_want, lse_want, a = dense(q, k, v, got, tau)
        f_want = jax.grad(lambda *x: jnp.sum(dense(*x, got, tau)[0] * c_out),
                          (0, 1, 2))(q, k, v)
        # every causal key selected: the sweeps' statistic is the causal
        # flash kernel's, o and its gradients the causal softmax's
        every = jnp.full_like(tau, -jnp.inf)
        every_lse = ix.dsa_lse(q, k, got, every, H, HKV, **blocks)
        attend = lambda *x: ix.dsa_attend_kl(
            *x, (qi, ki, w), got, every, every_lse,
            ix.selected_lse(got, every, rows=BLOCK), H, HKV, **blocks)[0]
        o = attend(q, k, v)
        f_got = jax.grad(lambda *x: jnp.sum(attend(*x) * c_out),
                         (0, 1, 2))(q, k, v)
        causal_o, causal_lse = causal_flash_fwd(
            q, k, v, D ** -0.5, True, block_q, BLOCK, True, H, HKV)
        causal_d = jax.grad(lambda *x: jnp.sum(dense(*x, got, every)[0]
                                               * c_out), (0, 1, 2))(q, k, v)
        # the pass with the statistic known: o, the KL term and, from a
        # cotangent of each, dq / dk / dv (o's alone) and the gradient of
        # the scores' operands (the KL's alone)
        known = ix.dsa_lse(q, k, got, tau, H, HKV, **blocks)
        lse_i = ix.selected_lse(got, tau, rows=BLOCK)
        fused = lambda q, k, v, *indexer: ix.dsa_attend_kl(
            q, k, v, indexer, got, tau, known, lse_i, H, HKV, **blocks)
        (o2, kl), pull = jax.vjp(fused, q, k, v, qi, ki, w)
        from_o = pull((c_out, jnp.zeros(())))
        from_kl = pull((jnp.zeros_like(c_out), jnp.ones(())))
        kl_want, kl_d_want = jax.value_and_grad(
            lambda *x: ref_kl(jnp.where(TRI, ref_scores(*x), -1e9),
                              ix.selected(got, tau), a),
            (0, 1, 2))(qi, ki, w)
        # a row that selects ONE key: the other block it sees holds none
        # and computes zeros
        high = tau.at[:, BLOCK + 3].set(jnp.max(got[:, BLOCK + 3], -1))
        lone = ix.dsa_attend_kl(
            q, k, v, (qi, ki, w), got, high,
            ix.dsa_lse(q, k, got, high, H, HKV, **blocks),
            ix.selected_lse(got, high), H, HKV, **blocks)
        lone_a = dense(q, k, v, got, high)[2]
        return dict(
            scores=(got, want), tau=tau, o=(o, causal_o),
            lse=(known, lse_want),
            known_lse=(every_lse, causal_lse[..., 0]),
            selected_lse=(lse_i, jax.nn.logsumexp(jnp.where(
                ix.selected(got, tau), got, -jnp.inf), -1)),
            fused_o=(o2, o_want),
            kl=(kl, kl_want),
            silent=(from_o[3:], from_kl[:3]),
            **{"kl_d" + n: (a_, b_) for n, a_, b_ in zip(
                ("q", "k", "w"), from_kl[3:], kl_d_want)},
            lone_o=(lone[0], dense(q, k, v, got, high)[0]),
            lone_kl=(lone[1], ref_kl(got, ix.selected(got, high), lone_a)),
            lone_kept=jnp.sum(ix.selected(got, high)[:, BLOCK + 3], -1),
            **{"fused_d" + n: (a_, b_) for n, a_, b_ in zip(
                "qkv", from_o, f_want)},
            **{"scores_d" + n: (a_, b_) for n, a_, b_ in zip(
                ("q", "k", "w"), g_got, g_want)},
            **{"flash_d" + n: (a_, b_) for n, a_, b_ in zip(
                "qkv", f_got, causal_d)})

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(program)())


@pytest.mark.parametrize("check,tolerance", [
    ("scores", 1e-5), ("scores_dq", 1e-5), ("scores_dk", 1e-5),
    ("scores_dw", 2e-5), ("o", 1e-5), ("lse", 1e-5), ("flash_dq", 1e-5),
    ("flash_dk", 1e-5), ("flash_dv", 1e-5), ("kl", 1e-6), ("kl_dq", 1e-5),
    ("kl_dk", 1e-5), ("kl_dw", 1e-5), ("known_lse", 1e-6),
    ("selected_lse", 1e-6), ("fused_o", 1e-5),
    ("fused_dq", 1e-5), ("fused_dk", 1e-5), ("fused_dv", 1e-5),
    ("lone_o", 1e-5), ("lone_kl", 1e-6)])
def test_a_kernel_agrees_with_its_float32_formula(case, check, tolerance):
    got, want = (np.asarray(x) for x in case[check])
    assert np.array_equal(np.isfinite(got), np.isfinite(want)), check
    ok = np.isfinite(want)
    assert np.max(np.abs(got[ok] - want[ok])) <= tolerance * max(
        1.0, np.max(np.abs(want[ok]))), check


def test_each_term_of_the_known_statistic_pass_reaches_its_own(case):
    """o's cotangent gives the scores' operands EXACTLY nothing and the
    KL's gives q, k and v exactly nothing; the lone row's case is what it
    says."""
    d_indexer, d_qkv = case["silent"]
    assert not any(np.any(x) for x in d_indexer + d_qkv)
    assert np.array_equal(case["lone_kept"], [1] * B)


def test_the_kth_largest_is_numpy_partition_s(case):
    scores = np.asarray(case["scores"][0])
    want = np.partition(scores, S - K, axis=-1)[..., S - K]
    assert np.array_equal(case["tau"], want)
    # fewer causal keys than k: no threshold, every causal key is read
    assert np.all(np.isneginf(case["tau"][:, :K - 1]))
    assert np.all(np.isfinite(case["tau"][:, K - 1:]))


@pytest.mark.parametrize("k", [1, 3, 64, 100])
def test_the_kth_largest_of_rows_with_ties_and_signs(k):
    r = np.random.RandomState(k)
    rows = r.randn(1, 16, 64).astype(np.float32)
    rows[0, :4, ::2] = 0.0                  # exact zeros, half a row
    rows[0, 4:8] = np.round(rows[0, 4:8])   # many ties
    rows[0, 8, :] = -0.0
    rows[0, 9, 10:] = -np.inf
    got = np.asarray(ix.kth_largest(jnp.asarray(rows), k, rows=8))
    n = min(k, 64)
    assert np.array_equal(got, np.partition(rows, 64 - n, axis=-1)[..., 64 - n])


def test_ties_at_the_threshold_are_all_kept():
    scores = np.full((1, 16, 16), -np.inf, np.float32)
    scores[np.tril(np.ones((1, 16, 16), bool))] = 1.0
    scores[0, :, 0] = 2.0
    tau = ix.kth_largest(jnp.asarray(scores), 4)
    kept = np.asarray(ix.selected(jnp.asarray(scores), tau)).sum(-1)[0]
    # the fourth largest is one of the tied 1.0s: every causal key stays
    assert np.array_equal(kept, np.arange(1, 17))


def test_the_heads_a_step_come_from_the_shapes(monkeypatch):
    """Where a group's heads do not fit VMEM in one step the most that do
    (a divisor of the group) ride it and the others are further sweeps of
    the same grid row: the statistic and dq the same numbers, dk and dv the
    same sums in another order."""
    assert ix.heads_a_step(8, lambda n: n * 2 ** 20) == 8
    assert ix.heads_a_step(8, lambda n: n * 20 * 2 ** 20) == 2
    assert ix.heads_a_step(6, lambda n: n * 20 * 2 ** 20) == 3
    # the one function of both files; where not even one head fits it says
    # one and the backward's call refuses (below)
    assert ix.heads_a_step is fa.heads_a_step
    assert ix.heads_a_step(8, lambda n: 65 * 2 ** 20) == 1
    r = np.random.RandomState(3)
    f32 = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    qi, ki, w = f32(1, S, HI * DI), f32(1, S, DI), f32(1, S, HI)
    q, k, v, c_out = f32(1, S, H * D), f32(1, S, D), f32(1, S, D), \
        f32(1, S, H * D)
    blocks = dict(block_q=BLOCK, block_k=BLOCK)
    scores = ix.indexer_scores(qi, ki, w, **blocks)
    tau = ix.kth_largest(scores, K, rows=BLOCK)

    def sweeps():
        lse = ix.dsa_lse(q, k, scores, tau, H, 1, **blocks)
        return (lse,) + jax.grad(lambda *x: jnp.sum(ix.dsa_attend_kl(
            *x, (qi, ki, w), scores, tau, lse, ix.selected_lse(scores, tau),
            H, 1, **blocks)[0] * c_out), (0, 1, 2))(q, k, v)

    whole = sweeps()
    # room for the accumulators and two heads of the eight
    monkeypatch.setattr(fa, "SWEEP_VMEM", ix.dsa_bwd_vmem_bytes(
        S, 2, D, D, 4, BLOCK, BLOCK))
    assert ix.heads_a_step(8, lambda n: ix.dsa_bwd_vmem_bytes(
        S, n, D, D, 4, BLOCK, BLOCK)) == 2
    lse, dq, dk, dv = sweeps()
    assert np.array_equal(lse, whole[0]) and np.array_equal(dq, whole[1])
    for got, want in ((dk, whole[2]), (dv, whole[3])):
        assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))
    # dk and dv of a sequence that one head's step cannot hold: refused
    monkeypatch.setattr(ix, "dsa_bwd_vmem_bytes", lambda *a, **k: 65 * 2 ** 20)
    with pytest.raises(AssertionError, match="do not fit VMEM"):
        sweeps()
