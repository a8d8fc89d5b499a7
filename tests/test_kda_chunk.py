"""``kernels/kda_chunk.py``: the chunked delta rule (``kda_chunked``) against
the recurrence a token at a time, in float64 on the host for the outputs and
``kda_recurrence`` for the five gradients, with decays drawn at the SEEDED
extremes (``a_log`` = ln 16, a step of 0.7: ``G`` falls by 11 a token, and
``exp(G_i) exp(-G_j)`` as two factors is ``exp(+700)`` inside one chunk: the
case that must come out finite and right) and near 1 (a state that outlives
every chunk: the carry must matter)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import kda_chunk as K

B, H, DK, DV, CHUNK = 2, 3, 16, 32, 64
DECAYS = {"extreme": (math.log(16.0), 0.7), "near_one": (0.0, 1e-3)}
NAMES = ("q", "k", "v", "g", "beta")


def _operands(S, decays, seed=1):
    """q (L2-normalised, scaled), k (L2-normalised), v, g <= 0 and beta in
    (0, 1) as the mixer hands them over, float32."""
    a_log, step = DECAYS[decays]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, DK))
    k = jax.random.normal(ks[1], (B, S, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, DV))
    g = -math.exp(a_log) * jax.nn.softplus(
        0.3 * jax.random.normal(ks[3], (B, S, H, DK))
        + math.log(math.expm1(step)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


def _recurrence64(q, k, v, g, beta, state=None):
    """The delta rule a token at a time in float64 numpy: outputs and the
    last state."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    S_ = np.zeros((B, H, DK, DV)) if state is None else state
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        S_ = S_ * np.exp(g[:, t])[..., None]
        seen = np.einsum("bhkv,bhk->bhv", S_, k[:, t])
        S_ = S_ + np.einsum("bhk,bhv->bhkv", beta[:, t, :, None] * k[:, t],
                            v[:, t] - seen)
        out[:, t] = np.einsum("bhkv,bhk->bhv", S_, q[:, t])
    return out, S_


# several whole chunks; a ragged last one; fewer tokens than a chunk; a
# number of chunks no GROUP divides
@pytest.mark.parametrize("S", [256, 200, 40, 11 * 64])
@pytest.mark.parametrize("decays", sorted(DECAYS))
def test_outputs_equal_the_recurrence(S, decays):
    args = _operands(S, decays)
    got = np.asarray(K.kda_chunked(*args, chunk=CHUNK))
    want, _ = _recurrence64(*args)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())
    np.testing.assert_allclose(np.asarray(K.kda_recurrence(*args)), want,
                               rtol=1e-4, atol=1e-5 * np.abs(want).max())


@pytest.fixture(scope="module", params=sorted(DECAYS))
def gradients(request):
    """Of sum(o * w): by the chunked form and by the recurrence."""
    S = 200             # three whole chunks and a ragged one
    args = _operands(S, request.param, seed=2)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, DV))
    grad = lambda fn: jax.grad(                      # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    return (request.param,
            grad(lambda *a: K.kda_chunked(*a, chunk=CHUNK)),
            grad(K.kda_recurrence))


@pytest.mark.parametrize("at", range(5), ids=NAMES)
def test_every_gradient_equals_the_recurrence_s(gradients, at):
    """1e-5 of the largest entry.  The log-decays' at the extremes: 3e-4.
    Every decayed product hands ``G_i`` and ``G_j`` the same number with
    opposite signs, the running sum's transpose adds them up again, and
    what should cancel to nothing leaves float32 rounding of the LARGEST
    term (9e-5 measured against float64, where the recurrence's own float32
    gradient reads 2e-7); under bf16 operands it is far under the noise."""
    decays, got, want = gradients
    g, w = np.asarray(got[at]), np.asarray(want[at])
    assert np.isfinite(g).all() and np.abs(w).max() > 0
    tol = 3e-4 if (NAMES[at], decays) == ("g", "extreme") else 1e-5
    np.testing.assert_allclose(g, w, rtol=0, atol=tol * np.abs(w).max())


def test_the_naive_two_factor_form_overflows_where_this_one_does_not():
    """The trap the file's docstring names, shown: at the seeded extremes
    ``exp(-G_j)`` at a chunk's last token is no float32."""
    *_, g, _ = _operands(CHUNK, "extreme")
    G = np.cumsum(np.asarray(g, np.float32), axis=1)
    assert G.min() < -600
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G).astype(np.float32)).any()
    rows, cols, diag = K._decay_blocks(jnp.moveaxis(jnp.asarray(G), 1, 2))
    for part in (rows, cols, diag):
        part = np.asarray(part)
        assert np.isfinite(part).all() and part.max() <= 1.0 \
            and part.min() >= 0.0


def test_the_carry_over_eight_chunks_matters():
    """Decays near 1: the second half's outputs follow the state the first
    half left (a run on the second half from a ZERO state is far off), and
    handed that state the chunked form gives the whole run's outputs."""
    S = 16 * CHUNK
    args = _operands(S, "near_one", seed=3)
    # weak writes: at beta near 1/2 a key of 16 columns is overwritten
    # within a few chunks by the delta rule itself, whatever the decay
    args = args[:4] + (0.02 * args[4],)
    whole = np.asarray(K.kda_chunked(*args, chunk=CHUNK))
    tail = tuple(a[:, S // 2:] for a in args)
    alone = np.asarray(K.kda_chunked(*tail, chunk=CHUNK))
    scale = np.abs(whole[:, S // 2:]).max()
    assert np.abs(alone - whole[:, S // 2:]).max() > 0.2 * scale
    _, state = _recurrence64(*(a[:, :S // 2] for a in args))
    carried = np.asarray(K.kda_chunked(
        *tail, chunk=CHUNK, state=jnp.asarray(state, jnp.float32)))
    np.testing.assert_allclose(carried, whole[:, S // 2:], rtol=1e-4,
                               atol=1e-5 * scale)
    # the very last chunk still sees the first eight
    assert np.abs(alone[:, -CHUNK:] - whole[:, -CHUNK:]).max() > 0.05 * scale


def test_the_unit_lower_inverse_is_the_inverse():
    A = np.tril(np.random.RandomState(0).uniform(-0.5, 0.5, (4, 64, 64)), -1)
    inv = np.asarray(K._unit_lower_inverse(jnp.asarray(A, jnp.float32)))
    np.testing.assert_allclose(inv @ (np.eye(64) + A),
                               np.broadcast_to(np.eye(64), A.shape),
                               atol=2e-5)


def test_bf16_operands_stay_near_float32_and_finite():
    """The model's path: operands in bfloat16, decays and state float32."""
    args = _operands(256, "extreme")
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got = np.asarray(K.kda_chunked(*low, chunk=CHUNK), np.float32)
    want, _ = _recurrence64(*(np.asarray(a, np.float32) for a in low))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert np.abs(got - want).max() < 3e-2 * np.abs(want).max()


def test_kept_state_bytes_is_a_state_a_chunk_and_head():
    assert K.kept_state_bytes(1, 16384, 64, 32, 128, 128) == 256 * 32 * 65536
    assert K.kept_state_bytes(2, 200, 64, 3, 16, 32) == 2 * 4 * 3 * 16 * 32 * 4
