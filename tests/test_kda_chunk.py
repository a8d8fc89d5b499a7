"""``kernels/kda_chunk.py``: the chunked delta rule (``kda_chunked``) against
the recurrence a token at a time, in float64 on the host for the outputs and
``kda_recurrence`` for the five gradients, with decays drawn at the SEEDED
extremes (``a_log`` = ln 16, a step of 0.7: ``G`` falls by 11 a token, and
``exp(G_i) exp(-G_j)`` as two factors is ``exp(+700)`` inside one chunk: the
case that must come out finite and right) and near 1 (a state that outlives
every chunk: the carry must matter)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import kda_chunk as K

B, H, DK, DV, CHUNK = 2, 3, 16, 32, 64
DECAYS = {"extreme": (math.log(16.0), 0.7), "near_one": (0.0, 1e-3)}
NAMES = ("q", "k", "v", "g", "beta")


# write strengths: a sigmoid, in (0, 1); twice one, in (0, 2) (negative
# eigenvalues: half the writes past 1); every write 1 (the state's component
# along k REPLACED) or 1.999 (all but a reflection)
STRENGTHS = {"under_one": None, "under_two": 2.0, "one": 1.0, "1.999": 1.999}


def _operands(S, decays, seed=1, shape=(B, H, DK, DV), dtype=jnp.float32,
              strength="under_one"):
    """q (L2-normalised, scaled), k (L2-normalised), v, g <= 0 and beta in
    (0, 1) (``strength``: STRENGTHS) as the mixer hands them over: q, k, v
    in ``dtype``, g and beta float32; ``shape`` = (batch, heads, dk, dv)."""
    b, h, dk, dv = shape
    a_log, step = DECAYS[decays]
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, S, h, dk))
    k = jax.random.normal(ks[1], (b, S, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, S, h, dv))
    g = -math.exp(a_log) * jax.nn.softplus(
        0.3 * jax.random.normal(ks[3], (b, S, h, dk))
        + math.log(math.expm1(step)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, S, h)))
    if strength == "under_two":
        beta = 2.0 * beta
    elif strength != "under_one":
        beta = jnp.full_like(beta, STRENGTHS[strength])
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)


def _recurrence64(q, k, v, g, beta, state=None):
    """The delta rule a token at a time in float64 numpy, of what the
    operands hold (bf16: of their rounded values): outputs and the last
    state."""
    q, k, v, g, beta = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                        for a in (q, k, v, g, beta))
    S_ = np.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:]) \
        if state is None else state
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


@pytest.mark.parametrize("strength", ["under_two", "one", "1.999"])
@pytest.mark.parametrize("decays", sorted(DECAYS))
def test_outputs_equal_the_recurrence_at_strengths_up_to_two(decays,
                                                             strength):
    """``beta`` in (0, 2): the transition along ``k_t`` is ``1 - beta_t`` in
    (-1, 1), the solve ``(I + diag(beta) P)^-1`` is as exact (``A`` is
    nilpotent whatever its entries) and an error along ``k`` is carried with
    its sign turned, not damped: three whole chunks and a ragged one at the
    float32 tolerance of the strengths under 1."""
    args = _operands(200, decays, strength=strength)
    got = np.asarray(K.kda_chunked(*args, chunk=CHUNK, over_one=True))
    want, _ = _recurrence64(*args)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())
    np.testing.assert_allclose(np.asarray(K.kda_recurrence(*args)), want,
                               rtol=1e-4, atol=1e-5 * np.abs(want).max())


def test_a_write_of_one_replaces_and_a_write_of_two_reflects():
    """Without decay, reading the state at the key just written: ``beta`` =
    1 returns ``v_t`` itself (what the state held along ``k_t`` is gone),
    ``beta`` = 2 returns ``2 v_t - seen`` (it is turned round: the
    eigenvalue -1 that ``beta`` < 2 stays short of)."""
    q, k, v, g, _ = _operands(40, "near_one", seed=7)
    g = jnp.zeros_like(g)
    for strength, mix in ((1.0, 0.0), (2.0, -1.0)):
        beta = jnp.full(k.shape[:3], strength)
        for form in (K.kda_recurrence,
                     lambda *a: K.kda_chunked(*a, chunk=16, over_one=True)):
            # q = k: the state is read where it was written; ``held``: the
            # same run with the last write left out
            o = np.asarray(form(k, k, v, g, beta))
            held = np.asarray(form(k, k, v, g, beta.at[:, -1].set(0.0)))
            np.testing.assert_allclose(
                o[:, -1], (1 - mix) * np.asarray(v)[:, -1] + mix * held[:, -1],
                rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module", params=[
    (d, "under_one") for d in sorted(DECAYS)] + [("extreme", "under_two"),
                                                  ("near_one", "1.999")],
    ids=lambda p: p[0] if p[1] == "under_one" else "-".join(p))
def gradients(request):
    """Of sum(o * w): by the chunked form and by the recurrence."""
    S = 200             # three whole chunks and a ragged one
    decays, strength = request.param
    args = _operands(S, decays, seed=2, strength=strength)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, DV))
    grad = lambda fn: jax.grad(                      # noqa: E731
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    return (decays,
            grad(lambda *a: K.kda_chunked(*a, chunk=CHUNK,
                                          over_one=strength != "under_one")),
            grad(K.kda_recurrence))


def _correlated_keys(S, heads=2, d=K.LANES, seed=3):
    """Operands as the MIXER makes them: q, k and v behind a ``silu`` (a
    positive mean: any two keys of a head at a cosine near 0.3, where
    Gaussian ones stand at 128^-1/2), decays near 1, every write 1.999."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.nn.silu(jax.random.normal(key, (1, S, heads, d)))
               for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jax.nn.softplus(0.3 * jax.random.normal(ks[3], (1, S, heads, d))
                         + math.log(math.expm1(1e-3)))
    return q, k, v, g, jnp.full((1, S, heads), 1.999)


def test_the_solve_by_doubling_keeps_the_digits_the_squarings_lose():
    """Why ``over_one`` is another solve and not another range: ``(I + A)^-1``
    is as well conditioned at ``beta`` near 2 (its entries stay under 1), but
    the squarings form ``A^2, A^4, .. A^32`` on the way, whose entries reach
    thousands where the keys of a chunk lean one way (the mixer's do: a
    ``silu`` stands before the norm) and cancel in float32 to three digits.
    Doubling forms nothing larger than the inverse's own blocks."""
    args = _correlated_keys(4 * CHUNK)
    want, _ = _recurrence64(*args)
    scale = np.abs(want).max()
    err = lambda got: np.abs(np.asarray(got) - want).max() / scale  # noqa: E731
    squarings = err(K.kda_chunked(*args, chunk=CHUNK))
    assert squarings > 1e-4
    assert err(K.kda_chunked(*args, chunk=CHUNK, over_one=True)) < 1e-5
    assert err(_kernels(*args, over_one=True)) < 1e-5
    assert err(_kernels(*args)) > 1e-4
    # the inverse itself, at ONE key for the whole chunk: entries 1.999 at
    # most, and the squarings' powers of A four thousand times that
    k = np.tile(np.asarray(args[1])[0, :1, 0], (CHUNK, 1))
    A = np.tril(1.999 * (k @ k.T), -1)
    exact = np.linalg.inv(np.eye(CHUNK) + A)
    assert np.abs(exact).max() < 2.0
    assert np.abs(np.linalg.matrix_power(A, 16)).max() > 4e3
    got = np.asarray(K._unit_lower_inverse(jnp.asarray(A, jnp.float32), True))
    assert np.abs(got - exact).max() < 1e-4


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
    """Since PR 72 a state and the chunks' solves side by side a STACK of 128
    rows and head, and THE RULE that paid for the solves: never more than
    the state a chunk the kernels kept before (537 MB a layer at
    Kimi-Linear's shape, 268 MB at Solar-Open2's), at chunks of two or more a
    stack (every configuration's: 64; ONE chunk a stack keeps its state and
    a solve as large)."""
    state, side = 128 * 128 * 4, 64 * 128 * 4
    kimi = K.kept_state_bytes(1, 16384, 64, 32, 128, 128)
    solar = K.kept_state_bytes(1, 4096, 64, 64, 128, 128)
    assert kimi == 128 * 32 * (state + side) == 402_653_184
    assert solar == 32 * 64 * (state + side) == 201_326_592
    assert kimi <= 256 * 32 * state == 536_870_912
    assert solar <= 64 * 64 * state == 268_435_456
    for chunk in (16, 32, 64):
        assert K.kept_state_bytes(2, 1024, chunk, 3, 128, 128) \
            <= 2 * (1024 // chunk) * 3 * state
    assert K.kept_state_bytes(2, 1024, 128, 3, 128, 128) \
        == 2 * 2 * 8 * 3 * state
    # a ragged sequence: whole stacks
    assert K.kept_state_bytes(2, 200, 64, 3, 128, 128) \
        == 2 * 2 * 3 * (state + side)


# ---------------------------------------------------------------------------
# The Pallas kernels (``kda_chunk``), in interpret mode: a head 128 wide

KB, KH, KD = 1, 2, K.LANES


def _kernel_operands(S, decays, seed=4, dtype=jnp.float32, heads=KH,
                     strength="under_one"):
    """``_operands`` at the kernels' head width."""
    return _operands(S, decays, seed, (KB, heads, KD, KD), dtype, strength)


def _kernels(q, k, v, g, beta, chunk=CHUNK, over_one=False):
    """``kda_chunk`` on operands shaped as ``kda_chunked``'s: a head a lane
    block of [b, S, heads x 128] at the kernels' door."""
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))         # noqa: E731
    return K.kda_chunk(flat(q), flat(k), flat(v), flat(g), beta,
                       heads=k.shape[2], chunk=chunk,
                       over_one=over_one).reshape(v.shape)


# float32 operands: the kernels ARE the chunked form; bf16: both stand as far
# from the float64 recurrence as bf16 operands of a chain of products do
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decays", sorted(DECAYS))
def test_the_kernels_outputs_equal_the_chunked_form_and_the_recurrence(
        decays, dtype, tol):
    args = _kernel_operands(4 * CHUNK, decays, dtype=dtype)
    got = _kernels(*args)
    assert got.dtype == dtype and got.shape == args[2].shape
    got = np.asarray(got.astype(jnp.float32))
    want, _ = _recurrence64(*args)
    jnp_form = np.asarray(K.kda_chunked(*args, chunk=CHUNK)
                          .astype(jnp.float32))
    assert np.isfinite(got).all()
    for other in (want, jnp_form):
        assert np.abs(got - other).max() < tol * np.abs(want).max()


@pytest.mark.parametrize("strength,heads", [
    ("under_two", KH), ("one", KH), ("1.999", KH), ("under_two", 64)],
    ids=["under_two", "one", "1.999", "under_two-64_heads"])
def test_the_kernels_outputs_at_strengths_up_to_two(strength, heads):
    """The kernels at ``beta`` in (0, 2), at 1 and at 1.999, and at the 64
    heads of 128 (8,192 lanes) of ``solar_open2_250b``: the chunked form's
    numbers and the float64 recurrence's, at the tolerance of the strengths
    under 1."""
    args = _kernel_operands(2 * CHUNK if heads > KH else 4 * CHUNK,
                            "near_one", seed=8, heads=heads,
                            strength=strength)
    got = np.asarray(_kernels(*args, over_one=True))
    want, _ = _recurrence64(*args)
    jnp_form = np.asarray(K.kda_chunked(*args, chunk=CHUNK, over_one=True))
    assert np.isfinite(got).all()
    for other in (want, jnp_form):
        assert np.abs(got - other).max() < 1e-5 * np.abs(want).max()


# chunks of 64: two a stack, the second from a state the backward REBUILDS
# from the stack's kept one; of 32: four a stack, three rebuilt; of 128: one,
# nothing rebuilt
@pytest.fixture(scope="module", params=[
    (d, t, "under_one", CHUNK) for d in sorted(DECAYS)
    for t in ("float32", "bfloat16")] + [
        ("extreme", "float32", "under_two", CHUNK),
        ("near_one", "float32", "1.999", CHUNK),
        ("near_one", "float32", "one", CHUNK),
        ("near_one", "bfloat16", "under_two", CHUNK),
        ("near_one", "float32", "under_one", 32),
        ("extreme", "float32", "under_two", 32),
        ("near_one", "bfloat16", "under_two", 32),
        ("near_one", "float32", "under_one", 128),
        ("near_one", "float32", "1.999", 128),
        ("extreme", "bfloat16", "under_one", 128)],
    ids=lambda p: "-".join(p[:2] if p[2] == "under_one" else p[:3])
    + ("" if p[3] == CHUNK else "-chunks_of_%d" % p[3]))
def kernel_gradients(request):
    """Of sum(o * w) over two stacks (four chunks of 64, eight of 32, two of
    128): by the kernels, by the chunked form and by the recurrence."""
    decays, dtype, strength, chunk = request.param
    S = 2 * K.ROWS
    args = _kernel_operands(S, decays, seed=5, dtype=jnp.dtype(dtype),
                            strength=strength)
    w = jax.random.normal(jax.random.PRNGKey(10), (KB, S, KH, KD))
    grad = lambda fn: jax.grad(                      # noqa: E731
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    over_one = strength != "under_one"
    return (decays, dtype,
            grad(lambda *a: _kernels(*a, chunk=chunk, over_one=over_one)),
            grad(lambda *a: K.kda_chunked(*a, chunk=chunk,
                                          over_one=over_one)),
            grad(K.kda_recurrence))


@pytest.mark.parametrize("at", range(5), ids=NAMES)
def test_every_gradient_of_the_kernels_equals_both_forms(kernel_gradients,
                                                         at):
    """float32: 2e-5 of the largest entry (the log-decays' at the extremes
    3e-4, as the chunked form's own: the running sum's transpose adds up
    what should cancel).  bf16 operands: 3e-2, 6e-2 for the log-decays (the
    chip receipt's limits)."""
    decays, dtype, got, jnp_form, want = kernel_gradients
    g = np.asarray(got[at].astype(jnp.float32))
    assert got[at].dtype == want[at].dtype if at > 2 else True
    assert np.isfinite(g).all()
    if dtype == "float32":
        tol = 3e-4 if (NAMES[at], decays) == ("g", "extreme") else 2e-5
    else:
        tol = 6e-2 if NAMES[at] == "g" else 3e-2
    for other in (jnp_form, want):
        o = np.asarray(other[at].astype(jnp.float32))
        assert np.abs(o).max() > 0
        np.testing.assert_allclose(g, o, rtol=0, atol=tol * np.abs(o).max())


def _saved(args, chunk, over_one, save=True):
    """``(o, T, kept)`` of the forward that runs for a backward (``save``
    False: ``(o,)`` of the one that keeps nothing)."""
    flat = lambda a: a.reshape(a.shape[:2] + (-1,))         # noqa: E731
    q, k, v, g, beta = args
    return K._fwd(flat(q), flat(k), flat(v), flat(g), beta,
                  (k.shape[2], chunk, True, over_one), save)


@pytest.mark.parametrize("over_one,strength,chunk", [
    (False, "under_one", 64), (True, "under_two", 64), (True, "1.999", 64),
    (False, "under_one", 32), (True, "1.999", 32), (True, "under_two", 128)])
def test_the_saving_forward_keeps_the_solve_and_a_state_a_stack(
        over_one, strength, chunk):
    """What ``kda_chunk_bwd`` reads and no longer makes: ``T`` of every
    chunk, side by side a stack, is ``_unit_lower_inverse`` of the same ``A``
    by the same solve (and an inverse: ``T (I + A) = I``); the kept states
    are the recurrence's at each stack's first token; ``o`` is the first
    forward's bit for bit."""
    S = 2 * K.ROWS
    args = q, k, v, g, beta = _kernel_operands(S, "near_one", seed=13,
                                               strength=strength)
    o, solve, kept = _saved(args, chunk, over_one)
    per, n = K.ROWS // chunk, S // chunk
    assert solve.shape == (KB, KH, S // K.ROWS, chunk, K.ROWS) \
        and kept.shape == (KB, S // K.ROWS, KH, KD, KD) \
        and solve.dtype == kept.dtype == jnp.float32
    assert solve.nbytes + kept.nbytes == K.kept_state_bytes(
        KB, S, chunk, KH, KD, KD)
    # [b, H, stacks, C, per x C] -> [b, H, n, C, C]
    got = np.asarray(jnp.moveaxis(solve.reshape(
        KB, KH, S // K.ROWS, chunk, per, chunk), 4, 3)).reshape(
            KB, KH, n, chunk, chunk)
    chunks = lambda a: jnp.moveaxis(                        # noqa: E731
        a.reshape((KB, n, chunk, KH) + a.shape[3:]), 3, 1)
    kc, G, bc = chunks(k), jnp.cumsum(chunks(g), axis=-2), chunks(beta)
    A = K._decayed_product(kc, kc, K._decay_blocks(G), jnp.float32) \
        * jnp.tril(jnp.ones((chunk, chunk)), -1) * bc[..., None]
    want = np.asarray(K._unit_lower_inverse(A, over_one))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(
        want).max())
    # the strengths under 1 and the doubling keep the digits; the squarings
    # past 1 are what ``over_one`` is for
    np.testing.assert_allclose(
        got.astype(np.float64) @ (np.eye(chunk) + np.asarray(A, np.float64)),
        np.broadcast_to(np.eye(chunk), got.shape), atol=2e-5)
    for stack in range(S // K.ROWS):
        _, state = _recurrence64(*(a[:, :stack * K.ROWS] for a in args))
        # the kernels' state is transposed: [dv, dk]
        np.testing.assert_allclose(
            np.asarray(kept[:, stack]), np.swapaxes(state, -1, -2),
            rtol=1e-4, atol=1e-5 * max(np.abs(state).max(), 1.0))
    first = _saved(args, chunk, over_one, save=False)
    assert len(first) == 1
    np.testing.assert_array_equal(np.asarray(first[0]), np.asarray(o))


# the first 16 hex digits of the sha256 of the kernel of the forward that
# keeps nothing (``save`` False: its jaxpr inside the ``pallas_call``, bf16
# operands, two heads, two stacks), taken on PR 72's parent (bacf4c1): it is
# the text it was, so its outputs are the parent's (and the saving forward's
# ``o`` is its ``o``: the test above)
@pytest.mark.parametrize("over_one,chunk,digest", [
    (False, 64, "b2dcb1e4ec2e268c"), (False, 32, "4217069676a8981f"),
    (False, 128, "65df2af1c93876e8"), (True, 64, "4b011043ebe26858"),
    (True, 32, "81bf38fca3e658b9"), (True, 128, "780a89a40eb1a046")])
def test_the_forward_that_keeps_nothing_is_the_parent_s_text(over_one, chunk,
                                                             digest):
    import hashlib

    sds = jax.ShapeDtypeStruct
    flat = (1, 2 * K.ROWS, 2 * K.LANES)
    args = (sds(flat, jnp.bfloat16),) * 3 + (
        sds(flat, jnp.float32), sds(flat[:2] + (2,), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda *a: K._fwd(
        *a, (2, chunk, True, over_one), False))(*args)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert hashlib.sha256(str(call.params["jaxpr"]).encode()).hexdigest()[
        :16] == digest


def _kernel_calls(jaxpr, found):
    """``(name, number of outputs)`` of every ``pallas_call`` under
    ``jaxpr``, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], len(eqn.outvars)))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, found)
    return found


def test_under_a_layer_s_remat_both_forwards_are_the_saving_kernel():
    """What the step runs, as a trace says it: a layer under
    ``jax.checkpoint`` in a scan, as the decoder's, differentiates through
    ``kda_chunk``'s ``custom_vjp`` forward in BOTH passes, so the FIRST
    forward is the saving kernel too and what it keeps is written and
    dropped (``save`` False runs where nothing is differentiated).  PR 72
    measured the step with the first forward keeping nothing (a
    ``custom_dce`` rule): its kernel 12.89 -> 11.27 ms a call in the trace
    and NOTHING end to end (PERF.md section 6), so the plain form stands."""
    args = _kernel_operands(2 * K.ROWS, "near_one", dtype=jnp.bfloat16)
    flat = tuple(a.reshape(a.shape[:2] + (-1,)) for a in args[:4]) + args[4:]
    rule = lambda *a: K.kda_chunk(*a, heads=KH, chunk=CHUNK)   # noqa: E731

    def loss(layer, q, *rest):
        y, _ = jax.lax.scan(lambda c, _: (layer(c, *rest), None), q, None,
                            length=2)
        return jnp.sum(y.astype(jnp.float32))

    grad = lambda layer: jax.make_jaxpr(jax.grad(         # noqa: E731
        functools.partial(loss, layer), argnums=(0, 1, 2, 3, 4)))(*flat).jaxpr
    assert _kernel_calls(grad(jax.checkpoint(rule)), []) == [
        ("kda_chunk_fwd", 3), ("kda_chunk_fwd", 3), ("kda_chunk_bwd", 5)]
    assert _kernel_calls(grad(rule), []) == [
        ("kda_chunk_fwd", 3), ("kda_chunk_bwd", 5)]
    assert _kernel_calls(jax.make_jaxpr(rule)(*flat).jaxpr, []) == [
        ("kda_chunk_fwd", 1)]


def test_the_kernels_carry_over_eight_chunks_with_a_given_state():
    """Thirty-two chunks, two grid steps of eight stacks of two: the second
    half's outputs are the chunked form's FROM the state the first half left
    (float64 recurrence), and far from a run of the second half alone, its
    last eight chunks still."""
    S = 32 * CHUNK
    args = _kernel_operands(S, "near_one", seed=6, heads=1)
    args = args[:4] + (0.02 * args[4],)
    assert S // K.ROWS == 2 * K._step_stacks(S // K.ROWS)
    whole = np.asarray(_kernels(*args))
    tail = tuple(a[:, S // 2:] for a in args)
    alone = np.asarray(_kernels(*tail))
    scale = np.abs(whole[:, S // 2:]).max()
    assert np.abs(alone - whole[:, S // 2:]).max() > 0.2 * scale
    _, state = _recurrence64(*(a[:, :S // 2] for a in args))
    carried = np.asarray(K.kda_chunked(
        *tail, chunk=CHUNK, state=jnp.asarray(state, jnp.float32)))
    np.testing.assert_allclose(whole[:, S // 2:], carried, rtol=1e-4,
                               atol=1e-5 * scale)
    assert np.abs(alone[:, -8 * CHUNK:]
                  - whole[:, -8 * CHUNK:]).max() > 0.05 * scale


@pytest.mark.parametrize("what,shape,dv,chunk,dtype,takes", [
    ("kimi_linear_48b_a3b.s16384_scan", (1, 16384, 32, 128), 128, 64,
     jnp.bfloat16, True),
    ("solar_open2_250b.s4096_scan", (1, 4096, 64, 128), 128, 64,
     jnp.bfloat16, True),
    ("float32 operands, fewer stacks than a grid step", (2, 256, 3, 128),
     128, 64, jnp.float32, True),
    ("four chunks of 32 a stack", (1, 1024, 2, 128), 128, 32, jnp.bfloat16,
     True),
    ("half a stack", (4, 64, 32, 128), 128, 64, jnp.bfloat16, False),
    ("a ragged sequence", (1, 16384 + 40, 32, 128), 128, 64, jnp.bfloat16,
     False),
    ("stacks no grid step divides", (1, 11 * 128, 32, 128), 128, 64,
     jnp.bfloat16, False),
    ("a head 64 wide", (1, 16384, 32, 64), 64, 64, jnp.bfloat16, False),
    ("values 64 wide", (1, 16384, 32, 128), 64, 64, jnp.bfloat16, False),
    ("a chunk that fills no stack", (1, 16128, 32, 128), 128, 48,
     jnp.bfloat16, False),
    ("float16 operands", (1, 16384, 32, 128), 128, 64, jnp.float16, False),
])
def test_supported_follows_from_the_shapes_alone(what, shape, dv, chunk,
                                                 dtype, takes):
    assert K.supported(shape, dv, chunk, dtype) is takes, what


@pytest.mark.parametrize("width,fused", [(128, 1), (16, 0)],
                         ids=["a head 128 wide", "a head 16 wide"])
def test_the_mixer_counts_the_form_that_ran(width, fused):
    """``monitor.kernels.kda_chunk_calls{fused, kept}``: 1 where the mixer's
    shapes take the kernels (``kept``: what their backward reads beside the
    operands, ``K.KEPT``: a state and the solve a stack), 0 where the ``jnp``
    form ran (a state a chunk), and the two give the same layer."""
    from paddle_tpu import monitor
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.parallel import transformer as T

    cfg = kimi_linear.kimi_linear_tiny_config(
        kda_heads=1, kda_head_dim=width, kda_chunk=64, max_seq=128)
    keys = jax.random.split(jax.random.PRNGKey(11), 1)
    stack = lambda fold, fan, shape: jax.vmap(         # noqa: E731
        lambda key: jax.random.normal(jax.random.fold_in(key, fold), shape)
        * fan ** -0.5)(keys)
    leaves = {n: a[0] for n, a in T._kda_leaves(stack, keys, cfg).items()}
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 128, cfg.hidden))
    mon = monitor.enable()
    try:
        # the registry outlives a session: count from where it stood
        assert K.KEPT == "stack+T"
        calls = [mon.registry.counter("monitor.kernels.kda_chunk_calls",
                                      fused=f, kept=kept)
                 for f, kept in ((0, "chunk"), (1, K.KEPT))]
        before = [c.value for c in calls]
        got = T.kda_mixer(leaves, h, cfg)
    finally:
        monitor.disable()
    counted = [c.value - b for c, b in zip(calls, before)]
    assert counted[fused] == 1 and counted[1 - fused] == 0
    off_session = [c.value for c in calls]
    T.kda_mixer(leaves, h, cfg)
    assert [c.value for c in calls] == off_session
    if fused:
        import unittest.mock as mock
        with mock.patch.object(K, "supported", lambda *a: False):
            want = T.kda_mixer(leaves, h, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5 * np.abs(want).max())


def test_vmem_bytes_follows_the_blocks_of_a_grid_step():
    # seven operand blocks and two float32 of 1,024 tokens, beta's lane
    # tile, eight kept states and eight solves of two chunks side by side,
    # beta's gradient, twice; the state's gradient; a stack's values
    assert K.vmem_bytes(64, 32, 2) == 2 * (
        7 * 1024 * 128 * 2 + 2 * 1024 * 128 * 4 + 1024 * 128 * 4
        + 8 * 128 * 128 * 4 + 8 * 64 * 128 * 4 + 8 * 128 * 4) \
        + 128 * 128 * 4 + 96 * 128 * 128 * 4 + (8 << 20)
    # the kept blocks of a grid step: never more than a state a chunk's
    for chunk in (32, 64, 128):
        assert K.vmem_bytes(chunk, 32, 2) - K.vmem_bytes(64, 32, 2) \
            == 2 * 8 * (chunk - 64) * 128 * 4
