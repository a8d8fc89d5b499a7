"""The gated DELTA RULE of Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692) with a decay of its own for every CHANNEL of the key, in
its chunked form: ``kda_chunked``.

Head by head, from a zero state ``S`` [dk (key), dv (value)] float32, with
the per-token log-decays ``g_t`` [dk] <= 0 and the write strengths ``beta_t``
in (0, 1):

    S' = diag(exp(g_t)) S_{t-1}                         the state decays,
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            is corrected toward
    o_t = S_t^T q_t                                     v_t at k_t, is read

(``kda_recurrence``: that, a token at a time; what the tests hold the chunked
form to).  What ``power_retention`` and ``ssd_scan`` carry is a state with a
decay; neither subtracts what the state already predicts (``S'^T k_t``), so
neither has the triangular solve below, and their decay is one scalar a head.

In chunks of ``C`` tokens, ``G_t`` the running sum of ``g`` inside the chunk
(its own token's included) and ``S_in`` the state the chunk found:

    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])        j < i
    T       = (I + A)^-1                          unit lower triangular
    W = T (beta k exp(G))     U = T (beta v)      U' = U - W S_in
    o       = (q exp(G)) S_in + tril(sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])) U'
    S_out   = diag(exp(G_C)) S_in + (k exp(G_C - G))^T U'

THE TRAP is ``exp(G_i - G_j)`` as two factors ``exp(G_i) exp(-G_j)``: at the
seeded extremes (``a_log`` = ln 16, a step of 0.7) ``G`` falls by 11 a token,
and ``exp(+700)`` at a chunk's last token is no float32.  A chunk is cut
into blocks of ``SUB`` rows.  A block pair UNDER the diagonal takes its
reference at the row block's FIRST row ``r``: ``exp(G_i - G_r)`` and ``exp(G_r
- G_j)`` are both at most 1 for every i in the row block and j before it, and
the pair is one matrix product.  A block ON the diagonal is summed channel by
channel over ``exp(G_i - G_j)`` [SUB, SUB, dk], masked BEFORE the exponential.
An underflow to 0 is exact enough; an ``inf`` is not, and none is formed.

``T``: ``A`` is strictly lower triangular, so ``A^C = 0`` and ``(I + A)^-1 =
(I - A)(I + A^2)(I + A^4)...`` exactly, log2(C) squarings in float32 at the
highest matmul precision (a row-by-row substitution is C dependent steps).

Two phases.  What a chunk needs of ITSELF (``A``, ``T``, ``W``, ``U``, the
masked ``q k`` block, the three decayed copies) is made for ``GROUP`` chunks
at a time, all heads at once, under a ``jax.checkpoint`` of its own (a
backward holds one group's [SUB, SUB, dk] blocks and never the sequence's);
then ONE scan over the chunks carries the state: four matrix products a
chunk.  The scan's backward keeps a state a chunk (``kept_state_bytes``), as
``ssd_scan``'s does.

Plain ``jax.numpy``, differentiated by JAX: the path every shape takes
today.  A Pallas kernel for the chunk bodies (the state in VMEM across a
sequence's chunks) is ROADMAP.md's; ``count_call("kda_chunk", fused=0)`` at
the call site says which ran.
"""

import functools

import jax
import jax.numpy as jnp

__all__ = ["kda_recurrence", "kda_chunked", "kept_state_bytes", "SUB",
           "GROUP"]

SUB = 16        # rows of a block inside a chunk
GROUP = 8       # chunks whose own parts are made at once
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def kept_state_bytes(batch, seq, chunk, heads, dk, dv):
    """What the scan over chunks keeps for its backward: a float32 state
    [dk, dv] a chunk and head."""
    return batch * -(-seq // chunk) * heads * dk * dv * 4


def kda_recurrence(q, k, v, g, beta, state=None):
    """The delta rule a token at a time, in float32: q, k, g [b, S, H, dk],
    v [b, S, H, dv], beta [b, S, H]; the outputs [b, S, H, dv] float32.
    ``state`` [b, H, dk, dv]: the state before the first token (None:
    zeros)."""
    b, S, H, dk = k.shape
    if state is None:
        state = jnp.zeros((b, H, dk, v.shape[-1]), _F32)

    def step(S_, x):
        q_t, k_t, v_t, g_t, b_t = x
        S_ = S_ * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", S_, k_t, precision=_HIGHEST)
        S_ = S_ + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t,
                             v_t - seen, precision=_HIGHEST)
        return S_, jnp.einsum("bhkv,bhk->bhv", S_, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0)
               for a in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(step, state, xs)[1], 0, 1)


def _decay_blocks(G):
    """Of the running log-decays ``G`` [..., C, dk] of whole chunks: the
    three factor arrays every decayed product of the chunk shares.  ``rows``
    [..., n, SUB, dk], ``exp(G_i - G_r)`` of each row against its block's
    first; ``cols`` [..., n (row block), n, SUB, dk], ``exp(G_r - G_j)`` of
    each row BEFORE that block against the block's first, zero from the
    block on; ``diag`` [..., n, SUB, SUB, dk], ``exp(G_i - G_j)`` inside a
    block for j <= i, zero above.  Every exponent formed is <= 0."""
    C, dk = G.shape[-2:]
    n = C // SUB
    Gb = G.reshape(G.shape[:-2] + (n, SUB, dk))
    first = Gb[..., 0, :]                                   # [..., n, dk]
    rows = jnp.exp(Gb - first[..., None, :])
    before = (jnp.arange(n)[:, None] > jnp.arange(n)[None, :])[
        :, :, None, None]                                   # [I, J, 1, 1]
    cols = jnp.where(before, jnp.exp(jnp.where(
        before, first[..., :, None, None, :] - Gb[..., None, :, :, :], 0.0)),
        0.0)
    under = (jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :])[
        :, :, None]                                         # [i, j, 1]
    diag = jnp.where(under, jnp.exp(jnp.where(
        under, Gb[..., :, None, :] - Gb[..., None, :, :], 0.0)), 0.0)
    return rows, cols, diag


def _decayed_product(a, b, blocks, dtype):
    """``M[i, j] = sum_c a_i[c] b_j[c] exp(G_i[c] - G_j[c])`` for j <= i,
    zero above: a, b [..., C, dk] float32 -> [..., C, C] float32.  Block
    pairs under the diagonal are ONE matrix product of the two sides'
    factored copies (operands in ``dtype``, float32 sums); the diagonal's
    blocks are summed channel by channel."""
    rows, cols, diag = blocks
    C, dk = a.shape[-2:]
    n = C // SUB
    ab = a.reshape(a.shape[:-2] + (n, SUB, dk))
    bb = b.reshape(ab.shape)
    off = jnp.einsum("...Iic,...IJjc->...IiJj", (ab * rows).astype(dtype),
                     (bb[..., None, :, :, :] * cols).astype(dtype),
                     preferred_element_type=_F32)
    on = jnp.sum(ab[..., :, None, :] * bb[..., None, :, :] * diag, axis=-1)
    same = jnp.eye(n, dtype=_F32)[:, None, :, None]         # [I, 1, J, 1]
    return (off + on[..., :, :, None, :] * same).reshape(
        a.shape[:-2] + (C, C))


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of strictly lower triangular ``A`` [..., C, C]: ``(I -
    A)(I + A^2)(I + A^4)...``, exact since ``A^C = 0``."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=_F32)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    inv, power, span = eye - A, A, 2
    while span < C:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def _chunk_parts(q, k, v, g, beta, dtype):
    """What each chunk needs of itself: q, k, g [..., C, dk], v [..., C,
    dv], beta [..., C] (the work is float32, whatever they arrive in).  ``(W, U, M, qg, kg, last)``: ``W`` [..., C,
    dk] and ``U`` [..., C, dv] behind the solve, ``M`` [..., C, C] the masked
    decayed ``q k`` block, ``qg = q exp(G)``, ``kg = k exp(G_C - G)`` and
    ``last = exp(G_C)`` [..., dk].  The matrix products' operands in
    ``dtype``."""
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)
    blocks = _decay_blocks(G)
    C = q.shape[-2]
    strict = jnp.tril(jnp.ones((C, C), _F32), -1)
    A = _decayed_product(k, k, blocks, dtype) * strict * beta[..., None]
    T = _unit_lower_inverse(A).astype(dtype)
    decay = jnp.exp(G)
    W = jnp.matmul(T, (beta[..., None] * k * decay).astype(dtype),
                   preferred_element_type=_F32)
    U = jnp.matmul(T, (beta[..., None] * v).astype(dtype),
                   preferred_element_type=_F32)
    M = _decayed_product(q, k, blocks, dtype)
    kg = k * jnp.exp(G[..., -1:, :] - G)
    return (W.astype(dtype), U, M.astype(dtype), (q * decay).astype(dtype),
            kg.astype(dtype), decay[..., -1, :])


def kda_chunked(q, k, v, g, beta, *, chunk=64, state=None):
    """The delta rule in chunks of ``chunk`` tokens (a multiple of SUB; a
    ragged last chunk is filled with tokens that write nothing): q, k [b, S,
    H, dk], v [b, S, H, dv], g [b, S, H, dk] float32 <= 0, beta [b, S, H]
    float32; the outputs [b, S, H, dv] in v's type.  The matrix products
    take their operands in q's type and sum in float32; decays, the solve
    and the carried state are float32.  ``state`` [b, H, dk, dv]: the state
    before the first token (None: zeros; the tests' handle on the carry)."""
    b, S, H, dk = k.shape
    dv = v.shape[-1]
    dtype = q.dtype
    assert chunk % SUB == 0, (chunk, SUB)
    n = -(-S // chunk)
    group = next(d for d in range(min(GROUP, n), 0, -1) if n % d == 0)

    def chunks(a):
        """[b, S, H, ...] -> [n / group, group, b, H, chunk, ...]"""
        a = jnp.pad(a, ((0, 0), (0, n * chunk - S)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, n // group, group, chunk, H) + a.shape[3:])
        return jnp.moveaxis(a, (1, 2, 0, 4), (0, 1, 2, 3))

    parts = jax.lax.map(
        jax.checkpoint(lambda xs: _chunk_parts(*xs, dtype=dtype)),
        tuple(chunks(a) for a in (q, k, v, g, beta)))
    # [n / group, group, ...] -> [n, ...]: the scan's turns
    W, U, M, qg, kg, last = (a.reshape((n,) + a.shape[2:]) for a in parts)
    if state is None:
        state = jnp.zeros((b, H, dk, dv), _F32)

    def turn(S_, x):
        W_, U_, M_, qg_, kg_, last_ = x
        seen = S_.astype(dtype)
        fresh = U_ - jnp.matmul(W_, seen, preferred_element_type=_F32)
        o = jnp.matmul(qg_, seen, preferred_element_type=_F32) + jnp.matmul(
            M_, fresh.astype(dtype), preferred_element_type=_F32)
        S_ = S_ * last_[..., None] + jnp.einsum(
            "...ck,...cv->...kv", kg_, fresh.astype(dtype),
            preferred_element_type=_F32)
        return S_, o.astype(v.dtype)

    o = jax.lax.scan(turn, state, (W, U, M, qg, kg, last))[1]
    # [n, b, H, chunk, dv] -> [b, S, H, dv]
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(
        b, n * chunk, H, dv)[:, :S]
