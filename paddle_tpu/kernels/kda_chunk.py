"""The gated DELTA RULE of Kimi Delta Attention (Kimi Linear,
arXiv:2510.26692) with a decay of its own for every CHANNEL of the key, in
its chunked form: ``kda_chunk`` (Pallas TPU kernels, forward and backward)
and ``kda_chunked`` (the same chunks in ``jax.numpy``).

Head by head, from a zero state ``S`` [dk (key), dv (value)] float32, with
the per-token log-decays ``g_t`` [dk] <= 0 and the write strengths ``beta_t``
in (0, 2) (Kimi-Linear's ``sigmoid`` keeps them under 1; Solar-Open2's
``kda_allow_neg_eigval`` doubles it, arXiv:2411.12537):

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

THE RANGE OF ``beta``.  Along ``k_t`` (a unit vector) a write multiplies what
the state held by ``1 - beta_t``: under 1 it shrinks it, AT 1 it replaces it
(``S^T k_t = v_t`` exactly, whatever stood there), past 1 it turns its sign,
and at 2 it would be a reflection, ``I - 2 k k^T``, that shrinks nothing.
Nothing in the chunks' algebra asks for ``beta`` < 1 (``A`` is nilpotent
whatever its entries), but two things change.  An ERROR the state carries
along ``k`` is no longer damped by a write there, it is carried on with its
sign turned, so what leaves such errors behind is the decay alone.  And the
squarings stop being exact enough: ``(I + A)^-1`` stays well conditioned
(its entries under 2, its condition about 30 at ``beta`` = 1.999), but
``A^2 .. A^32`` are formed on the way, and where a chunk's keys lean one way
(the mixer's do: a ``silu`` stands before the L2 norm, any two keys of a
head at a cosine near 0.1 to 0.3) every entry of ``A`` is positive, ``A^16``
reaches 4.6e3 and cancels in float32 to three digits: 1.8e-3 of ``T`` at
``beta`` = 1.999, 1.4e-4 at 1.5, 5e-6 at 1, 2e-7 at 0.5
(``tests/test_kda_chunk.py::test_the_solve_by_doubling_..``; PERF.md section
6, PR 67).  So where the strengths may pass 1 (``over_one``) the solve is BY
DOUBLING: the inverse of a block of 2 s rows from those of its halves,
``[[T1, 0], [-T2 A21 T1, T2]]``, from single rows up; level ``s`` is ``T <- T
- T (A on the level's pairs) T`` on the block-diagonal ``T`` so far, two
products a level as the squarings take, the first level none (``T = I``),
and nothing larger than the inverse's own blocks is ever formed (2e-7 at
every strength).  ``over_one`` False is the squarings, bit for bit what
Kimi-Linear's programs had.

``kda_chunked`` in two phases.  What a chunk needs of ITSELF (``A``, ``T``, ``W``, ``U``, the
masked ``q k`` block, the three decayed copies) is made for ``GROUP`` chunks
at a time, all heads at once, under a ``jax.checkpoint`` of its own (a
backward holds one group's [SUB, SUB, dk] blocks and never the sequence's);
then ONE scan over the chunks carries the state: four matrix products a
chunk.  The scan's backward keeps a state a chunk, as ``ssd_scan``'s does.

``kda_chunked`` is plain ``jax.numpy``, differentiated by JAX: where the
kernels below do not take the shapes (``supported``), and the tests' second
opinion beside ``kda_recurrence``.

THE KERNELS (``kda_chunk``: ``kda_chunk_fwd`` / ``kda_chunk_bwd`` in a
trace, under a ``jax.custom_vjp``).  Grid (batch, head, step), the last
sequential; a step walks STEP_STACKS stacks in a loop, a STACK being the
ROWS = 128 rows of 128 // C chunks, one under another.  q, k, v, g cross the
door as lane blocks of the arrays the mixer has ([b, S, H * 128]: head h is
lane block h, nothing is transposed or re-tiled in HBM; g float32), beta as
a column a head of [b, S, H].  The state lives in VMEM scratch across a
sequence's chunks, TRANSPOSED ([dv, dk]: a key channel a lane, as the
decays are).  What a chunk needs of ITSELF is made for a whole stack at
once, every matrix [ROWS, ROWS] with a chunk's block on its diagonal:

- the decays' rule above taken down to single rows (``_level``): the pair
  (i, j) belongs to the level whose aligned block of 2 s rows is the
  smallest that holds both, j in its left half and i in its right; against
  ``G_m`` at the right half's first row, ``f = exp(-|G - G_m|)`` is ``exp(G_i
  - G_m)`` on the right and ``exp(G_m - G_j)`` on the left, never over 1, so
  EVERY pair is a matrix product's (log2(C) products ``[q f; k f] (k f)^T``
  a stack, each kept where its level's pairs are) and no [SUB, SUB, dk]
  block is formed;
- ``T = (I + diag(beta) P)^-1`` by the same squarings as
  ``_unit_lower_inverse`` (``over_one``: by the same doubling, its levels'
  pairs the decays' own ``code`` masks), float32 on the MXU, the chunks'
  blocks side by side so that a product's rows are one chunk's;
- the forward takes ``[U | W] = T [beta v | beta k exp(G)]`` before the state
  is at hand, so that a chunk's turn at the state is two products: ``X = U
  - W S``, ``S' = diag(lam) S + kh^T X`` (``o = qt S + Q X`` behind them).

Out: ``o`` in v's type and, from the forward that runs for a backward
(``save``), what that backward READS, float32 a STACK (``kept_state_bytes``:
three quarters of a state a chunk): the state the stack FOUND [b, stacks, H,
dv, dk] and ``T`` of its chunks side by side [b, H, stacks, C, ROWS], as
either solve left it (``save`` False, the forward where nothing is
differentiated, writes neither and is the text it was; under a layer's remat
BOTH of a step's forwards are the saving one, the first one's kept values
dropped: PERF.md section 6, PR 72).  The backward walks the stacks in
reverse with the state's gradient ``D`` in VMEM, re-makes a stack's own
parts BUT the solve,
takes ``[U | W]`` by the forward's one product and the states the stack's
later chunks found and ``X`` by the forward's two lines (single-pass
products: no ``highest`` product stands between one chunk's state and the
next, and what is rebuilt is the forward's bit for bit), and
turns at ``D`` with two products a chunk (``dX = Q^T do + kh D``, ``D' =
do^T qt + diag(lam) D - dX^T W``: ``(beta dr)^T kt = dX^T W``); then, for
the whole stack, ``dr = T^T dX``, ``dA = -strict_tril(dr X^T)``, ``dQ =
tril(do X^T)``, beta's and v's gradients, and q's, k's and G's through the
levels' products by the SAME factors (``G_m``'s own share cancels: ``f_i
f_j`` does not depend on it); ``dg`` is the reverse running sum of ``dG``
inside the chunk.  Precision as ``kda_chunked``'s: matrix products take
operands in q's type and sum in float32; decays, the solve and the carried
state float32.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["kda_recurrence", "kda_chunked", "kda_chunk", "supported",
           "vmem_bytes", "kept_state_bytes", "KEPT", "SUB", "GROUP", "LANES",
           "ROWS", "STEP_STACKS"]

SUB = 16        # rows of a block inside a chunk
GROUP = 8       # chunks whose own parts are made at once
LANES = 128     # the head width the kernels are written for
ROWS = 128      # rows of a STACK: the chunks whose own parts are made at once
STEP_STACKS = 8     # stacks a grid step of the kernels walks
# what the kernels' backward reads of the saving forward, beside the operands
# (the mixer's ``kda_chunk_calls{kept}``): a state and the solve ``T`` a stack
KEPT = "stack+T"
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def kept_state_bytes(batch, seq, chunk, heads, dk, dv):
    """What the kernels' saving forward keeps for their backward beside the
    operands, float32, a STACK of ROWS rows and head: the state [dk, dv] the
    stack found and its chunks' solves side by side [chunk, ROWS].  403 MB
    a layer at Kimi-Linear's [16384, 32 x 128] and 201 MB at Solar-Open2's
    [4096, 64 x 128], chunks of 64: three quarters of a state a CHUNK (537
    and 268 MB, what ``kda_chunked``'s scan keeps and these kernels kept
    before PR 72); five eighths at chunks of 32; at ONE chunk a stack, which
    no configuration has, a state and a solve as large."""
    return batch * -(-seq // ROWS) * heads * (dk * dv + chunk * ROWS) * 4


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


def _unit_lower_inverse(A, over_one=False):
    """``(I + A)^-1`` of strictly lower triangular ``A`` [..., C, C]: ``(I -
    A)(I + A^2)(I + A^4)...``, exact since ``A^C = 0``; ``over_one``: by
    DOUBLING (the module's docstring), which no power of ``A`` enters."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=_F32)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    if over_one:
        i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        code = jnp.where(j < i, i ^ j, 0)
        inv, s = eye - jnp.where(code == 1, A, 0.0), 2
        while s < C:
            inv = inv - mm(mm(inv, jnp.where((code >= s) & (code < 2 * s),
                                             A, 0.0)), inv)
            s *= 2
        return inv
    inv, power, span = eye - A, A, 2
    while span < C:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        span *= 2
    return inv


def _chunk_parts(q, k, v, g, beta, dtype, over_one=False):
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
    T = _unit_lower_inverse(A, over_one).astype(dtype)
    decay = jnp.exp(G)
    W = jnp.matmul(T, (beta[..., None] * k * decay).astype(dtype),
                   preferred_element_type=_F32)
    U = jnp.matmul(T, (beta[..., None] * v).astype(dtype),
                   preferred_element_type=_F32)
    M = _decayed_product(q, k, blocks, dtype)
    kg = k * jnp.exp(G[..., -1:, :] - G)
    return (W.astype(dtype), U, M.astype(dtype), (q * decay).astype(dtype),
            kg.astype(dtype), decay[..., -1, :])


def kda_chunked(q, k, v, g, beta, *, chunk=64, state=None, over_one=False):
    """The delta rule in chunks of ``chunk`` tokens (a multiple of SUB; a
    ragged last chunk is filled with tokens that write nothing): q, k [b, S,
    H, dk], v [b, S, H, dv], g [b, S, H, dk] float32 <= 0, beta [b, S, H]
    float32; the outputs [b, S, H, dv] in v's type.  The matrix products
    take their operands in q's type and sum in float32; decays, the solve
    and the carried state are float32.  ``state`` [b, H, dk, dv]: the state
    before the first token (None: zeros; the tests' handle on the carry).
    ``over_one``: strengths may pass 1, the solve by doubling."""
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
        jax.checkpoint(lambda xs: _chunk_parts(*xs, dtype=dtype,
                                               over_one=over_one)),
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


# ---------------------------------------------------------------------------
# The same chunks as Pallas TPU kernels: ``kda_chunk``


def _step_stacks(n):
    """Stacks a grid step walks of a sequence's ``n``: STEP_STACKS, all of a
    shorter sequence, 0 where neither is whole."""
    if n % STEP_STACKS == 0:
        return STEP_STACKS
    return n if n < STEP_STACKS else 0


def supported(shape, dv, chunk, dtype):
    """Whether the kernels take keys of ``shape`` = [b, S, H, dk] beside
    values ``dv`` wide: a head one lane tile wide in both, chunks of whole
    SUB-row blocks that fill a stack of ROWS rows, whole stacks, whole grid
    steps of stacks, operands in bfloat16 or float32."""
    _, S, _, dk = shape
    return (dk == LANES and dv == LANES and chunk % SUB == 0
            and ROWS % chunk == 0 and S % ROWS == 0
            and _step_stacks(S // ROWS) > 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(_F32)))


def vmem_bytes(chunk, heads, itemsize, stacks=STEP_STACKS):
    """What a grid step of the BACKWARD (the larger of the two) asks Mosaic
    for: its pipelined blocks twice (q, k, v, do, dq, dk, dv a head wide in
    the operands' type; g and dg float32; the write strengths a column a
    head, padded to a lane tile; a state and a solve kept a stack; beta's
    gradient a row a stack), the state's gradient, and room for a stack's
    values.  The heads enter through beta's block alone: 22.1 MiB at 32
    heads and at 64 alike (a lane tile holds either; a grid step is ONE
    head's lane block whatever the array's width, 4,096 or 8,192 lanes)."""
    tokens = stacks * ROWS
    blocks = 2 * (7 * tokens * LANES * itemsize + 2 * tokens * LANES * 4
                  + tokens * -(-heads // LANES) * LANES * 4
                  + stacks * (LANES * LANES + chunk * ROWS) * 4
                  + stacks * ROWS * 4)
    return blocks + LANES * LANES * 4 + 96 * ROWS * LANES * 4 + (8 << 20)


def _indices(C):
    """``row`` [ROWS, 128]: a token's place in its chunk.  ``code`` [ROWS,
    ROWS] of the pair (i, j) of ONE chunk: ``i ^ j`` (>= 1) under the
    diagonal, whose highest bit is the half-width of the smallest aligned
    block that holds both, j in its left half and i in its right; 0 on the
    diagonal; -1 above it and between chunks."""
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) & (C - 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    return row, jnp.where((j < i) & ((i ^ j) < C), i ^ j,
                          jnp.where(j == i, 0, -1))


def _running(x, row, C, back=False):
    """The running sum down each chunk's rows of ``x`` [ROWS, 128], a row's
    own included (``back``: up from the chunk's last row), by log2(C)
    shifted adds."""
    s = 1
    while s < C:
        if back:
            x = x + jnp.where(row < C - s, pltpu.roll(x, ROWS - s, 0), 0.0)
        else:
            x = x + jnp.where(row >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _level(G, s, row):
    """The decays of the pairs whose smallest aligned block is 2 ``s`` rows,
    against ``G_m`` at the first row ``m`` of the block's right half: ``f``
    = ``exp(-|G - G_m|)``, which is ``exp(G_i - G_m)`` on the right half's
    rows and ``exp(G_m - G_j)`` on the left's: ``f_i f_j`` = ``exp(G_i -
    G_j)`` for i right and j left, and no factor over 1 (the module's trap;
    ``_decay_blocks``' rule taken down to single rows, so that every pair
    is a matrix product's)."""
    if 2 * s >= 8:      # whole sublane tiles: a row of G spread over each
        ref = jnp.concatenate(
            [jnp.broadcast_to(G[at + s:at + s + 1, :], (2 * s, LANES))
             for at in range(0, ROWS, 2 * s)], axis=0)
    else:               # inside a tile: m's row alone, rolled over its block
        ref, t = jnp.where((row & (2 * s - 1)) == s, G, 0.0), 1
        while t < s:
            ref = ref + pltpu.roll(ref, t, 0)
            t *= 2
        ref = ref + pltpu.roll(ref, ROWS - s, 0)
    return jnp.exp(-jnp.abs(G - ref))


def _mm32(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product of float32 operands (the solve's)."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _column(x, at):
    """Column ``at`` (traced) of a lane-narrow block x [rows, n], as [rows,
    1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == at, x, 0.0), axis=1, keepdims=True)


def _rows(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


class _Own:
    """What the chunks of one STACK (ROWS rows of one head: 128 // C chunks,
    one under another) need of THEMSELVES, whatever state they find, in the
    module's notation and all at once: every matrix is [ROWS, ROWS] with a
    chunk's block on its diagonal and zeros between chunks.  q, k [ROWS, dk]
    in the operands' type; g [ROWS, dk] and beta [ROWS, 1] float32.  ``side``:
    the solve a forward KEPT (``inverse``'s [C, ROWS]), read and not made
    again."""

    def __init__(self, q, k, g, beta, row, code, C, over_one=False,
                 side=None):
        self.C, self.over_one, dt = C, over_one, q.dtype
        self.chunks = [slice(at, at + C) for at in range(0, ROWS, C)]
        self.qf, self.kf = qf, kf = q.astype(_F32), k.astype(_F32)
        self.G = G = _running(g, row, C)
        self.Q = jnp.where(code == 0, jnp.sum(qf * kf, axis=1, keepdims=True),
                           0.0)
        self.P = jnp.zeros((ROWS, ROWS), _F32)
        self.levels, s = [], C // 2
        while s:
            # the level's half-width, where its pairs lie, the factor and
            # the decayed ``q f`` and ``k f`` in the operands' type
            f = _level(G, s, row)
            self.levels.append((s, (code >= s) & (code < 2 * s), f,
                                (qf * f).astype(dt), (kf * f).astype(dt)))
            s //= 2
        for _, here, _, qd, kd in self.levels:
            # every pair of a right half's row with a left half's of ANY
            # block (all finite: no factor is over 1); the level's are kept
            pairs = _dot(jnp.concatenate([qd, kd], axis=0), kd, _NT)
            self.Q = self.Q + jnp.where(here, pairs[:ROWS], 0.0)
            self.P = self.P + jnp.where(here, pairs[ROWS:], 0.0)
        if side is None:
            side = self.inverse(beta * self.P)
        else:
            self._lanes()
        self.side, self.T = side, self.spread(side)
        lasts = [G[c.stop - 1:c.stop, :] for c in self.chunks]
        self.lam = [jnp.exp(last) for last in lasts]        # [1, dk] each
        self.eG = jnp.exp(G)
        self.eH = jnp.exp(_rows([jnp.broadcast_to(last, (C, LANES))
                                 for last in lasts]) - G)
        self.kt, self.qt, self.kh = kf * self.eG, qf * self.eG, kf * self.eH

    def _lanes(self):
        """``lane`` and ``place`` of the chunks' blocks SIDE BY SIDE ([C,
        ROWS]: chunk c in lanes c C onward); ``own``: each chunk's lanes."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (self.C, ROWS), 1)
        place = jax.lax.broadcasted_iota(jnp.int32, (self.C, ROWS), 0)
        self.own = [(lane >= c.start) & (lane < c.stop) for c in self.chunks]
        return lane, place

    def spread(self, side):
        """The blocks side by side -> each on the diagonal of [ROWS, ROWS]."""
        return _rows([jnp.where(mine, side, 0.0) for mine in self.own])

    def inverse(self, A):
        """``(I + A)^-1`` of the chunks' strictly lower blocks on ``A``'s
        diagonal, the chunks' SIDE BY SIDE ([C, ROWS]: what a saving forward
        keeps, ``spread`` puts on the diagonal), as ``_unit_lower_inverse``:
        ``(I - A)(I + A^2)(I + A^4)...`` in float32 on the MXU, with the
        blocks side by side on a product's left, so that its rows are one
        chunk's, and on a diagonal on its right.  ``over_one``: by doubling
        (the module's docstring), in the same two products a level: ``inv``
        side by side times the level's pairs of ``A``, times ``inv`` on the
        diagonal."""
        C, spread = self.C, self.spread

        def beside(X):
            """Each chunk's block on the diagonal -> side by side."""
            return sum((X[c] for c in self.chunks[1:]), X[self.chunks[0]])

        power = beside(A)
        lane, place = self._lanes()
        eye = jnp.where((lane & (C - 1)) == place, 1.0, 0.0)
        if self.over_one:
            # the levels from single rows up; the first finds inv = I
            pairs = [jnp.where(here, A, 0.0)
                     for _, here, _, _, _ in reversed(self.levels)]
            inv = eye - beside(pairs[0])
            for level in pairs[1:]:
                inv = inv - _mm32(_mm32(inv, level), spread(inv))
            return inv
        inv = eye - power
        wide, span = spread(power), 2
        while span < C:
            power = _mm32(power, wide)
            wide = spread(power)
            inv = inv + _mm32(inv, wide)
            span *= 2
        return inv


def _solved(own, beta, vf, dt):
    """``[U | W] = T [beta v | beta kt]`` of a stack in ONE float32 product,
    before any state is at hand: ``U`` float32, ``W`` in the operands'
    type."""
    uw = _mm32(own.T, jnp.concatenate([beta * vf, beta * own.kt], axis=1))
    return uw[:, :LANES], uw[:, LANES:].astype(dt)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, chunk,
                save, over_one):
    """The state TRANSPOSED, [dv, dk]: a key channel a lane, as the decays
    are.  ``X = U - W S`` with ``[U | W] = T [beta v | beta kt]`` made
    before the state is at hand: a chunk's turn at the state is two
    products.  ``save``: for the backward, the solve of every stack side by
    side (``_Own.side``) and the state each STACK found."""
    if save:
        t_ref, kept_ref, s_ref = rest
    else:
        (s_ref,) = rest
    dt = q_ref.dtype
    head = pl.program_id(1)
    row, code = _indices(chunk)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def one(p, carry):
        at = pl.ds(pl.multiple_of(p * ROWS, ROWS), ROWS)
        beta = _column(beta_ref[at, :], head)
        own = _Own(q_ref[at, :], k_ref[at, :], g_ref[at, :], beta, row,
                   code, chunk, over_one)
        U, W = _solved(own, beta, v_ref[at, :].astype(_F32), dt)
        qt, kh = own.qt.astype(dt), own.kh.astype(dt)
        if save:
            t_ref[p], kept_ref[p] = own.side, s_ref[...]
        reads, xs = [], []
        for c, rows in enumerate(own.chunks):
            st = s_ref[...]
            sb = st.astype(dt)
            x = (U[rows] - _dot(W[rows], sb, _NT)).astype(dt)
            reads.append(_dot(qt[rows], sb, _NT))
            s_ref[...] = st * own.lam[c] + _dot(x, kh[rows], _TN)
            xs.append(x)
        o_ref[at, :] = (_rows(reads) + _dot(own.Q.astype(dt), _rows(xs))
                        ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // ROWS, one, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, t_ref,
                kept_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_ref, *,
                chunk):
    """The stacks from the last to the first, each from the solve the saving
    forward made of it (``t_ref``: whichever solve that was) and the state
    it FOUND; the states its later chunks found and ``X`` are the forward's
    own lines again (``[U | W] = T [beta v | beta kt]``, ``X = U - W S``,
    ``S' = diag(lam) S + kh^T X``), bit for bit.  ``D`` [dv, dk] the
    gradient of the state a chunk leaves.  A chunk's turn at ``D`` is two
    products: ``dX = Q^T do + kh D`` and ``D' = do^T qt + diag(lam) D - dX^T
    W`` (``W = T beta kt``: ``(beta dr)^T kt = dX^T W``); everything else of
    the module's docstring is made for the whole stack behind it."""
    C, dt = chunk, q_ref.dtype
    n = q_ref.shape[0] // ROWS
    head = pl.program_id(1)
    row, code = _indices(C)
    which = jax.lax.broadcasted_iota(jnp.int32, (n, ROWS), 0)

    @pl.when(pl.program_id(2) == 0)                 # the LAST stacks
    def _():
        d_ref[...] = jnp.zeros_like(d_ref)

    def one(t, dbetas):
        p = n - 1 - t
        at = pl.ds(pl.multiple_of(p * ROWS, ROWS), ROWS)
        beta = _column(beta_ref[at, :], head)
        own = _Own(q_ref[at, :], k_ref[at, :], g_ref[at, :], beta, row,
                   code, C, side=t_ref[p])
        qf, kf, do = own.qf, own.kf, do_ref[at, :]
        qt, kt, kh = (a.astype(dt) for a in (own.qt, own.kt, own.kh))
        vf = v_ref[at, :].astype(_F32)
        U, W = _solved(own, beta, vf, dt)
        found, sbs, xs = [kept_ref[p]], [], []
        for c, rows in enumerate(own.chunks):
            sbs.append(found[c].astype(dt))
            xs.append((U[rows] - _dot(W[rows], sbs[c], _NT)).astype(dt))
            if c + 1 < len(own.chunks):
                found.append(found[c] * own.lam[c]
                             + _dot(xs[c], kh[rows], _TN))
        xb = _rows(xs)
        rr = vf - _rows(
            [_dot(kt[rows], sb, _NT) for rows, sb in zip(own.chunks, sbs)])
        qdo = _dot(own.Q.astype(dt), do, _TN)
        D = d_ref[...]
        dX, dkh, ends = ([None] * len(own.chunks) for _ in range(3))
        for c, rows in reversed(list(enumerate(own.chunks))):
            db = D.astype(dt)
            dX[c] = qdo[rows] + _dot(kh[rows], db, _NT)
            dkh[c] = _dot(xb[rows], db)
            # d G_C: through kh = k exp(G_C - G) and lam = exp(G_C)
            ends[c] = jnp.broadcast_to(
                jnp.sum(own.kh[rows] * dkh[c], axis=0, keepdims=True)
                + own.lam[c] * jnp.sum(found[c] * D, axis=0, keepdims=True),
                (C, LANES))
            D = _dot(do[rows], qt[rows], _TN) + D * own.lam[c] \
                - _dot(dX[c].astype(dt), W[rows], _TN)
        d_ref[...] = D
        dX, dkh = _rows(dX), _rows(dkh)
        dr = _mm32(own.T, dX, _TN)
        bdr = beta * dr
        bdrb = bdr.astype(dt)
        dQ = jnp.where(code >= 0, _dot(do, xb, _NT), 0.0)
        dA = -jnp.where(code >= 1, _dot(dr.astype(dt), xb, _NT), 0.0)
        dbeta = jnp.sum(dr * rr, axis=1, keepdims=True) \
            + jnp.sum(dA * own.P, axis=1, keepdims=True)
        dkt = -_rows([_dot(bdrb[rows], sb)
                      for rows, sb in zip(own.chunks, sbs)])
        dqt = _rows([_dot(do[rows], sb)
                     for rows, sb in zip(own.chunks, sbs)])
        on = jnp.sum(jnp.where(code == 0, dQ, 0.0), axis=1, keepdims=True)
        dq = dqt * own.eG + on * kf
        dk = dkt * own.eG + dkh * own.eH + on * qf
        dG = own.qt * dqt + own.kt * dkt - own.kh * dkh \
            + jnp.where(row == C - 1, _rows(ends), 0.0)
        dP = beta * dA
        for _, here, f, qd, kd in own.levels:
            m = jnp.concatenate([jnp.where(here, dQ, 0.0),
                                 jnp.where(here, dP, 0.0)],
                                axis=0).astype(dt)
            # the rows' share (right halves: elsewhere m's rows are zero)
            # and the columns' (left halves)
            dl = _dot(m, kd)
            dc = _dot(m, jnp.concatenate([qd, kd], axis=0), _TN)
            dq = dq + dl[:ROWS] * f
            dk = dk + (dl[ROWS:] + dc) * f
            # G_m's own share cancels: f_i f_j does not depend on it
            dG = dG + f * (dl[:ROWS] * qf + (dl[ROWS:] - dc) * kf)
        dq_ref[at, :] = dq.astype(dq_ref.dtype)
        dk_ref[at, :] = dk.astype(dk_ref.dtype)
        dv_ref[at, :] = bdr.astype(dv_ref.dtype)
        dg_ref[at, :] = _running(dG, row, C, back=True)
        # the stack's column as a row of the step's [stacks, ROWS]
        as_row = jnp.sum(jnp.where(code == 0, dbeta, 0.0), axis=0,
                         keepdims=True)
        return jnp.where(which == p, as_row, dbetas)

    dbeta_ref[...] = jax.lax.fori_loop(0, n, one,
                                       jnp.zeros((n, ROWS), _F32))


class _Geom:
    """The shapes of one call and its block specs; ``flip`` walks the grid
    steps from the end."""

    def __init__(self, q, heads, chunk, flip=False):
        self.B, self.S, width = q.shape
        assert width == heads * LANES and supported(
            (self.B, self.S, heads, LANES), LANES, chunk, q.dtype), \
            (q.shape, heads, chunk, q.dtype)
        stacks = _step_stacks(self.S // ROWS)
        steps = self.S // ROWS // stacks
        at = (lambda i: steps - 1 - i) if flip else (lambda i: i)
        tokens = stacks * ROWS
        self.head = pl.BlockSpec((None, tokens, LANES),
                                 lambda b, h, i: (b, at(i), h))
        self.beta = pl.BlockSpec((None, tokens, heads),
                                 lambda b, h, i: (b, at(i), 0))
        # what a saving forward keeps for the backward, a stack: the solve
        # side by side and the state found
        self.solve = pl.BlockSpec((None, None, stacks, chunk, ROWS),
                                  lambda b, h, i: (b, h, at(i), 0, 0))
        self.kept = pl.BlockSpec((None, stacks, None, LANES, LANES),
                                 lambda b, h, i: (b, at(i), h, 0, 0))
        self.dbeta = pl.BlockSpec((None, None, stacks, ROWS),
                                  lambda b, h, i: (b, h, at(i), 0))
        self.grid = (self.B, heads, steps)
        # the state, or its gradient
        self.scratch = [pltpu.VMEM((LANES, LANES), _F32)]
        self.params = _CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(chunk, heads, q.dtype.itemsize,
                                        stacks))


def _fwd(q, k, v, g, beta, static, save):
    heads, chunk, interpret, over_one = static
    geom = _Geom(q, heads, chunk)
    out_specs = [geom.head]
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if save:
        out_specs += [geom.solve, geom.kept]
        out_shape += [
            jax.ShapeDtypeStruct(
                (geom.B, heads, geom.S // ROWS, chunk, ROWS), _F32),
            jax.ShapeDtypeStruct(
                (geom.B, geom.S // ROWS, heads, LANES, LANES), _F32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, save=save,
                          over_one=over_one),
        grid=geom.grid,
        in_specs=[geom.head] * 4 + [geom.beta],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=geom.scratch,
        compiler_params=geom.params, interpret=interpret,
        name="kda_chunk_fwd",
    )(q, k, v, g, beta)


def _bwd(static, res, do):
    heads, chunk, interpret, _ = static
    q, k, v, g, beta, solve, kept = res
    geom = _Geom(q, heads, chunk, flip=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=geom.grid,
        in_specs=[geom.head] * 4 + [geom.beta, geom.head, geom.solve,
                                    geom.kept],
        out_specs=[geom.head] * 4 + [geom.dbeta],
        out_shape=[like(q), like(k), like(v), like(g),
                   jax.ShapeDtypeStruct(
                       (geom.B, heads, geom.S // ROWS, ROWS), _F32)],
        scratch_shapes=geom.scratch,
        compiler_params=geom.params, interpret=interpret,
        name="kda_chunk_bwd",
    )(q, k, v, g, beta, do, solve, kept)
    # [B, H, stacks, ROWS] -> [B, S, H]
    return dq, dk, dv, dg, dbeta.reshape(geom.B, heads, geom.S).swapaxes(1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _delta(q, k, v, g, beta, static):
    return _fwd(q, k, v, g, beta, static, False)[0]


def _delta_fwd(q, k, v, g, beta, static):
    o, solve, kept = _fwd(q, k, v, g, beta, static, True)
    return o, (q, k, v, g, beta, solve, kept)


_delta.defvjp(_delta_fwd, _bwd)


def kda_chunk(q, k, v, g, beta, *, heads, chunk=64, interpret=None,
              over_one=False):
    """``kda_chunked`` from a zero state by the kernels, on the arrays as the
    mixer has them: q, k, v, g [b, S, heads * 128] (head h is lane block h;
    g float32), beta [b, S, heads] float32; the outputs [b, S, heads * 128]
    in v's type, at ``kda_chunked``'s precision (``supported`` must hold).
    Differentiable in all five; what the backward needs is the operands and,
    a stack, the state found and the solve (``kept_state_bytes``, ``KEPT``).
    ``over_one``: the
    strengths may pass 1 (``beta`` in (0, 2)), the solve by doubling."""
    b, S, width = k.shape
    assert width == heads * LANES and q.shape == v.shape == g.shape \
        == k.shape and k.dtype == v.dtype == q.dtype and supported(
            (b, S, heads, LANES), LANES, chunk, q.dtype), \
        (q.shape, k.shape, v.shape, g.shape, heads, chunk, q.dtype)
    if interpret is None:
        interpret = not _on_tpu()
    return _delta(q, k, v, g.astype(_F32), beta.astype(_F32),
                  (int(heads), int(chunk), bool(interpret), bool(over_one)))
