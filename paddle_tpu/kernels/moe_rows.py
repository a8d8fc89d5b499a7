"""The sum back of an expert layer's rows, as a Pallas TPU kernel that
follows the rows HELD: ``moe_rows_sum``.

``rows`` [M, E] are the (token, expert) pairs' results in sorted order;
``inv`` [T*k] says where pair j of token t lies among them (place
``inv[t*k + j]``), or holds a number >= M for a pair that has no row (its
expert is not on this device).  Token t of the result is the float32 sum of
its rows in slot order j = 0..k-1, rounded once to ``rows.dtype``: what
``sum(rows.at[inv.reshape(-1, k)].get(mode="fill", fill_value=0)
.astype(f32), 1)`` gives, bit for bit.

Why a manual kernel (PERF.md section 6, PR 31 and PR 40): XLA's gather
fetches a row for EVERY pair slot, a row at a time whatever it fetches (a
fill row costs what a real one does), and a device that holds a share of
the experts has a row for that share of the slots only (a quarter in
SmallThinker and LFM2, a sixteenth in Mistral-Small-4).  Here the work
follows the rows that exist:

- ``moe_rows_words``, one pass over the rows: a bfloat16 row becomes E/2
  32-bit words (whole registers of them: ``_half``) that lie CONTIGUOUS in
  HBM.  A tiled 16-bit array keeps two
  rows in each word and eight words' rows in a tile, so no DMA can lift one
  row out of it; the word pairs column c of the row's left half with column
  c of its right half, which a shift and a mask undo.  32-bit rows go as
  they are.
- XLA sorts each block of 256 tokens' pairs so that those with a row come
  first (a sort along 256 * k keys a block), and counts them.
- ``moe_rows_sum``, a grid step a block of tokens: the block's list comes to
  scalar memory, the scalar core starts ONE row DMA for each pair that has a
  row, all in flight at once, into a zeroed slot-major ``[k * tb, E/2]`` VMEM
  buffer, and waits for what it started by the bits of the count; the vector
  core sums the k slots in float32, sixteen tokens a trip, and writes the
  block in the result's own tiles.  A pair with no row costs nothing but its
  key in the sort.

On a v5e (my chip runs, PR 40, ``scripts/moe_rows_bench.py``): 1.22 ms where
the gather and sum take 7.62 at SmallThinker's 98,304 slots of which 24,576
hold a row of 2,560; 16 ns a fetched row.

interpret=None auto-selects the Pallas interpreter off-TPU, so the CPU tests
run the same code (kernels/flash_attention.py idiom).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (CompilerParams as _CompilerParams,
                      count_call as _count_call, on_tpu as _on_tpu)

__all__ = ["moe_rows_sum", "token_block", "vmem_bytes"]

TOKEN_BLOCK = 256               # tokens a grid step owns
BUFFER_VMEM = 9 * 2 ** 20       # the buffer of fetched rows may take this much
UNROLL = 4                      # rows a trip of the scalar loop starts
SUM_TOKENS = 16                 # tokens a trip of the vector loop sums


def token_block(k, width):
    """Tokens a grid step owns: TOKEN_BLOCK, halved while the ``[k * tb,
    width]`` buffer of fetched rows (32-bit elements) would pass BUFFER_VMEM
    (256 at the three held cells' shapes: 7.9, 4.2 and 8.4 MB)."""
    tb = TOKEN_BLOCK
    while tb > SUM_TOKENS and k * tb * width * 4 > BUFFER_VMEM:
        tb //= 2
    return tb


def vmem_bytes(k, tb, width):
    """What a call asks Mosaic for: the buffer of 32-bit elements, the
    output block twice (the pipeline's two copies), two float32 accumulators
    of the block, and room."""
    return (k + 4) * tb * width * 4 + 2 * 2 ** 20


WORDS_BLOCK = 256               # rows a grid step of ``_words`` packs


def _float32(high_bits):
    """The float32 whose bits are given: a bfloat16 in the high half is
    that number exactly."""
    return jax.lax.bitcast_convert_type(high_bits, jnp.float32)


def _lanes(width):
    """Lanes of the view the rows are summed in and the result is written
    in, [.., width / lanes, lanes] for a row: a register's 128 where the
    width is whole registers (every width the chip is given), else the row."""
    return 128 if width % 128 == 0 else width


def _half(width):
    """Words a bfloat16 row of ``width`` columns goes in: half the columns,
    in whole registers where the row is whole registers (an ODD number of
    them goes as the larger half: 2,688 = 21 x 128 columns are 11 x 128
    words, the last register's high bits zero)."""
    return -(-width // 256) * 128 if width % 128 == 0 else width // 2


def _words_kernel(rows_ref, out_ref):
    """rows_ref: [bm, E] bfloat16; out_ref: [bm * chunks, lanes] uint32, row
    r's words in its rows [r * chunks, (r + 1) * chunks): a register column
    of the block goes out one sublane every ``chunks``.  Word c holds column
    c in its low bits and column ``_half(E) + c`` in its high bits (zero
    past the row's end)."""
    bm, width = rows_ref.shape
    lanes = out_ref.shape[1]
    chunks = out_ref.shape[0] // bm
    half = chunks * lanes
    bits = lambda v: jax.lax.bitcast_convert_type(
        v.astype(jnp.float32), jnp.uint32)
    for chunk in range(chunks):
        word = bits(rows_ref[:, pl.ds(chunk * lanes, lanes)]) >> 16
        if half + chunk * lanes < width:
            word = word | (bits(rows_ref[:, pl.ds(half + chunk * lanes,
                                                  lanes)])
                           & jnp.uint32(0xFFFF0000))
        out_ref[pl.ds(chunk, bm, stride=chunks)] = word


def _words(rows, interpret):
    """bfloat16 ``rows`` [M, E] as 32-bit words [M, 1, ``_half(E)``], column
    c of the left half in a word's low bits and column c of the right half
    in its high bits, each row's words contiguous in memory: a row DMA moves
    whole 32-bit words of ONE row, and a tiled 16-bit array keeps two rows
    in each word and eight such words' rows in a tile.  One pass."""
    m, width = rows.shape[0], _half(rows.shape[1])
    lanes = _lanes(width)
    chunks = width // lanes
    bm = min(WORDS_BLOCK, m)
    return pl.pallas_call(
        _words_kernel, grid=(-(-m // bm),),
        in_specs=[pl.BlockSpec((bm, rows.shape[1]), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((bm * chunks, lanes), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((m * chunks, lanes), jnp.uint32),
        compiler_params=_CompilerParams(dimension_semantics=("parallel",)),
        name="moe_rows_words",
        interpret=interpret,
    )(rows).reshape(m, 1, width)


def _rows_sum_kernel(count_ref, to_ref, place_ref, rows_ref, out_ref, buf,
                     sem, *, k, tb, words):
    """One block of ``tb`` tokens.  count_ref: [blocks] int32, the rows each
    block fetches; to_ref, place_ref: [1, tb * k] int32 in scalar memory,
    the block's held pairs first: pair i is row ``place_ref[0, i]`` of
    rows_ref ([M, 1, W] in HBM) and goes to row ``to_ref[0, i]`` = j * tb + t
    of buf ([k * tb + 1, 1, W] VMEM, slot-major; the last row takes what a
    trip of the loop starts past the count); out_ref: [tb, E]; sem: one DMA
    semaphore that every row copy signals.  With ``words`` the elements are
    two bfloat16 each (``_words``)."""
    buf[...] = jnp.zeros_like(buf)
    trips = (count_ref[pl.program_id(0)] + (UNROLL - 1)) // UNROLL

    def fetch(i, c):
        for u in range(UNROLL):
            at = i * UNROLL + u
            pltpu.make_async_copy(rows_ref.at[place_ref[0, at]],
                                  buf.at[to_ref[0, at]], sem).start()
        return c

    jax.lax.fori_loop(0, trips, fetch, 0)

    # the semaphore counts what has arrived, and a wait takes off what its
    # descriptor would move: so the rows started are waited for by the bits
    # of their count, 2^b rows at a time, and not one by one
    started = trips * UNROLL
    for bit in range((tb * k).bit_length()):
        part = buf.at[pl.ds(0, 1 << bit)]

        @pl.when((started & (1 << bit)) != 0)
        def _wait():
            pltpu.make_async_copy(part, part, sem).wait()

    # SUM_TOKENS tokens a trip, a register column (128 lanes) at a time: in
    # the buffer a row is width / lanes consecutive sublanes of a 128-lane
    # view, so a strided read brings the same columns of successive tokens
    # into one register, which is how the result's tiles hold them
    width = buf.shape[-1]
    lanes = _lanes(width)
    chunks = width // lanes
    flat = buf.reshape((k * tb + 1) * chunks, lanes)

    def sum_tokens(i, c):
        first = pl.multiple_of(i * SUM_TOKENS, SUM_TOKENS)
        tokens = pl.ds(first, SUM_TOKENS)
        for chunk in range(chunks):
            # zero first, as a reduce does: -0 alone sums to +0
            low = high = jnp.zeros((SUM_TOKENS, lanes), jnp.float32)
            for j in range(k):
                some = flat[pl.ds((j * tb + first) * chunks + chunk,
                                  SUM_TOKENS, stride=chunks)]
                if words:
                    low = low + _float32(some << 16)
                    high = high + _float32(some & jnp.uint32(0xFFFF0000))
                else:
                    low = low + some.astype(jnp.float32)
            out_ref[tokens, pl.ds(chunk * lanes, lanes)] = low.astype(
                out_ref.dtype)
            # (of an odd number of registers the last word has no high half)
            if words and width + chunk * lanes < out_ref.shape[1]:
                out_ref[tokens, pl.ds(width + chunk * lanes, lanes)] = (
                    high.astype(out_ref.dtype))
        return c

    jax.lax.fori_loop(0, tb // SUM_TOKENS, sum_tokens, 0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _rows_sum(rows, inv, k, interpret):
    (m, out_width), dtype = rows.shape, rows.dtype
    words = dtype == jnp.bfloat16
    assert words or dtype.itemsize == 4, dtype
    rows = _words(rows, interpret) if words else rows[:, None, :]
    width = rows.shape[2]
    tokens = inv.shape[0] // k
    tb = token_block(k, width)
    blocks = -(-tokens // tb)
    # a block's pairs that hold a row, first: each with its place among the
    # rows and its row of the block's buffer, j * tb + t; a token past the
    # last has no row anywhere, and a pair with none is sent to the
    # buffer's spare row (from row 0) should the loop's last trip reach it
    inv = jnp.pad(inv.astype(jnp.int32), (0, blocks * tb * k - tokens * k),
                  constant_values=m).reshape(blocks, tb * k)
    held = inv < m
    slot = jnp.arange(tb * k, dtype=jnp.int32)
    to, place = jax.lax.sort(
        (jnp.where(held, slot % k * tb + slot // k, k * tb),
         jnp.where(held, inv, 0)), dimension=1, num_keys=1, is_stable=False)
    pairs = pl.BlockSpec((None, 1, tb * k), lambda b, count: (b, 0, 0),
                         memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_rows_sum_kernel, k=k, tb=tb, words=words),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[pairs, pairs, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb, out_width), lambda b, count: (b, 0)),
            scratch_shapes=[pltpu.VMEM((k * tb + 1, 1, width), rows.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((blocks * tb, out_width), dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(k, tb, width)),
        name="moe_rows_sum",
        interpret=interpret,
    )(jnp.sum(held, axis=1, dtype=jnp.int32), to[:, None], place[:, None],
      rows)
    return out[:tokens]


def moe_rows_sum(rows, inv, k, interpret=None):
    """``rows`` [M, E] (bfloat16 with E even, or a 32-bit type), ``inv``
    [T*k] int (a row's index, or >= M for a pair with no row): [T, E], token
    t the float32 sum of ``rows[inv[t*k + j]]`` over its held slots in order
    j = 0..k-1, rounded once to ``rows.dtype``.  Under a monitor session
    every traced call counts in ``monitor.kernels.moe_rows_sum_calls``
    (``fused`` 1: there is no other path)."""
    if interpret is None:
        interpret = not _on_tpu()
    _count_call("moe_rows_sum", fused=1, k=k)
    return _rows_sum(rows, inv, k, bool(interpret))
