"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

Parity target: the reference's fused attention CUDA op
(operators/fused/multihead_matmul_op.cu, surfaced by
ir/multihead_matmul_fuse_pass.cc) — but trained-path capable: blockwise
streaming softmax never materializes the [S, S] score matrix in HBM, so both
memory and HBM traffic drop from O(S^2) to O(S * block).

Two layouts, one set of kernels (``_Geom``): the packed [B, S, H*D] entry the
models use (``flash_attention_packed``: the projections' own layout, each
128-lane head-block addressed in place by the BlockSpec index maps) and
[BH, S, D] behind ``flash_attention`` for head shapes the packed layout
cannot tile.

Several kv blocks (S above the block size): a grid row is one (batch row,
key/value head-block) pair and a grid step one (q block, kv block) tile of
``step_table``, built on the host from the shapes and the mask and read by
the index maps as scalar-prefetch operands: only the pairs the mask lets
something through are steps (the triangle under the diagonal, a window's
band, the rectangle, the block-diffusion rule's three parts).  A step holds
``heads_a_step`` query head-blocks,
looped inside it: of the key/value head-block's group where the queries are
grouped (PR 68), and where they are not, that many ADJACENT head-blocks of
the batch row, each with k and v of its own (PR 70: the k and v blocks are
as many head-blocks wide and the grid's key/value axis counts the row's
head-blocks a step's worth at a time; SWEEP_HEAD_BLOCKS at most, in the
backward SWEEP_BWD_HEAD_BLOCKS).  Either
way their q and o blocks are adjacent lane blocks of the packed array (one
DMA), k and v arrive once a step, the tile's mask is built once, and the
heads' chains (matmul, reduce, ``exp``, matmul) stand side by side in the
body for the compiler to interleave; ``heads`` is the largest divisor of
the group (of the row's head-blocks) whose step fits SWEEP_VMEM, from the
shapes alone, and a group's other chunks are positions of the grid
(forward) or further sweeps of the same grid row (backward).  The running max
(m), denominator (l) and output accumulator live in VMEM scratch across a q
block's sweep, a slot a head (the standard TPU flash schedule).  The
backward recomputes
the probabilities blockwise from the saved row logsumexp, in ONE kernel
(``flash_bwd_fused``): a grid row is a (batch row, key/value head-block)
pair, its steps the forward's table over each chunk of the group's query
head-blocks in turn, and a step computes of each of its heads one score
tile, one ``exp`` and from
them all three products (5 matmuls): dq into the q sweep's scratch, dk and
dv, summed over the step's heads, ONCE a step into rows ``kv block`` of two
float32 accumulators that hold the WHOLE
sequence in VMEM ([Sk, lanes] each: 16 MiB at S = 16,384), which sum over
the group because they are the key/value head's own, and leave once at the
grid row's last step.  Ungrouped heads of a step sum over nothing: the
accumulators and their output blocks are ``heads`` head-blocks wide and a
head adds into columns of its own, so every sum keeps its order and all
five results are the one-head step's bit for bit.  That runs wherever the accumulators and their output
blocks fit SWEEP_VMEM (``bwd_sweeps``, from the shapes alone; the call
states its ``vmem_limit_bytes``); a longer sequence takes the
FlashAttention-2 schedule of two kernels (``flash_bwd_dq`` q-major,
``flash_bwd_dkv`` kv-major: 7 matmuls and two ``exp`` passes a pair) with
the same sums in the same order.  One kv
block (S up to the block size, every BERT shape): grid (row-groups x
head-block-groups, q blocks, 1), the forward needs no running statistics
and the backward is the one-block ``flash_bwd_fused``, which shares its
recomputed probability tile between dq, dk and dv in the same way.  There,
where the whole
sequence is one block, a grid step carries a fixed amount of work whatever
S is: ``step_geometry`` packs G batch rows and Hg head-blocks into the
step's blocks; the kernel bodies loop over the rows and unroll over the
head-blocks (the compiler interleaves the independent heads), each
(row, head) computed exactly as a step of its own would.

Three more modes of the packed entry, all of the same kernels:

- grouped queries (``n_kv_heads`` < ``n_heads``): k and v are
  [B, S, n_kv_heads*D] and query head h reads key/value head
  ``h // (n_heads // n_kv_heads)``.  At a head width of 128 lanes or more
  a lane block is one head and the index maps alone address it.  At 64 a
  lane block is two heads: a group is a whole number of query blocks, so
  both heads of a query block read ONE key/value head, which is one HALF
  of a key/value lane block (``_Geom.kv_half``); the index maps bring the
  block.  The several-block kernels then take no half at all: at a q
  sweep's first step the two heads are stacked along rows into scratch,
  each moved to the lanes of that half with zeros in the others
  (``_stack_heads``: q; in the backward do, lse and delta too), and every
  step is ONE [2 * bq, bk] tile against the WHOLE k and v lane blocks: one
  score matmul (the zeros drop the other head's keys from the
  contraction), one softmax pass with a statistic a stacked row, one matmul
  a product; dk and dv contract over both heads' rows and land in the
  half's lanes of the accumulators (zeros beside them), and the sweep's
  last step puts the two heads side by side again (``_unstack_heads``).
  Only the one-block forward still selects the half of k and v a head.
  The backward runs once per KEY/VALUE lane block and its table walks the
  query blocks that read it (``heads_a_step`` of them a step, each stacked
  on rows of its own), so dk and dv are summed over the group in the
  kernel's accumulators.  One (row, key/value head-block) pair a grid row
  whatever S is, and the several-block backward even at one block.
- a sliding window (``window`` = W < S, causal): query i sees keys j with
  i - W < j <= i.  The sweeps' tables hold the BAND (at most 9 kv blocks of
  512 a q block for W = 4096, not S / 512), and the kernels carry names of
  their own (``flash_swa_fwd``, ``flash_swa_bwd_fused``; ``flash_swa_bwd_dq``
  and ``_dkv``) so that a trace's reader can tell a windowed layer's calls
  from a full one's.  A window of S or more is the causal mask and runs the
  causal kernels.
- the block-diffusion rule (``block_diffusion`` = Bd, not causal): the S rows
  are a noised copy of a sequence over its clean copy, S / 2 positions each
  in blocks of Bd, and a noised query sees the noised keys of its own block
  (both directions) and the clean keys of EARLIER blocks, a clean query the
  clean keys of its own and earlier blocks, nothing else
  (``blockdiff_seen``).  ONE sweep over all S queries and S keys, not two
  (clean on clean; noised on both): the rule is a fourth table of
  ``step_table`` (a copy is whole tiles and a tile whole blocks, so a tile's
  quadrant and the blocks it spans say whether it holds a pair: ``nq (nq +
  1) + nq`` tiles a head at nq tiles a copy, 288 of 1,024 at S / 2 = 8,192;
  ``blockdiff_live_share``) and a mask ``_seen`` builds in the tile from the
  quadrant (scalars of the table's entries) and the rows' and columns'
  blocks; everything else (the grouped heads inside a step, the fused
  backward's whole-sequence accumulators, which are the key/value rows' of
  BOTH copies) is the sweeps' as they stand, where two calls would read k
  and v of the clean copy twice and sum a clean key's dk and dv outside the
  kernel.  The noised-on-noised part stands alone as nq diagonal tiles of
  which Bd x Bd squares are live (0.8 % at 512-row tiles and Bd = 4): 5.6 %
  of the sweep's tiles, the price of no second kernel.  No mask is an
  operand and nothing [S, S] stands anywhere; the kernels carry the names
  ``flash_bd_fwd`` / ``flash_bd_bwd_fused`` (``_dq``, ``_dkv``).

All matmuls feed the MXU in the input dtype with f32 accumulation.
interpret=True (CPU tests) is selected automatically off-TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_delta
from ._common import (CompilerParams as _CompilerParams,
                      count_call as _count_call, on_tpu as _on_tpu)

__all__ = ["flash_attention", "flash_attention_packed"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

LANES = 128   # running row stats ride full-lane [bq, 128] layouts: a lane-1
              # layout forces Mosaic relayouts on every broadcast against the
              # [bq, bk] score tile (the single biggest cost in the r2 kernel)


def _lanes_to(x, n):
    """Broadcast a [rows, LANES] lane-replicated stat to n lanes."""
    if n >= LANES:
        return jnp.tile(x, (1, n // LANES))
    return x[:, :n]


def packed_layout_supported(n_heads, head_dim, n_kv_heads=None,
                            v_head_dim=None):
    """True when the packed [B, S, H*D] entry can address this head shape
    (Mosaic lane-tiling rule; see _heads_per_block).  Grouped queries need
    a lane block whose heads all read one key/value head: a block that is
    one head (D a multiple of 128), or a group that is whole blocks (D = 64:
    an even group) over key/value heads that fill whole blocks too.  A value
    width of its own (``v_head_dim`` other than ``head_dim``): both whole
    lane blocks, every head its own key/value head."""
    if v_head_dim not in (None, head_dim):
        return (head_dim % LANES == 0 and v_head_dim % LANES == 0
                and n_kv_heads in (None, n_heads))
    hpb = max(1, LANES // head_dim)
    if n_kv_heads not in (None, n_heads) and (
            n_heads % n_kv_heads or (n_heads // n_kv_heads) % hpb
            or n_kv_heads % hpb):
        return False
    return (head_dim * hpb) % LANES == 0 and n_heads % hpb == 0


def _heads_per_block(D):
    """Packed layout: Mosaic requires the last block dim be a multiple of 128
    (or the full array dim), so D=64 heads pair up 2-per-block; D>=128 heads
    stand alone."""
    return max(1, LANES // D)


# ---------------------------------------------------------------------------
# geometry of one grid step
# ---------------------------------------------------------------------------

STEP_ROWS = 512              # rows of 128 lanes per operand that one grid step
                             # should move: what a step at S=512 moves
STEP_HEAD_BLOCKS = 3         # head-blocks a step holds at most.  The bodies
                             # unroll over them and the compiler interleaves
                             # the independent heads' matmuls, exp and
                             # reductions, which is where most of the gain at
                             # S=128 comes from (one head-block: a chain of
                             # dependent steps, latencies exposed).  B=256,
                             # S=128, 12 heads of 64 on a v5e, forward /
                             # backward us a layer: 1 head-block 1125 / 1514,
                             # 2: 632 / 1214, 3: 602 / 1140, 6: 787 / 1059;
                             # the same six as separate loops 918 / 1344, so
                             # it is the unrolling, not the wider DMA
VMEM_BUDGET = 12 * 2 ** 20   # bytes one step may hold: the double-buffered
                             # operand and statistics blocks of the widest
                             # kernel (flash_bwd_fused) and its live f32 tiles;
                             # under the 16 MiB Mosaic scopes by default
SWEEP_HEAD_BLOCKS = 8        # head-blocks of a row a several-block step holds
                             # at most where the queries are NOT grouped:
                             # each brings k and v of its own, so past the
                             # step's own cost nothing is shared and the
                             # gain flattens while every unrolled head adds
                             # trace and compile seconds.  32 heads of 128,
                             # S=16,384 on a v5e, forward us a layer (first
                             # call, with the backward): 1 head-block 18,106
                             # (0.9 s), 2: 15,965, 4: 14,312 (2.4 s), 8:
                             # 13,882 (3.7 s), 16: 13,525 (8.2 s); a layer's
                             # kernels as a remat step runs them (forward
                             # twice, backward at 2) 65,296 / 64,436 / 63,722
                             # at 4 / 8 / 16: 8 is the least within 2 % of
                             # the best
SWEEP_BWD_HEAD_BLOCKS = 2    # and a step of the one-sweep backward: its call
                             # asks VMEM for the heads' whole-sequence
                             # accumulators too (40 MiB at four heads of
                             # 4,096 positions, 23 at two), and what the
                             # largest kernel scope takes of the 128 MiB XLA
                             # no longer has for arrays of its own.  Four a
                             # step is the kernel's best by 7 % (B=2, S=4,096,
                             # 16 heads of 128: 2,882 / 2,568 / 2,391 us a
                             # layer at 1 / 2 / 4), but in Ouro's step one
                             # bf16[2, 4096, 2048] then left VMEM for HBM
                             # (+34.6 MB, a fusion 9 ms slower: 5,505 tokens/s
                             # where two a step read 5,526) while OLMoE's read
                             # 43,073 against 42,942: a wash end to end, so
                             # the smaller scope (PERF.md 7 (cx))
SCOPED_VMEM = 16 * 2 ** 20  # what Mosaic gives a kernel unless told otherwise
SWEEP_VMEM = 64 * 2 ** 20   # bytes a several-block sweep may ask for
                            # (``fwd_sweep_vmem_bytes``,
                            # ``fused_sweep_vmem_bytes``): half of a v5e
                            # core's 128 MiB.  The one-sweep backward at one
                            # head-block a step: S = 16,384 at 128 lanes
                            # asks for 40 MiB (24 of accumulators and output
                            # blocks, Mosaic's 16), 32,768 for 64; past it
                            # the backward is two sweeps.  Two ungrouped
                            # head-blocks a step at 16,384 ask for 59 (48
                            # and a step's 11; Mosaic takes 54.7), two at
                            # 4,096 for 23 (four would ask 40, eight 74)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def step_vmem_bytes(G, S, Hg, lanes, itemsize):
    """What one grid step of ``flash_bwd_fused`` (eight operand blocks; the
    forward has four) holds in VMEM at blocks of G rows x S x Hg*lanes."""
    width = Hg * max(lanes, LANES)                 # narrow blocks pad to a tile
    operands = 8 * 2 * G * S * width * itemsize    # double-buffered
    stats = 2 * G * S * LANES * 4                  # [G, S, <=128] f32, padded
    tiles = 4 * S * S * 4 + 4 * S * LANES * 4      # s, p, dov, ds; dq/dk/dv
    return operands + stats + tiles


def step_geometry(B, S, n_head_blocks, lanes, itemsize):
    """(G, Hg): the batch rows and the head-blocks (``lanes`` wide) that one
    grid step holds where the whole sequence is one block.  From the shapes
    alone: head-blocks of one row first, at most STEP_HEAD_BLOCKS (the
    kernel bodies unroll over them), then rows (a loop), until the step
    moves STEP_ROWS rows of 128 lanes per operand; G divides B and Hg the
    head-blocks (so 1 for a prime B), and the step stays under VMEM_BUDGET.
    S=512: (1, 1), the step as it always was."""
    def fits(G, Hg):
        return step_vmem_bytes(G, S, Hg, lanes, itemsize) <= VMEM_BUDGET

    def enough(G, Hg):
        return G * Hg * S * max(lanes, LANES) >= STEP_ROWS * LANES

    G = Hg = 1
    for h in _divisors(n_head_blocks):
        if enough(G, Hg) or h > STEP_HEAD_BLOCKS or not fits(G, h):
            break
        Hg = h
    for g in _divisors(B):
        if enough(G, Hg) or not fits(g, Hg):
            break
        G = g
    return G, Hg


def heads_a_step(group, vmem_bytes, most=None):
    """The query head-blocks of a group that ride one grid step of a
    several-block sweep: the most (a divisor of the group, ``most`` or
    fewer where given) whose ``vmem_bytes(heads)`` fits SWEEP_VMEM, and one
    where none does (a step of one lives in what its call asks anyway).
    From the shapes alone; the group's other heads are further sweeps of the
    same grid row (``step_table``'s ``group``) or positions of the grid's
    own axis.  Where the queries are not grouped the "group" is the row's
    head-blocks and ``most`` SWEEP_HEAD_BLOCKS or SWEEP_BWD_HEAD_BLOCKS
    (``_Geom.heads_in_step``)."""
    fit = [n for n in _divisors(group)
           if vmem_bytes(n) <= SWEEP_VMEM and n <= (most or group)]
    return fit[-1] if fit else 1


def past_scoped(need, least=SCOPED_VMEM):
    """``vmem_limit_bytes`` where a kernel needs more than ``least`` (what
    it has without asking), else nothing: the call stays as it was."""
    return {"vmem_limit_bytes": int(need)} if need > least else {}


def fwd_sweep_vmem_bytes(heads, lanes, itemsize, v_lanes=None, halves=1,
                         bq=512, bk=512, kv_heads=1):
    """What a several-block forward asks of VMEM at ``heads`` query
    head-blocks a step: their q and o blocks and the k and v block
    (``kv_heads`` head-blocks wide: a group's one, or each head's own where
    the queries are not grouped) twice each, their statistic twice (an
    output block, a column a head padded to a lane tile), their running
    max, denominator and accumulator (and the stack of q where ``halves``
    heads of a lane block ride stacked), and four [rows, bk] float32 values
    of a step's own and one more a head (Mosaic keeps about one a head that
    it interleaves: 6.5 MiB of them at 6 heads of 128, 8.3 at 8, 15.8 at
    16, by its own count for a v5e)."""
    vw, rows = lanes if v_lanes is None else v_lanes, halves * bq
    return (2 * (heads * bq + kv_heads * bk) * (lanes + vw) * itemsize
            + 2 * heads * bq * LANES * 4
            + heads * rows * (2 * LANES + vw) * 4
            + (halves > 1) * heads * rows * lanes * itemsize
            + (4 + heads) * rows * bk * 4)


def fused_sweep_vmem_bytes(Sk, lanes, itemsize, v_lanes=None, heads=1,
                           halves=1, bq=512, bk=512, kv_heads=1):
    """What ``flash_bwd_fused`` over several blocks asks of VMEM at a
    key/value length of Sk and head-blocks ``lanes`` wide (the values'
    ``v_lanes``, where they have a width of their own): the two float32
    accumulators that hold dk and dv of the whole sequence, their two output
    blocks (one buffer each: they leave once a grid row), all four
    ``kv_heads`` head-blocks wide (a group's one; each head's own where the
    queries are not grouped, so ``heads`` times the 24 MiB that 16,384
    positions at 128 lanes ask for), and what a step holds.  One query
    head-block a step: Mosaic's own scope, which is what
    the two sweeps' steps live in (double-buffered [512, lanes] operand
    blocks, the [512, 512] tiles).  ``heads`` a step: their
    blocks of q, dq and do twice each and dq's float32 scratch, their
    ``lse`` and ``delta`` (a column a head padded to a lane tile) twice, k
    and v twice, the stack's scratch where ``halves`` heads of a lane block
    ride stacked, and six [rows, bk] float32 values of a step's own (Mosaic
    keeps 3.9 MiB of them at 6, 7, 8 and 16 heads of 128 alike, by its own
    count for a v5e)."""
    # narrow blocks pad to a tile
    lanes, vw = max(lanes, LANES), max(lanes if v_lanes is None else v_lanes,
                                       LANES)
    width, rows = lanes + vw, halves * bq
    step = SCOPED_VMEM if heads == 1 else (
        heads * bq * (2 * (lanes + width) * itemsize + 2 * 2 * LANES * 4)
        + heads * rows * lanes * 4 + 2 * kv_heads * bk * width * itemsize
        + (halves > 1) * heads * rows * (width * itemsize + 2 * LANES * 4)
        + 6 * rows * bk * 4)
    return kv_heads * Sk * width * (4 + itemsize) + step


def bwd_sweeps(Sk, bk, lanes, itemsize, group=1, v_lanes=None):
    """The kernels of one layer's backward, from the shapes alone: 1
    (``flash_bwd_fused``: dq, dk and dv off one recomputed probability tile)
    where the Sk keys are one block of bk and the queries are not grouped,
    and over several blocks wherever dk and dv of the whole sequence fit
    SWEEP_VMEM; else 2 (``flash_bwd_dq``, ``flash_bwd_dkv``)."""
    if Sk == bk and group == 1:
        return 1
    fits = fused_sweep_vmem_bytes(Sk, lanes, itemsize, v_lanes) <= SWEEP_VMEM
    return 1 if fits else 2


def grid_geometry(B, S, Sk, n_head_blocks, lanes, itemsize, bq, bk, group=1):
    """(G, Hg, (row, head-block) pairs over the steps' G x Hg) for blocks of
    bq x bk: ``step_geometry`` where the sequence is one block both ways;
    otherwise (and wherever ``group`` query heads share a key/value head)
    the blocks are of one row and the several-block sweeps' steps hold
    ``heads_a_step`` head-blocks of a group or, where the queries are not
    grouped, of the row (``_Geom.heads_in_step``)."""
    G, Hg = (step_geometry(B, max(S, Sk), n_head_blocks, lanes, itemsize)
             if S == bq and Sk == bk and group == 1 else (1, 1))
    return G, Hg, (B // G) * (n_head_blocks // Hg)


FIRST, LAST = 1, 2    # a step's flags in ``step_table``


def blockdiff_seen(S, bq, bk, blocks):
    """``seen(i, j)`` of ``step_table`` under the BLOCK-DIFFUSION rule: the S
    rows are a noised copy of a sequence over its clean copy, ``[x_t ; x_0]``
    of S / 2 positions each, in blocks of ``blocks`` positions, ``b(r) = (r
    mod S / 2) // blocks``.  A noised query sees the noised keys of its own
    block (both directions) and the clean keys of EARLIER blocks; a clean
    query the clean keys of its own and earlier blocks; nothing else.  A
    tile lies in one quadrant (the halves are whole tiles) and holds whole
    blocks (``blocks`` divides both tile heights), so whether a pair of it is
    let through follows from the blocks its rows and columns span: at bq ==
    bk, nq = S / 2 / bq tiles a half, nq on the noised diagonal and nq (nq +
    1) / 2 in each of the two triangles over the clean keys, ``nq (nq + 1) +
    nq`` of the square's ``4 nq^2`` (288 of 1,024 at S / 2 = 8,192 in
    512-row tiles; of a diagonal noised tile ``blocks / bq`` is live, 0.8 %
    at blocks of 4)."""
    half = S // 2
    assert S == 2 * half and half % bq == 0 and half % bk == 0 \
        and bq % blocks == 0 and bk % blocks == 0, (S, bq, bk, blocks)

    def span(n, rows):
        """Tile n of ``rows`` rows: noised?, its first and last block."""
        first = n * rows % half
        return n * rows < half, first // blocks, (first + rows - 1) // blocks

    def seen(i, j):
        noised_q, q_lo, q_hi = span(i, bq)
        noised_k, k_lo, k_hi = span(j, bk)
        if noised_k:
            return noised_q and k_lo <= q_hi and q_lo <= k_hi
        # clean keys: of earlier blocks, and a clean query's own
        return k_lo < q_hi + (not noised_q)

    return seen


def blockdiff_live_share(S, bq, bk, blocks):
    """(tiles of one head's sweep under the rule, the share of their (query,
    key) pairs the rule lets through): S / 2 (S / 2 + blocks) pairs over
    ``tiles * bq * bk``."""
    tiles = kv_blocks(S, bq, bk, False, blocks=blocks)
    return tiles, (S // 2) * (S // 2 + blocks) / (tiles * bq * bk)


def step_table(S, Sk, bq, bk, causal, window=None, group=1, kv_major=False,
               blocks=None):
    """The grid steps of one multi-block sweep, in order: int32 [4, steps],
    the columns ``(q block, kv block, head, flags)`` of each step.  A step
    is a (q block, kv block) pair that holds at least one (query, key) pair
    the mask lets through (key <= query, and query - key < ``window`` where
    there is one; every pair where not ``causal``; under ``blocks``, a block
    length, the block-diffusion rule over a noised and a clean copy:
    ``blockdiff_seen``), so the triangle, the band, the rectangle and the
    rule's three parts are four tables of this one builder and no step of a
    grid is empty.

    q-major (the forward and the dq sweep, whose grids hold the group as an
    axis and ask for ``group`` 1; the fused backward, whose table walks it):
    the ``group`` chunks of a key/value head-block's query head-blocks in
    turn (``head``: a chunk is the ``heads_a_step`` head-blocks one grid
    step holds, so ``group`` here is the callers' group over that; one
    head-block a chunk in the two sweeps), of each its q blocks, of each its
    kv blocks ascending.
    kv-major (the dk/dv sweep): by kv block, then the query head-blocks that
    read it, then q block ascending, so the sum over the group stays in the
    kernel's scratch.  Either way a kv block meets its (head, q block) pairs
    in the same order, head first: the order dk and dv are summed in (inside
    a chunk, its heads first).
    Flags: FIRST and LAST open and close a sweep (zero the scratch, write the
    output block).  Every step masks its scores: a third flag for the blocks
    the mask cuts, with an unmasked body for the others, was slower on the
    chip at head width 128 (PERF.md section 6, PR 34)."""
    far = float("inf") if window is None else window - 1

    def seen(i, j):
        """query - key runs from lo to hi over block (i, j) and the mask
        lets 0 .. far through."""
        lo, hi = i * bq - (j * bk + bk - 1), i * bq + bq - 1 - j * bk
        return not causal or (hi >= 0 and lo <= far)

    if blocks:
        assert S == Sk and not causal and window is None, (S, Sk, causal)
        seen = blockdiff_seen(S, bq, bk, blocks)
    nq, nk = S // bq, Sk // bk
    sweeps = [[(i, j, h) for h in range(group) for i in range(nq)
               if seen(i, j)] for j in range(nk)] if kv_major else \
        [[(i, j, h) for j in range(nk) if seen(i, j)]
         for h in range(group) for i in range(nq)]
    assert all(sweeps), "a block no query and key meet in: %r" % (
        (S, Sk, bq, bk, window),)
    return np.array([
        (i, j, h, FIRST * (n == 0) | LAST * (n == len(sweep) - 1))
        for sweep in sweeps for n, (i, j, h) in enumerate(sweep)], np.int32).T


def kv_blocks(S, bq, bk, causal=True, window=None, blocks=None):
    """The (q block, kv block) grid steps of one head's forward sweep."""
    return step_table(S, S, bq, bk, causal, window, blocks=blocks).shape[1]


def packed_grid(B, S, n_heads, head_dim, block_q, block_k, itemsize=2,
                n_kv_heads=None, causal=False, window=None, part="fwd",
                blocks=None):
    """What ``flash_attention_packed`` runs for these shapes, for whoever
    wants to say so without tracing it (the tests, ``scripts/``):
    (pairs per grid step, grid steps of one layer's forward pass; ``part``
    "bwd": of its backward, where that is one kernel).  Several blocks: a
    step is a tile of ``step_table`` for a (row, key/value head-block) pair
    and the ``heads_a_step`` query head-blocks of its group that ride it
    (ungrouped: for that many head-blocks of the row).  ``blocks``: the
    block-diffusion rule's block length, S the rows of both copies (its
    tiles a head and their live share: ``blockdiff_live_share``)."""
    bq, bk = min(block_q, S), min(block_k, S)
    # shapes and an element size are all the geometry reads of q and k
    g = _Geom(*(jax.ShapeDtypeStruct((B, S, n * head_dim),
                                     np.dtype("V%d" % itemsize))
                for n in (n_heads, n_kv_heads or n_heads)),
              n_heads, bq, bk, n_kv_heads, window, blocks=blocks)
    if S == bk and part == "fwd":
        return g.G * g.Hg, g.grid_b * (S // bq)
    heads, _ = g.heads_in_step(part)
    return heads, g.grid_b // heads * kv_blocks(S, bq, bk, causal, window,
                                                blocks)


class _Geom:
    """Grid/block geometry for the two layouts.  H=None: [BH, S, D]
    separate-heads.  H=int: packed [B, S, H*D] — per-head column slices are
    addressed by the BlockSpec index maps, so the model never materializes a
    [B, H, S, D] transpose (the r2 wrapper's main HBM cost).  A block is G
    rows of the leading axis by Hg head-blocks (``grid_geometry``; 1 by 1
    wherever the sequence is more than one block, where a step's q, o and
    statistics blocks are ``heads_in_step`` head-blocks wide, of a group or,
    ungrouped, of the row, and then its k and v blocks too:
    ``q_spec(.., heads)``, ``kv_spec(.., kv_heads(heads))``).

    ``Hkv`` < H: grouped queries, k and v hold Hkv heads and q head h reads
    kv head ``h // group``.  ``window`` (None: none) and the causal mask
    shape the several-block sweeps through their ``step_table`` alone.

    ``Dv`` (None: D): the values' head width where it is not q's and k's (v,
    o, do and dv are [B, S, H*Dv]; both widths whole lane blocks, a block a
    head, no grouping): ``vw`` is a value head-block's lanes where ``qw`` is
    a query's, and every product with v or do runs at ``vw``.

    ``blocks`` (None: none): the block-diffusion rule's block length, the
    rows a noised copy over a clean one (``blockdiff_seen``): several blocks
    always, the table and the in-tile mask the rule's, the kernels' names
    ``flash_bd_*``."""

    def __init__(self, q, k, H, bq, bk, Hkv=None, window=None, Dv=None,
                 blocks=None):
        B, self.S, E = q.shape
        self.Sk = k.shape[1]
        # (rows of a copy, block length): what the in-tile mask reads
        self.rule = (self.S // 2, blocks) if blocks else None
        if H is None:
            self.D, self.hpb, self.Hb = E, 1, 1
        else:
            self.D = E // H
            self.hpb = _heads_per_block(self.D)
            assert H % self.hpb == 0 and (self.D * self.hpb) % LANES == 0, (H, self.D)
            self.Hb = H // self.hpb   # head-blocks per batch row
        # with grouped queries ``group`` counts in lane blocks: the query
        # blocks that read one key/value block (the query heads of a group
        # where a block is one head; with hpb heads a block, hpb key/value
        # heads are read by hpb * heads-per-group / hpb query blocks)
        self.group = 1 if Hkv in (None, H) else H // Hkv
        assert self.group == 1 or (H == Hkv * self.group
                                   and self.group % self.hpb == 0
                                   and Hkv % self.hpb == 0)
        # a key/value lane block holds hpb heads and a query block reads one:
        # its heads ride the several-block sweeps stacked (``_stack_heads``)
        self.halves = self.hpb if self.group > 1 else 1
        self.qw = self.D * self.hpb   # width of one head-block (lane dim)
        self.Dv = self.D if Dv is None else Dv
        assert self.Dv == self.D or (self.hpb == self.group == 1
                                     and self.Dv % LANES == 0), (self.D, Dv)
        self.vw = self.Dv * self.hpb  # of a value's
        self.bq, self.bk, self.itemsize = bq, bk, q.dtype.itemsize
        self.G, self.Hg, self.grid_b = grid_geometry(
            B, self.S, self.Sk, self.Hb, self.qw, q.dtype.itemsize, bq, bk,
            self.group)
        self.nq = self.S // bq
        self.one_block = self.Sk == bk
        self.bwd_sweeps = bwd_sweeps(self.Sk, bk, self.qw, q.dtype.itemsize,
                                     self.group, self.vw)
        self.window = window
        self.o_shape = q.shape[:-1] + (E // self.D * self.Dv,)
        self.dk_shape = k.shape
        self.dv_shape = k.shape[:-1] + (k.shape[-1] // self.D * self.Dv,)
        # stats are 4-D so the block's last dim equals the array's (Mosaic
        # tiling rule): [row, head-block group, S, heads of the group]
        self.stat_shape = (B, self.Hb // self.Hg, self.S, self.Hg * self.hpb)

    def heads_in_step(self, part):
        """(query head-blocks a grid step holds, the bytes of VMEM such a
        step's call needs) of the several-block forward (``part`` "fwd") or
        one-sweep backward ("bwd"): ``heads_a_step`` of the shapes, over a
        group's head-blocks or, where the queries are not grouped, over the
        row's (adjacent ones, each with k and v of its own:
        SWEEP_HEAD_BLOCKS at most, SWEEP_BWD_HEAD_BLOCKS in the backward,
        and a lane block of one head)."""
        sizes = dict(itemsize=self.itemsize, v_lanes=self.vw,
                     halves=self.halves, bq=self.bq, bk=self.bk)
        count = functools.partial(fwd_sweep_vmem_bytes, lanes=self.qw, **sizes)\
            if part == "fwd" else functools.partial(
                fused_sweep_vmem_bytes, self.Sk, self.qw, **sizes)

        def need(n):
            return count(heads=n, kv_heads=self.kv_heads(n))

        if self.group > 1:
            heads = heads_a_step(self.group, need)
        else:
            heads = heads_a_step(
                self.Hb if self.hpb == 1 else 1, need,
                SWEEP_HEAD_BLOCKS if part == "fwd" else SWEEP_BWD_HEAD_BLOCKS)
        return heads, need(heads)

    def kv_heads(self, heads):
        """The key/value head-blocks a several-block step of ``heads`` query
        head-blocks holds: their group's one, or each head's own."""
        return 1 if self.group > 1 else heads

    def chunks(self, heads):
        """The chunks of ``heads`` query head-blocks a key/value block's
        group makes; one where the queries are not grouped (the step's
        ``heads`` key/value head-blocks are the grid row's)."""
        return max(self.group // heads, 1)

    def kv_half(self, q_block):
        """Which head of its key/value lane block a query head-block reads;
        None where a block is one head or the heads pair up one to one."""
        if self.halves == 1:
            return None
        return (q_block * self.hpb // self.group) % self.hpb

    # index maps of the one-block kernels: (b, i, j) with i indexing q rows,
    # j kv rows; b runs over (row group, head-block group), head-block
    # groups fastest
    def qmap(self):
        n = self.Hb // self.Hg
        return lambda b, i, j=0: (b // n, i, b % n)

    def kmap(self):
        n = self.Hb // self.Hg
        if self.group == 1:
            return lambda b, i, j=0: (b // n, j, b % n)
        return lambda b, i, j=0: (b // n, j, (b % n) // self.group)

    def smap(self):
        n = self.Hb // self.Hg
        return lambda b, i, j=0: (b // n, b % n, i, 0)

    def sweep_maps(self, heads=1, walks_group=False):
        """(q rows, kv rows, row statistics) index maps of a several-block
        sweep whose step holds ``heads`` query head-blocks of a group (one
        block of the q map, ``heads`` head-blocks wide; ``heads`` rows of
        the statistics').  Its grid is (batch row, key/value head-block,
        chunk of ``heads`` of that one's group, step t of its
        ``step_table``; ungrouped, the key/value blocks are ``heads``
        head-blocks wide too, the second axis counts those and the third is
        1), or without the third axis where the table walks
        the chunks (``head_of``: the dk/dv sweep and the fused backward,
        which sum over the group); the table's columns arrive as
        scalar-prefetch operands.  No map divides: on the chip a
        (row, head-block) pair unpacked from one grid index by ``//`` and
        ``%`` cost each of the sweep's steps 30 to 60 ns (PERF.md section 6,
        PR 34)."""
        chunks = self.chunks(heads)

        def at(pick):
            if walks_group:
                return lambda r, kh, t, q_of, kv_of, head_of, flags: pick(
                    r, kh, kh * chunks + head_of[t], q_of[t], kv_of[t])
            return lambda r, kh, g, t, q_of, kv_of, head_of, flags: pick(
                r, kh, kh * chunks + g, q_of[t], kv_of[t])

        return (at(lambda r, kh, qh, i, j: (r, i, qh)),
                at(lambda r, kh, qh, i, j: (r, j, kh)),
                at(lambda r, kh, qh, i, j: (r, qh, i, 0)))

    def step(self, heads=1, head_of=None):
        """(t, chunk) of a sweep's grid position: the step's query
        head-blocks are ``chunk * heads`` and the ``heads - 1`` behind it;
        ``head_of``: of a sweep whose table walks the chunks."""
        chunks = self.chunks(heads)
        if head_of is None:
            return pl.program_id(3), \
                pl.program_id(1) * chunks + pl.program_id(2)
        t = pl.program_id(2)
        return t, pl.program_id(1) * chunks + head_of[t]

    def q_spec(self, bq, index_map=None, heads=1):
        """``heads``: the query head-blocks of a several-block sweep's step
        (``heads_a_step``), adjacent lane blocks of the packed array."""
        return pl.BlockSpec((self.G, bq, heads * self.Hg * self.qw),
                            index_map or self.qmap())

    def kv_spec(self, bk, index_map=None, heads=1):
        """``heads``: the key/value head-blocks of a several-block sweep's
        step (``kv_heads``), adjacent lane blocks of the packed array."""
        return pl.BlockSpec((self.G, bk, heads * self.Hg * self.qw),
                            index_map or self.kmap())

    def o_spec(self, bq, index_map=None, heads=1):
        """Of o and do: q's rows at the values' width."""
        return pl.BlockSpec((self.G, bq, heads * self.Hg * self.vw),
                            index_map or self.qmap())

    def v_spec(self, bk, index_map=None, heads=1):
        """Of v and dv: k's rows at the values' width."""
        return pl.BlockSpec((self.G, bk, heads * self.Hg * self.vw),
                            index_map or self.kmap())

    def stat_spec(self, bq, index_map=None, heads=1):
        return pl.BlockSpec((self.G, heads, bq, self.Hg * self.hpb),
                            index_map or self.smap())


def _rows(G, row):
    """Run row(g) for the G rows of a block, in turn: a loop with a dynamic
    leading index, so the body is compiled once however many rows."""
    if G == 1:
        row(0)
    else:
        def step(g, carry):
            row(g)
            return carry
        jax.lax.fori_loop(0, G, step, 0)


def _cat(cols):
    """The heads of one head-block side by side."""
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _kv_cols(block, hh, D, half, halves):
    """Head ``hh``'s key/value columns of a lane block [rows, hpb * D]: its
    own slice, or where the block's query heads all read ONE key/value head
    (``half`` of ``halves``, a traced scalar) that head's."""
    if half is None:
        return block[:, hh * D:(hh + 1) * D]
    cols = block[:, :D]
    for at in range(1, halves):
        cols = jnp.where(half == at, block[:, at * D:(at + 1) * D], cols)
    return cols


def _stack_heads(block, hpb, D, half):
    """[rows, hpb * D] -> [hpb * rows, hpb * D]: the heads of a query lane
    block one under the other, each moved to the columns of the ONE
    key/value head they all read (``half``, a traced scalar) and zeros in the
    other columns.  A product of the stack with the whole key/value lane
    block then contracts over that head alone, and one that lands on the
    key/value rows (dk, dv) lands in that head's columns, summed over the
    stacked heads, zeros beside them: no half is taken of k or v."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    into = (lane >= half * D) & (lane < (half + 1) * D)
    wide = block.astype(jnp.float32)            # lanes rotate 32 bits wide
    heads = []
    for hh in range(hpb):
        moved = wide                            # half == hh: where it lies
        for at in range(hpb):
            if at != hh:
                moved = jnp.where(
                    half == at,
                    pltpu.roll(wide, (at - hh) % hpb * D, 1), moved)
        heads.append(jnp.where(into, moved, 0.0).astype(block.dtype))
    return jnp.concatenate(heads, axis=0)


def _unstack_heads(stacked, hpb, D, half):
    """[hpb * rows, hpb * D] -> [rows, hpb * D]: the stacked heads side by
    side again, of each the columns of the key/value head it read."""
    rows = stacked.shape[0] // hpb
    return _cat([_kv_cols(stacked[hh * rows:(hh + 1) * rows], hh, D, half, hpb)
                 for hh in range(hpb)])


def _stack_stat(stat, hpb):
    """[rows, hpb] -> [hpb * rows, LANES]: a row statistic of the stacked
    heads, a head's column under the other's, replicated along lanes (the
    layout ``m_scr`` has: no relayout against a score tile)."""
    return jnp.concatenate(
        [jnp.broadcast_to(stat[:, hh:hh + 1], (stat.shape[0], LANES))
         for hh in range(hpb)], axis=0)


def _cols(n, width):
    """Columns of the n-th of adjacent blocks ``width`` wide."""
    return slice(n * width, (n + 1) * width)


def _stacked(q_ref, do_ref, lse_ref, delta_ref, hpb, D, half, h=0):
    """(q, do, lse, delta) of head-block ``h`` of a backward step's q block,
    stacked."""
    qw = hpb * D
    return (_stack_heads(q_ref[0, :, _cols(h, qw)], hpb, D, half),
            _stack_heads(do_ref[0, :, _cols(h, qw)], hpb, D, half),
            _stack_stat(lse_ref[0, h], hpb), _stack_stat(delta_ref[0, h], hpb))


def _stack_sweep(stk, *refs, halves):
    """A backward q sweep's first step: ``_stacked`` into the scratch, the
    step's head-blocks (one of ``halves`` each) one under the other."""
    rows = stk[0].shape[0] // len(halves)
    for h, half in enumerate(halves):
        for scr, value in zip(stk, _stacked(*refs, half, h)):
            scr[_cols(h, rows)] = value


def _bwd_tiles(tile, stk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               hpb, bq, bk, heads=1):
    """A backward step's tiles, ``tile(q, k, v, do, lse, delta, cs, vs, qs,
    last, wrap)``, of its ``heads`` query head-blocks in turn: of each ONE
    over its rows of the stack ``stk`` (q, do, lse, delta: ``_stack_sweep``'s
    scratch or ``_stacked``'s values) against the whole k and v lane blocks,
    or, with no stack, a head at a time on its own columns ``cs`` of k (of v:
    ``vs``; of k and v blocks as wide as the q block, which hold those of
    each of the step's head-blocks and not one group's, the head-block's
    own) and ``qs`` of the step's q block (where its dq lies).  ``last``:
    no further head of the step lands in these columns of dk and dv.  lse
    and delta go over as thunks, so ``tile`` reads them where it uses
    them."""
    D, Dv = q_ref.shape[-1] // (heads * hpb), do_ref.shape[-1] // (heads * hpb)
    own = k_ref.shape[-1] > hpb * D     # k and v columns of a head's own
    for h in range(heads):
        last = own or h == heads - 1
        if stk:
            (q, do, lse, delta), rows = stk, _cols(h, hpb * bq)
            tile(q[rows], k_ref[0], v_ref[0], do[rows],
                 lambda: _lanes_to(lse[rows], bk),
                 lambda: _lanes_to(delta[rows], bk), slice(None), slice(None),
                 _cols(h, hpb * D), last, wrap=bq)
            continue
        for hh in range(hpb):
            n = h * hpb + hh
            kv = n if own else hh
            cs, vs = _cols(kv, D), _cols(kv, Dv)
            # of the wide blocks, the head-block's lanes alone are read
            tile(q_ref[0, :, _cols(h, hpb * D)][:, _cols(hh, D)],
                 k_ref[0][:, cs], v_ref[0][:, vs],
                 do_ref[0, :, _cols(h, hpb * Dv)][:, _cols(hh, Dv)],
                 lambda: lse_ref[0, h][:, hh:hh + 1],
                 lambda: delta_ref[0, h][:, hh:hh + 1], cs, vs, _cols(n, D),
                 last)


def _block_of(first, offset, rule):
    """The rule's block of row ``first + offset`` (``first`` a tile's first
    row, a scalar) within its copy."""
    half, length = rule
    pos = jnp.where(first < half, first, first - half) + offset
    return pos >> (length.bit_length() - 1)     # a power of two


def _seen(shape, q0, k0, window, wrap=None, rule=None):
    """[bq, bk] bool: key position <= query position, and inside the window
    (query - key < window) where there is one.  ``wrap``: the rows are
    several heads' query blocks of ``wrap`` rows, one under the other.
    ``rule`` = (rows of a copy, block length): the block-diffusion rule
    instead (``blockdiff_seen``).  The tile lies in ONE quadrant, which its
    first row and column say: with d = the query's block - the key's, noised
    on noised lets d == 0 through, noised on clean d >= 1, clean on clean d
    >= 0, clean on noised nothing (no table holds such a tile)."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if wrap is not None:
        row = jax.lax.rem(row, wrap)
    if rule:
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        noised_q, noised_k = q0 < rule[0], k0 < rule[0]
        d = _block_of(q0, row, rule) - _block_of(k0, col, rule)
        least = jnp.where(noised_q & ~noised_k, 1, 0)
        most = jnp.where(noised_k, jnp.where(noised_q, 0, -1),
                         jnp.iinfo(jnp.int32).max)
        return (d >= least) & (d <= most)
    qpos = q0 + row
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


def _scores(q, k, scale, causal, q0, k0, window=None, wrap=None, seen=None,
            rule=None):
    """[bq, bk] f32 scaled scores of one head (of ``_stack_heads``' rows:
    ``wrap``), future positions (and those behind the window) masked, or
    what the block-diffusion ``rule`` shuts out (``_seen``).
    ``seen`` (a list, empty at first): the mask of a grid step's tile, built
    by the first of the step's heads and shared by the others."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal or rule:
        if seen:
            mask = seen[0]
        else:
            mask = _seen(s.shape, q0, k0, window, wrap, rule)
            if seen is not None:
                seen.append(mask)
        s = jnp.where(mask, s, NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, bq,
                hpb, G, Hg, geom):
    """One kv block.  hpb = heads per head-block.  The packed [B, S, H*D]
    layout needs 128-wide lane blocks (Mosaic tiling rule), so for D=64 a
    head-block is 2 adjacent heads: its columns are per-head slices and
    every head keeps independent statistics.  hpb=1 is the [BH, S, D]
    layout.  Heads never mix: each dot contracts only its own D columns.

    The block is [G, bq, Hg*hpb*D]: G rows by Hg head-blocks, every
    (row, head) computed on its own.  The whole of K/V is in the block:
    softmax in one pass, no running statistics (the numbers are the sweep's
    own: its first block meets m = -inf, l = 0, acc = 0)."""
    D, Dv = q_ref.shape[-1] // (Hg * hpb), v_ref.shape[-1] // (Hg * hpb)
    i = pl.program_id(1)
    window = geom.window
    half = geom.kv_half(pl.program_id(0) % geom.Hb)

    def row(g):
        for hb in range(Hg):
            cols = pl.ds(hb * hpb * D, hpb * D)
            vcols = pl.ds(hb * hpb * Dv, hpb * Dv)
            qb, kb, vb = q_ref[g, :, cols], k_ref[g, :, cols], v_ref[g, :, vcols]
            out = []
            for hh in range(hpb):
                cs = slice(hh * D, (hh + 1) * D)
                kh = _kv_cols(kb, hh, D, half, geom.halves)
                vh = _kv_cols(vb, hh, Dv, half, geom.halves)
                s = _scores(qb[:, cs], kh, scale, causal, i * bq, 0, window)
                m = jnp.max(s, axis=1)[:, None]            # [bq, 1]
                p = jnp.exp(s - m)                          # [bq, bk] f32
                l = jnp.maximum(jnp.sum(p, axis=1)[:, None], 1e-30)
                out.append(jax.lax.dot_general(
                    p.astype(vb.dtype), vh,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) / l)
                # lse rides a [bq, heads] lane-narrow block, a column a
                # head: the DMA transfers only the valid lanes, and no
                # in-kernel transpose is needed (a lane-replicated
                # [bq, 128] output costs ~150MB/layer of HBM traffic at
                # bench shapes; a lane-oriented [1, bq] output costs a
                # Mosaic relayout per block — both measured slower)
                lse_ref[g, 0, :, pl.ds(hb * hpb + hh, 1)] = m + jnp.log(l)
            o_ref[g, :, vcols] = _cat(out).astype(o_ref.dtype)

    _rows(G, row)


def _fwd_sweep_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref, o_ref,
                      lse_ref, m_scr, l_scr, acc_scr, *q_stk, scale, causal,
                      bq, bk, hpb, heads, geom):
    """Several kv blocks.  A grid row is a (batch row, key/value head-block)
    pair and a step one (q block, kv block) tile of ``step_table`` with
    ``heads`` query head-blocks looped inside (``heads_a_step``): of that
    key/value block's group, which share the step's k and v block, or,
    where the queries are not grouped, adjacent head-blocks of the row, each
    on its own columns of k and v blocks ``heads`` head-blocks wide.  The
    mask of the tile is
    built once and shared, and the heads' chains (product, max, ``exp``,
    product) stand side by side for the compiler to interleave.  Running
    max, denominator and accumulator live in scratch across a q block's
    sweep, a slot of lanes a head.

    Where the heads of the lane block read one key/value head
    (``geom.halves`` > 1) they ride the sweep stacked along rows: q is
    restacked once, at the sweep's first step (``_stack_heads`` into
    ``q_stk``, a head-block under the other), a head-block's tile is ONE
    [hpb * bq, bk] tile against the whole k and v lane blocks, the
    statistics a row of the stack each, and the last step puts the heads
    side by side again."""
    D, Dv = q_ref.shape[-1] // (heads * hpb), o_ref.shape[-1] // (heads * hpb)
    qw, vw, rows = hpb * D, hpb * Dv, m_scr.shape[0]
    own = k_ref.shape[-1] > qw          # k and v columns of a head's own
    t, chunk = geom.step(heads)
    half = [geom.kv_half(chunk * heads + h) for h in range(heads)] \
        if q_stk else None

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        for h in range(heads if q_stk else 0):
            q_stk[0][_cols(h, rows)] = _stack_heads(
                q_ref[0, :, _cols(h, qw)], hpb, D, half[h])

    seen = []       # the tile's mask, the step's heads' own

    def tile(q, cs, vs, ls, os, wrap=None):
        """The rows of q against columns ``cs`` of this kv block's keys and
        ``vs`` of its values: the statistics at lanes ``ls`` of their
        scratch, the accumulator at columns ``os``."""
        s = _scores(q, k_ref[0][:, cs], scale, causal, q_of[t] * bq,
                    kv_of[t] * bk, geom.window, wrap, seen,
                    geom.rule)                          # [rows, bk]

        m_prev = m_scr[:, ls]                          # [rows, LANES]
        m_cur = jnp.max(s, axis=1)[:, None]            # [rows, 1]
        m_new = jnp.maximum(m_prev, m_cur)             # [rows, LANES]
        p = jnp.exp(s - _lanes_to(m_new, bk))          # [rows, bk] f32
        alpha = jnp.exp(m_prev - m_new)                # [rows, LANES]
        l_scr[:, ls] = l_scr[:, ls] * alpha + jnp.sum(p, axis=1)[:, None]
        acc_scr[:, os] = acc_scr[:, os] * _lanes_to(
            alpha, q.shape[1] // D * Dv) + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0][:, vs],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_scr[:, ls] = m_new

    for h in range(heads):
        if q_stk:
            tile(q_stk[0][_cols(h, rows)], slice(None), slice(None),
                 _cols(h, LANES), _cols(h, vw), wrap=bq)
            continue
        qb = q_ref[0, :, _cols(h, qw)]      # the head-block's lanes alone
        for hh in range(hpb):
            n = h * hpb + hh
            kv = n if own else hh
            tile(qb[:, _cols(hh, D)], _cols(kv, D), _cols(kv, Dv),
                 _cols(n, LANES), _cols(n, Dv))

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        l = jnp.maximum(l_scr[:], 1e-30)
        for h in range(heads):
            if q_stk:
                lh = l[:, _cols(h, LANES)]
                o_ref[0, :, _cols(h, vw)] = _unstack_heads(
                    acc_scr[:, _cols(h, vw)] / _lanes_to(lh, vw), hpb, D,
                    half[h]).astype(o_ref.dtype)
                lse = m_scr[:, h * LANES:h * LANES + 1] + jnp.log(lh[:, :1])
                lse_ref[0, h] = jnp.concatenate(       # [hpb * bq, 1] each
                    [lse[hh * bq:(hh + 1) * bq] for hh in range(hpb)], axis=1)
                continue
            at = [_cols(h * hpb + hh, LANES) for hh in range(hpb)]
            alpha_cols = jnp.concatenate(
                [_lanes_to(l[:, ls], D) for ls in at], axis=1) \
                if hpb > 1 else _lanes_to(l[:, at[0]], Dv)
            o_ref[0, :, _cols(h, vw)] = (
                acc_scr[:, _cols(h, vw)] / alpha_cols).astype(o_ref.dtype)
            lse_ref[0, h] = jnp.concatenate(
                [m_scr[:, ls.start:ls.start + 1]
                 + jnp.log(l[:, ls.start:ls.start + 1]) for ls in at], axis=1)


def _name(kernel, g):
    """``flash_<kernel>``; ``flash_swa_<kernel>`` for a windowed call,
    ``flash_bd_<kernel>`` for one under the block-diffusion rule."""
    if g.rule:
        return "flash_bd_" + kernel
    return ("flash_swa_" if g.window is not None else "flash_") + kernel


def _sweep_call(kernel, g, table, operands, in_specs, out_specs, out_shape,
                scratch_shapes, heads, walks_group, interpret, name, **params):
    """One several-block sweep over the steps of ``table`` (its columns
    scalar-prefetched), for the grid positions ``_Geom.sweep_maps`` names at
    ``heads`` query head-blocks a step (the key/value head-blocks a step at
    a time, and the chunks of a group's query head-blocks as a grid axis
    unless the table walks them); ``params``: further compiler parameters."""
    rows = (g.Hb // g.group // g.kv_heads(heads),) + (
        () if walks_group else (g.chunks(heads),))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(table),
            grid=(operands[0].shape[0],) + rows + (table.shape[1],),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=_CompilerParams(dimension_semantics=(
            "parallel",) * (1 + len(rows)) + ("arbitrary",), **params),
        interpret=interpret,
        name=_name(name, g),
    )(*(jnp.asarray(column) for column in table), *operands)


def _fwd(q, k, v, scale, causal, bq, bk, interpret, H=None, Hkv=None,
         window=None, Dv=None, blocks=None):
    """H=None: q/k/v are [BH, S, D].  H=int: q/k/v are [B, S, H*D] (k, v
    [B, S, Hkv*D] with grouped queries; v [B, S, H*Dv] with a value width of
    its own)."""
    g = _Geom(q, k, H, bq, bk, Hkv, window, Dv, blocks)
    if blocks:
        assert not g.one_block, (g.S, bk)   # a copy is whole tiles
        _count_call("flash_blockdiff", part="fwd", fused=1, blocks=blocks)
    out_shape = [
        jax.ShapeDtypeStruct(g.o_shape, q.dtype),
        jax.ShapeDtypeStruct(g.stat_shape, jnp.float32),
    ]
    if not g.one_block:
        heads, need = g.heads_in_step("fwd")
        _count_call("flash_sweep", part="fwd", group=g.group,
                    heads_in_step=heads)
        qm, km, sm = g.sweep_maps(heads)
        kvh = g.kv_heads(heads)
        # statistics: a head a group of lanes, or a row of the stack
        rows, lanes = g.halves * bq, g.hpb // g.halves * LANES
        return _sweep_call(
            functools.partial(_fwd_sweep_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, hpb=g.hpb, heads=heads, geom=g),
            g, step_table(g.S, g.Sk, bq, bk, causal, window, blocks=blocks),
            (q, k, v),
            [g.q_spec(bq, qm, heads), g.kv_spec(bk, km, kvh),
             g.v_spec(bk, km, kvh)],
            [g.o_spec(bq, qm, heads), g.stat_spec(bq, sm, heads)], out_shape,
            [pltpu.VMEM((rows, heads * lanes), jnp.float32),
             pltpu.VMEM((rows, heads * lanes), jnp.float32),
             pltpu.VMEM((rows, heads * g.vw), jnp.float32)]
            + [pltpu.VMEM((heads * rows, g.qw), q.dtype)] * (g.halves > 1),
            heads, False, interpret, "fwd", **past_scoped(need))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, hpb=g.hpb, G=g.G, Hg=g.Hg, geom=g)
    o, lse = pl.pallas_call(
        kernel,
        grid=(g.grid_b, g.nq, 1),
        in_specs=[
            g.q_spec(bq),
            g.kv_spec(bk),
            g.v_spec(bk),
        ],
        out_specs=[
            g.o_spec(bq),
            # row stats as narrow-lane blocks (see the kernel)
            g.stat_spec(bq),
        ],
        out_shape=out_shape,
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=_name("fwd", g),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# fused backward (single kernel) for the single-kv-block case: when all of
# K/V fits one block (Sk == bk), dq/dk/dv share ONE recomputed probability
# matrix — one exp pass and 5 matmuls.  This is the hot path for the BERT
# shapes (S=512, block 512; S=128, one block, G x Hg pairs a step); several
# kv blocks share the tile the same way in ``_bwd_sweep_kernel``.
# ---------------------------------------------------------------------------


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *scratch,
                      scale, causal, bq, bk, hpb, nq, G, Hg, window=None):
    i = pl.program_id(1)
    D, Dv = q_ref.shape[-1] // (Hg * hpb), v_ref.shape[-1] // (Hg * hpb)

    if nq > 1:
        # several q blocks (one pair a step): dk, dv accumulate in scratch
        dk_scr, dv_scr = scratch

        @pl.when(i == 0)
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def row(g):
        lse = lse_ref[g, 0]                                 # [bq, heads]
        for hb in range(Hg):
            cols = pl.ds(hb * hpb * D, hpb * D)
            vcols = pl.ds(hb * hpb * Dv, hpb * Dv)
            qb, kb, vb = q_ref[g, :, cols], k_ref[g, :, cols], v_ref[g, :, vcols]
            ob, dob = o_ref[g, :, vcols], do_ref[g, :, vcols]
            dq_cols, dk_cols, dv_cols = [], [], []
            for hh in range(hpb):
                cs, vs = slice(hh * D, (hh + 1) * D), \
                    slice(hh * Dv, (hh + 1) * Dv)
                q, k, v, do = qb[:, cs], kb[:, cs], vb[:, vs], dob[:, vs]
                s = _scores(q, k, scale, causal, i * bq, 0, window)
                h = hb * hpb + hh
                p = jnp.exp(s - lse[:, h:h + 1])            # [bq, bk] — the ONE exp
                dv_cols.append(jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                delta = jnp.sum(do.astype(jnp.float32)
                                * ob[:, vs].astype(jnp.float32),
                                axis=1)[:, None]            # [bq, 1]
                dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                          preferred_element_type=jnp.float32)
                ds = (p * (dov - delta) * scale).astype(q.dtype)  # [bq, bk]
                dq_cols.append(jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                dk_cols.append(jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq_ref[g, :, cols] = _cat(dq_cols).astype(dq_ref.dtype)
            if nq > 1:
                dk_scr[:] += _cat(dk_cols)
                dv_scr[:] += _cat(dv_cols)
            else:
                dk_ref[g, :, cols] = _cat(dk_cols).astype(dk_ref.dtype)
                dv_ref[g, :, vcols] = _cat(dv_cols).astype(dv_ref.dtype)

    _rows(G, row)

    if nq > 1:
        @pl.when(i == nq - 1)
        def _final():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused(scale, causal, bq, bk, interpret, res, do, H=None,
               window=None, Dv=None):
    q, k, v, o, lse = res
    g = _Geom(q, k, H, bq, bk, window=window, Dv=Dv)
    nq = g.S // bq
    # 2-arg index maps (grid has no kv axis): kv lives at block 0
    qm, km, sm = g.qmap(), g.kmap(), g.smap()
    qs = g.q_spec(bq, lambda b, i: qm(b, i, 0))
    ks = g.kv_spec(bk, lambda b, i: km(b, i, 0))
    os = g.o_spec(bq, lambda b, i: qm(b, i, 0))
    vs = g.v_spec(bk, lambda b, i: km(b, i, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, hpb=g.hpb, nq=nq, G=g.G, Hg=g.Hg,
                          window=window),
        grid=(g.grid_b, nq),
        in_specs=[qs, ks, vs, os, os,
                  g.stat_spec(bq, lambda b, i: sm(b, i, 0))],
        out_specs=[qs, ks, vs],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(g.dk_shape, k.dtype),
            jax.ShapeDtypeStruct(g.dv_shape, v.dtype),
        ],
        scratch_shapes=[] if nq == 1 else [
            pltpu.VMEM((bk, g.qw), jnp.float32),
            pltpu.VMEM((bk, g.vw), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=_name("bwd_fused", g),
    )(q, k, v, o, do, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# backward over several blocks.  One sweep (``_bwd_sweep_kernel``) where dk
# and dv of the whole sequence fit VMEM; else the dq sweep (grid
# kv-innermost) and the dk/dv sweep (grid q-innermost)
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, acc_scr, *stk, scale, causal,
                   bq, bk, geom, hpb=1):
    """The q-major sweep of the two: dq of a q block over its kv blocks
    (``stk``: as ``_bwd_sweep_kernel``)."""
    t, q_block = geom.step()
    D = q_ref.shape[-1] // hpb
    half = geom.kv_half(q_block)

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if stk:
            _stack_sweep(stk, q_ref, do_ref, lse_ref, delta_ref, hpb, D,
                         halves=[half])

    def tile(q, k, v, do, lse, delta, cs, vs, qs, last, wrap=None):
        s = _scores(q, k, scale, causal, q_of[t] * bq, kv_of[t] * bk,
                    geom.window, wrap, rule=geom.rule)
        p = jnp.exp(s - lse())                         # [rows, bk]
        dov = jax.lax.dot_general(do, v,
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta()) * scale               # [rows, bk] f32
        acc_scr[:, cs] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _bwd_tiles(tile, stk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               hpb, bq, bk)

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        dq = acc_scr[:]
        dq_ref[0] = (_unstack_heads(dq, hpb, D, half) if stk else dq
                     ).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, causal, bq, bk, geom, hpb=1):
    """The kv-major sweep of the two: dk and dv of a kv block over the q
    blocks that see it.  Every step meets another q block, so where the
    heads ride stacked (``geom.halves`` > 1) a step stacks its own."""
    # q blocks innermost here (of each of the group's query heads in turn)
    t, q_block = geom.step(head_of=head_of)
    D = q_ref.shape[-1] // hpb
    half = geom.kv_half(q_block)

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(q, k, v, do, lse, delta, cs, vs, qs, last, wrap=None):
        s = _scores(q, k, scale, causal, q_of[t] * bq, kv_of[t] * bk,
                    geom.window, wrap, rule=geom.rule)
        p = jnp.exp(s - lse())                         # [rows, bk]
        # dv_j += p^T dO
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_scr[:, vs] += dv
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta()) * scale
        # dk_j += ds^T q
        dk = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:, cs] += dk

    stk = _stacked(q_ref, do_ref, lse_ref, delta_ref, hpb, D,
                   half) if geom.halves > 1 else ()
    _bwd_tiles(tile, stk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               hpb, bq, bk)

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_sweep_kernel(q_of, kv_of, head_of, flags, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_scr,
                      dk_acc, dv_acc, *stk, scale, causal, bq, bk, geom,
                      hpb=1, heads=1):
    """Several blocks, ONE sweep: a grid row is a (batch row, key/value
    head-block) pair and its steps walk the group's query head-blocks in
    chunks of ``heads`` (``heads_a_step``), of each chunk its q blocks, of
    each its visible kv blocks (``step_table``, q-major over the chunks).  A
    step is one (q block, kv block) tile with the chunk's heads looped
    inside: of each one probability tile and from it all three products, dq
    into its columns of the q sweep's scratch, dk and dv SUMMED over the
    step's heads and added ONCE a step into rows ``kv block`` of two float32
    accumulators that hold the whole sequence and are the key/value head's
    own, so they sum over the group; both leave once, at the grid row's last
    step.  Where the queries are not grouped the step's heads are adjacent
    head-blocks of the row with k, v, dk and dv of their own: blocks and
    accumulators ``heads`` head-blocks wide, a head's dk and dv added into
    its own columns and summed with no other's.  The mask of the tile is
    built once a step and the heads' chains
    stand side by side for the compiler to interleave.

    Where the heads of the lane block read one key/value head
    (``geom.halves`` > 1) they ride the q sweep stacked along rows
    (``stk``: q, do, lse and delta restacked at its first step, a head-block
    under the other): ONE [hpb * bq, bk] tile a head-block against the whole
    k and v lane blocks, dk and dv contracted over both heads' rows into the
    accumulators' full width (``_stack_heads``: zeros beside the half), dq
    unstacked at the last."""
    t, chunk = geom.step(heads, head_of)
    D = q_ref.shape[-1] // (heads * hpb)
    half = [geom.kv_half(chunk * heads + h) for h in range(heads)] \
        if stk else None

    def rows_of(kv_block):
        return pl.ds(pl.multiple_of(kv_block * bk, bk), bk)

    def kv_blocks_of_the_sequence(block):
        def step(n, carry):
            block(rows_of(n))
            return carry
        jax.lax.fori_loop(0, geom.Sk // bk, step, 0)

    rows = rows_of(kv_of[t])

    @pl.when(t == 0)
    def _open():
        def zero(at):
            if dv_acc.shape == dk_acc.shape:
                dk_acc[at, :] = dv_acc[at, :] = jnp.zeros(
                    (bk, dk_acc.shape[1]), jnp.float32)
                return
            for acc in (dk_acc, dv_acc):
                acc[at, :] = jnp.zeros((bk, acc.shape[1]), jnp.float32)
        kv_blocks_of_the_sequence(zero)

    @pl.when((flags[t] & FIRST) != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        if stk:
            _stack_sweep(stk, q_ref, do_ref, lse_ref, delta_ref, hpb, D,
                         halves=half)

    seen, sums = [], {}     # the tile's mask; dk and dv of the step's heads

    def add(acc, cols, part, last):
        """``part`` of one head into rows ``kv block`` of ``acc``: summed
        over the step's heads, one read-modify-write a step."""
        if id(acc) in sums:
            part = sums.pop(id(acc)) + part
        if last:
            acc[rows, cols] += part
        else:
            sums[id(acc)] = part

    def tile(q, k, v, do, lse, delta, cs, vs, qs, last, wrap=None):
        """The rows of q and do against columns ``cs`` of this kv block's
        keys and ``vs`` of its values; dq at columns ``qs``."""
        s = _scores(q, k, scale, causal, q_of[t] * bq, kv_of[t] * bk,
                    geom.window, wrap, seen, geom.rule)
        p = jnp.exp(s - lse())                     # [rows, bk] - the ONE exp
        # dv_j += p^T dO
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        add(dv_acc, vs, dv, last)
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = (p * (dov - delta()) * scale).astype(q.dtype)     # [rows, bk]
        dq_scr[:, qs] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dk_j += ds^T q
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        add(dk_acc, cs, dk, last)

    _bwd_tiles(tile, stk, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               hpb, bq, bk, heads)

    @pl.when((flags[t] & LAST) != 0)
    def _final():
        if not stk:
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)
        for h in range(heads if stk else 0):
            at = _cols(h, hpb * D)
            dq_ref[0, :, at] = _unstack_heads(
                dq_scr[:, at], hpb, D, half[h]).astype(dq_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _close():
        def leave(at):
            dk_ref[0, at, :] = dk_acc[at, :].astype(dk_ref.dtype)
            dv_ref[0, at, :] = dv_acc[at, :].astype(dv_ref.dtype)
        kv_blocks_of_the_sequence(leave)


def _delta(o, do, g, packed, interpret):
    """``sum_d(o * do)`` a head, float32 ``g.stat_shape``: the several-block
    backward kernels' row statistic.  The packed layout's in ONE pass of the
    row kernel (``kernels/flash_delta.py``) wherever it takes the shape; a
    shape it refuses and the [BH, S, D] layout keep the ``jnp`` lines, which
    the tests hold that kernel to.  Under a monitor session every traced
    call counts in ``monitor.kernels.flash_delta_calls`` (``fused`` 1 for
    the kernel)."""
    fused = packed and o.dtype == do.dtype and flash_delta.supported(
        o.shape, g.Dv, o.dtype.itemsize)
    _count_call("flash_delta", fused=int(fused), head_dim=g.Dv)
    if fused:
        return flash_delta.flash_delta(o, do, head_dim=g.Dv,
                                       interpret=interpret)
    if packed:
        return flash_delta.flash_delta_reference(o, do, g.Dv)
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True).reshape(g.stat_shape)


def _bwd(scale, causal, bq, bk, interpret, res, do, H=None, Hkv=None,
         window=None, Dv=None, blocks=None):
    q, k, v, o, lse = res
    g = _Geom(q, k, H, bq, bk, Hkv, window, Dv, blocks)
    if blocks:
        _count_call("flash_blockdiff", part="bwd", fused=1, blocks=blocks,
                    sweeps=g.bwd_sweeps)
    if g.one_block and g.group == 1:
        return _bwd_fused(scale, causal, bq, bk, interpret, res, do, H=H,
                          window=window, Dv=Dv)
    delta = _delta(o, do, g, H is not None, interpret)

    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dkv_shapes = [jax.ShapeDtypeStruct(g.dk_shape, k.dtype),
                  jax.ShapeDtypeStruct(g.dv_shape, v.dtype)]

    stack = g.halves * bq       # rows of a head-block's tile

    def stacked(heads=1):
        """What a q sweep keeps of its q block where it is a stack."""
        return [pltpu.VMEM((heads * stack, g.qw), q.dtype),
                pltpu.VMEM((heads * stack, g.qw), do.dtype),
                pltpu.VMEM((heads * stack, LANES), jnp.float32),  # lse, delta
                pltpu.VMEM((heads * stack, LANES), jnp.float32)
                ] * (g.halves > 1)

    def sweep(kernel, name, out_specs, out_shape, scratch_shapes,
              walks_group=False, kv_major=False, heads=1, **params):
        qm, km, sm = g.sweep_maps(heads, walks_group)
        kvh = g.kv_heads(heads)
        qs, ks = g.q_spec(bq, qm, heads), g.kv_spec(bk, km, kvh)
        os, vs = g.o_spec(bq, qm, heads), g.v_spec(bk, km, kvh)
        stats = g.stat_spec(bq, sm, heads)
        return _sweep_call(
            functools.partial(kernel, scale=scale, causal=causal, bq=bq,
                              bk=bk, hpb=g.hpb, geom=g),
            g, step_table(g.S, g.Sk, bq, bk, causal, window,
                          g.chunks(heads) if walks_group else 1, kv_major,
                          blocks),
            (q, k, v, do, lse, delta), [qs, ks, vs, os, stats, stats],
            out_specs(qs, ks, vs), out_shape, scratch_shapes,
            heads, walks_group, interpret, name, **params)

    if g.bwd_sweeps == 1:
        # dk and dv of the whole sequence: one block a grid row, so one
        # buffer (it leaves VMEM once, and the next row's has nothing to
        # overlap with but that)
        def whole(lanes):
            return pl.BlockSpec((1, g.Sk, lanes),
                                lambda r, kh, t, *table: (r, 0, kh),
                                pipeline_mode=pl.Buffered(1))

        heads, need = g.heads_in_step("bwd")
        _count_call("flash_sweep", part="bwd", group=g.group,
                    heads_in_step=heads)
        # dk and dv: the key/value head's own, so a group's one head-block
        # wide and, ungrouped, a head-block of each of the step's heads
        kw, vw = g.kv_heads(heads) * g.qw, g.kv_heads(heads) * g.vw
        return sweep(
            functools.partial(_bwd_sweep_kernel, heads=heads), "bwd_fused",
            lambda qs, ks, vs: [qs, whole(kw), whole(vw)],
            [dq_shape] + dkv_shapes,
            [pltpu.VMEM((stack, heads * g.qw), jnp.float32),
             pltpu.VMEM((g.Sk, kw), jnp.float32),
             pltpu.VMEM((g.Sk, vw), jnp.float32)] + stacked(heads),
            walks_group=True, heads=heads, vmem_limit_bytes=need)
    dq = sweep(_bwd_dq_kernel, "bwd_dq", lambda qs, ks, vs: qs, dq_shape,
               [pltpu.VMEM((stack, g.qw), jnp.float32)] + stacked())
    # the dk/dv sweep's rows run over the key/value heads
    dk, dv = sweep(_bwd_dkv_kernel, "bwd_dkv", lambda qs, ks, vs: [ks, vs],
                   dkv_shapes,
                   [pltpu.VMEM((bk, g.qw), jnp.float32),
                    pltpu.VMEM((bk, g.vw), jnp.float32)],
                   walks_group=True, kv_major=True)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, bq, bk, interpret):
    o, _ = _fwd(q, k, v, scale, causal, bq, bk, interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, bq, bk, interpret):
    o, lse = _fwd(q, k, v, scale, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, bq, bk, interpret, res, do):
    return _bwd(scale, causal, bq, bk, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_packed(q, k, v, heads, scale, causal, bq, bk, interpret):
    """``heads`` = (H, Hkv, window[, Dv[, blocks]])."""
    o, _ = _fwd(q, k, v, scale, causal, bq, bk, interpret, *heads)
    return o


def _flash_packed_fwd(q, k, v, heads, scale, causal, bq, bk, interpret):
    o, lse = _fwd(q, k, v, scale, causal, bq, bk, interpret, *heads)
    return o, (q, k, v, o, lse)


def _flash_packed_bwd(heads, scale, causal, bq, bk, interpret, res, do):
    return _bwd(scale, causal, bq, bk, interpret, res, do, *heads)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=256, interpret=None):
    """q, k, v: [B, S, H, D] (model layout).  Returns [B, S, H, D].

    Falls back gracefully: callers should gate on shape divisibility (see
    parallel/transformer.py attention dispatch).
    """
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)

    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), float(scale), bool(causal),
               bq, bk, bool(interpret))
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_packed(q, k, v, n_heads, causal=False, scale=None,
                           block_q=256, block_k=256, interpret=None,
                           n_kv_heads=None, window=None, v_head_dim=None,
                           block_diffusion=None):
    """Packed-layout flash attention: q, k, v are [B, S, H*D] exactly as the
    qkv projections produce them; returns [B, S, H*D] ready for the output
    projection.  The per-head D-wide column slices are addressed by the
    Pallas BlockSpec index maps, so no [B, H, S, D] transpose or reshape ever
    touches HBM (~8 layout copies/layer saved vs the bshd entry at bench
    shapes).

    ``n_kv_heads`` < ``n_heads``: grouped queries, k and v [B, S, Hkv*D].
    ``window``: query i sees keys j with i - window < j <= i (causal only;
    a window of S or more is the causal mask and changes nothing).
    ``v_head_dim`` other than D: v is [B, S, H*v_head_dim] and so is the
    result; q's and k's heads and v's are whole lane blocks, each product
    with v (``P V``, ``dP``, ``dV``) runs at the values' width, and no
    grouping rides with it (a window does: the band's table and mask know
    no width).  Give ``scale`` where D holds lanes
    that are not the head's (zeros behind a head of 192 in 256 lanes).
    ``block_diffusion`` = Bd: the S rows are a noised copy of a sequence over
    its clean copy, S / 2 positions each in blocks of Bd, and the mask is the
    block-diffusion rule's three parts (``blockdiff_seen``: not ``causal``,
    no window; a copy is whole tiles and a tile whole blocks).  The rule
    follows from the shapes: no mask operand, nothing [S, S] anywhere."""
    B, S, E = q.shape
    H = n_heads
    assert E % H == 0, (E, H)
    D = E // H
    Hkv = n_kv_heads or H
    Dv = D if v_head_dim is None else int(v_head_dim)
    if not packed_layout_supported(H, D, Hkv, Dv):
        raise ValueError(
            "packed layout cannot tile H=%d (kv %d) heads of D=%d (needs "
            "D*hpb a multiple of %d lanes with hpb dividing H, and with "
            "grouped queries hpb dividing the group and the key/value "
            "heads); use flash_attention on [B, S, H, D]"
            % (H, Hkv, D, LANES))
    assert k.shape[-1] == Hkv * D and v.shape[-1] == Hkv * Dv, (
        k.shape, v.shape, Hkv, D, Dv)
    if window is not None:
        assert causal and window >= 1 and k.shape[1] == S, (causal, window)
        if window >= S:
            window = None
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = not _on_tpu()
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)
    heads = (H, Hkv, window) + ((Dv,) if Dv != D else ())
    if block_diffusion:
        # (a block length that is a power of two: the in-tile mask shifts)
        assert not causal and window is None and Sk == S \
            and block_diffusion & (block_diffusion - 1) == 0, (
                causal, window, block_diffusion)
        heads = (H, Hkv, None, Dv, int(block_diffusion))
    return _flash_packed(q, k, v, heads, float(scale), bool(causal), bq, bk,
                         bool(interpret))
