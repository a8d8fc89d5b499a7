"""Mixture-of-Experts FFNs.  ONE dropless layer in three layouts, and what
is left of an older one:

- ``dropless_moe_ffn``: top-k routing with NO capacity and no dropped token.
  The T*k (token, expert) pairs are sorted by
  expert, the rows gathered in that order, and two GROUPED matmuls run over
  the sorted rows (the Pallas ``megablox`` kernels that ship with JAX: row i
  meets the weights of its own group only, so nothing is computed for a
  pair that was not routed; their tiles follow each call's static shapes,
  ``_tiling``: a row tile of which a group expects four, the contraction
  whole where the blocks fit the kernel's VMEM, every cut in equal parts;
  measured on the v5e by ``scripts/moe_grouped_matmul_bench.py``, PERF.md
  section 6, PR 46),
  the router weight riding the hidden rows
  between them so that nothing after the down projection is kept for the
  backward; then the sort is undone and each token's k rows are summed
  (the Pallas row kernel ``kernels/moe_rows.py``: a block of tokens' pairs
  that have a row, one row DMA each, summed in float32 in slot order).
  The FFN of a ``TransformerConfig`` with ``n_experts > 0`` (models/olmoe.py,
  models/smallthinker.py, models/lfm2.py).  By configuration: the routing
  rule (``RULES``: softmax over all experts and the k largest as they are;
  the k largest logits and a softmax over those; or sigmoid scores, the k
  experts chosen by score PLUS a per-expert bias and weighted by the
  scores WITHOUT it, renormalised, the bias a running state that
  ``balance_bias`` moves against the load and no gradient reaches), router
  logits the caller computed from another input, the gate's activation
  (``ACTIVATIONS``), and WHERE THE EXPERTS ARE:

  * ALL HELD: every expert on this device (OLMoE);
  * A SHARE (``first_held`` and the leading size of the experts' leaves):
    the router still ranks all n, the pairs whose expert is held are
    sorted to the front, only a static number of rows that covers them is
    gathered and multiplied (``_held_capacities``), the sum back fetches the
    rows that exist and skips the pair slots that have none (a gather would
    fetch a row or the zero row for every slot), and what the absent experts
    would add is left out.  No pair that meets a held expert is dropped,
    whatever the routing;
  * EXPERT-PARALLEL (``ep_axis``, by convention ``dp``: "EP rides DP";
    ``TransformerConfig.expert_parallel``): the devices of the axis hold
    n / ep experts each (``parallel/rules.py`` splits the experts' leaves
    over the axis) and EXCHANGE rows.  A device's pairs are sorted by expert,
    hence by destination, packed into a static number of rows a destination
    (``_held_capacities``' first capacity: 1.25 times what uniform routing
    sends), sent with ``lax.all_to_all``, sorted there by local expert, run
    through the same grouped matmuls, sent back and summed a token by the
    same row kernel.  NO PAIR IS DROPPED whatever the routing: a step makes
    as many ROUNDS of that exchange as the fullest destination of any
    device needs (``pmax`` over the axis, so every device makes the same
    number and the collectives line up): one under balance, ``ceil(T k /
    capacity)`` where every pair of every device meets one device's experts
    (``_exchange_ffn``).  On an axis of size 1 this is the all-held layer:
    no collective, no packing.

- ``switch_moe_ffn``: top-1 (Switch) routing with a capacity limit that
  DROPS the overflow, experts sharded over a mesh axis and exchanged with
  ``lax.all_to_all``.  Net-new
  against the reference (SURVEY.md section 2.9: it has no expert
  parallelism; its sparse story is the PSLib parameter server,
  fleet/fleet_wrapper.h:55).  The multichip dry run is its one caller; it is
  what is LEFT TO FOLD into the dropless exchange above, which now has a
  model and a cell (ROADMAP.md Design 11: a ``simplicity`` PR).

Per-device code for use inside shard_map bodies (parallel/train.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from . import collectives as col
from .mesh import DP
from ..kernels._common import count_call, on_tpu
from ..kernels.moe_rows import moe_rows_sum
from ..monitor import devscope

__all__ = ["init_moe_params", "switch_moe_ffn", "init_dropless_moe_params",
           "dropless_moe_ffn", "route_top_k", "router_logits", "RULES",
           "ACTIVATIONS", "balance_bias"]

# routing rules: how the k experts' weights come from the router's logits
SOFTMAX_TOP_K = "softmax_top_k"     # softmax over all n, the k largest as they are
TOP_K_SOFTMAX = "top_k_softmax"     # the k largest logits, softmax over those k
# sigmoid scores; the k largest of score + bias; their scores, without the
# bias, over their sum
SIGMOID_BIASED = "sigmoid_biased_top_k"
RULES = (SOFTMAX_TOP_K, TOP_K_SOFTMAX, SIGMOID_BIASED)
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def init_moe_params(key, n_experts, hidden, ffn_hidden, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": _normal(k1, (hidden, n_experts), hidden, jnp.float32),
        "w1": _normal(k2, (n_experts, hidden, ffn_hidden), hidden, dtype),
        "w2": _normal(k3, (n_experts, ffn_hidden, hidden), ffn_hidden, dtype),
    }


def init_dropless_moe_params(key, n_experts, hidden, ffn_hidden,
                             dtype=jnp.float32):
    """``router`` [E, n] float32; ``we_gate_up`` [n, E, 2F], the gate
    projection in columns [0, F) and the up projection in [F, 2F);
    ``we_down`` [n, F, E]."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": _normal(k1, (hidden, n_experts), hidden, jnp.float32),
        "we_gate_up": _normal(k2, (n_experts, hidden, 2 * ffn_hidden), hidden,
                              dtype),
        "we_down": _normal(k3, (n_experts, ffn_hidden, hidden), ffn_hidden,
                           dtype),
    }


def moe_param_specs(ep_axis=DP):
    """Derived from the rule tree (parallel/rules.py moe_rules)."""
    from . import rules as shard_rules

    leaf = shard_rules.SkeletonLeaf
    return shard_rules.match_partition_rules(
        shard_rules.moe_rules(ep_axis),
        {"router": leaf(), "w1": leaf(), "w2": leaf()})


def _per_expert(top_e, n):
    """Assignments to each of ``n`` experts, int32 [n]: a compare and a column
    sum (0.2 ms for 131,072 on the chip; ``jnp.bincount`` scatters: 1.2)."""
    return jnp.sum(top_e.reshape(-1, 1) == jnp.arange(n), axis=0,
                   dtype=jnp.int32)


@devscope.scoped(devscope.ROUTER)
def router_logits(router, x):
    """``x @ router`` in float32: [T, n]."""
    return x.astype(jnp.float32) @ router.astype(jnp.float32)


@devscope.scoped(devscope.ROUTER)
def route_top_k(router, x, k, rule=SOFTMAX_TOP_K, logits=None, bias=None,
                scale=1.0):
    """Router of a dropless layer on the tokens ``x`` [T, E] (or on
    ``logits`` [T, n] where the caller computed them from another input):
    the k experts of each token and their weights, [T, k] each.  By
    ``rule``: the k largest of ``softmax(logits)`` (float32, over ALL
    experts; as they are, not renormalised to sum to one), or the k largest
    logits and a softmax over those k (the same as renormalising the
    first); whichever the rule, times ``scale`` once formed.  And the
    layer's auxiliary values over the dp-global batch:

    - ``load_balance`` = n * sum_e f_e * P_e, ``f_e`` the share of the T*k
      assignments that went to expert e (a count: no gradient), ``P_e`` the
      mean of p_e over tokens; 1 at a uniform router;
    - ``router_z`` = mean_t logsumexp(logits_t)^2;
    - ``load_max_over_mean``: the busiest expert's assignments over the mean.

    SIGMOID_BIASED: ``s = sigmoid(logits)``; the k experts with the largest
    ``s + bias`` (``bias`` [n] float32: it decides who is chosen and
    nothing else, so no gradient reaches it); weights ``s_e / (sum of the
    chosen s + 1e-6)``.  A softmax's auxiliary losses mean nothing to it:
    ``aux`` holds ``load_max_over_mean`` and ``load`` [n], the assignments
    to each expert, which is what ``balance_bias`` reads."""
    assert rule in RULES, rule
    n = router.shape[-1]
    if logits is None:
        logits = router_logits(router, x)
    tokens = logits.shape[0] * col.axis_size_in(DP)
    if rule == SIGMOID_BIASED:
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
        top_s = jnp.take_along_axis(scores, top_e, axis=-1)
        top_p = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6)
        counts = col.psum(_per_expert(top_e, n), DP)
        return _scaled(top_p, scale), top_e, {
            "load": counts,
            "load_max_over_mean": jnp.max(counts) * (n / (tokens * k))}
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    if rule == SOFTMAX_TOP_K:
        top_p, top_e = jax.lax.top_k(probs, k)
    else:
        top_l, top_e = jax.lax.top_k(logits, k)
        top_p = jax.nn.softmax(top_l, axis=-1)
    counts = col.psum(_per_expert(top_e, n), DP)
    share = counts.astype(jnp.float32) / (tokens * k)
    mean_p = col.psum_forward(jnp.sum(probs, axis=0), DP) / tokens
    aux = {"load_balance": n * jnp.sum(share * mean_p),
           "router_z": col.psum_forward(jnp.sum(jnp.square(lse)), DP)
           / tokens,
           "load_max_over_mean": jnp.max(share) * n}
    return _scaled(top_p, scale), top_e, aux


def _scaled(top_p, scale):
    return top_p if scale == 1.0 else top_p * scale


def balance_bias(bias, load, rate):
    """The selection biases after a step that routed ``load`` [..., n]
    assignments to each expert: ``bias + rate * sign(mean(load) - load)``,
    an expert under the mean up and one over it down by the same ``rate``
    whatever the distance (the auxiliary-loss-free balance of Wang et al.,
    arXiv:2408.15664).  The one rule that moves them."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """Row i of the result is token ``order[i] // k``: x [T, E] gathered into
    the sorted order of its T*k assignments.  ``order`` is a permutation with
    inverse ``inv``, so the transpose is ``_combine``.

    With experts absent, ``order`` is the first M places of the sort (the
    held pairs come first) and ``inv`` [T*k] gives a held pair's row and M
    for every other pair; rows past the held pairs are some token's, and
    nothing reads what is computed from them."""
    return x[order // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, order, inv, k):
    """Token t of the result is the float32 sum of its k rows, ``rows[inv]``
    (the sort undone) k at a time in slot order: ``_dispatch``'s transpose,
    as it is its.  A pair whose place is past the rows (its expert is
    absent) adds zero, and costs no fetch: the sum is the row kernel's
    (``kernels/moe_rows.py``), which starts one row copy for each pair that
    HAS a row, where XLA's gather fetches a row, or the zero row, for every
    pair slot, one at a time."""
    return moe_rows_sum(rows, inv, k, interpret=not on_tpu())


@jax.custom_vjp
def _move(v, to, back):
    """Element i of the vector ``v`` moved to place ``to[i]`` (a permutation,
    ``back`` its inverse) by a sort: 0.2 ms for 131,072 floats on the chip
    where the gather ``v[back]`` takes 1.2.  Its transpose moves them back."""
    return jax.lax.sort((to, v), num_keys=1)[1]


# each one's backward is the other on the cotangent; a custom_vjp backward is
# traced on its own, in the backward pass, and names its scope itself
_scoped = devscope.scoped(devscope.MOE)
_dispatch.defvjp(lambda *a: (_dispatch(*a), a[1:3]),      # keeps order, inv
                 _scoped(lambda k, res, g: (_combine(g, *res, k), None, None)))
_combine.defvjp(lambda *a: (_combine(*a), a[1:3]),
                _scoped(lambda k, res, g: (_dispatch(g, *res, k), None, None)))
_move.defvjp(lambda v, to, back: (_move(v, to, back), (back, to)),
             _scoped(lambda res, g: (_move(g, *res), None, None)))


ROW_TILES = (512, 256, 128)      # tm: the tallest a group expects TILES_A_GROUP of
TILES_A_GROUP = 4                # so the tile a group's edge wastes is a quarter of it
LANES = 128                      # a cut dimension is whole lane tiles
MIN_COLUMNS = 256                # gmm's column tile is not under this, nor under tm
VMEM_BUDGET = 12 * 2 ** 20       # of the 16 MiB a v5e kernel's scope has by default


def _vmem_bytes(tm, tk, tn, itemsize, dw=False):
    """What a grid step of ``gmm`` (``tgmm`` where ``dw``) holds in VMEM at
    these tiles: both operand blocks and the result's, twice each for the
    pipeline, and the float32 accumulator of the result's block.  ``gmm``
    reads [tm, tk] rows and [tk, tn] weights for a [tm, tn] block; ``tgmm``
    reads [tm, tk] and [tm, tn] rows for a [tk, tn] block of dW.  The
    compiled kernels take up to 2.1 MiB more at the cells' shapes (the
    masks' float32 copies, a transposed weight block's copy):
    ``tests/test_chip_compile_moe.py`` holds that against the scope."""
    read = tm * tk + (tm * tn if dw else tk * tn)
    out = tk * tn if dw else tm * tn
    return 2 * (read + out) * itemsize + 4 * out


def _parts(size, least=LANES):
    """``size`` whole, then its equal parts of whole lane tiles that are not
    under ``least``, widest first."""
    return [size] + [size // p for p in range(2, size // least + 1)
                     if size % p == 0 and size // p % LANES == 0]


def _tiling(m, k, n, groups=1, itemsize=2, dw=False):
    """Tiles (rows, contraction, columns) of one megablox call, from what
    the call is compiled for: ``m`` rows in ``groups`` groups, contraction
    ``k`` and ``n`` columns of ``itemsize`` bytes (``dw``: ``tgmm``, whose
    ``k`` x ``n`` is a group's block of dW).

    - ROWS.  ``gmm`` and ``tgmm`` visit a whole row tile for every (tile,
      group) pair that has a row, so a call pays about ``rows + groups *
      tm`` rows of MXU work: ``tm`` is the tallest of ROW_TILES of which a
      group expects TILES_A_GROUP (``m // groups`` rows; 128 at the least),
      and all the rows where they are fewer.
    - Only tiles that DIVIDE their dimension (a remainder tile of the
      contraction is masked element by element, one of the columns computed
      whole and cut) and keep ``_vmem_bytes`` within VMEM_BUDGET.
    - ``gmm``, whose grid is (column tiles, visits, k tiles): the
      contraction WHOLE where a column tile fits beside it, else in the
      fewest equal parts that leave room for one, and then the widest
      column tile.  With one k tile a group's weight block keeps its index
      over the group's consecutive visits and is fetched once, where a cut
      contraction fetches it again every visit and reads its partial sums
      back: a thin group's call (Trinity's 96 rows an expert) becomes bound
      by its weights' bytes, which is what the shapes require.  Every
      column tile sweeps all the rows again, so it is not narrower than the
      row tile (MIN_COLUMNS at the least).
    - ``tgmm``, whose grid is (column tiles, k tiles, visits): the block of
      dW that has the rows read the fewest times, ``k / tk`` sweeps of the
      cotangent's and ``n / tn`` of the rows'.

    Measured on the v5e at one layer's shapes of the five sparse cells
    (``scripts/moe_grouped_matmul_bench.py``; PERF.md section 6, PRs 27 and
    46): 512 x 1024 x 1024, PR 27's best of three at OLMoE's shape, is
    still what that shape's dW and down projection get."""
    expect = m // groups
    tm = min(m, next((t for t in ROW_TILES if TILES_A_GROUP * t <= expect),
                     ROW_TILES[-1]))
    fits = lambda tk, tn: _vmem_bytes(tm, tk, tn, itemsize, dw) <= VMEM_BUDGET
    if dw:
        fitting = [(tk, tn) for tk in _parts(k) for tn in _parts(n)
                   if fits(tk, tn)]
        return (tm,) + min(fitting, key=lambda t: (
            k // t[0] * n + n // t[1] * k, -t[1]))
    columns = _parts(n, min(n, max(tm, MIN_COLUMNS)))
    return (tm,) + next(((tk, tn) for tk in _parts(k) for tn in columns
                         if fits(tk, tn)), (LANES, columns[-1]))


def _note_tiling(kernel, tiling, m, groups):
    """Under a monitor session, one count a COMPILED call of a megablox
    kernel (this runs when a program is traced, not when it runs):
    ``monitor.kernels.moe_grouped_matmul_calls`` by kernel, tiles and
    ``thin`` (1 where a group expects fewer rows than the tallest row
    tile), so a summary says how many of a program's grouped matmuls were
    compiled off the 512-row tile."""
    count_call("moe_grouped_matmul", kernel=kernel, tm=tiling[0],
               tk=tiling[1], tn=tiling[2],
               thin=int(m // groups < ROW_TILES[0]))


def _whole_row_tiles(rows, tm):
    """Rows padded to whole tiles; the kernels skip rows past the groups."""
    return jnp.pad(rows, ((0, -rows.shape[0] % tm), (0, 0)))


def _gmm(rows, weights, group_sizes, transpose_rhs=False):
    m, k = rows.shape
    tiling = _tiling(m, k, weights.shape[1 if transpose_rhs else 2],
                     weights.shape[0], rows.dtype.itemsize)
    _note_tiling("gmm", tiling, m, weights.shape[0])
    out = gmm(_whole_row_tiles(rows, tiling[0]), weights, group_sizes,
              rows.dtype, tiling, transpose_rhs=transpose_rhs,
              interpret=not on_tpu())
    return out[:m]


@jax.custom_vjp
def _grouped_matmul(rows, weights, group_sizes):
    """rows [M, K] sorted by group, weights [G, K, N]: row i times the weights
    of its own group, nothing for a pair that was not routed.  The Pallas
    grouped matmul that ships with JAX (``megablox``) at the tiles
    ``_tiling`` gives each call: a quarter faster at OLMoE's shape than
    XLA's lowering of ``jax.lax.ragged_dot`` (35.9 against 48.3 ms a
    layer's forward and backward; 2.8 against 6.4 at Trinity's thin
    groups), and its instructions keep the program's scope in their
    ``op_name``."""
    return _gmm(rows, weights, group_sizes)


@devscope.scoped(devscope.MOE)
def _grouped_matmul_bwd(res, g):
    rows, weights, group_sizes = res
    tiling = _tiling(*rows.shape, g.shape[1], weights.shape[0],
                     rows.dtype.itemsize, dw=True)
    _note_tiling("tgmm", tiling, rows.shape[0], weights.shape[0])
    d_weights = tgmm(
        _whole_row_tiles(rows, tiling[0]).swapaxes(0, 1),
        _whole_row_tiles(g, tiling[0]), group_sizes, weights.dtype, tiling,
        num_actual_groups=weights.shape[0], interpret=not on_tpu())
    d_rows = _gmm(g, weights, group_sizes, transpose_rhs=True)
    # dW where its operands are live: left to itself the scheduler puts all
    # six tgmm at the end of the step and gathers their rows a second time
    d_rows, d_weights = jax.lax.optimization_barrier((d_rows, d_weights))
    return d_rows, d_weights, None


_grouped_matmul.defvjp(lambda *args: (_gmm(*args), args), _grouped_matmul_bwd)


HELD_GRANULE = 512           # a capacity is whole row tiles, whichever of ROW_TILES
HELD_HEADROOM = 1.25         # first capacity over the rows uniform routing gives


def _held_capacities(pairs, count, n):
    """The static row counts a layer that holds ``count`` of ``n`` experts
    is compiled for: HELD_HEADROOM times the rows uniform routing brings
    (``pairs * count / n``) in whole HELD_GRANULE rows (every row tile
    ``_tiling`` may choose divides it, so no capacity is padded), and all
    ``pairs`` (T*k), so no routing overflows; at T*k = 98,304 and 16 of 64,
    (30720, 98304).  The first capacity over ``count`` is the rows a group
    EXPECTS, which the kernels' row tile follows.  A
    step runs the first where it covers its held pairs (``lax.switch``), so
    the dispatch's gather and the kernels' grids follow the rows held as
    long as the routing stays within the headroom of balance, and a step
    past it pays them for every pair.  The sum back (``_combine``, and as
    ``_dispatch``'s transpose) follows the rows HELD at either capacity: it
    starts one row copy a pair that has a row, whatever the rows' count."""
    cap = -int(-HELD_HEADROOM * pairs * count / n // HELD_GRANULE) * HELD_GRANULE
    return (cap, pairs) if cap < pairs else (pairs,)


def _held(top_e, first, count):
    """Which (token, expert) pairs meet an expert in [first, first + count)."""
    return (top_e >= first) & (top_e < first + count)


def _expert_ffn(x, top_p, top_e, w_gate_up, w_down, k, act, first, rows_max):
    """``sum_e p_te * down_e(act(gate_e x_t) * up_e x_t)`` over the experts
    [first, first + count) that ``w_gate_up`` [count, E, 2F] / ``w_down``
    hold, through ``rows_max`` sorted rows, which cover the pairs that meet
    a held expert (None: every expert is held and every pair has a row).
    Where ``w_gate_up`` is as wide as ``w_down`` is tall ([count, E, F]) the
    experts are UNGATED: ``down_e(act(up_e x_t))``."""
    count, absent = w_gate_up.shape[0], rows_max is not None
    key = top_e
    if absent:
        held = _held(top_e.reshape(-1), first, count)
        key = jnp.where(held, top_e.reshape(-1) - first, count)   # absent: last
    order = jnp.argsort(key.reshape(-1), stable=True).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    group_sizes = _per_expert(key, count)
    # with experts absent: the sort's first rows_max places, and a pair's row
    # or, for a pair that has none, the place past the last
    place = (order[:rows_max], jnp.where(held, inv, rows_max)) if absent \
        else (order, inv)

    rows = _dispatch(x, *place, k)                               # [M, E]
    weight = _move(top_p.reshape(-1), inv, order)                # [T*k]
    if absent:
        # rows past the held pairs belong to no group: the kernels leave
        # their outputs unwritten, so their weight's gradient is cut here,
        # as no pair's place points at them
        live = jnp.arange(rows_max) < jnp.sum(group_sizes)
        weight = jnp.where(live, weight[:rows_max], 0.0)
    out = _sorted_rows_ffn(rows, weight, w_gate_up, w_down, group_sizes, act)
    return _combine(out, *place, k)


def _sorted_rows_ffn(rows, weight, w_gate_up, w_down, group_sizes, act):
    """The experts' FFN over ``rows`` [M, E] sorted by group, the router
    weight of each row riding its HIDDEN row (the triple product in float32,
    rounded once): the two grouped matmuls of every layout."""
    up = _grouped_matmul(rows, w_gate_up, group_sizes)
    if w_gate_up.shape[2] == 2 * w_down.shape[1]:
        gate, up = jnp.split(up, 2, axis=-1)
        hidden = (ACTIVATIONS[act](gate.astype(jnp.float32))
                  * up.astype(jnp.float32)
                  * weight[:, None]).astype(rows.dtype)
    else:
        hidden = (ACTIVATIONS[act](up.astype(jnp.float32))
                  * weight[:, None]).astype(rows.dtype)
    return _grouped_matmul(hidden, w_down, group_sizes)


def _held_tier(top_e, first, count, caps):
    """Index of the smallest capacity that covers the held pairs."""
    held = jnp.sum(_held(top_e, first, count), dtype=jnp.int32)
    return sum((held > cap).astype(jnp.int32) for cap in caps[:-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_expert_ffn(x, top_p, top_e, w_gate_up, w_down, static):
    """``_expert_ffn`` at the smallest of the static capacities that covers
    this step's held pairs.  ``static`` = (k, act, first, capacities).

    A ``custom_vjp`` whose residuals are its arguments and whose backward
    makes the choice again: differentiated as it stands, ``lax.switch``
    would hand the backward every branch's residuals, the largest
    capacity's among them, zero-filled for the branches not taken.  The
    chosen branch's forward up to the hidden rows is computed again in the
    backward; under ``jax.checkpoint`` the layer's recomputed forward then
    has nothing of the expert FFN to keep and the compiler drops it, so the
    kernel calls a step makes are those of the all-held layer."""
    k, act, first, caps = static
    branches = [functools.partial(_expert_ffn, k=k, act=act, first=first,
                                  rows_max=cap) for cap in caps]
    return jax.lax.switch(
        _held_tier(top_e, first, w_gate_up.shape[0], caps), branches,
        x, top_p, top_e, w_gate_up, w_down)


@devscope.scoped(devscope.MOE)
def _held_expert_ffn_bwd(static, res, g):
    k, act, first, caps = static
    x, top_p, top_e, w_gate_up, w_down = res

    def branch(cap):
        def ffn(x, top_p, w_gate_up, w_down):
            return _expert_ffn(x, top_p, top_e, w_gate_up, w_down, k, act,
                               first, cap)
        return lambda *a: jax.vjp(ffn, *a[:4])[1](a[4])

    dx, dp, dgu, dd = jax.lax.switch(
        _held_tier(top_e, first, w_gate_up.shape[0], caps),
        [branch(cap) for cap in caps], x, top_p, w_gate_up, w_down, g)
    return dx, dp, None, dgu, dd


_held_expert_ffn.defvjp(lambda *a: (_held_expert_ffn(*a), a[:5]),
                        _held_expert_ffn_bwd)


# -- expert-parallel: the experts ride a mesh axis and rows are exchanged ----

_exchanged = devscope.scoped(devscope.EXCHANGE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pack(x, slot_pair, pair_row, k):
    """Slot i of the send buffer is token ``slot_pair[i] // k``: ``_dispatch``
    over the slots of one round, under the exchange's scope.  ``pair_row``
    [T*k] is a pair's slot, or the slots' count for a pair that travels in
    another round; a slot past a destination's pairs holds some token's row,
    and nothing reads what is computed from it."""
    return x[slot_pair // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _unpack(rows, slot_pair, pair_row, k):
    """Token t of the result is the float32 sum of the rows that came home
    for its pairs IN THIS ROUND (``kernels/moe_rows.py``; a pair that has no
    slot in it adds zero and costs no fetch): ``_pack``'s transpose, as it
    is its."""
    return moe_rows_sum(rows, pair_row, k, interpret=not on_tpu())


@jax.custom_vjp
def _permute(rows, perm, inv):
    """``rows[perm]``, ``perm`` a permutation with inverse ``inv``: the
    transpose is the same gather by ``inv``."""
    return rows[perm]


_pack.defvjp(lambda *a: (_pack(*a), a[1:3]),
             _exchanged(lambda k, res, g: (_unpack(g, *res, k), None, None)))
_unpack.defvjp(lambda *a: (_unpack(*a), a[1:3]),
               _exchanged(lambda k, res, g: (_pack(g, *res, k), None, None)))
_permute.defvjp(lambda rows, perm, inv: (rows[perm], (inv, perm)),
                _exchanged(lambda res, g: (_permute(g, *res), None, None)))


def _exchange_capacity(pairs, ep):
    """The static rows a DESTINATION is sent in one round of the exchange:
    ``_held_capacities``' first capacity for a device that holds 1 / ep of
    the experts (1.25 times the ``pairs / ep`` rows uniform routing sends
    it, in whole HELD_GRANULE rows; all ``pairs`` where that is no fewer)."""
    return _held_capacities(pairs, 1, ep)[0]


def _exchange_plan(top_e, per, axis, cap):
    """What a step's exchange follows, from its routing ``top_e`` [T, k]
    alone (integers: nothing here has a gradient; ``per`` experts a device),
    the same for every round:

    - ``order`` [P]: the pairs sorted by expert, hence by DESTINATION
      (expert // per), ``inv`` its inverse, ``dest`` [P] each pair's
      destination and ``pos`` [P] its place among the rows this device sends
      there;
    - ``sent`` [ep] the rows for each destination and ``start`` [ep] where
      each one's begin in the sort;
    - ``recv`` [ep, per]: the rows each SOURCE sends this device for each of
      its experts (the senders' counts, exchanged);
    - ``rounds``: how many rounds of ``cap`` rows a destination the fullest
      destination of ANY device needs (``pmax`` over the axis: every device
      makes the same number of rounds, so the collectives line up)."""
    ep = col.axis_size_in(axis)
    flat = top_e.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    send = _per_expert(flat, ep * per).reshape(ep, per)
    sent = jnp.sum(send, axis=1)
    start = jnp.cumsum(sent) - sent
    dest = (flat // per).astype(jnp.int32)
    inv = jnp.argsort(order).astype(jnp.int32)
    # ``start[dest]`` without a gather: ep compares a pair
    pos = inv - jnp.sum(jnp.where(dest[:, None] == jnp.arange(ep), start, 0),
                        axis=1)
    rounds = jnp.maximum(col.pmax(jnp.max(-(-sent // cap)), axis), 1)
    return {"order": order, "inv": inv, "dest": dest, "pos": pos,
            "sent": sent, "start": start, "rounds": rounds,
            "recv": jax.lax.all_to_all(send, axis, 0, 0)}


def _slices(sorted_values, first, cap):
    """[ep, cap]: ``cap`` places of the sorted vector from each of ``first``
    [ep] on (a destination's rows stand together in the sort, so a round's
    slots are ep SLICES of it, not a gather); the vector is padded by
    ``cap``, so a slice that runs past a destination's rows, or past the
    end, reads places that no slot that counts uses."""
    padded = jnp.concatenate(
        [sorted_values, jnp.zeros((cap,), sorted_values.dtype)])
    return jnp.stack([jax.lax.dynamic_slice(padded, (first[d],), (cap,))
                      for d in range(first.shape[0])])


def _exchange_round(x, sorted_p, w_gate_up, w_down, plan, r, static):
    """Round ``r`` of the exchange: what the experts of every device add to
    this device's tokens for the pairs whose place among their
    destination's rows lies in [r cap, (r + 1) cap).

    ``sorted_p`` [T k]: the router's weights in the sort's order (moved
    once a step, ``_move``).  PACK (scope ``exchange``): slot (d, j) of the
    send buffer [ep, cap, E]
    is the pair at place ``r cap + j`` among destination d's, its router
    weight beside it in a float32 buffer of its own, so that the weight
    still rides the HIDDEN row on the device that computes it and nothing
    after the down projection is kept for the backward.  One row a PAIR is
    sent: a token that meets two experts of one device travels twice.
    ``all_to_all`` over the axis: chunk d leaves for device d.  UNPACK: the
    received chunks (source s in chunk s, each sorted by local expert, its
    unused slots last) are sorted by local expert into one run a group,
    slots that hold no pair behind the last group, where the kernels skip
    them.  The grouped matmuls are the all-held layer's.  The way home is
    the way out, transposed: the inverse permutation, ``all_to_all``, and
    the row kernel's sum a token over the slots its pairs have."""
    k, act, axis, cap = static
    ep = plan["recv"].shape[0]
    slots, lo = ep * cap, r * cap
    with jax.named_scope(devscope.EXCHANGE):
        place = lo + jnp.arange(cap, dtype=jnp.int32)
        slot_pair = _slices(plan["order"], plan["start"] + lo,
                            cap).reshape(slots)
        used = place[None] < plan["sent"][:, None]
        here = (plan["pos"] >= lo) & (plan["pos"] < lo + cap)
        pair_row = jnp.where(here, plan["dest"] * cap + plan["pos"] - lo,
                             slots)
        rows = _pack(x, slot_pair, pair_row, k).reshape(ep, cap, -1)
        weight = jnp.where(used, _slices(sorted_p, plan["start"] + lo, cap),
                           0.0)
        rows = jax.lax.all_to_all(rows, axis, 0, 0)
        weight = jax.lax.all_to_all(weight, axis, 0, 0)
        # the rows of source s are sorted by local expert; slot j of its
        # chunk is that expert's whose running count first passes lo + j
        ends = jnp.cumsum(plan["recv"], axis=1)                 # [ep, per]
        group = jnp.sum(place[None, :, None] >= ends[:, None, :], axis=-1)
        group_sizes = jnp.sum(
            jnp.clip(ends, lo, lo + cap)
            - jnp.clip(ends - plan["recv"], lo, lo + cap), axis=0)
        order = jnp.argsort(group.reshape(slots), stable=True).astype(
            jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        rows = _permute(rows.reshape(slots, -1), order, inv)
        # slots past the last group hold no pair: the kernels leave their
        # outputs unwritten, and their weight's gradient is cut here
        weight = jnp.where(jnp.arange(slots) < jnp.sum(group_sizes),
                           _move(weight.reshape(slots), inv, order), 0.0)
    out = _sorted_rows_ffn(rows, weight, w_gate_up, w_down, group_sizes, act)
    with jax.named_scope(devscope.EXCHANGE):
        out = _permute(out, inv, order).reshape(ep, cap, -1)
        out = jax.lax.all_to_all(out, axis, 0, 0)
        return _unpack(out.reshape(slots, -1), slot_pair, pair_row, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _exchange_ffn(x, top_p, top_e, w_gate_up, w_down, static):
    """The expert-parallel layer: ``_exchange_round`` summed over as many
    rounds as this step's routing needs.  ``static`` = (k, act, axis, cap).

    Round 0 always runs; rounds 1 .. ``rounds`` - 1 are a loop whose trip
    count is the step's (the same on every device of the axis), so a step
    under balance pays for one round and a step whose every pair meets one
    device's experts for ``ceil(T k / cap)``, each at the FIRST capacity's
    buffers: no routing makes the layer hold more than one round's rows.
    (``lax.switch`` over static capacities, as a share's
    ``_held_expert_ffn`` has it, would compile the step for the LAST
    capacity's buffers, ep T k rows on every device.)  A token whose pairs
    travel in different rounds has its rows summed in float32 within a
    round and in the stream's type across them.

    A ``custom_vjp`` whose residuals are its arguments: the loop is not
    differentiated, the backward is the same loop over each round's own
    ``jax.vjp`` with the gradients summed, and under ``jax.checkpoint`` the
    layer's recomputed forward has nothing of the expert FFN to keep."""
    plan = _exchange_plan(top_e, w_gate_up.shape[0], *static[2:])
    one = functools.partial(
        _exchange_round, x, _sorted_weights(top_p, plan), w_gate_up, w_down,
        plan, static=static)
    return jax.lax.fori_loop(1, plan["rounds"],
                             lambda r, y: y + one(r), one(0))


def _sorted_weights(top_p, plan):
    with jax.named_scope(devscope.EXCHANGE):
        return _move(top_p.reshape(-1), plan["inv"], plan["order"])


@devscope.scoped(devscope.MOE)
def _exchange_ffn_bwd(static, res, g):
    x, top_p, top_e, w_gate_up, w_down = res
    plan = _exchange_plan(top_e, w_gate_up.shape[0], *static[2:])
    sorted_p, unsort = jax.vjp(lambda p: _sorted_weights(p, plan), top_p)

    def one(r):
        return jax.vjp(lambda *a: _exchange_round(*a, plan, r, static),
                       x, sorted_p, w_gate_up, w_down)[1](g)

    dx, dp, dgu, dd = jax.lax.fori_loop(
        1, plan["rounds"],
        lambda r, acc: jax.tree.map(jnp.add, acc, one(r)), one(0))
    return dx, unsort(dp)[0], None, dgu, dd


_exchange_ffn.defvjp(lambda *a: (_exchange_ffn(*a), a[:5]), _exchange_ffn_bwd)


def _exchange_aux(top_e, n, axis, cap):
    """What a step's exchange did, the same on every device of the axis:
    ``rows_sent``, the (token, expert) pairs of the whole batch that LEFT
    their device; ``rows_received``, the rows the fullest device's grouped
    matmuls ran over (its own included); ``exchange_fullest``, the most
    rows any device had for one destination, which ``exchange_capacity``
    rows a round carry; ``exchange_tier``, the rounds past the first (0
    under balance)."""
    ep = col.axis_size_in(axis)
    by_dest = jnp.sum(_per_expert(top_e.reshape(-1), n).reshape(ep, -1),
                      axis=1)
    received = col.psum(by_dest, axis)          # [ep]: by every device
    fullest = col.pmax(jnp.max(by_dest), axis)
    return {
        "rows_sent": jnp.sum(received) - col.psum(
            by_dest[col.axis_index(axis)], axis),
        "rows_received": jnp.max(received),
        "exchange_fullest": fullest,
        "exchange_capacity": jnp.int32(cap),
        "exchange_tier": jnp.maximum(-(-fullest // cap), 1) - 1}


@devscope.scoped(devscope.MOE)
def dropless_moe_ffn(params, x, k, rule=SOFTMAX_TOP_K, act="silu",
                     logits=None, first_held=0, bias=None, scale=1.0,
                     ep_axis=None):
    """Top-k dropless expert FFN.  x [T, E] (flatten batch and sequence
    before the call); returns ``(y [T, E], aux)`` with
    ``y_t = sum_{e in top k, held} p_te * down_e(act(gate_e x_t) * up_e x_t)``
    (ungated experts, whose one up matrix is the leaf ``we_up``:
    ``down_e(act(up_e x_t))``)
    and ``aux`` as ``route_top_k`` gives it (``rule``, ``logits``, ``bias``
    and ``scale`` are its).  ``we_gate_up`` / ``we_down`` hold the experts
    [first_held, first_held + their leading size) of the router's n: all of
    them (OLMoE), or this device's share, and then ``y`` is the part of the
    layer's result that its experts give, and ``aux`` also counts the pairs
    that met one of them (``rows_held``, over the dp-global batch).
    ``ep_axis``: the mesh axis the experts ride (EXPERT-PARALLEL: the
    leaves hold this device's n / ep experts, device c those from c n / ep
    on); ``y`` is then the WHOLE layer's result for this device's tokens,
    whichever devices computed its parts, and ``aux`` also says what the
    exchange did (``_exchange_aux``).  On an axis of size 1, or outside a
    mesh, it is the all-held layer.

    ``p_te`` multiplies the HIDDEN rows (the triple product in float32,
    rounded once); the down projection is linear, so ``y`` is the same.  Its
    gradient then needs the hidden rows, which the down matmul keeps anyway,
    and nothing computed after them: no residual follows the down
    projection, and a rematerialised forward stops at the gate/up matmul."""
    w_up = params["we_gate_up" if "we_gate_up" in params else "we_up"]
    n, count = params["router"].shape[-1], w_up.shape[0]
    ep = col.axis_size_in(ep_axis)
    if ep > 1:
        assert first_held == 0 and count * ep == n, (first_held, count, ep, n)
    else:
        assert 0 <= first_held and first_held + count <= n, \
            (first_held, count, n)
    top_p, top_e, aux = route_top_k(params["router"], x, k, rule, logits,
                                    bias, scale)
    ffn = (x, top_p, top_e, w_up, params["we_down"])
    if ep > 1:
        cap = _exchange_capacity(top_e.size, ep)
        aux = dict(aux, **_exchange_aux(top_e, n, ep_axis, cap))
        return _exchange_ffn(*ffn, (k, act, ep_axis, cap)), aux
    if count == n:
        return _expert_ffn(*ffn, k, act, 0, None), aux
    caps = _held_capacities(top_e.size, count, n)
    aux = dict(aux, rows_held=col.psum(jnp.sum(
        _held(top_e, first_held, count), dtype=jnp.int32), DP))
    return _held_expert_ffn(*ffn, (k, act, first_held, caps)), aux


def switch_moe_ffn(params, x, ep_axis=DP, capacity_factor=1.25):
    """Switch-routed expert FFN.  x: [tokens_local, E] (flatten batch*seq
    before calling).  Experts sharded over `ep_axis`; router replicated
    (its gradient must be psum'd over ep_axis — spec it accordingly)."""
    T, E = x.shape
    n_local = params["w1"].shape[0]          # experts on this rank
    ep = col.axis_size_in(ep_axis)
    n_experts = n_local * ep

    logits = (x.astype(jnp.float32) @ params["router"])          # [T, nE]
    probs = jax.nn.softmax(logits, axis=-1)
    gate = jnp.max(probs, axis=-1)                               # [T]
    expert = jnp.argmax(probs, axis=-1)                          # [T]

    cap = int(max(1, round(T * capacity_factor / n_experts)))
    # position of each token within its expert's queue
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)  # [T, nE]
    pos = jnp.cumsum(onehot, axis=0) * onehot                    # 1-based
    pos_in_expert = jnp.sum(pos, axis=-1) - 1                    # [T]
    keep = (pos_in_expert >= 0) & (pos_in_expert < cap)

    # scatter tokens into [nE, cap, E] send buffer
    buf = jnp.zeros((n_experts, cap, E), x.dtype)
    tok_idx = jnp.where(keep, expert * cap + jnp.clip(pos_in_expert, 0, cap - 1), 0)
    buf = buf.reshape(n_experts * cap, E).at[tok_idx].add(
        jnp.where(keep[:, None], x, 0), mode="drop"
    ).reshape(n_experts, cap, E)

    # exchange: [nE, cap, E] -> [n_local, ep*cap, E] (tokens from every rank)
    if ep > 1:
        buf = col.all_to_all(buf, ep_axis, split_dim=0, concat_dim=1)

    # run local experts
    h = jnp.einsum("gce,gef->gcf", buf.astype(params["w1"].dtype), params["w1"])
    h = jax.nn.gelu(h)
    out = jnp.einsum("gcf,gfe->gce", h, params["w2"])

    # route back
    if ep > 1:
        out = col.all_to_all(out, ep_axis, split_dim=1, concat_dim=0)
    out = out.reshape(n_experts * cap, E)

    # gather each token's result, weight by its gate prob
    y = out[tok_idx] * keep[:, None].astype(out.dtype)
    return (y.astype(jnp.float32) * gate[:, None]).astype(x.dtype)
