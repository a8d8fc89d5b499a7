"""The dropless top-k MoE layer (``parallel/moe.py``) against the dense
all-experts formula it must equal: every expert evaluated on every token,
the k largest router probabilities as weights, zero elsewhere.  float32 on
the CPU, so the tolerance can be 1e-5."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_rows
from paddle_tpu.parallel import moe, rules

N, E, F = 8, 16, 8
TOL = dict(rtol=1e-5, atol=1e-5)


def _dense(params, x, k):
    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    weight = (jax.nn.one_hot(top_e, N) * top_p[..., None]).sum(1)   # [T, n]
    gu = jnp.einsum("te,nef->ntf", x, params["we_gate_up"])
    out = jnp.einsum("ntf,nfe->nte", jax.nn.silu(gu[..., :F]) * gu[..., F:],
                     params["we_down"])
    return jnp.einsum("nte,tn->te", out, weight)


def _case(name):
    """(params, x, k) of a routing the sort must survive."""
    ks = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 2)
    params = moe.init_dropless_moe_params(ks[0], N, E, F)
    tokens, k = {"top_1": (12, 1), "top_2": (12, 2), "top_8_of_8": (12, 8),
                 "every_token_to_one_expert": (10, 1),
                 "an_expert_with_no_token": (16, 2),
                 "odd_number_of_assignments": (7, 3),
                 "non_uniform_router": (33, 2)}[name]
    x = jax.random.normal(ks[1], (tokens, E), jnp.float32)
    router = params["router"]
    if name == "every_token_to_one_expert":
        x = jnp.abs(x) + 0.1                       # expert 3 wins every token
        router = jnp.zeros_like(router).at[:, 3].set(5.0)
    elif name == "an_expert_with_no_token":
        x = jnp.abs(x) + 0.1                       # expert 5 loses every token
        router = router.at[:, 5].set(-5.0)
    elif name == "non_uniform_router":
        router = router * 6.0                      # peaky: a few experts busy
    return dict(params, router=router), x, k


CASES = ("top_1", "top_2", "top_8_of_8", "every_token_to_one_expert",
         "an_expert_with_no_token", "odd_number_of_assignments",
         "non_uniform_router")


@pytest.mark.parametrize("name", CASES)
def test_layer_equals_the_dense_formula(name):
    params, x, k = _case(name)
    y, aux = jax.jit(moe.dropless_moe_ffn, static_argnums=2)(params, x, k)
    np.testing.assert_allclose(y, _dense(params, x, k), **TOL)
    counts = np.bincount(np.asarray(jax.lax.top_k(
        jax.nn.softmax(x @ params["router"]), k)[1]).ravel(), minlength=N)
    assert counts.sum() == x.shape[0] * k          # nothing dropped
    if name == "every_token_to_one_expert":
        assert counts[3] == x.shape[0] and float(aux["load_max_over_mean"]) == N
    if name == "an_expert_with_no_token":
        assert counts[5] == 0
    np.testing.assert_allclose(aux["load_max_over_mean"],
                               counts.max() / counts.mean(), rtol=1e-6)


@pytest.mark.parametrize("name", CASES)
def test_gradients_equal_the_dense_formulas(name):
    """Every input's gradient: the dispatch and the combine as each other's
    backward (gathers by the inverse permutation, where autodiff would
    scatter), the router weight's through the hidden rows it rides on, and
    the grouped matmul's (``gmm`` transposed for dX, ``tgmm`` for dW)."""
    params, x, k = _case(name)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)

    def scalar(fn):
        return lambda p, x: jnp.sum(fn(p, x) * probe)

    got = jax.jit(jax.grad(scalar(
        lambda p, x: moe.dropless_moe_ffn(p, x, k)[0]), argnums=(0, 1)))(params, x)
    want = jax.grad(scalar(lambda p, x: _dense(p, x, k)), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, **TOL)


def _skewed_sort(tokens, k):
    """``order`` and ``inv`` of a routing where expert 1 takes half the
    assignments and expert 5 none."""
    expert = jax.random.choice(
        jax.random.PRNGKey(tokens), N, (tokens * k,),
        p=jnp.array([.1, .5, .1, .1, .1, 0., .05, .05]))
    assert (np.bincount(np.asarray(expert), minlength=N)[5] == 0
            and np.bincount(np.asarray(expert)).max() > tokens * k // 3)
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32)


def _absent(order, inv, k, tokens):
    """``_expert_ffn``'s places where the last experts' pairs have no row:
    the sort's first M places, and M for a pair past them (M no multiple of
    8, and fewer than the pairs)."""
    m = tokens * k * 5 // 8 + 1
    return order[:m], jnp.where(inv < m, inv, m), m


@pytest.mark.parametrize("absent", (False, True), ids=("all_held", "absent"))
@pytest.mark.parametrize("k", (1, 2, 8))
@pytest.mark.parametrize("which", ("dispatch", "combine"))
def test_dispatch_and_combine_are_each_other_s_transpose(which, k, absent):
    """Each one's result and ``vjp`` equal the plain gather / un-sort and
    sum written without ``custom_vjp`` and differentiated by autodiff (which
    scatters): so the backward of one IS the other.  The sum back, and so
    the dispatch's backward, is the row kernel's (``kernels/moe_rows.py``),
    with every pair's row there and with experts ``absent``."""
    tokens = 11
    order, inv = _skewed_sort(tokens, k)
    m = tokens * k
    if absent:
        order, inv, m = _absent(order, inv, k, tokens)
    ks = jax.random.split(jax.random.PRNGKey(k), 2)
    if which == "dispatch":
        fn = lambda a: moe._dispatch(a, order, inv, k)
        plain = lambda a: a[order // k]
        a = jax.random.normal(ks[0], (tokens, E), jnp.float32)
    else:
        fn = lambda a: moe._combine(a, order, inv, k)
        plain = lambda a: jnp.sum(
            a.at[inv].get(mode="fill", fill_value=0).reshape(tokens, k, E),
            axis=1)
        a = jax.random.normal(ks[0], (m, E), jnp.float32)
    got, got_vjp = jax.vjp(fn, a)
    want, want_vjp = jax.vjp(plain, a)
    np.testing.assert_allclose(got, want, **TOL)
    g = jax.random.normal(ks[1], want.shape, jnp.float32)
    np.testing.assert_allclose(got_vjp(g)[0], want_vjp(g)[0], **TOL)


def _gather_and_sum(rows, inv, k, interpret=None):
    """The sum back as it was before the row kernel, over every pair slot."""
    back = rows.at[inv.reshape(-1, k)].get(mode="fill", fill_value=0)
    return jnp.sum(back.astype(jnp.float32), axis=1).astype(rows.dtype)


def _places(tokens, k, m, share, seed):
    """``inv`` [tokens * k] of a routing that holds a row for ``share`` of
    the pair slots (at most m): the rows [0, held) in a random order at
    random slots, m at the others."""
    rng = np.random.RandomState(seed)
    held = min(int(round(tokens * k * share)), m)
    inv = np.full(tokens * k, m, np.int32)
    inv[rng.choice(tokens * k, held, replace=False)] = rng.permutation(held)
    return jnp.asarray(inv), held


@pytest.mark.parametrize("share", (0.0, 1 / 16, 1 / 4, 1.0),
                         ids=("none", "a_sixteenth", "a_quarter", "all"))
@pytest.mark.parametrize("k", (1, 4, 6, 8))
def test_the_row_kernel_is_the_gather_and_sum_bit_for_bit(k, share):
    """bfloat16 rows as the cells run them: 275 tokens (a block of 256 and
    a part of one), M no multiple of 8 (but where every slot holds a row:
    then M is the slots), 512 columns (two registers of words
    a row, so the strided reads and the packed halves both count)."""
    tokens, width = 275, 512
    m = tokens * k if share == 1.0 else tokens * k // 3 + 4
    inv, held = _places(tokens, k, m, share, seed=k)
    assert (m % 8 or share == 1.0) and tokens % moe_rows.token_block(
        k, width // 2)
    assert held == (m if share == 1.0 else round(tokens * k * share))
    rows = jax.random.normal(jax.random.PRNGKey(k), (m, width),
                             jnp.float32).astype(jnp.bfloat16)
    got = moe_rows.moe_rows_sum(rows, inv, k)
    want = _gather_and_sum(rows, inv, k)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint16), np.asarray(want).view(np.uint16))


@pytest.mark.parametrize("what,tokens,k,width,share", [
    ("a row narrower than a register", 40, 3, 24, 0.5),
    ("whole registers", 19, 2, 256, 0.25),
    ("minus zero alone sums to plus zero", 16, 1, 16, 1.0),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else None)
def test_the_row_kernel_on_float32_rows(what, tokens, k, width, share):
    m = tokens * k if share == 1.0 else tokens * k // 2 + 1
    inv, _ = _places(tokens, k, m, share, seed=tokens)
    rows = jax.random.normal(jax.random.PRNGKey(tokens), (m, width))
    rows = rows.at[0].set(-0.0)
    got = moe_rows.moe_rows_sum(rows, inv, k)
    np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32),
        np.asarray(_gather_and_sum(rows, inv, k)).view(np.uint32))


def test_a_vector_moves_by_a_sort_as_it_would_by_a_gather():
    """The router weights reach the sorted rows by ``_move`` (a sort by the
    target place: a scalar gather is six times slower on the chip); result
    and ``vjp`` equal the gather's, differentiated by autodiff."""
    order, inv = _skewed_sort(13, 4)
    v, g = jax.random.normal(jax.random.PRNGKey(4), (2, 52), jnp.float32)
    got, got_vjp = jax.vjp(lambda v: moe._move(v, inv, order), v)
    want, want_vjp = jax.vjp(lambda v: v[order], v)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_vjp(g)[0], want_vjp(g)[0])


@pytest.mark.parametrize("routing", ("random", "skewed", "one_expert_takes_all"))
def test_assignments_per_expert_equal_bincount(routing):
    """Group sizes and the router's shares come from a compare and a column
    sum (``jnp.bincount``'s scatter-add took 1.2 ms a layer and pass on the
    chip): the same counts, and the stable sort they describe."""
    key = jax.random.PRNGKey(len(routing))
    top_e = {"random": jax.random.randint(key, (37, 3), 0, N),
             "skewed": jax.random.choice(key, N, (37, 3), p=jnp.array(
                 [.02, .6, .02, .3, .02, 0., .02, .02])),
             "one_expert_takes_all": jnp.full((37, 3), 6)}[routing]
    counts = moe._per_expert(top_e, N)
    assert counts.dtype == jnp.int32
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(top_e).ravel(), minlength=N))
    # row i of the sorted rows belongs to the group the sizes say
    order = np.argsort(np.asarray(top_e).ravel(), kind="stable")
    np.testing.assert_array_equal(np.asarray(top_e).ravel()[order],
                                  np.repeat(np.arange(N), np.asarray(counts)))


def _eqns_named(jaxpr, name):
    return [e for e in jaxpr.eqns if e.params.get("name") == name]


def test_nothing_after_the_down_projection_is_kept_for_the_backward():
    """The router weight is applied BEFORE the down projection, so the
    backward needs nothing computed from that matmul's output, and under
    ``jax.checkpoint`` the recomputed forward stops at the gate/up matmul.
    Read off the forward's jaxpr: its outputs are the layer's result and
    every array the backward keeps."""
    params, x, k = _case("an_expert_with_no_token")
    fwd = jax.make_jaxpr(lambda p, x: jax.vjp(
        lambda p, x: moe.dropless_moe_ffn(p, x, k)[0], p, x))(params, x).jaxpr
    gate_up, down = _eqns_named(fwd, "gmm")
    assert down.outvars[0].aval.shape[1] == E          # [T*k, E]: the down one
    tainted = set(down.outvars)
    for eqn in fwd.eqns[fwd.eqns.index(down) + 1:]:
        if tainted.intersection(v for v in eqn.invars
                                if not isinstance(v, jax.extend.core.Literal)):
            tainted.update(eqn.outvars)
    y, *kept = fwd.outvars
    assert y in tainted                     # the walk does follow the data
    assert kept and not tainted.intersection(kept)
    # so the remat'd backward runs 5 grouped matmuls and 2 transposed ones
    # where a weight applied after the down projection made it 6 and 2
    grad = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jax.checkpoint(
        lambda p, x: moe.dropless_moe_ffn(p, x, k)[0])(p, x)),
        argnums=(0, 1)))(params, x)
    assert _count(grad.jaxpr, "gmm") == 5 and _count(grad.jaxpr, "tgmm") == 2


def _count(jaxpr, name):
    """Calls of the jitted function ``name`` in ``jaxpr``, nested ones too."""
    total = len(_eqns_named(jaxpr, name))
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, name)
    return total


# -- the kernels at their real tiling ------------------------------------------
# Above, every case is one whole-dimension tile.  At training sizes the
# megablox kernels run the tiles ``moe._tiling`` gives a call (a row tile of
# which a group expects four, 128 rows at the least; the contraction whole
# where a column tile fits beside it): a row tile
# holds the end of one group and the start of the next, a group spans tiles,
# the last row tile is padding past the groups (``_whole_row_tiles``), and a
# contraction or a column dimension of 2048 is two tiles.  Interpret mode
# runs the same grid and index maps on the CPU.

# rows, K, N, group sizes, the forward call's tiles (float32 rows)
TILED = {
    # 1,300 rows in eleven row tiles (108 rows of padding), whole K and N
    "groups_straddle_row_tiles": (1300, 16, 24, (0, 700, 3, 0, 88, 509),
                                  (128, 16, 24)),
    # several tiles in N, a group boundary ON a tile's edge
    "two_tiles_each_way": (1100, 2048, 2048, (512, 0, 1, 587),
                           (128, 2048, 512)),
    # fewer rows than five tiles and an empty first and last group
    "one_short_tile": (520, 1536, 1024, (0, 519, 1, 0), (128, 1536, 512)),
    # a contraction too long for VMEM beside any column tile: two k tiles
    "contraction_in_two_parts": (260, 4096, 256, (129, 131), (128, 2048, 256)),
    # THIN groups, a held share's first capacity: eight groups of 60 to 140
    # rows in 1,024, about one 128-row tile each, the contraction whole
    "thin_groups_fill_the_rows": (
        1024, 1536, 2048, (140, 140, 140, 140, 140, 140, 124, 60),
        (128, 1536, 512)),
    # an empty group, the first group's edge ON a tile's edge, and 244 rows
    # past the groups, which are nobody's (a step's rows past its held pairs)
    "thin_groups_an_empty_one_and_rows_past_them": (
        1024, 1280, 1536, (128, 0, 100, 140, 60, 96, 120, 136),
        (128, 1280, 768)),
}


def _tiled_case(name):
    m, k, n, sizes, tiles = TILED[name]
    assert sum(sizes) <= m and moe._tiling(m, k, n, len(sizes), 4) == tiles
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    rows = jax.random.normal(ks[0], (m, k), jnp.float32)
    weights = jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32) * k ** -0.5
    # rows past the groups are no group's: the kernels leave theirs unwritten
    probe = jax.random.normal(ks[2], (m, n), jnp.float32) * (
        jnp.arange(m) < sum(sizes))[:, None]
    return rows, weights, jnp.asarray(sizes, jnp.int32), probe


def _row_by_row(rows, weights, sizes):
    """Row i times the weights of its own group, one dense matmul a group;
    zero for a row past the groups."""
    sizes = np.asarray(sizes)
    group = np.repeat(np.arange(len(sizes) + 1),
                      np.append(sizes, rows.shape[0] - sizes.sum()))
    per_group = jnp.einsum(
        "mk,gkn->gmn", rows, jnp.pad(weights, ((0, 1), (0, 0), (0, 0))),
        precision=jax.lax.Precision.HIGHEST)
    return per_group[group, jnp.arange(rows.shape[0])]


@pytest.mark.parametrize("name", TILED)
def test_grouped_matmul_at_the_real_tiling(name):
    """``gmm`` forward, and ``gmm`` transposed and ``tgmm`` as the backward,
    against the dense formula and ITS gradients."""
    rows, weights, sizes, probe = _tiled_case(name)
    tol = dict(rtol=2e-5, atol=2e-5)
    live = int(sum(TILED[name][3]))
    got = jax.jit(moe._grouped_matmul)(rows, weights, sizes)
    np.testing.assert_allclose(
        got[:live], _row_by_row(rows, weights, sizes)[:live], **tol)
    d_rows, d_weights = jax.jit(jax.grad(
        lambda r, w: jnp.sum(moe._grouped_matmul(r, w, sizes) * probe),
        argnums=(0, 1)))(rows, weights)
    want_rows, want_weights = jax.grad(
        lambda r, w: jnp.sum(_row_by_row(r, w, sizes) * probe),
        argnums=(0, 1))(rows, weights)
    np.testing.assert_allclose(d_rows[:live], want_rows[:live], **tol)
    # dW sums over a group's rows: up to 700 terms of size one
    np.testing.assert_allclose(d_weights, want_weights, rtol=2e-5, atol=2e-4)
    # an empty group's weights get a gradient of exactly zero, not a stale tile
    for g, size in enumerate(TILED[name][3]):
        assert size or not np.asarray(d_weights[g]).any()


# One layer's grouped matmuls of the benchmark's five sparse cells at the
# FIRST capacity (``_held_capacities``), Trinity's all-pairs tier and a tiny
# shape: (rows M, groups, E, F) and the tiles of the six calls a layer's
# forward and backward make, bf16: gate/up and down forward, their dX
# (``gmm`` with the weights transposed: the contraction is the forward's
# columns), their dW (``tgmm``: k x n is a group's block of dW).
CELL_SHAPES = {
    "olmoe_1b_7b": (131072, 64, 2048, 1024),              # 2,048 rows a group
    "lfm2_8b_a1b": (20480, 8, 2048, 1792),                # 2,560
    "smallthinker_21b_a3b": (30720, 16, 2560, 768),       # 1,920
    "mistral_small_4_119b": (5120, 8, 4096, 2048),        # 640
    "trinity_large_preview": (1024, 8, 3072, 3072),       # 128
    "trinity_large_preview, every pair": (24576, 8, 3072, 3072),
    "tiny": (24, 2, 16, 8),
}
CALLS = {      # (k, n) of a call from (E, F), and whether it is tgmm's
    "gate_up.fwd": (lambda E, F: (E, 2 * F), False),
    "down.fwd": (lambda E, F: (F, E), False),
    "gate_up.dx": (lambda E, F: (2 * F, E), False),
    "down.dx": (lambda E, F: (E, F), False),
    "gate_up.dw": (lambda E, F: (E, 2 * F), True),
    "down.dw": (lambda E, F: (F, E), True),
}
CELL_TILES = {cell: dict(zip(CALLS, tiles)) for cell, tiles in {
    "olmoe_1b_7b": ((512, 2048, 512), (512, 1024, 1024), (512, 2048, 512),
                    (512, 2048, 512), (512, 1024, 1024), (512, 1024, 1024)),
    "lfm2_8b_a1b": ((512, 2048, 512), (512, 1792, 512), (512, 1792, 512),
                    (512, 1024, 896), (512, 1024, 896), (512, 896, 1024)),
    "smallthinker_21b_a3b": (
        (256, 2560, 768), (256, 768, 1280), (256, 1536, 1280),
        (256, 2560, 768), (256, 1280, 768), (256, 768, 1280)),
    "mistral_small_4_119b": (
        (128, 4096, 512), (128, 2048, 1024), (128, 4096, 512),
        (128, 4096, 512), (128, 1024, 1024), (128, 1024, 1024)),
    "trinity_large_preview": (
        (128, 3072, 768), (128, 3072, 768), (128, 6144, 256),
        (128, 3072, 768), (128, 768, 1536), (128, 768, 1536)),
    "trinity_large_preview, every pair": (
        (512, 1536, 768), (512, 1536, 768), (512, 2048, 512),
        (512, 1536, 768), (512, 1024, 1024), (512, 1024, 1024)),
    "tiny": ((24, 16, 16), (24, 8, 16), (24, 16, 16), (24, 16, 8),
             (24, 16, 16), (24, 8, 16)),
}.items()}       # a cell's tiles in CALLS' order


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("cell", CELL_SHAPES)
def test_the_tiles_follow_the_call_s_shapes(cell, call):
    """``moe._tiling``: the tallest row tile of which a group expects four
    (128 rows at the least), the contraction whole where a column tile fits
    beside it (else in the fewest equal parts), dW in the block that reads
    the rows the fewest times; every cut dimension in equal parts of whole
    lane tiles, within VMEM_BUDGET; ``HELD_GRANULE`` rows are whole row
    tiles, so no capacity is padded."""
    m, groups, E, F = CELL_SHAPES[cell]
    (k, n), dw = CALLS[call][0](E, F), CALLS[call][1]
    tm, tk, tn = got = moe._tiling(m, k, n, groups, 2, dw=dw)
    assert got == CELL_TILES[cell][call]
    assert moe._vmem_bytes(tm, tk, tn, 2, dw) <= moe.VMEM_BUDGET < 16 * 2 ** 20
    assert tm == m or (tm in moe.ROW_TILES and moe.HELD_GRANULE % tm == 0
                       and tm <= max(m // groups // 4, 128))
    assert k % tk == 0 and n % tn == 0
    assert (tk == k or tk % 128 == 0) and (tn == n or tn % 128 == 0)
    # float32 operands are twice the bytes: the same rule, smaller blocks
    assert moe._vmem_bytes(*moe._tiling(m, k, n, groups, 4, dw=dw), 4,
                           dw) <= moe.VMEM_BUDGET


def test_compiled_calls_are_counted_by_their_tiles(tmp_path):
    """Under a monitor session a traced call of either kernel counts once in
    ``monitor.kernels.moe_grouped_matmul_calls`` under its tiles and whether
    its groups are thin; off the monitor nothing is touched."""
    from paddle_tpu import monitor

    rows, weights, sizes, probe = _tiled_case("thin_groups_fill_the_rows")
    grad = lambda: jax.make_jaxpr(jax.grad(lambda r, w: jnp.sum(
        moe._grouped_matmul(r, w, sizes) * probe), argnums=(0, 1)))(
            rows, weights)
    grad()                                       # off: nothing is touched
    mon = monitor.enable(str(tmp_path), flight=False)
    try:
        grad()
        got = {(r["labels"]["kernel"], r["labels"]["tm"], r["labels"]["tk"],
                r["labels"]["tn"], r["labels"]["thin"]): r["value"]
               for r in mon.registry.snapshot()
               if r["name"] == "monitor.kernels.moe_grouped_matmul_calls"}
    finally:
        monitor.disable()
    assert got == {("gmm", 128, 1536, 512, 1): 1,     # forward
                   ("gmm", 128, 2048, 512, 1): 1,     # dX, weights transposed
                   ("tgmm", 128, 768, 1024, 1): 1}    # dW


def test_layer_over_several_row_tiles_equals_the_dense_formula():
    """The whole layer on 656 tokens, top-2: 1,312 sorted rows in three row
    tiles; the tokens share an offset, which the router turns into a bias per
    expert, so the groups are uneven and straddle the tiles; output and every
    gradient."""
    ks = jax.random.split(jax.random.PRNGKey(27), 3)
    params = moe.init_dropless_moe_params(ks[0], N, E, F)
    x = jax.random.normal(ks[1], (656, E), jnp.float32) + 1.0
    probe = jax.random.normal(ks[2], x.shape, jnp.float32)
    counts = np.bincount(np.asarray(jax.lax.top_k(
        jax.nn.softmax(x @ params["router"]), 2)[1]).ravel(), minlength=N)
    edges = np.cumsum(counts)[:-1]
    assert counts.sum() == 1312 and (edges % 512 != 0).all()
    assert counts.max() > 2 * counts.min()
    y, _ = jax.jit(moe.dropless_moe_ffn, static_argnums=2)(params, x, 2)
    np.testing.assert_allclose(y, _dense(params, x, 2), **TOL)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(
        moe.dropless_moe_ffn(p, x, 2)[0] * probe), argnums=(0, 1)))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(_dense(p, x, 2) * probe),
                    argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)


def test_auxiliary_values_and_their_gradient():
    params, x, k = _case("non_uniform_router")
    _, _, aux = moe.route_top_k(params["router"], x, k)
    logits = np.asarray(x @ params["router"], np.float64)
    lse = np.log(np.exp(logits).sum(-1))
    probs = np.exp(logits - lse[:, None])
    chosen = np.argsort(-probs, axis=-1)[:, :k]
    share = np.bincount(chosen.ravel(), minlength=N) / chosen.size
    np.testing.assert_allclose(aux["load_balance"],
                               N * (share * probs.mean(0)).sum(), rtol=1e-5)
    np.testing.assert_allclose(aux["router_z"], (lse ** 2).mean(), rtol=1e-5)
    # a uniform router balances exactly: lb = 1, z = log(n)^2
    _, _, flat = moe.route_top_k(jnp.zeros_like(params["router"]), x, k)
    np.testing.assert_allclose(flat["load_balance"], 1.0, rtol=1e-6)
    np.testing.assert_allclose(flat["router_z"], np.log(N) ** 2, rtol=1e-6)
    # the counts carry no gradient; the mean probabilities and the z do
    g = jax.grad(lambda r: sum(
        moe.route_top_k(r, x, k)[2][n] for n in ("load_balance", "router_z")
    ))(params["router"])
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_the_switch_layer_still_runs_and_drops():
    """The top-1 capacity layer kept for the expert-parallel dry run: a
    token over its expert's capacity comes back as zero."""
    params = moe.init_moe_params(jax.random.PRNGKey(0), 4, E, F)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (16, E))) + 0.1
    crowded = dict(params, router=jnp.zeros((E, 4)).at[:, 2].set(5.0))
    y = moe.switch_moe_ffn(crowded, x, ep_axis=None, capacity_factor=1.0)
    kept = np.abs(np.asarray(y)).sum(-1) > 0
    assert kept.sum() == 4 and kept[:4].all()      # capacity 16 / 4


def test_rule_trees_cover_both_layers():
    leaf = rules.SkeletonLeaf
    specs = rules.match_partition_rules(rules.moe_rules("dp"), {
        "router": leaf(), "w1": leaf(), "w2": leaf(),
        "we_gate_up": leaf(), "we_down": leaf()})
    assert specs["w1"] == specs["w2"] == jax.sharding.PartitionSpec("dp")
    assert specs["we_gate_up"] == specs["we_down"] == specs["router"] \
        == jax.sharding.PartitionSpec()


# -- a share of the experts (PR 31) ----------------------------------------------
# The layer holds ``count`` of the router's N experts from ``first``: it routes
# over all N, computes its own experts' part for the pairs that meet them, and
# leaves the rest out.  Rules and activation are configuration too.

def _dense_share(params, x, k, first, count, rule, act, logits=None):
    """Every HELD expert on every token, the rule's top-k weights at their
    experts' columns, zero elsewhere; ``params`` holds all N experts."""
    logits = x @ params["router"] if logits is None else logits
    if rule == moe.SOFTMAX_TOP_K:
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        top_l, top_e = jax.lax.top_k(logits, k)
        top_p = jax.nn.softmax(top_l, axis=-1)
    weight = (jax.nn.one_hot(top_e, N) * top_p[..., None]).sum(1)   # [T, n]
    held = slice(first, first + count)
    gu = jnp.einsum("te,nef->ntf", x, params["we_gate_up"][held])
    out = jnp.einsum("ntf,nfe->nte",
                     moe.ACTIVATIONS[act](gu[..., :F]) * gu[..., F:],
                     params["we_down"][held])
    return jnp.einsum("nte,tn->te", out, weight[:, held])


def _share(params, first, count):
    return dict(params,
                we_gate_up=params["we_gate_up"][first:first + count],
                we_down=params["we_down"][first:first + count])


#   what                             case                         first count rule                act
SHARES = [
    ("first quarter",                "top_2",                     0, 2, moe.TOP_K_SOFTMAX, "relu"),
    ("last quarter",                 "non_uniform_router",        6, 2, moe.TOP_K_SOFTMAX, "relu"),
    ("a middle half, OLMoE's rule",  "top_2",                     2, 4, moe.SOFTMAX_TOP_K, "silu"),
    ("a share that receives no row", "every_token_to_one_expert", 4, 2, moe.TOP_K_SOFTMAX, "relu"),
    ("a share that receives all",    "every_token_to_one_expert", 2, 2, moe.TOP_K_SOFTMAX, "relu"),
    ("top 3 of odd rows",            "odd_number_of_assignments", 1, 3, moe.TOP_K_SOFTMAX, "silu"),
]


@pytest.fixture
def small_granule(monkeypatch):
    """Capacities in tiles of 8 rows, so that the tiny cases have two."""
    monkeypatch.setattr(moe, "HELD_GRANULE", 8)


@pytest.mark.parametrize("what,case,first,count,rule,act", SHARES,
                         ids=[s[0] for s in SHARES])
def test_a_share_of_the_experts_gives_its_part(small_granule, what, case,
                                               first, count, rule, act):
    """Result and every gradient of the layer holding experts
    [first, first + count), against the dense formula over those experts."""
    params, x, k = _case(case)
    caps = moe._held_capacities(x.shape[0] * k, count, N)
    assert caps[-1] == x.shape[0] * k and list(caps) == sorted(set(caps))

    def layer(p, x):
        return moe.dropless_moe_ffn(_share(p, first, count), x, k, rule=rule,
                                    act=act, first_held=first)[0]

    def dense(p, x):
        return _dense_share(p, x, k, first, count, rule, act)

    np.testing.assert_allclose(jax.jit(layer)(params, x), dense(params, x),
                               **TOL)
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(layer(p, x) * probe),
                           argnums=(0, 1)))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(dense(p, x) * probe),
                    argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_both_capacities_give_the_same_part(small_granule):
    """The capacity is only a size: at the first, which covers this case's
    held pairs, and at every pair, ``_expert_ffn`` gives the same rows'
    sum."""
    params, x, k = _case("non_uniform_router")
    first, count = 2, 2
    p = _share(params, first, count)
    top_p, top_e, _ = moe.route_top_k(p["router"], x, k, moe.TOP_K_SOFTMAX)
    local = np.asarray(top_e) - first
    held = int(((local >= 0) & (local < count)).sum())
    caps = moe._held_capacities(top_e.size, count, N)
    assert len(caps) == 2 and held <= caps[0] < caps[1] == top_e.size
    assert int(moe._held_tier(top_e, first, count, caps)) == 0
    want = _dense_share(params, x, k, first, count, moe.TOP_K_SOFTMAX, "relu")
    for cap in caps:
        got = moe._expert_ffn(x, top_p, top_e, p["we_gate_up"], p["we_down"],
                              k, "relu", first, cap)
        np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("tier", (0, 1), ids=("first_capacity", "every_pair"))
def test_the_layer_through_the_row_kernel_is_the_gather_s(small_granule,
                                                          monkeypatch, tier):
    """Output and all four gradients of ``_expert_ffn`` at each capacity,
    with the sum back by the row kernel and by the gather over every pair
    slot that it replaced: the same bits."""
    params, x, k = _case("non_uniform_router")
    first, count = 2, 2
    p = _share(params, first, count)
    top_p, top_e, _ = moe.route_top_k(p["router"], x, k, moe.TOP_K_SOFTMAX)
    cap = moe._held_capacities(top_e.size, count, N)[tier]
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def run():
        def ffn(x, top_p, w_gate_up, w_down):
            return moe._expert_ffn(x, top_p, top_e, w_gate_up, w_down, k,
                                   "relu", first, cap)
        out, vjp = jax.vjp(ffn, x, top_p, p["we_gate_up"], p["we_down"])
        return (out,) + vjp(probe)

    got = run()
    monkeypatch.setattr(moe, "moe_rows_sum", _gather_and_sum)
    for g, w in zip(got, run()):
        assert np.abs(np.asarray(w)).max() > 0
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("name", ("top_2", "top_8_of_8",
                                  "odd_number_of_assignments"))
def test_the_all_held_layer_through_the_row_kernel_is_the_gather_s(
        monkeypatch, name):
    """Every expert on the device (OLMoE): the layer sums back through the
    same kernel (it gained 1.6 % there on the chip, PR 40), and its output
    and gradients are those of ``rows[inv]`` summed k at a time."""
    params, x, k = _case(name)
    run = lambda: jax.value_and_grad(
        lambda p, x: jnp.sum(jnp.sin(moe.dropless_moe_ffn(p, x, k)[0])),
        argnums=(0, 1))(params, x)
    assert "moe_rows_sum" in str(jax.make_jaxpr(
        lambda p, x: moe.dropless_moe_ffn(p, x, k)[0])(params, x))
    got = run()
    monkeypatch.setattr(
        moe, "moe_rows_sum", lambda rows, inv, k, interpret=None: jnp.sum(
            rows[inv].reshape((-1, k) + rows.shape[1:]), axis=1))
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(run())):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def test_the_shares_parts_add_up_to_the_whole_layer():
    params, x, k = _case("non_uniform_router")
    whole = _dense_share(params, x, k, 0, N, moe.TOP_K_SOFTMAX, "relu")
    parts = sum(moe.dropless_moe_ffn(_share(params, f, 2), x, k,
                                     rule=moe.TOP_K_SOFTMAX, act="relu",
                                     first_held=f)[0] for f in range(0, N, 2))
    np.testing.assert_allclose(parts, whole, **TOL)
    all_held = moe.dropless_moe_ffn(params, x, k, rule=moe.TOP_K_SOFTMAX,
                                    act="relu")[0]
    np.testing.assert_allclose(all_held, whole, **TOL)


def test_logits_from_the_caller_route_the_layer():
    """Router logits computed from ANOTHER input than the experts': the
    layer ranks by them and the router's gradient flows through them."""
    params, x, k = _case("top_2")
    other = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def layer(p, x, other):
        return moe.dropless_moe_ffn(
            p, x, k, rule=moe.TOP_K_SOFTMAX, act="relu",
            logits=moe.router_logits(p["router"], other))[0]

    def dense(p, x, other):
        return _dense_share(p, x, k, 0, N, moe.TOP_K_SOFTMAX, "relu",
                            logits=other @ p["router"])

    np.testing.assert_allclose(layer(params, x, other),
                               dense(params, x, other), **TOL)
    got = jax.grad(lambda *a: jnp.sum(layer(*a) ** 2), argnums=(0, 2))(
        params, x, other)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), argnums=(0, 2))(
        params, x, other)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_a_share_under_remat_keeps_nothing_of_the_expert_ffn(small_granule):
    """Per capacity, a rematerialised step's jaxpr holds 6 ``gmm`` + 2
    ``tgmm``: the forward's two; NONE in the recomputed forward (the expert
    FFN's residuals are its arguments, so nothing of it is kept); and in the
    backward, which makes its own forward, gate/up and down (the down one's
    result is the ``vjp``'s unused primal: dead code to the compiler, which
    leaves the all-held layer's 5 + 2) and the two transposed ones."""
    params, x, k = _case("non_uniform_router")
    p = _share(params, 2, 2)
    caps = moe._held_capacities(x.shape[0] * k, 2, N)
    grad = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(jax.checkpoint(
        lambda p, x: moe.dropless_moe_ffn(p, x, k, first_held=2)[0])(p, x)),
        argnums=(0, 1)))(p, x)
    assert _count(grad.jaxpr, "gmm") == len(caps) * (2 + 0 + 4)
    assert _count(grad.jaxpr, "tgmm") == len(caps) * 2


@pytest.mark.parametrize("model,held,n,k", [
    ("smallthinker", 2, 8, 2), ("lfm2", 2, 8, 2), ("mistral4", 2, 8, 2),
    ("olmoe", 8, 8, 2)])
def test_the_row_kernel_s_rows_follow_the_share_held(model, held, n, k):
    """The (token, expert) pair slots a layer's sum back covers, T * k, and
    the rows its row kernel can be asked for at the layer's first capacity,
    of each sparse decoder's tiny configuration: from the function the
    layer takes its capacities from (with every expert held the rows are
    the slots)."""
    import importlib

    module = importlib.import_module("paddle_tpu.models." + model)
    cfg = getattr(module, model + "_tiny_config")()
    assert (cfg.experts_here, cfg.n_experts, cfg.experts_per_token) == (
        held, n, k)
    slots = 4096 * cfg.experts_per_token
    # 1.25 x the quarter of 8,192 slots that balance brings, in 512-row tiles
    assert [slots, moe._held_capacities(
        slots, cfg.experts_here, cfg.n_experts)[0]] == [
            8192, 2560 if held < n else 8192]
