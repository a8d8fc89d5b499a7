"""The LM head makes each logit once a training step
(``transformer._weighted_vocab_nll``): its forward rule keeps a row block's
float32 logits from their ``lse`` to their gradient, its backward rule is a
multiply.  Held here to the dense ``logsumexp`` formula in float32 and with
bf16 operands, in the lowered text (three ``dot_general`` a vocabulary
chunk, not four) alone and at every cell's head shape, and through
``final_logits_loss`` and ``exit_weighted_loss``."""

import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, E, V = 256, 16, 50
R = T.head_row_block(N)                                         # 16
NORMS = {"layer": ("layer", 1e-6), "rms": ("rms", 1e-5)}
KINDS = ["ones", "hot15", "ragged", "one_row", "weights", "zeros"]


def _inputs(dtype=jnp.float32, norm="layer", seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(N, E), dtype),
            jnp.asarray(1 + 0.1 * rng.randn(E), jnp.float32),
            jnp.asarray(0.1 * rng.randn(E), jnp.float32)
            if norm == "layer" else None,
            jnp.asarray(0.3 * rng.randn(V, E), dtype),
            jnp.asarray(rng.randint(0, V, N), jnp.int32))


def _weights(kind, seed=1):
    """A loss's weights a row, normaliser included: dead rows, a count that
    is no multiple of R, weights other than 0 / 1."""
    rng = np.random.RandomState(seed)
    m = np.zeros(N, np.float32)
    if kind == "hot15":
        m[rng.permutation(N)[:int(0.15 * N)]] = 1
    elif kind == "ones":
        m[:] = 1
    elif kind == "one_row":
        m[N - 7] = 1
    elif kind == "ragged":
        m[rng.permutation(N)[:3 * R + 5]] = 1
    elif kind == "weights":
        m[:] = (rng.rand(N) < 0.4) * (0.25 + rng.rand(N))
    else:
        assert kind == "zeros"
    return jnp.asarray(m / max(m.sum(), 1.0))


def _dense(x, scale, bias, emb, labels, wgt, norm=NORMS["layer"]):
    """The plain formula: every row's logits at once."""
    logits = (T._head_norm(norm, x, scale, bias) @ emb.T).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(wgt * nll)


def _carried(x, scale, bias, emb, labels, wgt, norm=NORMS["layer"]):
    return T._weighted_vocab_nll(x, scale, bias, emb, labels, wgt,
                                 norm=norm)[0]


def _grads(fn, args, wgt, norm, times=1.0):
    """Value and the gradients of x, scale, (bias,) emb AND the weights."""
    at = tuple(i for i, a in enumerate(args[:4]) if a is not None) + (5,)
    return jax.jit(jax.value_and_grad(
        lambda *a: times * fn(*a, norm=NORMS[norm]), argnums=at))(*args, wgt)


def _close(got, want, tol):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-9)
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("norm", sorted(NORMS))
@pytest.mark.parametrize("kind", KINDS)
def test_float32_value_and_gradients_are_the_dense_formulas(kind, norm):
    args, wgt = _inputs(norm=norm), _weights(kind)
    want, dwant = _grads(_dense, args, wgt, norm)
    got, dgot = _grads(_carried, args, wgt, norm)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6, atol=1e-9)
    live = np.asarray(wgt) != 0
    for g, w in zip(dgot[:-1], dwant[:-1]):
        _close(g, w, 1e-6 * max(live.sum(), 1) ** 0.5)
    # the weights' gradient is the row's nll: a dead row's reads 0 (nothing
    # computed it), a live row's the formula's
    np.testing.assert_allclose(np.asarray(dgot[-1])[live],
                               np.asarray(dwant[-1])[live], rtol=1e-5)
    assert (np.asarray(dgot[-1])[~live] == 0).all()
    assert (np.asarray(dgot[0])[~live] == 0).all()
    if kind == "zeros":
        assert float(got) == 0.0
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in dgot)


# the gradient's matmuls take ``d`` in the head matrix's dtype: each term of
# a sum is off by up to half an ulp of bf16, so one ulp of the largest entry
# bounds it (``tests/test_lm_head_rows.py`` holds float32 operands to it)
BF16_TOL = 2.0 ** -7


@pytest.mark.parametrize("norm", sorted(NORMS))
@pytest.mark.parametrize("kind", KINDS)
def test_bf16_operands_keep_the_gradients_within_an_ulp_of_the_formulas(
        kind, norm):
    """bf16 x and head matrix, as a cell trains: the gradients come back in
    their operands' types and stand within a bf16 ulp of the dense formula's
    on the same operands in float32."""
    args, wgt = _inputs(jnp.bfloat16, norm), _weights(kind)
    wide = tuple(a.astype(jnp.float32) if a is not None
                 and a.dtype == jnp.bfloat16 else a for a in args)
    want, dwant = _grads(_dense, wide, wgt, norm)
    got, dgot = _grads(_carried, args, wgt, norm)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2, atol=1e-9)
    at = [a for a in args[:4] if a is not None] + [wgt]
    # a dead row's weight gets no gradient: nothing computed its nll
    dwant = dwant[:-1] + (dwant[-1] * (wgt != 0),)
    for g, w, a in zip(dgot, dwant, at):
        assert g.dtype == a.dtype and g.shape == a.shape
        _close(g.astype(jnp.float32), w, 4 * BF16_TOL)


@pytest.mark.parametrize("norm", sorted(NORMS))
@pytest.mark.parametrize("times", [0.37, -2.0, 0.0])
def test_a_cotangent_other_than_one_multiplies_the_residuals(times, norm):
    args, wgt = _inputs(norm=norm), _weights("ragged")
    _, dwant = _grads(_dense, args, wgt, norm, times)
    _, dgot = _grads(_carried, args, wgt, norm, times)
    live = (np.asarray(wgt) != 0).astype(np.float32)
    for g, w in zip(dgot, dwant[:-1] + (dwant[-1] * live,)):
        _close(g, w, 2e-5) if times else np.testing.assert_array_equal(g, 0)


def test_the_rows_nll_comes_back_beside_the_sum_and_carries_no_gradient():
    args, wgt = _inputs(), _weights("ragged")
    total, nll = jax.jit(T._weighted_vocab_nll)(*args, wgt)
    logits = T._head_norm(NORMS["layer"], *args[:3]) @ args[3].T
    want = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, args[4][:, None], -1)[:, 0]
    live = np.asarray(wgt) != 0
    np.testing.assert_allclose(np.asarray(nll)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(nll)[~live] == 0).all()
    assert float(total) == float(jnp.sum(wgt * nll))
    # under a gradient the forward rule's rows are the same numbers
    (_, under), _ = jax.value_and_grad(
        lambda x: T._weighted_vocab_nll(x, *args[1:], wgt), has_aux=True)(
            args[0])
    np.testing.assert_allclose(np.asarray(under), np.asarray(nll), rtol=1e-6)
    dx = jax.grad(lambda x: jnp.sum(
        T._weighted_vocab_nll(x, *args[1:], wgt)[1]))(args[0])
    assert float(jnp.max(jnp.abs(dx))) == 0.0


def _dots(text):
    return len(re.findall(r"stablehlo\.dot_general", text))


def _head_dots(fn, args, wgt):
    """``dot_general`` in the lowered text of ``fn``'s value alone and of
    its gradient, and how many of the gradient's lie under ``lm_head``."""
    value = jax.jit(fn).lower(*args, wgt).as_text()
    grad = jax.jit(jax.grad(fn, argnums=(0, 1, 3))).lower(
        *args, wgt).as_text(debug_info=True)
    dots = [line for line in grad.splitlines()
            if "stablehlo.dot_general" in line]
    under = sum(1 for line in dots if _scope_of(line, grad, "lm_head"))
    return _dots(value), len(dots), under


def _scope_of(line, text, scope):
    """Whether the operation on ``line`` carries ``scope`` in its location
    (the line's own, or the ``#loc<n>`` it names)."""
    ref = re.search(r"loc\((#loc\d+)\)", line)
    if ref is None:
        return scope in line
    named = re.search(r"^%s = .*$" % re.escape(ref.group(1)), text, re.M)
    return named is not None and scope in named.group(0)


def test_lowered_gradient_holds_three_matmuls_a_chunk():
    args = _inputs(jnp.bfloat16)
    wgt, chunks = _weights("ones"), len(T._vocab_chunks(args[3]))
    assert chunks == 4
    # no gradient asked for: the logits alone; asked for one: the logits, dh
    # and demb, each once (the parent's backward made the logits again: 4)
    assert _head_dots(_carried, args, wgt) == (chunks, 3 * chunks,
                                               3 * chunks)


# --- the callers, against the plain formulas --------------------------------

def _cfg(**kw):
    d = dict(vocab_size=V, hidden=E, n_layers=1, n_heads=2, ffn_hidden=32,
             max_seq=32, causal=True, dtype="float32", norm="rms",
             norm_eps=1e-5, tie_head=False, positions="rotary")
    d.update(kw)
    return T.TransformerConfig(**d)


def _head_params(cfg, seed=0):
    rng = np.random.RandomState(seed)
    return {"lnf_scale": jnp.asarray(1 + 0.1 * rng.randn(cfg.hidden),
                                     jnp.float32),
            "lm_head": jnp.asarray(
                0.3 * rng.randn(cfg.vocab_size, cfg.hidden), cfg.jdtype)}


def _rows_nll(cfg, params, x, labels):
    """Every row's nll by the plain formula, in ``x``'s leading shape."""
    logits = T.head_logits(params, x, cfg)
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("divisor", [None, 64.0])
@pytest.mark.parametrize("weights", ["ones", "hot15", "weights", "zeros"])
def test_final_logits_loss_is_the_weighted_mean_of_the_plain_formula(
        weights, divisor):
    """``sum(nll * mask) / max(sum(mask), 1)``, or over ``divisor`` (SDAR's
    denoising loss: weights other than 0 / 1 stay exact), as the parent's
    per-row head gave it, value and gradients."""
    cfg = _cfg()
    rng = np.random.RandomState(4)
    b, s = 4, N // 4
    x = jnp.asarray(rng.randn(b, s, cfg.hidden), cfg.jdtype)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    mask = _weights(weights).reshape(b, s) * 7.0

    def plain(p, x):
        total = jnp.sum(_rows_nll(cfg, p, x, labels) * mask)
        return total / (divisor or jnp.maximum(jnp.sum(mask), 1.0))

    want, dwant = jax.jit(jax.value_and_grad(plain, argnums=(0, 1)))(
        _head_params(cfg), x)
    got, dgot = jax.jit(jax.value_and_grad(
        lambda p, x: T.final_logits_loss(p, x, labels, mask, cfg,
                                         divisor=divisor),
        argnums=(0, 1)))(_head_params(cfg), x)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6, atol=1e-9)
    for g, w in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        _close(g, w, 2e-5)


@pytest.mark.parametrize("coef", [0.0, 0.05])
def test_exit_gates_receive_the_gradient_the_per_row_head_gave_them(coef):
    """Ouro's loss: ``p_t * mask / count`` goes in as the rows' weights and
    the head's gradient with respect to them, the rows' nll, is what the
    parent's ``p * nll`` outside the head handed the gates (the parent's
    formula, on the plain per-row nll, is the reference)."""
    cfg = _cfg(loop_passes=4, exit_entropy_coef=coef)
    rng = np.random.RandomState(5)
    t, b, s = cfg.loop_passes, 2, N // 8
    exits = jnp.asarray(rng.randn(t, b, s, cfg.hidden), cfg.jdtype)
    gates = jnp.asarray(2 * rng.randn(t, b, s), jnp.float32)
    labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (b, s)), jnp.int32)
    mask = jnp.asarray(rng.rand(b, s) < 0.8, jnp.float32)

    def parents(p, exits, gates):
        nll = _rows_nll(cfg, p, exits, jnp.broadcast_to(labels, (t, b, s)))
        log_p = T.exit_log_probs(gates)
        each = jnp.sum(jnp.exp(log_p) * (nll + coef * log_p), axis=0)
        return jnp.sum(each * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    want, dwant = jax.jit(jax.value_and_grad(parents, argnums=(0, 1, 2)))(
        _head_params(cfg), exits, gates)
    got, dgot = jax.jit(jax.value_and_grad(
        lambda p, e, g: T.exit_weighted_loss(p, e, g, labels, mask, cfg),
        argnums=(0, 1, 2)))(_head_params(cfg), exits, gates)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    assert float(jnp.max(jnp.abs(dwant[2]))) > 1e-4          # the gates'
    for g, w in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        _close(g, w, 2e-5)


# --- at the cells' shapes ----------------------------------------------------

def _cells(name):
    """``(configuration, rows a device)`` of every cell of ``name``."""
    from benchmark.harness import build, manifest as mf

    manifest = mf.load(ROOT)
    config = mf.read_json(ROOT, "benchmark", "configs", name + ".json")
    path, factory = config["config_factory"]["path"].rsplit(".", 1)
    cfg = getattr(importlib.import_module(path), factory)(
        **config["config_factory"]["kwargs"])
    for cell in manifest["workloads"]:
        if cell["config"] != name or not hasattr(cfg, "vocab_size"):
            continue
        traffic = mf.read_json(ROOT, "benchmark", "traffic",
                               cell["name"] + ".json")
        dims = build.cell_dims(config, traffic)
        yield cfg, (dims["B"] // traffic["mesh"].get("dp", 1) * dims["S"]
                    * cfg.loop_passes)


@pytest.mark.parametrize("name", sorted(
    os.path.basename(f)[:-5]
    for f in glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))))
def test_every_cells_head_makes_its_logits_once(name):
    """The lowered gradient of ``final_logits_loss`` at the width,
    vocabulary and rows a device of every cell of a configuration (shapes,
    not arrays): three ``dot_general`` a vocabulary chunk, each on a row
    block, whatever the width (BERT's 768 as the decoders' 2,048 to 5,120).
    A configuration with no such head (ResNet) has nothing to hold."""
    cells = list(_cells(name))
    assert cells or name == "resnet50"
    for cfg, rows in cells:
        sds = jax.ShapeDtypeStruct
        params = {"lnf_scale": sds((cfg.hidden,), jnp.float32),
                  "tok_emb" if cfg.tie_head else "lm_head": sds(
                      (cfg.vocab_size, cfg.hidden), cfg.jdtype)}
        if cfg.norm == "layer":
            params["lnf_bias"] = sds((cfg.hidden,), jnp.float32)
        text = jax.jit(jax.grad(
            lambda p, x, labels, mask: T.final_logits_loss(
                p, x, labels, mask, cfg), argnums=(0, 1))).lower(
                    params, sds((1, rows, cfg.hidden), cfg.jdtype),
                    sds((1, rows), jnp.int32),
                    sds((1, rows), jnp.float32)).as_text()
        chunks = T._vocab_chunks(params[
            "tok_emb" if cfg.tie_head else "lm_head"])
        dots = [line for line in text.splitlines()
                if "stablehlo.dot_general" in line]
        assert len(dots) == 3 * len(chunks)
        block = T.head_row_block(rows)
        assert block < rows
        assert all("tensor<%dx" % block in line
                   and "tensor<%dx" % rows not in line for line in dots)
