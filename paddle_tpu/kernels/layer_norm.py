"""Fused LayerNorm as a Pallas TPU kernel (fwd + custom-VJP bwd).

Parity target: the reference's layer_norm op (operators/layer_norm_op.cu —
fused CUDA row-stat kernels).  At bench shapes XLA's LN decomposition costs
~0.4ms/LN fwd+bwd against a ~0.06ms HBM floor (reduction fusion barriers
force several full passes over the activation); this kernel does one pass
forward and one pass backward.

Layout: x is [N, E] (callers flatten leading dims).  Grid is (N // bn,);
each step normalizes a [bn, E] row block in registers.  The backward
accumulates dscale/dbias in VMEM scratch across the sequential grid and
writes them once at the last step — no separate reduction pass.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import CompilerParams as _CompilerParams, on_tpu as _on_tpu

__all__ = ["fused_layer_norm"]


def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, mu_ref, rs_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)                 # [bn, E]
    mu = jnp.mean(xf, axis=1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(jnp.square(xc), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y_ref[...] = (xc * rstd * s_ref[...] + b_ref[...]).astype(y_ref.dtype)
    mu_ref[...] = mu
    rs_ref[...] = rstd


def _bwd_kernel(x_ref, s_ref, dy_ref, mu_ref, rs_ref,
                dx_ref, ds_ref, db_ref, ds_scr, db_scr):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    xf = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    rstd = rs_ref[...]
    xhat = (xf - mu_ref[...]) * rstd                     # [bn, E]
    g = dy * s_ref[...]
    c1 = jnp.mean(g, axis=1, keepdims=True)
    c2 = jnp.mean(g * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (g - c1 - xhat * c2)).astype(dx_ref.dtype)
    ds_scr[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_scr[...] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _final():
        ds_ref[...] = ds_scr[...]
        db_ref[...] = db_scr[...]


def _pick_bn(N):
    # 256 rows x E=768: the bwd kernel's ~6 f32 temporaries stay ~4.5MB,
    # inside the 16MB scoped VMEM (1024 rows OOMs the stack allocator)
    for bn in (256, 128, 512, 8):
        if N % bn == 0:
            return bn
    return None


def _fwd(x, scale, bias, eps, interpret):
    N, E = x.shape
    bn = _pick_bn(N)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, E), x.dtype),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="layer_norm_fwd",
    )(x, scale.reshape(1, E), bias.reshape(1, E))
    return y, mu, rstd


def _bwd(eps, interpret, res, dy):
    x, scale, mu, rstd = res
    N, E = x.shape
    bn = _pick_bn(N)
    dx, ds, db = pl.pallas_call(
        _bwd_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, E), x.dtype),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, E), jnp.float32),
            pltpu.VMEM((1, E), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",)),   # sequential: dscale accum
        interpret=interpret,
        name="layer_norm_bwd",
    )(x, scale.reshape(1, E), dy, mu, rstd)
    return dx, ds.reshape(scale.shape), db.reshape(scale.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x, scale, bias, eps, interpret):
    y, _, _ = _fwd(x, scale, bias, eps, interpret)
    return y


def _ln_fwd(x, scale, bias, eps, interpret):
    y, mu, rstd = _fwd(x, scale, bias, eps, interpret)
    return y, (x, scale, mu, rstd)


def _ln_bwd(eps, interpret, res, dy):
    return _bwd(eps, interpret, res, dy)


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, scale, bias, eps=1e-6, interpret=None):
    """x: [..., E]; scale/bias: [E] (any float dtype — stats and params run
    in f32, output in x.dtype).  Returns layer-normalized x."""
    if interpret is None:
        interpret = not _on_tpu()
    shape = x.shape
    E = shape[-1]
    N = 1
    for d in shape[:-1]:
        N *= d
    if _pick_bn(N) is None:
        # row count not tileable: caller should use the unfused path
        raise ValueError("fused_layer_norm: N=%d not divisible" % N)
    x2 = x.reshape(N, E)
    y = _ln(x2, scale.astype(jnp.float32), bias.astype(jnp.float32),
            float(eps), bool(interpret))
    return y.reshape(shape)
