"""Control-flow ops (parity: operators/controlflow/ — while_op.cc:43,
conditional_block_op.cc, recurrent_op.cc, compare/logical ops live in math_ops).

Design translation: the reference runs sub-blocks through a nested C++
Executor with step scopes (while_op.cc:43).  Here sub-blocks lower into
lax.while_loop / lax.cond / lax.scan bodies — compiled control flow with a
fixed carried-state pytree (the explicit loop_vars), which is the XLA-legal
form of the reference's scope-mutation semantics (SURVEY.md §7 hard part 6).
"""

import jax
import jax.numpy as jnp
from jax import lax

from ..registry import register_op
from .common import x, out


@register_op("while")
def _while(ins, attrs, ctx):
    """attrs: sub_block_index, cond_name, loop_var_names.

    Carried state = loop_var_names' values.  The sub-block is re-interpreted
    as the loop body; anything it reads from the outer env but does not carry
    is closure-captured (loop-invariant)."""
    names = list(attrs["loop_var_names"])
    cond_name = attrs["cond_name"]
    sub_idx = int(attrs["sub_block_index"])
    outer_env = dict(ctx.env)
    init = tuple(outer_env[n] for n in names)

    def cond_fn(carry):
        e = dict(outer_env)
        e.update(zip(names, carry))
        return e[cond_name].reshape(())

    def body_fn(carry):
        e = dict(outer_env)
        e.update(zip(names, carry))
        e = ctx.interpret_block(sub_idx, e)
        return tuple(e[n] for n in names)

    final = lax.while_loop(cond_fn, body_fn, init)
    return out(Out=list(final))


@register_op("conditional_block")
def _conditional_block(ins, attrs, ctx):
    """Single-branch conditional (ref conditional_block_op.cc): if Cond, run
    the sub-block, else pass carried vars through unchanged."""
    cond = x(ins, "Cond")
    names = list(attrs["carried_var_names"])
    sub_idx = int(attrs["sub_block_index"])
    outer_env = dict(ctx.env)
    init = tuple(outer_env[n] for n in names)

    def true_fn(carry):
        e = dict(outer_env)
        e.update(zip(names, carry))
        e = ctx.interpret_block(sub_idx, e)
        return tuple(e[n] for n in names)

    final = lax.cond(cond.reshape(()), true_fn, lambda c: c, init)
    return out(Out=list(final))


@register_op("cond")
def _cond(ins, attrs, ctx):
    """Two-branch cond (ref layers/control_flow.py cond): lowers both
    sub-blocks and selects outputs."""
    pred = x(ins, "Cond")
    true_idx = int(attrs["true_block_index"])
    false_idx = int(attrs["false_block_index"])
    true_outs = list(attrs["true_out_names"])
    false_outs = list(attrs["false_out_names"])
    outer_env = dict(ctx.env)

    def branch(idx, names):
        def fn(_):
            e = ctx.interpret_block(idx, dict(outer_env))
            return tuple(e[n] for n in names)

        return fn

    res = lax.cond(pred.reshape(()), branch(true_idx, true_outs), branch(false_idx, false_outs), 0)
    return out(Out=list(res))


@register_op("scan")
def _scan(ins, attrs, ctx):
    """Microbatch/time scan (net-new vs reference's recurrent_op/StaticRNN —
    the TPU-idiomatic replacement; see layers.StaticRNN).

    attrs: sub_block_index, carry_names, xs_names (scanned inputs, leading
    axis = time), ys_names (stacked outputs), length.
    """
    carry_names = list(attrs["carry_names"])
    xs_names = list(attrs["xs_names"])
    ys_names = list(attrs["ys_names"])
    sub_idx = int(attrs["sub_block_index"])
    outer_env = dict(ctx.env)
    # initial carries / scanned inputs come from the op's INPUT VALUES (the
    # outer init vars); carry_names/xs_names are the sub-block-local names
    # the body binds them to
    init = tuple(ins.get("Carry", []))
    xs = tuple(ins.get("Xs", []))

    def body(carry, xt):
        e = dict(outer_env)
        e.update(zip(carry_names, carry))
        e.update(zip(xs_names, xt))
        e = ctx.interpret_block(sub_idx, e)
        return tuple(e[n] for n in carry_names), tuple(e[n] for n in ys_names)

    final_carry, ys = lax.scan(body, init, xs)
    return out(CarryOut=list(final_carry), Ys=list(ys))


@register_op("select_input")
def _select_input(ins, attrs, ctx):
    mask = x(ins, "Mask")
    branches = ins["X"]
    r = branches[0]
    for i, b in enumerate(branches[1:], start=1):
        r = jnp.where(mask.reshape(()) == i, b, r)
    return out(Out=r)


@register_op("print")
def _print(ins, attrs, ctx):
    v = x(ins, "In")
    msg = attrs.get("message") or "{}"
    if "{}" not in msg:
        # escape literal braces so str.format inside debug.print can't choke
        msg = msg.replace("{", "{{").replace("}", "}}") + " {}"
    jax.debug.print(msg, v)
    return out(Out=v)


@register_op("backward_meta")
def _backward_meta(ins, attrs, ctx):
    raise RuntimeError(
        "backward_meta must be handled by the Executor's top-level lowering "
        "(it marks the jax.value_and_grad split); it cannot appear in a sub-block"
    )
