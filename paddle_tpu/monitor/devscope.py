"""Device time under the program's own names.

A device trace names each operation by the compiled instruction that ran
(``fusion.2345``), a name XLA invents anew with every compile.  The train
path therefore names its work itself: ``jax.named_scope`` at the layer
boundaries, from the one vocabulary below.  A scope is metadata: it reaches
the compiled module as the ``op_name`` of every instruction
(``jit(multi)/while/body/.../transpose(jvp(lm_head))/dot_general``) and
changes no instruction.  This module keeps, for every program a trainer
dispatched, what is needed to read ``instruction name -> op_name`` off the
program's own compiled text, and reads it only when asked.  It records no
time: ``monitor/trace.py`` is the host's tracer and the profiler the
device's; this says what the profiler's instruction names mean.

    trainer.run_steps(batches, lr)        # registers the program, once
    ...profile...
    for label, names in devscope.scope_maps().items():
        phase, scope = devscope.classify(names["fusion.2345"])

Only plain ``jax.jit`` programs are registered.  A step built with a
``warm_key`` is a ``warm.WarmCallable``, which has no ``lower``: it is
skipped (neither ``build_*_trainer`` nor the benchmark passes one).
"""

import functools
import re
import weakref

import jax

__all__ = ["VOCABULARY", "PHASES", "scoped", "register", "scope_maps",
           "classify"]

EMBED, ATTENTION, MLP, LAYER_NORM, LM_HEAD = (
    "embed", "attention", "mlp", "layer_norm", "lm_head")
CONV, BN, POOL, FC, LOSS = "conv", "bn", "pool", "fc", "loss"
GRAD_SYNC, OPTIMIZER = "grad_sync", "optimizer"
# an expert FFN (dispatch, grouped matmuls, combine) and, inside it, its
# router (logits, softmax, top-k, auxiliary losses)
MOE, ROUTER = "moe", "router"
# the gated short convolution that stands where attention does in some
# layers of a stack (both projections, the gates and the taps between them)
SHORT_CONV = "short_conv"
# power retention where attention stands: projections, q/k norm, rotary
# positions, the gate, the chunked-scan kernels, the output projection
RETENTION = "retention"
# latent attention where attention stands: both low-rank chains and their
# latents' norms, rotation and assembly of the heads, the flash kernels, the
# output projection
LATENT_ATTENTION = "latent_attention"
# the dense gated FFN every token meets beside its routed experts
SHARED_EXPERT = "shared_expert"
# attention's sigmoid output gate: the sigmoid of the gate's projection and
# its product with the heads' output, between the flash kernel and ``wo``
# (the projection itself is attention's)
ATTN_GATE = "attn_gate"
# sandwich norms: the RMS norm on a branch's OUTPUT (attention's, the FFN's)
POST_NORM = "post_norm"
# the Mamba-1 mixer where attention stands (both projections, the causal
# filter, the step sizes' chain, the output gate) and, inside it, the
# selective scan's kernels
MAMBA, SELECTIVE_SCAN = "mamba", "selective_scan"
# the scan over the stacked layers itself: its slices of each layer's leaves,
# the activations it keeps for the backward pass and the gradients it stacks
# (a layer's own work carries the layer's scopes, which lie further in)
LAYER_SCAN = "layer_scan"
VOCABULARY = (EMBED, ATTENTION, MLP, LAYER_NORM, LM_HEAD, CONV, BN, POOL, FC,
              LOSS, GRAD_SYNC, OPTIMIZER, MOE, ROUTER, SHORT_CONV, RETENTION,
              LATENT_ATTENTION, SHARED_EXPERT, LAYER_SCAN, ATTN_GATE,
              POST_NORM, MAMBA, SELECTIVE_SCAN)
PHASES = ("forward", "backward", "recompute", GRAD_SYNC, OPTIMIZER)

# `%fusion.12 = bf16[..] fusion(%p.1, %copy-done.2), ..., metadata={op_name="jit(multi)/..." ...}`:
# one instruction a line, in entry, loop-body and fused computations alike
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]+)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPERS = re.compile(r"(?:jvp|transpose)\(|\)")
_RECOMPUTE = "rematted_computation"

_programs = []          # (label, weak reference to the jitted function, avals)


def scoped(name):
    """Decorator: the operations of every call go under ``name``.  (A
    ``jax.named_scope`` object used as a decorator is ONE context manager
    shared by every call; nested, it loses its way back.)"""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def _aval(x):
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x                                    # a Python scalar
    # an uncommitted array went in with no sharding of its own
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def register(label, jitted, args):
    """Remember that ``jitted`` was dispatched with ``args``: their shapes,
    dtypes and shardings, not their buffers, and the function weakly.
    Lowers, compiles and reads nothing.  Returns True, for the caller's
    once-only flag."""
    if hasattr(jitted, "lower"):
        _programs.append((label, weakref.ref(jitted),
                          jax.tree.map(_aval, tuple(args))))
    return True


def _names(text):
    """``{instruction name: op_name}`` of a compiled module's text.  What
    XLA adds itself (the ``copy-start`` / ``copy-done`` of a prefetch, a
    ``slice-done``, a layout ``copy``) has no ``op_name``: it takes that of
    the first instruction that uses its result, through others of its kind
    (the wait for a prefetch is the consumer's time).  So does what the TPU
    compiler rewrites into calls of its own and names anew, a lone word
    with no path (``gather``, ``sort``, ``scatter-add``, ``reduce_sum``):
    the program's paths always hold a ``/``."""
    names, bare, first_user = {}, [], {}
    for name, rest in _INSTRUCTION.findall(text):
        m = _OP_NAME.search(rest)
        if m and "/" in m.group(1):
            names[name] = m.group(1)
        else:
            bare.append(name)
        for operand in _OPERAND.findall(rest):
            first_user.setdefault(operand, name)
    for name in bare:
        user = first_user.get(name)
        while user is not None and user not in names:
            user = first_user.get(user)     # a user is a later line: no cycle
        if user is not None:
            names[name] = names[user]
    return names


def scope_maps():
    """``{label: {instruction name: op_name}}`` for every registered program
    whose owner is alive, read off ``lower(*avals).compile().as_text()``.
    The executable comes from JAX's caches where they hold it (the trace of
    the arguments' shapes and the compile are cached in the process, the
    compile in the persistent cache too), so this costs one pass over the
    text; it is for after a profiled run, never for a hot path.  Two live
    programs under one label are told apart as ``label`` and ``label#2``."""
    out, live = {}, []
    for entry in _programs:
        label, ref, avals = entry
        jitted = ref()
        if jitted is None:
            continue                        # its trainer is gone
        live.append(entry)
        key, n = label, 1
        while key in out:
            n += 1
            key = "%s#%d" % (label, n)
        out[key] = _names(jitted.lower(*avals).compile().as_text())
    _programs[:] = live
    return out


def classify(op_name):
    """``(phase, scope)`` of an instruction's ``op_name``.  ``scope`` is the
    innermost vocabulary word on the path (``jvp(``, ``transpose(`` and
    ``)`` stripped), None where the path holds none.  ``phase`` is the scope
    itself for ``optimizer`` and ``grad_sync``; else ``recompute`` for the
    forward that ``jax.checkpoint`` runs again inside the backward pass
    (``.../checkpoint/rematted_computation/...``; the checkpointed body's
    own backward is ``.../checkpoint/...`` under ``transpose(``),
    ``backward`` where the path holds ``transpose(``, else ``forward``."""
    scope = None
    for part in reversed(op_name.split("/")):
        word = _WRAPPERS.sub("", part)
        if word in VOCABULARY:
            scope = word
            break
    if scope in (OPTIMIZER, GRAD_SYNC):
        return scope, scope
    if _RECOMPUTE in op_name:
        return "recompute", scope
    if "transpose(" in op_name:
        return "backward", scope
    return "forward", scope
