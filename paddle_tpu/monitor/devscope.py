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
device's; this says what the profiler's instruction names mean.  The
executable it reads them from is fetched once a program and kept
(``executables()``): ``monitor/memscope.py`` reads the same one for the
program's memory ledger and for what is large in it (``value_sizes``).

    trainer.run_steps(batches, lr)        # registers the program, once
    ...profile...
    for label, names in devscope.scope_maps().items():
        phase, scope = devscope.classify(names["fusion.2345"])

Only plain ``jax.jit`` programs are registered.  A ``warm.WarmCallable``
has no ``lower``: it is skipped (``make_train_step`` builds none).
"""

import collections
import functools
import re
import weakref

import jax

__all__ = ["VOCABULARY", "PHASES", "scoped", "register", "executables",
           "scope_maps", "classify", "value_sizes"]

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
# the Mamba-2 mixer as a layer's one branch (both projections, the causal
# filter, the step sizes, the gated group norm) and, inside it, the chunked
# state-space dual form's kernels
MAMBA2, SSD_SCAN = "mamba2", "ssd_scan"
# Kimi Delta Attention where attention stands (the three projections and
# their filters, the L2 norms, the decay's and the output's gates, the
# head-wise norm, the output projection) and, inside it, the delta rule
# itself: the chunks' own parts, the solve and the scan that carries the state
KDA, KDA_CHUNK = "kda", "kda_chunk"
# learned-sparse attention, inside attention's scope: the indexer (its
# projections, the key's norm, rotation, the scores' kernel), the selection
# (the k-th largest score a row), the flash calls under that mask, and the
# indexer's own loss term (the target from the main attention, the KL)
INDEXER, INDEXER_SELECT, SPARSE_ATTN, INDEXER_KL = (
    "indexer", "indexer_select", "sparse_attn", "indexer_kl")
# latent attention of two SHAPES in one stack, each kind's position under a
# word of its own where ``latent_attention`` would stand: the full layers'
# (under an indexer: ``indexer*`` and ``sparse_attn`` lie further in) and the
# sliding layers' (their own ranks and widths, under a window)
MLA_DSA, MLA_SWA = "mla_dsa", "mla_swa"
# the scan over the stacked layers itself: its slices of each layer's leaves,
# the activations it keeps for the backward pass and the gradients it stacks
# (a layer's own work carries the layer's scopes, which lie further in)
LAYER_SCAN = "layer_scan"
# a looped stack's passes themselves (the whole stack applied several times
# over one set of leaves): the stream carried from pass to pass, the exits
# kept, the running sum of the stacked gradients over the passes; and the
# exit gate at the end of every pass with what the loss makes of it (the
# distribution over the exits, its entropy, the weighted sum)
LOOP_SCAN, EXIT_GATE = "loop_scan", "exit_gate"
# block-diffusion training outside the layers: the noising of a batch's ids
# (the mask from its noise, the mask token put in) and the two copies put one
# over the other (the rows ``[x_t ; x_0]`` the stack runs on), and the noised
# rows cut out again in front of the head
NOISE = "noise"
# expert parallelism outside the matmuls, inside an expert FFN's scope: the
# pack of a device's (token, expert) rows by destination, the ``all_to_all``
# out and back, the sort by local expert on arrival and its inverse, forward
# and backward
EXCHANGE = "exchange"
VOCABULARY = (EMBED, ATTENTION, MLP, LAYER_NORM, LM_HEAD, CONV, BN, POOL, FC,
              LOSS, GRAD_SYNC, OPTIMIZER, MOE, ROUTER, SHORT_CONV, RETENTION,
              LATENT_ATTENTION, SHARED_EXPERT, LAYER_SCAN, ATTN_GATE,
              POST_NORM, MAMBA, SELECTIVE_SCAN, MAMBA2, SSD_SCAN, LOOP_SCAN,
              EXIT_GATE, KDA, KDA_CHUNK, INDEXER, INDEXER_SELECT, SPARSE_ATTN,
              INDEXER_KL, MLA_DSA, MLA_SWA, NOISE, EXCHANGE)
PHASES = ("forward", "backward", "recompute", GRAD_SYNC, OPTIMIZER)

# `%fusion.12 = bf16[..] fusion(%p.1, %copy-done.2), ..., metadata={op_name="jit(multi)/..." ...}`:
# one instruction a line, in entry, loop-body and fused computations alike
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]+)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPERS = re.compile(r"(?:jvp|transpose)\(|\)")
_RECOMPUTE = "rematted_computation"

# [label, weak reference to the jitted function, avals, its executable once
# somebody has asked for it]
_programs = []

# for ``value_sizes``: a computation's first line, the halves of an
# instruction's right-hand side, and what an element of a shape occupies
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) \(.*\{$")
_ARRAY = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_CALL = re.compile(r"^([\w\-]+)\((.*?)\)(?:, |$)")
_COMMENT = re.compile(r"/\*.*?\*/")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
             "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
# results that are another value's bytes, or no bytes of the step's
_NO_VALUE = ("parameter", "tuple", "get-tuple-element", "bitcast", "constant",
             "while", "after-all", "partition-id", "replica-id")


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
        _programs.append([label, weakref.ref(jitted),
                          jax.tree.map(_aval, tuple(args)), None])
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


def executables():
    """``{label: compiled executable}`` for every registered program whose
    owner is alive: ``lower(*avals).compile()``, fetched once a program,
    whoever asks first, and kept while its owner lives.  The executable
    comes from JAX's caches where they hold it (the trace of the arguments'
    shapes and the compile are cached in the process, the compile in the
    persistent cache too); it is for after a run, never for a hot path.
    Two live programs under one label are told apart as ``label`` and
    ``label#2``."""
    out, live = {}, []
    for entry in _programs:
        label, ref, avals, compiled = entry
        jitted = ref()
        if jitted is None:
            continue                        # its trainer is gone
        live.append(entry)
        key, n = label, 1
        while key in out:
            n += 1
            key = "%s#%d" % (label, n)
        if compiled is None:
            compiled = entry[3] = jitted.lower(*avals).compile()
        out[key] = compiled
    _programs[:] = live
    return out


def scope_maps():
    """``{label: {instruction name: op_name}}`` of ``executables()``, read
    off each one's text: one pass over it a call."""
    return {key: _names(compiled.as_text())
            for key, compiled in executables().items()}


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


# one instruction of a compiled module's text: ``shape`` as written (a
# tuple's keeps its parentheses), ``rest`` the whole right-hand side
_Instr = collections.namedtuple(
    "_Instr", "name is_root shape opcode operands rest")


def _instruction(name, line, rest):
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, call = rest[:i + 1], rest[i + 2:]
    else:
        shape, _, call = rest.partition(" ")
    m = _CALL.match(call)
    return _Instr(name, line.lstrip().startswith("ROOT"), shape,
                  m.group(1) if m else None,
                  _OPERAND.findall(m.group(2)) if m else [], rest)


def _computations(text):
    """``({computation: [_Instr]}, the ENTRY computation's name)``."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            cur.append(_instruction(m.group(1), line, m.group(2)))
    return comps, entry


def _arrays(shape):
    """``[(shape as written, bytes)]`` of the arrays in a shape's text, in
    order: one for an array, one an element for a (flat) tuple."""
    out = []
    for m in _ARRAY.finditer(_COMMENT.sub("", shape)):
        size = _ITEMSIZE.get(m.group(1), 1 if m.group(1).startswith("f8")
                             else 0)
        for d in m.group(2).split(","):
            size *= int(d) if d else 1
        out.append((m.group(0), size))
    return out


def _body(comps, loop):
    m = _BODY.search(loop.rest)
    return comps.get(m.group(1), ()) if m else ()


def value_sizes(text):
    """What is large in a compiled module, by its own text: ``[(bytes,
    shape, instruction, op_name)]``, largest first, of

    - the result of every instruction of the ENTRY computation and of the
      bodies of its loops (under a scan over steps the step is one loop
      down), parameters, tuples and views left out, and
    - every array that a ``while`` loop of any computation CARRIES and its
      body changes, as ``<while>[<index>]`` under the loop's ``op_name``:
      the layer scan's stacked residuals and stacked gradients.  An element
      the body hands on as it came (the stacked weights, a batch) is the
      caller's, not the loop's, and one that a loop of the entry computation
      takes from the program's parameters (the train state under a scan over
      steps) is an argument.

    It has no liveness: two of these may never be held together, and a
    loop's body holds more inside.  It names candidates for a peak; the
    peak is the compiler's (``memscope.program_ledger``)."""
    comps, entry = _computations(text)
    names, out = _names(text), []
    top = comps.get(entry, ())
    # the step's own level: the entry computation and, under a scan over
    # steps, the bodies of its loops
    for comp in [top] + [_body(comps, i) for i in top if i.opcode == "while"]:
        # what a loop starts from is the loop's own buffer: listed there
        inits = {i.operands[0] for i in comp
                 if i.opcode == "while" and i.operands}
        started = {operand for i in comp if i.name in inits
                   for operand in i.operands}
        for i in comp:
            if i.opcode not in _NO_VALUE and not i.shape.startswith("(") \
                    and i.name not in started:
                out += [(size, written, i.name, names.get(i.name))
                        for written, size in _arrays(i.shape)]
    for comp in comps.values():
        for loop in comp:
            if loop.opcode != "while":
                continue
            kept = _handed_on(_body(comps, loop))
            if comp is top and loop.operands:
                kept |= _arguments(comp, loop.operands[0])
            out += [(size, written, "%s[%d]" % (loop.name, n),
                     names.get(loop.name))
                    for n, (written, size) in enumerate(_arrays(loop.shape))
                    if n not in kept]
    return sorted(out, key=lambda v: (-v[0], v[2]))


def _arguments(comp, init):
    """The indices of the tuple ``init`` of the entry computation that are
    the program's own parameters (through a copy or a view): the train
    state that a scan over steps carries, which is counted with the
    arguments."""
    by_name = {i.name: i for i in comp}
    init = by_name.get(init)
    found = set()
    for n, name in enumerate(init.operands if init is not None
                             and init.opcode == "tuple" else ()):
        i = by_name.get(name)
        while i is not None and i.opcode in ("copy", "bitcast") \
                and i.operands:
            i = by_name.get(i.operands[0])
        if i is not None and i.opcode == "parameter":
            found.add(n)
    return found


def _handed_on(body):
    """The indices of a loop's tuple that ``body`` returns as it got them:
    its ROOT ``tuple``'s operand ``n`` is ``get-tuple-element(parameter),
    index=n``."""
    by_name = {i.name: i for i in body}
    root = next((i.operands for i in body
                 if i.is_root and i.opcode == "tuple"), ())
    kept = set()
    for n, operand in enumerate(root):
        i = by_name.get(operand)
        if i is not None and i.opcode == "get-tuple-element" and i.operands \
                and getattr(by_name.get(i.operands[0]), "opcode", None) \
                == "parameter" and re.search(r"\bindex=%d\b" % n, i.rest):
            kept.add(n)
    return kept
