"""What ONE attention (or retention, Mamba, Mamba-2 or KDA) layer of a cell
moves through HBM outside its matmuls and kernels, read off the compiled
text, no chip:

    python3 scripts/attn_outside_hlo.py trinity_large_preview.s6144_scan
        [--kind 3] [--top 30] [--tiny]

The branch the block runs (``transformer._attention_heads_mode``,
``power_retention``, ``mamba_mixer``, ``mamba2_mixer`` or ``kda_mixer``) on
the cell's batch
and sequence at the configuration's widths, under ``jax.checkpoint``; its vjp
alone is compiled (the recomputed forward and the backward: what a layer
costs a second time in the step) for
a described ``v5e:2x2``, the kernels' ``_on_tpu`` patched True in THIS
process, shapes not arrays.  Bytes = operands + results of every
instruction the entry computation runs (a ``while``'s body times its trip
count; an async pair once; parameters, constants, tuples and bitcasts move
nothing; a ``dynamic-slice`` reads the block it cuts and an in-place
``dynamic-update-slice`` writes the block it is given, alone or inside a
fusion, so a loop over row blocks counts its blocks and not the whole array
every trip), in three groups:

- ``matmul``: fusions that hold a ``convolution`` / ``dot``;
- ``kernel``: ``tpu_custom_call``s, by the kernels' names;
- ``other``: what is left, the norm, rotation, gate and relayouts; its
  instructions over ``--big`` MB (140) are listed, each with XLA's own
  ``estimated_cycles`` (the cost model's count for the described chip, at
  ``CLOCK`` cycles a second; ``other_estimated_ms`` is their sum: ISSUE 55's
  table was made so.  An estimate, as the bytes are a least: PERF.md
  section 6, PR 55, has what the chip read beside it).

The branch is differentiated with respect to the leaves it READS (and its
input): a layer's other leaves, the experts' and the MLP's, would come back
as zero gradients, broadcasts of their whole size that no step makes (0.51
GB of Trinity's "other" before PR 55).

``--kind i`` takes the i-th layer kind of the period (default: the first with
rotary positions, or retention, a Mamba kind or KDA; a position with a shape
of its own, an ``AttentionShape``, runs as the block runs it: through
``cfg.position(kind)``, the learned-sparse branch where it has an indexer);
``--tiny`` takes the model's tiny configuration at S = 256 (the smoke test's).
This is the reading ISSUEs 47, 49, 53, 55 and 60 were sized by, and PR 66
after the fact (PERF.md section 6). Bytes over 819 GB/s are a LEAST time, not
a time: a time comes from the chip."""

import argparse
import collections
import contextlib
import importlib
import json
import os
import pkgutil
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 819e9
CLOCK = 1.5e9       # a v5e's cycles a second: 197e12 / (4 * 128 * 128 * 2)
ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
        "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
MOVES_NOTHING = {"parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast", "iota", "after-all", "partition-id", "replica-id"}
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _bytes(types):
    """Bytes of every ``dtype[dims]`` in a type string (a tuple's sum)."""
    total = 0
    for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", types):
        if dtype in ITEM:
            n = 1
            for d in dims.split(","):
                n *= int(d) if d else 1
            total += n * ITEM[dtype]
    return total


def _operands(rest):
    """The names in an instruction's operand list (to its closing paren)."""
    depth, end = 1, len(rest)
    for i, c in enumerate(rest):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            end = i
            break
    return re.findall(r"%[\w.\-]+", rest[:end]), rest[end:]


def computations(text):
    """{computation name: [(name, result types, opcode, operands, attrs)]}
    and the entry computation's name."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY\s+)?(%?[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            current = comps.setdefault(head.group(2).lstrip("%"), [])
            if head.group(1):
                entry = head.group(2).lstrip("%")
            continue
        m = INSTRUCTION.match(line)
        if m and current is not None:
            names, attrs = _operands(m.group(4))
            if m.group(3) in ("constant", "parameter"):
                attrs = m.group(4)          # keep the value: "8), ..."
            current.append((m.group(1), m.group(2), m.group(3), names, attrs))
    return comps, entry


def _kernel_name(name):
    return re.sub(r"[.\d]+$|^transpose_jvp_|^jvp_|_+$", "",
                  name.lstrip("%"))


def _cycles(attrs):
    """XLA's ``estimated_cycles`` of an instruction (0 where it gives
    none: a custom call, an async pair)."""
    m = re.search(r'"estimated_cycles":"(\d+)"', attrs)
    return int(m.group(1)) if m else 0


def _sliced(body):
    """What a fusion ``body`` moves where it cuts a block out of a larger
    array or writes one into it (a row-block loop's ``dynamic-slice`` /
    ``dynamic-update-slice``): {parameter index: bytes read of it} for the
    parameters only slices read (the slices' bytes) or only an in-place
    update writes into (none), and the bytes to count for the result in
    place of the updated array's (None where no update is the result)."""
    made = {n: (op, ops) for n, _, op, ops, _ in body}
    size = {n: _bytes(t) for n, t, _, _, _ in body}

    def source(n):      # through bitcasts, to what was cut or updated
        while made.get(n, ("", ()))[0] == "bitcast":
            n = made[n][1][0]
        return n

    index = {n: int(a.split(")")[0]) for n, _, op, _, a in body
             if op == "parameter"}
    reads = {n: [] for n in index}
    for n, _, op, ops, _ in body:
        if op == "bitcast":
            continue
        for at, o in enumerate(ops):
            o = source(o)
            if o in reads:
                reads[o].append(
                    size[n] if op == "dynamic-slice" and at == 0 else
                    0 if op == "dynamic-update-slice" and at == 0 else None)
    operands = {index[n]: sum(r) for n, r in reads.items()
                if r and None not in r}
    root = body[-1][0]
    parts = made[root][1] if made[root][0] == "tuple" else [root]
    result, updated = 0, False
    for part in map(source, parts):
        op, ops = made[part]
        if op == "dynamic-update-slice" and source(ops[0]) in index:
            result, updated = result + size[ops[1]], True
        else:
            result += size[part]
    return operands, result if updated else None


def account(text):
    """{"matmul", "kernel", "other": bytes}, {kernel name: bytes}, and the
    other group's instructions [(bytes, name, opcode, result type, XLA's
    estimated cycles)]."""
    comps, entry = computations(text)
    has_matmul = {name: any(op in ("convolution", "dot")
                            for _, _, op, _, _ in body)
                  for name, body in comps.items()}
    # a fusion may hold a kernel's custom call (a while body's do)
    held_kernel = {name: next((_kernel_name(n) for n, _, op, _, attrs in body
                               if op == "custom-call"
                               and "tpu_custom_call" in attrs), None)
                   for name, body in comps.items()}
    groups = collections.Counter()
    kernels = collections.Counter()
    others = []

    def trips(attrs):
        """A counted loop's trip count: the constant its condition compares
        the counter with (``lax.map`` / ``scan`` from 0 in steps of 1)."""
        cond = re.search(r"condition=%?([\w.\-]+)", attrs).group(1)
        bounds = [int(m.group(1)) for _, types, op, _, attrs_ in comps[cond]
                  if op == "constant" and types.startswith("s32[]")
                  for m in [re.match(r"(\d+)\)", attrs_ or "")] if m]
        return bounds[0] if len(bounds) == 1 else 1

    def walk(comp, times):
        size = {name: _bytes(types) for name, types, _, _, _ in comps[comp]}
        for name, types, op, names, attrs in comps[comp]:
            if op in MOVES_NOTHING or op.endswith("-done"):
                continue
            if op == "while":
                body = re.search(r"body=%?([\w.\-]+)", attrs).group(1)
                walk(body, times * trips(attrs))
                continue
            called = re.search(r"calls=%?([\w.\-]+)", attrs)
            called = called and called.group(1)
            read, result = [size.get(n, 0) for n in names], size[name]
            if op == "dynamic-slice":           # the block it cuts
                read = [result]
            elif op == "dynamic-update-slice":  # in place: the update
                read, result = [read[1]], read[1]
            elif op == "fusion" and called:
                cut, updated = _sliced(comps[called])
                read = [cut.get(i, r) for i, r in enumerate(read)]
                result = result if updated is None else updated
            moved = times * (2 * sum(read) if op.endswith("-start")
                             else sum(read) + result)
            kernel = _kernel_name(name) if (
                op == "custom-call" and "tpu_custom_call" in attrs) \
                else op == "fusion" and called and held_kernel[called]
            if kernel:
                if op == "fusion":      # the call's own operands, not the
                    inner = {n: _bytes(t) for n, t, _, _, _ in comps[called]}
                    moved = times * sum(       # stacked arrays it is cut from
                        inner[n] + sum(inner.get(o, 0) for o in ops)
                        for n, _, o_, ops, a in comps[called]
                        if o_ == "custom-call" and "tpu_custom_call" in a)
                groups["kernel"] += moved
                kernels[kernel] += moved
            elif op == "fusion" and called and has_matmul[called]:
                groups["matmul"] += moved
            else:
                groups["other"] += moved
                others.append((moved, name, op, types.split("{")[0],
                               times * _cycles(attrs)))

    walk(entry, 1)
    return dict(groups), dict(kernels), sorted(others, reverse=True)


@contextlib.contextmanager
def kernels_as_on_a_tpu():
    """Every kernel module's ``on_tpu`` / ``_on_tpu`` probe True, so that a
    call compiles through Mosaic where the default backend is the CPU; put
    back on the way out."""
    from paddle_tpu import kernels

    probes = [(mod, attr)
              for found in pkgutil.iter_modules(kernels.__path__)
              for mod in [importlib.import_module(
                  "paddle_tpu.kernels." + found.name)]
              for attr in ("on_tpu", "_on_tpu") if hasattr(mod, attr)]
    before = [getattr(mod, attr) for mod, attr in probes]
    try:
        for mod, attr in probes:
            setattr(mod, attr, lambda: True)
        yield
    finally:
        for (mod, attr), probe in zip(probes, before):
            setattr(mod, attr, probe)


def layer_shapes(cfg, batch, seq, kind):
    """(one layer's leaves of ``kind``, its input [batch, seq, hidden]) as
    shapes on one chip of a described ``v5e:2x2``."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.parallel import transformer as T

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(
        lambda: T._init_params(jax.random.PRNGKey(0), cfg))["params_layers"]
    stacked = 1             # leading axes of a layer's leaves: the periods
    if cfg.run_scan:        # a run's tree: [periods, run length, ...]
        params = params["r%d" % [k for _, k, _ in cfg.runs].index(kind)]
        stacked = 2
    elif cfg.per_position:
        params = params["p%d" % cfg.layer_kinds.index(kind)]
    leaves = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape[stacked:], a.dtype, sharding=one_chip), params)
    return leaves, jax.ShapeDtypeStruct((batch, seq, cfg.hidden), cfg.jdtype,
                                        sharding=one_chip)


def branch_of(cfg, kind):
    """``branch(leaves, h)``: the mixer a layer of ``kind`` runs."""
    from paddle_tpu.parallel import transformer as T

    def branch(pl, h):
        if kind == T.RETENTION:
            return T.power_retention(pl, h, cfg)
        if kind == T.MAMBA:
            return T.mamba_mixer(pl, h, cfg)
        if kind == T.MAMBA2:
            return T.mamba2_mixer(pl, h, cfg)
        if kind == T.KDA:
            return T.kda_mixer(pl, h, cfg)
        # the configuration as this position reads it (an ``AttentionShape``
        # has its own heads, ranks, widths, window, indexer or none)
        at, plain = cfg.position(kind)
        if at.indexer_heads:    # the branch alone: the loss term apart
            return T._sparse_attention(pl, h, at, plain[1], None)[0]
        return T._attention_heads_mode(pl, h, at, plain)

    return branch


def compiled_text(cfg, batch, seq, kind):
    """The compiled text of one layer's recompute + backward for a described
    v5e; ``kind`` an entry of ``cfg.layer_kinds``."""
    import jax

    leaves, h = layer_shapes(cfg, batch, seq, kind)
    branch = branch_of(cfg, kind)

    def recompute_and_backward(read, rest, h, g):
        return jax.vjp(jax.checkpoint(
            lambda read, h: branch({**rest, **read}, h)), read, h)[1](g)

    with kernels_as_on_a_tpu():
        read = leaves_read(branch, leaves, h)
        rest = {k: v for k, v in leaves.items() if k not in read}
        return jax.jit(recompute_and_backward).lower(read, rest, h, h) \
            .compile().as_text()


def leaves_read(branch, leaves, h):
    """The entries of a layer's ``leaves`` that ``branch(leaves, h)``
    reads: those with a leaf among the operands of its traced equations."""
    import jax

    flat, tree = jax.tree.flatten(leaves)
    jaxpr = jax.make_jaxpr(
        lambda flat, h: branch(tree.unflatten(flat), h))(flat, h).jaxpr
    seen = {id(v) for eqn in jaxpr.eqns for v in eqn.invars} \
        | {id(v) for v in jaxpr.outvars}
    used = tree.unflatten([id(v) in seen for v in jaxpr.invars[:len(flat)]])
    return {k: leaves[k] for k, u in used.items()
            if any(jax.tree.leaves(u))}


def cell_config(cell, tiny):
    """(TransformerConfig, batch, sequence) of a benchmark cell; ``tiny``:
    the same model's tiny configuration, 2 x 256."""
    from benchmark.harness import build, manifest as mf

    m = mf.load(ROOT)
    config = mf.read_json(ROOT, mf.config_entry(
        m, mf.cell(m, cell)["config"])["file"])
    if tiny:
        factory = config["config_factory"]["path"]
        stem = factory.rsplit(".", 1)[0]
        return build.resolve("%s.%s_tiny_config" % (
            stem, stem.rsplit(".", 1)[1]))(), 2, 256
    traffic = mf.read_json(ROOT, "benchmark", "traffic", cell + ".json")
    return (build._call(config["config_factory"]), traffic["batch"],
            traffic["dims"]["S"])


def default_kind(cfg):
    from paddle_tpu.parallel import transformer as T

    kinds = cfg.layer_kinds
    return next(k for k in kinds
                if k in (T.RETENTION, T.MAMBA, T.MAMBA2, T.KDA)
                or (T._is_attention(k) and cfg.position(k)[1][1]))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--kind", type=int)
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--big", type=float, default=140.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    cfg, batch, seq = cell_config(args.cell, args.tiny)
    kind = default_kind(cfg) if args.kind is None \
        else cfg.layer_kinds[args.kind]
    text = compiled_text(cfg, batch, seq, kind)
    groups, kernels, others = account(text)
    big = [o for o in others if o[0] >= args.big * 1e6]
    report = {"cell": args.cell, "kind": str(kind), "batch": batch,
              "seq": seq, "gb": {k: v / 1e9 for k, v in groups.items()},
              "kernels_gb": {k: v / 1e9 for k, v in kernels.items()},
              "other_least_ms": groups.get("other", 0) / HBM * 1e3,
              "other_estimated_ms": sum(o[4] for o in others) / CLOCK * 1e3,
              "other_big": {"count": len(big),
                            "gb": sum(o[0] for o in big) / 1e9}}
    for moved, name, op, types, cycles in others[:args.top]:
        print("%9.1f MB %10d cycles  %-36s %-14s %s" % (
            moved / 1e6, cycles, name, op, types))
    print(json.dumps(report))
    return report


if __name__ == "__main__":
    main()
