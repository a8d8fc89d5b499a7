"""The TPU compiler's own count of what a cell's ``run_steps`` holds, no chip:

    JAX_PLATFORMS=cpu python3 scripts/step_memory_count.py <cell> [key=value ...]

The cell's trainer is built from SHAPES (``jax.eval_shape`` of the seeded
tree and of the optimizer's state) on the cell's mesh of described
``v5e:2x2`` devices (one; all four for a ``dp`` 4 cell, whose report is ONE
device's, collectives and all), the kernels' ``_on_tpu`` patched True in THIS process, and
``run_steps`` over the cell's staged batches is compiled for it with
``--xla_dump_to`` set: the dump's memory-usage report is the count PERF.md
section 4 gives for every decoder cell (PR 37's recipe), printed beside
``memory_analysis()``, the program's NEED by it (``memscope.need_line``:
the line a chip run's memory account prints for the same program, one
definition) and the largest buffers of the report.  ``key=value``
overrides the configuration factory's arguments (``n_layers=28``); ``S=256``
the traffic's sequence length (``count``'s ``dims``: the tests' tiny-shape
case).  The kernels' ``_on_tpu`` are put back as ``count`` returns.  A
compile that passes is not a chip run: what the chip reserves is read off
the cell's own run (``peak_hbm_gb`` and the line's ``memory`` fields)."""

import glob
import importlib
import json
import math
import os
import pkgutil
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
DUMP = tempfile.mkdtemp(prefix="step_memory_")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
    + " --xla_dump_to=%s --xla_dump_hlo_as_text=false" % DUMP

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark.harness import build, manifest as mf  # noqa: E402
from benchmark.harness.batches import resolve_shape  # noqa: E402
from paddle_tpu import kernels  # noqa: E402
from paddle_tpu.monitor import memscope  # noqa: E402
from paddle_tpu.parallel import decoder, optim, transformer as T  # noqa: E402
from paddle_tpu.parallel.mesh import DP, MeshSpec  # noqa: E402
from paddle_tpu.parallel.train import (TrainState, make_train_step,  # noqa: E402
                                       state_specs)


def count(cell, *overrides):
    """``(compiled run_steps, parameters, a device's state bytes)`` of
    ``cell`` for the described v5e devices of its mesh (one, or the host's
    four), the kernels' ``_on_tpu`` True while it lowers."""
    patched = []
    for info in pkgutil.iter_modules(kernels.__path__):
        module = importlib.import_module("paddle_tpu.kernels." + info.name)
        if hasattr(module, "_on_tpu"):
            patched.append((module, module._on_tpu))
            module._on_tpu = lambda: True
    try:
        return _count(cell, *overrides)
    finally:
        for module, was in patched:
            module._on_tpu = was


def _count(cell, *overrides):
    manifest = mf.load(ROOT)
    entry = mf.cell(manifest, cell)
    config = mf.read_json(ROOT, "benchmark", "configs",
                          entry["config"] + ".json")
    traffic = mf.read_json(ROOT, "benchmark", "traffic", cell + ".json")
    kwargs = dict(config["config_factory"]["kwargs"])
    for item in overrides:
        key, value = item.split("=")
        if key in traffic["dims"]:
            traffic["dims"][key] = json.loads(value)
        else:
            kwargs[key] = json.loads(value)
    path, name = config["config_factory"]["path"].rsplit(".", 1)
    cfg = getattr(importlib.import_module(path), name)(**kwargs)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the cell's own mesh (a four-chip cell's dp = 4: the described host's
    # four devices, the step's collectives compiled with the rest)
    spec = MeshSpec(**traffic["mesh"])
    mesh = spec.build(devices=topo.devices[:spec.size])
    optimizer = optim.adamw()
    params = jax.eval_shape(
        lambda: T._init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda p: TrainState.create(p, optimizer), params)
    pspecs = T.transformer_param_specs(cfg)
    sspecs = state_specs(pspecs, state)
    multi = make_train_step(
        decoder.make_loss_fn(cfg), mesh, pspecs, T.grad_sync_axes(cfg),
        optimizer, decoder.batch_specs(cfg),
        stepped=tuple(decoder.STEPPED & set(params))).multi(state)
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        state, sspecs)
    dims = build.cell_dims(config, traffic)
    # ``ids``, and what else the trainer reads of a batch (its noise)
    batches = {f["name"]: jax.ShapeDtypeStruct(
        (int(traffic["staged_batches"]),) + resolve_shape(f["shape"], dims),
        jnp.dtype(f["dtype"]), sharding=NamedSharding(mesh, P(None, DP)))
        for f in config["batch_fields"]
        if f["name"] in decoder.batch_specs(cfg)}
    n_params = sum(a.size for a in jax.tree.leaves(params))
    # a DEVICE's bytes: a leaf split over the mesh counts its shard
    return (multi.lower(state, batches, 1e-5).compile(), n_params,
            sum(math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(state)))


def main(cell, *overrides):
    compiled, n_params, state_bytes = count(cell, *overrides)
    print("parameters: %.1f M; state leaves a device: %.3f GB"
          % (n_params / 1e6, state_bytes / 1e9))
    print("memory_analysis():", compiled.memory_analysis())
    print(memscope.need_line(cell.split(".")[0]
                             + ".run_steps (described v5e)",
                             memscope.program_ledger(compiled)))
    reports = sorted(glob.glob(os.path.join(DUMP, "*memory-usage-report*")),
                     key=os.path.getsize)
    if not reports:
        print("no memory-usage report under", DUMP)
        return 1
    text = open(reports[-1]).read()
    print(reports[-1])
    print("\n".join(text.splitlines()[:40]))
    shapes = set(re.findall(r"\b(?:f32|bf16)\[[\d,]+\]", text))
    for shape in sorted(shapes, key=lambda s: -math.prod(
            int(n) for n in s[s.index("[") + 1:-1].split(",")))[:25]:
        print(shape)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
