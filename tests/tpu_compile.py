"""What the described-chip compile tests (``tests/test_chip_compile_*.py``, a
file a kernel family) share: the described v5e (``one_chip``), the readers of a
compiled or lowered program's text (grids, Mosaic digests, scoped VMEM) and
the cells' attention shapes.  Not collected itself.

Nothing runs and no chip is needed; the chip is described inside a fixture,
so that only a worker that is given one of those files loads the TPU's
library.  A kernel PR's described-chip compiles go into its family's file
(``ROADMAP.md`` Design 24)."""

import base64
import hashlib
import importlib
import os
import re
import sys

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compiled(attn, *shapes):
    """(the compiled program's text, {kernel name: grid}, the digests of the
    kernels' Mosaic modules in call order) of ``attn``'s forward and
    backward: the flash kernels' own.  The row kernel in front of a
    several-block backward (``flash_delta``, PR 55) is another module's and
    has a test of its own (``tests/test_chip_compile_rows.py``)."""
    def both(q, k, v, do):
        o, vjp = jax.vjp(attn, q, k, v)
        return (o,) + vjp(do)

    traced = jax.jit(both).trace(*shapes)
    grids = {name: tuple(int(n) for n in grid.split(",") if n.strip())
             for grid, name in re.findall(
                 r"grid=\(([\d, ]*)\).*?name=(flash_\w+)", str(traced.jaxpr),
                 re.S)}
    grids.pop("flash_delta", None)
    lowered = traced.lower()
    return (lowered.compile().as_text(), grids,
            _mosaic_digests(lowered.as_text(), skip=("flash_delta",)))


def _mosaic_digests(lowered_text, skip=()):
    """sha1 (12 hex digits) of each ``tpu_custom_call`` body's Mosaic text
    without debug info, in call order, but for the kernels named ``skip``."""
    from jaxlib.mlir import ir

    digests = []
    for body, name in re.findall(
            r'body\\22: \\22([A-Za-z0-9+/=]+).*?kernel_name = "(\w+)"',
            lowered_text):
        if name in skip:
            continue
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            digests.append(hashlib.sha1(module.operation.get_asm(
                enable_debug_info=False).encode()).hexdigest()[:12])
    return digests


def _grids(jaxpr_text):
    """``{kernel name: grid}`` of a traced program's Pallas calls: a call
    prints its grid, its kernel's body, then its name on a line of its own,
    so a name's grid is the last one printed before it (the row kernel in
    front of a flash backward, ``flash_delta``, has both of its own)."""
    grids = [(m.start(), m.group(1)) for m in re.finditer(
        r"grid=\(([\d, ]*)\)", jaxpr_text)]
    out = {}
    for m in re.finditer(r"^\s*name=(\w+)$", jaxpr_text, re.M):
        before = [grid for at, grid in grids if at < m.start()]
        if before:
            out[m.group(1)] = tuple(
                int(n) for n in before[-1].split(",") if n.strip())
    return out


def _vmem(text, kernel):
    """(bytes of VMEM the call of ``kernel`` asks Mosaic for, None where it
    leaves the scope at its default; bytes the compiled kernel took).  XLA
    may keep an array of its own in VMEM beside the call (the forward's
    ``o`` between the kernels that read it, where nothing of XLA's does:
    64 MiB at OLMoE's shape, PR 55): the call's scope then starts past it,
    and what the kernel took is counted from the scope's start."""
    scope = r'\{"memory_space":"1","offset":"(\d+)","size":"(\d+)"\}'
    line, = [l for l in text.splitlines()
             if "tpu_custom_call" in l and re.search(
                 r"%%?[\w.\-]*%s[\w.\-]* = " % kernel, l)]
    (start, asked), = re.findall(
        r'"scoped_memory_configs":\[(?:%s)?\]' % scope, line)
    (first, took), = re.findall(
        r'"used_scoped_memory_configs":\[%s\]' % scope, line)
    took = int(first) + int(took) - max(int(start or 0), int(first))
    return (int(asked) if asked else None), took


SMALLTHINKER, LFM2 = (1, 16384, 28, 4, 128), (2, 8192, 32, 8, 64)
MISTRAL4 = (1, 16384, 32, 32, 128)      # every head its own key and value
TRINITY, NEMOTRON = (1, 6144, 48, 8, 128), (2, 8192, 32, 2, 128)
SOLAR, JAMBA = (1, 4096, 64, 8, 128), (1, 8192, 20, 1, 128)


def _script(name):
    """A module of ``scripts/``."""
    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    return importlib.import_module(name)


def _moved(text, at_door):
    """Of a door's instructions ({array: names}, ``jamba_kernels_receipt.
    door``) those that move the array; XLA's own prefetch of a small
    operand (``copy-start``) keeps its tiles."""
    comps, entry = _script("attn_outside_hlo").computations(text)
    ops = {name: op for name, _, op, _, _ in comps[entry]}
    return {array: [n for n in names if ops[n] in (
        "copy", "reshape", "transpose", "fusion", "slice")]
        for array, names in at_door.items()}


MAMBA_KERNELS = {"selective_scan_fwd", "selective_scan_bwd",
                 "mamba_filter_fwd", "mamba_filter_bwd"}
