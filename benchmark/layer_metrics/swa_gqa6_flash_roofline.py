"""Kernels: ``swa_flash_roofline``'s reading (the least time the chip could
take for the attention the shapes require, full and windowed layers apart
and counted by call, over the time the flash kernels took;
``benchmark/flops/flash_attention_gqa.py`` gives each kind's FLOPs and
bytes) under a name of its own, for a stack of 48 query heads on 8
key/value heads of 128 (a group of 6) at S past the window, whose
configuration names the window ``sliding_window``: that reader is handed
the key it reads, ``sliding_window_size``, and says its line under its own
name.  The gate is not the kernels' and not in it."""

from . import swa_flash_roofline


def read(trace, spans, counters, cell):
    model = cell["config"]["model"]
    keyed = dict(model, sliding_window_size=model.get("sliding_window"))
    return swa_flash_roofline.read(
        trace, spans, counters,
        dict(cell, config=dict(cell["config"], model=keyed)))
