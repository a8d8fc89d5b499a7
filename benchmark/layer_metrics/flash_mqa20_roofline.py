"""Kernels: ``flash_gqa64_roofline``'s reading (the least time the chip
could take for the causal grouped-query attention the shapes require, by
call, over the time the flash kernels took;
``benchmark/flops/flash_attention_gqa.py`` gives the FLOPs and bytes) under
a name of its own, for MULTI-QUERY attention: 20 query heads of 128 on ONE
key/value head, no positions, one layer in fourteen.  That reader takes the
heads and the head width from the configuration's own keys; its line is
said under this name."""

from . import flash_gqa64_roofline

THEIRS, OURS = "flash_gqa64_roofline", "flash_mqa20_roofline"


def read(trace, spans, counters, cell):
    say = cell["say"]
    return flash_gqa64_roofline.read(
        trace, spans, counters,
        dict(cell, say=lambda line: say(line.replace(THEIRS, OURS, 1))))
