"""Kernels: ``flash_causal_roofline``'s reading (the least time the chip
could take for the causal attention the shapes require, by call, over the
time the flash kernels took; ``benchmark/flops/flash_attention.py`` gives
the FLOPs and bytes) under a name of its own, for 16 heads of 128 on as many
key/value heads at B = 2, S = 4,096 in a LOOPED stack: every layer
application is a call, so a step of 12 layers and 4 passes under remat is 96
``flash_fwd`` events and 48 backward.  A configuration that is not looped
(no ``total_ut_steps``) is another's cell: nothing is read."""

from . import flash_causal_roofline

THEIRS, OURS = "flash_causal_roofline", "flash_mha16_roofline"


def read(trace, spans, counters, cell):
    say, model = cell["say"], cell["config"]["model"]
    if "total_ut_steps" not in model:
        return None
    assert model["hidden_size"] \
        == model["num_attention_heads"] * model["head_dim"]
    return flash_causal_roofline.read(
        trace, spans, counters,
        dict(cell, say=lambda line: say(line.replace(THEIRS, OURS, 1))))
