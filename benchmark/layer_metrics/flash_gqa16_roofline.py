"""Kernels: ``flash_gqa64_roofline``'s reading (the least time the chip
could take for the causal grouped-query attention the shapes require, by
call, over the time the flash kernels took;
``benchmark/flops/flash_attention_gqa.py`` gives the FLOPs and bytes) under
a name of its own, for a GROUP OF 16: 32 query heads of 128 on 2 key/value
heads, no positions, one layer in nine.  That reader takes the head width as
``hidden_size`` over the heads, which is not it here (2,688 / 32 = 84): it
is handed the configuration's own ``head_dim`` key in that form, and its
line is said under this name."""

from . import flash_gqa64_roofline

THEIRS, OURS = "flash_gqa64_roofline", "flash_gqa16_roofline"


def read(trace, spans, counters, cell):
    say, config = cell["say"], cell["config"]
    model = config["model"]
    if "head_dim" not in model:
        return None                 # another configuration's cell
    width = model["num_attention_heads"] * model["head_dim"]
    return flash_gqa64_roofline.read(
        trace, spans, counters,
        dict(cell, say=lambda line: say(line.replace(THEIRS, OURS, 1)),
             config=dict(config, model=dict(model, hidden_size=width))))
